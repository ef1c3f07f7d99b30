"""On-device photometric augmentation: the TPU answer to a CPU-bound host.

The reference runs every augmentation op on the host inside torch DataLoader
workers (reference: core/utils/augmentor.py:78-111 via core/stereo_datasets.py:311).
That scales with host cores — and starves the chip when cores are scarce:
the photometric chain (jitter + eraser) is roughly half the per-sample host
cost measured on the KITTI (sparse-augmentor) pipeline. This module moves exactly that chain into
the jitted training step, where it fuses with the input normalization and
costs microseconds of TPU time; shape-changing work (decode, scale/stretch,
flip, crop, sparse scatter) stays on the host, which is the natural split —
everything on-device is fixed-shape.

Semantics mirror the host ``ColorJitter``/eraser (same factor ranges, same
random op order, same asymmetric/eraser probabilities and eye-swap-flip
eraser-target distribution, per-op [0,255] clipping) with two documented
differences:

* hue rotates in continuous fp32 HSV rather than PIL's 8-bit quantized HSV;
* ops apply after the spatial crop rather than before the resize, and
  intermediate values are never rounded to uint8.

Both change the augmentation distribution imperceptibly (augmentation is
noise by design); the host path remains the reference-exact default.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


# ----------------------------------------------------------- color space

def rgb_to_hsv(rgb: jax.Array) -> jax.Array:
    """(3, ...) channel-FIRST in [0,1] -> HSV (3, ...) in [0,1]."""
    r, g, b = rgb[0], rgb[1], rgb[2]
    mx = jnp.max(rgb, axis=0)
    mn = jnp.min(rgb, axis=0)
    d = mx - mn
    safe = jnp.where(d > 0, d, 1.0)
    h = jnp.where(
        mx == r, (g - b) / safe,
        jnp.where(mx == g, 2.0 + (b - r) / safe, 4.0 + (r - g) / safe))
    h = jnp.where(d > 0, (h / 6.0) % 1.0, 0.0)
    s = jnp.where(mx > 0, d / jnp.where(mx > 0, mx, 1.0), 0.0)
    return jnp.stack([h, s, mx])


def hsv_to_rgb(hsv: jax.Array) -> jax.Array:
    h, s, v = hsv[0], hsv[1], hsv[2]
    i = jnp.floor(h * 6.0)
    f = h * 6.0 - i
    p = v * (1 - s)
    q = v * (1 - f * s)
    t = v * (1 - (1 - f) * s)
    i = i.astype(jnp.int32) % 6

    def sector(table):
        # Explicit select cascade: jnp.choose lowers to per-element GATHERS
        # on TPU (measured ~5x an elementwise pass); wheres stay on the VPU.
        out = table[5]
        for k in range(4, -1, -1):
            out = jnp.where(i == k, table[k], out)
        return out

    r = sector([v, q, p, p, t, v])
    g = sector([t, v, v, q, p, p])
    b = sector([p, p, t, v, v, q])
    return jnp.stack([r, g, b])


# ----------------------------------------------------------- jitter ops
# All ops run CHANNEL-FIRST, (3, H, W) float32 in [0, 255], W in the lane
# dimension: with NHWC's C=3 minor, every elementwise/reduce pass uses 3 of
# 128 VPU lanes and the whole chain measured ~700 ms per step; channel-first
# it is bandwidth-bound and negligible. Each op clips like the host _blend
# (augment.py). Contrast blends against the CURRENT image's gray mean
# (order-dependent, like the host's adjust_contrast); the symmetric path
# feeds both eyes stacked as one image, so the mean is the joint one —
# exactly the host's stacked-image call (color_transform).

def _gray(img):
    """(3, H, W) -> (1, H, W) luma."""
    return (img[0] * 0.299 + img[1] * 0.587 + img[2] * 0.114)[None]


def _brightness(img, f):
    return jnp.clip(img * f, 0, 255)


def _contrast(img, f, mean_map):
    # mean_map: per-pixel blend target — each eye's own gray mean in the
    # asymmetric case, the joint mean in the symmetric case (host stacks
    # the eyes into one image, so its adjust_contrast sees the joint mean).
    return jnp.clip(mean_map + f * (img - mean_map), 0, 255)


def _saturation(img, f):
    g = _gray(img)
    return jnp.clip(g + f * (img - g), 0, 255)


def _hue(img, shift):
    """(3, H, W), shift scalar or (H, 1)-broadcastable per-row map."""
    hsv = rgb_to_hsv(jnp.clip(img, 0, 255) / 255.0)
    h = (hsv[0] + shift) % 1.0
    return hsv_to_rgb(jnp.stack([h, hsv[1], hsv[2]])) * 255.0


class DevicePhotometric:
    """Batched, jittable photometric augmentation (jitter + eraser).

    Call with a PRNG key and (B, H, W, 3) float32 [0,255] image batches:
        img1, img2 = aug(key, img1, img2)
    Per-sample randomness comes from splitting the key over the batch, so a
    given (key, step) reproduces exactly — fold the step counter into the
    key upstream (see train.step).
    """

    def __init__(self, brightness=0.4, contrast=0.4,
                 saturation: Sequence[float] = (0.6, 1.4), hue=0.5 / 3.14,
                 gamma: Sequence[float] = (1, 1, 1, 1),
                 asymmetric_prob=0.2, eraser_prob=0.5,
                 eraser_bounds: Tuple[int, int] = (50, 100),
                 erase_left_prob=0.0):
        self.brightness = brightness
        self.contrast = contrast
        self.saturation = tuple(saturation)
        self.hue = hue
        self.gamma = tuple(gamma)
        self.asymmetric_prob = asymmetric_prob
        self.eraser_prob = eraser_prob
        self.eraser_bounds = eraser_bounds
        # The host erases PRE-flip img2; a stereo eye-swap flip (do_flip='h',
        # augment.py spatial_transform) then turns the erased eye into the
        # LEFT input with the flip's probability. The host flip draw is
        # independent of the eraser, so an independent target-eye draw here
        # reproduces the distribution exactly.
        self.erase_left_prob = erase_left_prob

    # ---- per-sample pieces ------------------------------------------------

    def _factors(self, key):
        kb, kc, ks, kh = jax.random.split(key, 4)
        return (
            jax.random.uniform(kb, (), minval=max(0, 1 - self.brightness),
                               maxval=1 + self.brightness),
            jax.random.uniform(kc, (), minval=max(0, 1 - self.contrast),
                               maxval=1 + self.contrast),
            jax.random.uniform(ks, (), minval=self.saturation[0],
                               maxval=self.saturation[1]),
            jax.random.uniform(kh, (), minval=-self.hue, maxval=self.hue),
        )

    # NO per-sample lax.cond/lax.switch anywhere: under vmap those execute
    # EVERY branch for every sample (measured 7x the whole train step).
    # Random op order is instead expressed data-parallel: every op has a
    # neutral factor (brightness/contrast/saturation 1, hue 0) that makes it
    # an exact identity, so one fixed chain per position with
    # position-scheduled factors applies each op exactly once, in the
    # per-eye random order. 4 positions x 4 ops = 16 cheap elementwise
    # evaluations per pair instead of 2 x 24 branch bodies.

    def _jitter_stacked(self, x, factors2, order2, gamma2, gain2, asym):
        """x: (3, 2H, W) channel-first stacked pair; factors2/order2: (2, 4)
        per-eye op factors and op-order (op index at each position);
        gamma2/gain2: (2,); asym: scalar bool selecting per-eye vs joint
        contrast mean."""
        h2 = x.shape[1]
        half = jnp.arange(h2) >= h2 // 2            # row -> eye index
        neutral = jnp.asarray([1.0, 1.0, 1.0, 0.0])

        def fmap(v2):                               # (2,) -> (2H, 1)
            return jnp.where(half, v2[1], v2[0])[:, None]

        for k in range(4):
            active = order2[:, k]                   # (2,) op id at position k
            fk = jnp.where(jnp.arange(4)[None, :] == active[:, None],
                           factors2, neutral[None, :])   # (2, 4)
            m_top = jnp.mean(_gray(x[:, : h2 // 2]))
            m_bot = jnp.mean(_gray(x[:, h2 // 2:]))
            joint = 0.5 * (m_top + m_bot)
            mean_map = jnp.where(
                asym, fmap(jnp.stack([m_top, m_bot])), joint)
            x = _brightness(x, fmap(fk[:, 0]))
            x = _contrast(x, fmap(fk[:, 1]), mean_map)
            x = _saturation(x, fmap(fk[:, 2]))
            x = _hue(x, fmap(fk[:, 3]))
        if self.gamma != (1, 1, 1, 1):
            x = jnp.clip(255.0 * fmap(gain2)
                         * (x / 255.0) ** fmap(gamma2), 0, 255)
        return jnp.clip(x, 0, 255)

    def _eraser_one(self, key, stacked):
        """stacked: (3, 2H, W) channel-first pair; erases ONE eye — the
        right one, or the left with ``erase_left_prob`` (the post-flip image
        of the host's pre-flip img2; see __init__)."""
        h2, w = stacked.shape[1:]
        h = h2 // 2
        ka, kn, kr, ke = jax.random.split(key, 4)
        apply = jax.random.uniform(ka, ()) < self.eraser_prob
        n = jax.random.randint(kn, (), 1, 3)       # 1 or 2 rectangles
        left = jax.random.uniform(ke, ()) < self.erase_left_prob
        row0 = jnp.where(left, 0, h)               # target eye's first row
        m_top = jnp.mean(stacked[:, :h], axis=(1, 2))
        m_bot = jnp.mean(stacked[:, h:], axis=(1, 2))
        mean_color = jnp.where(left, m_top, m_bot)  # (3,)
        yy = jnp.arange(h2)[:, None]
        xx = jnp.arange(w)[None, :]
        lo, hi = self.eraser_bounds
        for r, krr in enumerate(jax.random.split(kr, 2)):
            kx, ky, kdx, kdy = jax.random.split(krr, 4)
            x0 = jax.random.randint(kx, (), 0, w)
            y0 = jax.random.randint(ky, (), 0, h) + row0
            dx = jax.random.randint(kdx, (), lo, hi)
            dy = jax.random.randint(kdy, (), lo, hi)
            # The rectangle clips at the target eye's bottom edge, exactly
            # like the host slice assignment clips at the image edge.
            mask = (apply & (r < n) & (yy >= y0) & (yy < y0 + dy)
                    & (yy < row0 + h)
                    & (xx >= x0) & (xx < x0 + dx))
            stacked = jnp.where(mask[None], mean_color[:, None, None],
                                stacked)
        return stacked

    def _sample(self, key, img1, img2):
        k_asym, k_p1, k_p2, k_ord1, k_ord2, kg1, kg2, k_er = \
            jax.random.split(key, 8)
        asym = jax.random.uniform(k_asym, ()) < self.asymmetric_prob

        def eye_params(kp, ko, kg):
            f = jnp.stack(self._factors(kp))                      # (4,)
            order = jnp.argsort(jax.random.uniform(ko, (4,)))     # random perm
            gmin, gmax, gainmin, gainmax = self.gamma
            ka, kb = jax.random.split(kg)
            gamma = jax.random.uniform(ka, (), minval=gmin, maxval=gmax)
            gain = jax.random.uniform(kb, (), minval=gainmin, maxval=gainmax)
            return f, order, gamma, gain

        f1, o1, gamma1, gain1 = eye_params(k_p1, k_ord1, kg1)
        f2_, o2_, gamma2_, gain2_ = eye_params(k_p2, k_ord2, kg2)
        # Symmetric draw shares eye 1's parameters (host jitters the stacked
        # pair once); the select is on the small parameter vectors only.
        f2 = jnp.where(asym, f2_, f1)
        o2 = jnp.where(asym, o2_, o1)
        gamma2 = jnp.where(asym, gamma2_, gamma1)
        gain2 = jnp.where(asym, gain2_, gain1)

        # Channel-first throughout (W in lanes; see the op-block comment).
        # The transposes are two cheap bandwidth-bound copies per pair.
        stacked = jnp.concatenate([img1, img2], axis=0).transpose(2, 0, 1)
        out = self._jitter_stacked(
            stacked,
            jnp.stack([f1, f2]), jnp.stack([o1, o2]),
            jnp.stack([gamma1, gamma2]), jnp.stack([gain1, gain2]), asym)
        out = self._eraser_one(k_er, out)
        h = img1.shape[0]
        return (out[:, :h].transpose(1, 2, 0),
                out[:, h:].transpose(1, 2, 0))

    def __call__(self, key: jax.Array, img1: jax.Array, img2: jax.Array):
        keys = jax.random.split(key, img1.shape[0])
        return jax.vmap(self._sample)(keys, img1, img2)
