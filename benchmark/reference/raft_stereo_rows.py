"""The plain reference of ``raft_stereo.py`` for pairs too large to run it whole.

``raft_stereo.py`` stays the definition: float32 ``jax.numpy``, every
contraction at ``Precision.HIGHEST``, no kernels.  At a six-megapixel pair
(a 2048x3008 bucket, a 512x752 field) two of its values do not fit a 16 GB
chip beside anything else, and this module computes the same equations with
those two taken in pieces.  Everything else is imported from there.

Departures, all of them:

1. *The lookup.*  ``raft_stereo._sample_w`` writes the two-tap read as a sum
   over the whole row against a hat tensor of rows x W x 9 x W floats: 10 GB
   at level 0 of this size.  ``lookup_rows`` runs the same ``lookup`` over
   blocks of rows under ``lax.map`` (``ROW_BLOCK_BYTES`` of hat weights a
   block); the pyramid is cut into those blocks once, outside the loop.  A
   row's sums are the same sums in the same order.
2. *The feature encoder.*  ``raft_stereo.encode`` runs ``fnet`` over both
   images stacked on the batch axis: float32 activations of 2 x 2048 x 3008
   x 64 are 3.2 GB each.  ``encode`` here runs ``fnet`` over the two images
   one after the other (``lax.map``).  Its instance norm takes statistics per
   image, so nothing an image's features depend on changes.  (The shared
   backbone form stacks the images for its batch norm's trunk too and is
   passed through to ``raft_stereo.encode`` unchanged.)

``benchmark/tests/test_reference_rows.py`` holds the two forward passes equal
on seeded weights at a size where both fit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import raft_stereo as R

ROW_BLOCK_BYTES = 256 * 2 ** 20     # hat weights of one block of rows


def encode(p, cfg, image1, image2, operand_dtype=None):
    """``raft_stereo.encode`` with ``fnet`` over one image at a time.  The
    context half (heads, GRU states, biases) is its code line for line:
    that function is one piece and cannot be called for a half."""
    if cfg.get("shared_backbone"):
        return R.encode(p, cfg, image1, image2, operand_dtype)
    ops = R._Ops(operand_dtype)
    d, n = cfg["n_downsample"], cfg["n_gru_layers"]
    hd = list(cfg["hidden_dims"])
    kind = cfg.get("context_norm", "batch")

    def scaled(img):
        return 2.0 * (img.astype(jnp.float32) / 255.0) - 1.0

    def fnet(img):
        return ops.conv(p, "fnet.conv2", R._trunk(
            ops, p, "fnet.", scaled(img), "instance", d), 1, 0)

    fmap1, fmap2 = (f[0] for f in jax.lax.map(
        fnet, jnp.stack([image1, image2]))[:, None])
    x = R._trunk(ops, p, "cnet.", scaled(image1), kind, d)

    def heads(lvl, y):
        if lvl == "32":
            return [ops.conv(p, f"cnet.outputs32.{hi}", y) for hi in (0, 1)]
        return [ops.conv(p, f"cnet.outputs{lvl}.{hi}.1", R._block(
            ops, p, f"cnet.outputs{lvl}.{hi}.0", y, 1, kind))
            for hi in (0, 1)]

    outs = [heads("08", x)]
    if n >= 2:
        y = R._block(ops, p, "cnet.layer4.1",
                     R._block(ops, p, "cnet.layer4.0", x, 2, kind), 1, kind)
        outs.append(heads("16", y))
    if n >= 3:
        z = R._block(ops, p, "cnet.layer5.1",
                     R._block(ops, p, "cnet.layer5.0", y, 2, kind), 1, kind)
        outs.append(heads("32", z))
    nets = [jnp.tanh(o[0]) for o in outs]
    ctx = []
    for i, o in enumerate(outs):
        c = ops.conv(p, f"context_zqr_convs.{i}", jax.nn.relu(o[1]))
        ctx.append((c[..., :hd[i]], c[..., hd[i]:2 * hd[i]],
                    c[..., 2 * hd[i]:]))
    return nets, ctx, fmap1, fmap2


def pyramid_rows(pyr, rows: int):
    """Each level (B, H, W, Wl) as (H // rows, B, rows, W, Wl)."""
    out = []
    for v in pyr:
        b, h, w, wl = v.shape
        out.append(v.reshape(b, h // rows, rows, w, wl).swapaxes(0, 1))
    return out


def rows_per_block(h: int, w: int, radius: int) -> int:
    """Largest divisor of ``h`` whose level-0 hat weights stay inside
    ``ROW_BLOCK_BYTES``."""
    cap = max(1, ROW_BLOCK_BYTES // (4 * w * (2 * radius + 1) * w))
    return max(r for r in range(1, min(h, cap) + 1) if h % r == 0)


def lookup_rows(pyr_rows, x, radius: int):
    """``raft_stereo.lookup`` a block of rows at a time: ``pyr_rows`` from
    ``pyramid_rows``, x (B, H, W) -> (B, H, W, levels*(2r+1))."""
    nb, b, rows = pyr_rows[0].shape[:3]
    w = x.shape[-1]
    xb = x.reshape(b, nb, rows, w).swapaxes(0, 1)
    out = jax.lax.map(lambda a: R.lookup(a[0], a[1], radius),
                      (tuple(pyr_rows), xb))
    return out.swapaxes(0, 1).reshape(b, nb * rows, w, out.shape[-1])


def forward(p, cfg, image1, image2, iters: int, operand_dtype=None):
    """``raft_stereo.forward`` (its two results) with ``encode`` and the
    lookup of this module."""
    ops = R._Ops(operand_dtype)
    nets, ctx, fmap1, fmap2 = encode(p, cfg, image1, image2, operand_dtype)
    b, h, w, _ = nets[0].shape
    rows = rows_per_block(h, w, cfg["corr_radius"])
    pyr = pyramid_rows(R.corr_pyramid(ops, fmap1, fmap2, cfg["corr_levels"]),
                       rows)
    grid = jnp.broadcast_to(jnp.arange(w, dtype=jnp.float32)[None, None, :],
                            (b, h, w))
    disp = jnp.zeros((b, h, w, 1), jnp.float32)

    def step(carry, _):
        nets, disp = carry
        disp = jax.lax.stop_gradient(disp)
        corr = lookup_rows(pyr, grid + disp[..., 0], cfg["corr_radius"])
        nets, delta = R.update(ops, p, cfg, nets, ctx, corr, disp)
        return (tuple(nets), disp + delta), None

    (nets, disp), _ = jax.lax.scan(step, (tuple(nets), disp), None,
                                   length=iters)
    return disp, R.convex_upsample(disp, R.upsample_mask(ops, p, nets[0]),
                                   2 ** cfg["n_downsample"])


def serve_reference(p, cfg, left, right, iters, divis_by=32,
                    bucket_multiple=64, operand_dtype=None):
    """``raft_stereo.serve_reference`` over this module's ``forward``."""
    h, w = left.shape[:2]
    t, b, l, r = R.bucket_pad((h, w), divis_by, bucket_multiple)
    pad = ((0, 0), (t, b), (l, r), (0, 0))
    i1 = jnp.pad(jnp.asarray(left, jnp.float32)[None], pad, mode="edge")
    i2 = jnp.pad(jnp.asarray(right, jnp.float32)[None], pad, mode="edge")
    _, up = forward(p, cfg, i1, i2, iters, operand_dtype)
    return up[0, t:t + h, l:l + w, 0]
