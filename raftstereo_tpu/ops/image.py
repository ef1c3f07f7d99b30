"""Image-space primitives shared by the model, correlation engine and eval.

All tensors are NHWC (TPU-native convolution layout), in contrast to the
reference's NCHW.  Semantics are kept bit-compatible with the reference ops
they replace so that converted checkpoints reproduce the same numerics:

* ``resize_bilinear_align_corners``  ==  ``F.interpolate(..., mode='bilinear',
  align_corners=True)`` (reference: core/update.py:93-95, core/utils/utils.py:82-84)
* ``avg_pool2x``  ==  ``F.avg_pool2d(x, 3, stride=2, padding=1)`` with
  count_include_pad=True (reference: core/update.py:87-88)
* ``avg_pool_w2``  ==  ``F.avg_pool2d(x, [1,2], stride=[1,2])`` over the W axis
  (reference: core/corr.py:124)
* ``InputPadder``  ==  replicate padding to a divisibility constraint
  (reference: core/utils/utils.py:7-26)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _axis_resize_indices(in_size: int, out_size: int):
    """Source indices + lerp weight for align-corners resize along one axis."""
    if out_size == 1 or in_size == 1:
        idx = np.zeros((out_size,), np.int32)
        return idx, idx, np.zeros((out_size,), np.float32)
    pos = np.arange(out_size, dtype=np.float64) * (in_size - 1) / (out_size - 1)
    i0 = np.floor(pos).astype(np.int32)
    i0 = np.minimum(i0, in_size - 1)
    i1 = np.minimum(i0 + 1, in_size - 1)
    w = (pos - i0).astype(np.float32)
    return i0, i1, w


def resize_bilinear_align_corners(x: jax.Array, out_hw: Tuple[int, int]) -> jax.Array:
    """Bilinear resize with align_corners=True semantics.  x: (B, H, W, C).

    ``jax.image.resize`` uses half-pixel centres, which does not match the
    reference's ``align_corners=True`` (core/update.py:94); this separable
    gather+lerp formulation does, and XLA fuses it cleanly.
    """
    b, h, w, c = x.shape
    oh, ow = out_hw
    if (h, w) == (oh, ow):
        return x
    # Lerp in the INPUT dtype for the two compute dtypes the model uses:
    # fp32 inputs keep exact fp32 lerps (the eval-parity path), while
    # bf16 inputs stay bf16 end to end — the fp32 upcast doubled the
    # in-loop resizes' HBM traffic for weight precision the
    # bf16-quantized operands cannot use.  Everything else (ints, fp16)
    # lerps in fp32 as before.
    dtype = x.dtype
    cdt = dtype if dtype in (jnp.float32, jnp.bfloat16) else jnp.float32
    xf = x.astype(cdt)
    i0, i1, wh = _axis_resize_indices(h, oh)
    wh = wh.astype(cdt)
    xf = (xf[:, i0] * (1 - wh)[None, :, None, None]
          + xf[:, i1] * wh[None, :, None, None])
    j0, j1, ww = _axis_resize_indices(w, ow)
    ww = ww.astype(cdt)
    xf = (xf[:, :, j0] * (1 - ww)[None, None, :, None]
          + xf[:, :, j1] * ww[None, None, :, None])
    return xf.astype(dtype)


def avg_pool2x(x: jax.Array) -> jax.Array:
    """3x3/stride-2/pad-1 average pool, zeros counted in the divisor.

    Matches torch ``F.avg_pool2d(x, 3, stride=2, padding=1)`` defaults
    (count_include_pad=True), used to pass fine GRU state down one level
    (reference: core/update.py:87-88).
    """
    # Plain-python 0.0 init (weak-typed): a concrete bf16 zero constant here
    # breaks linearization when the surrounding computation is differentiated
    # inside a lax.fori_loop body.
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add,
        window_dimensions=(1, 3, 3, 1), window_strides=(1, 2, 2, 1),
        padding=((0, 0), (1, 1), (1, 1), (0, 0)))
    return s / jnp.asarray(9.0, dtype=x.dtype)


def avg_pool4x(x: jax.Array) -> jax.Array:
    """5x5/stride-4/pad-1 average pool (reference: core/update.py:90-91)."""
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add,
        window_dimensions=(1, 5, 5, 1), window_strides=(1, 4, 4, 1),
        padding=((0, 0), (1, 1), (1, 1), (0, 0)))
    return s / jnp.asarray(25.0, dtype=x.dtype)


def avg_pool_w2(x: jax.Array) -> jax.Array:
    """Average-pool by 2 along the second-to-last (W2) axis of (..., W2).

    Valid padding: an odd trailing element is dropped, matching torch's floor
    behaviour for ``F.avg_pool2d(x, [1,2], stride=[1,2])``
    (reference: core/corr.py:124).  Operates on the LAST axis.
    """
    w = x.shape[-1]
    x = x[..., : (w // 2) * 2]
    shape = x.shape[:-1] + (w // 2, 2)
    return jnp.mean(x.reshape(shape), axis=-1)


def gauss_blur(x: jax.Array, n: int = 5, std: float = 1.0) -> jax.Array:
    """Depthwise Gaussian blur (reference: core/utils/utils.py:86-93)."""
    g = np.arange(n, dtype=np.float64) - n // 2
    k = np.exp(-(g[:, None] ** 2 + g[None, :] ** 2) / (2 * std ** 2))
    k = (k / max(k.sum(), 1e-4)).astype(np.float32)
    c = x.shape[-1]
    kernel = jnp.tile(jnp.asarray(k)[:, :, None, None], (1, 1, 1, c))
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), kernel,
        window_strides=(1, 1), padding=[(n // 2, n // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c).astype(x.dtype)


def forward_interpolate(flow: np.ndarray) -> np.ndarray:
    """Forward-warp a flow field for warm-starting the next frame's estimate
    (reference: core/utils/utils.py:28-56).

    Host-side by design (as in the reference, which moves to CPU first): this
    runs once per frame between device steps, feeding the model's
    ``flow_init`` hook.  ``flow`` is (2, H, W) [dx, dy] or (H, W) x-flow only
    (the stereo case); returns the same shape, float32.  Each source pixel's
    flow is splatted to where it lands; holes are filled by nearest-neighbour
    interpolation, out-of-frame splats are dropped.
    """
    from scipy import interpolate as _interp

    flow = np.asarray(flow, np.float32)
    stereo = flow.ndim == 2
    if stereo:
        flow = np.stack([flow, np.zeros_like(flow)], axis=0)
    dx, dy = flow[0], flow[1]
    ht, wd = dx.shape
    x0, y0 = np.meshgrid(np.arange(wd), np.arange(ht))
    x1 = (x0 + dx).reshape(-1)
    y1 = (y0 + dy).reshape(-1)
    dxf, dyf = dx.reshape(-1), dy.reshape(-1)
    valid = (x1 > 0) & (x1 < wd) & (y1 > 0) & (y1 < ht)
    if not valid.any():
        out = np.zeros_like(flow)
        return out[0] if stereo else out
    pts = (x1[valid], y1[valid])
    fx = _interp.griddata(pts, dxf[valid], (x0, y0), method="nearest",
                          fill_value=0)
    if stereo:
        return fx.astype(np.float32)
    fy = _interp.griddata(pts, dyf[valid], (x0, y0), method="nearest",
                          fill_value=0)
    return np.stack([fx, fy], axis=0).astype(np.float32)


def replicate_pad(x: jax.Array, pad: Sequence[int]) -> jax.Array:
    """Edge-replicate pad; pad = (left, right, top, bottom) on (B, H, W, C)."""
    l, r, t, b = pad
    return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)), mode="edge")


class InputPadder:
    """Pads NHWC images so H and W are divisible by ``divis_by``.

    Same layout policy as the reference (core/utils/utils.py:7-26):
    'sintel' mode splits padding around the image, otherwise all height
    padding goes to the bottom.  Works on jax arrays and numpy arrays.
    """

    def __init__(self, dims: Sequence[int], mode: str = "sintel", divis_by: int = 8):
        self.ht, self.wd = dims[-3:-1] if len(dims) == 4 else dims[-2:]
        pad_ht = (((self.ht // divis_by) + 1) * divis_by - self.ht) % divis_by
        pad_wd = (((self.wd // divis_by) + 1) * divis_by - self.wd) % divis_by
        if mode == "sintel":
            self._pad = (pad_wd // 2, pad_wd - pad_wd // 2,
                         pad_ht // 2, pad_ht - pad_ht // 2)
        else:
            self._pad = (pad_wd // 2, pad_wd - pad_wd // 2, 0, pad_ht)

    @property
    def padded_hw(self) -> Tuple[int, int]:
        l, r, t, b = self._pad
        return self.ht + t + b, self.wd + l + r

    def pad(self, *inputs: jax.Array):
        assert all(x.ndim == 4 for x in inputs)
        out = [replicate_pad(x, self._pad) for x in inputs]
        return out if len(out) > 1 else out[0]

    def unpad(self, x: jax.Array) -> jax.Array:
        assert x.ndim == 4
        l, r, t, b = self._pad
        ht, wd = x.shape[1:3]
        return x[:, t:ht - b, l:wd - r, :]


class BucketPadder:
    """Single source of truth for the pad-and-bucket shape policy shared by
    the eval runner (eval/runner.py) and the serving engine (serve/engine.py).

    Two stages: ``InputPadder`` alignment to ``divis_by`` first (same split
    policy as the reference), then an optional round-up of the padded shape
    to the coarser ``bucket_multiple`` grid with edge-replicate rows/columns
    on the bottom/right, so near-identical image sizes share one compiled
    executable.  Callers that agree on (divis_by, bucket_multiple, mode)
    produce bitwise-identical padded tensors — the property the serve layer's
    batched outputs == single-image Evaluator outputs test rests on.

    ``dims`` may be (H, W), (H, W, C) or (B, H, W, C).
    """

    def __init__(self, dims: Sequence[int], divis_by: int = 32,
                 bucket_multiple: Optional[int] = None, mode: str = "sintel"):
        if len(dims) == 3:
            hw: Sequence[int] = dims[:2]
        elif len(dims) == 4:
            hw = dims[1:3]
        else:
            hw = dims
        self._padder = InputPadder(hw, mode=mode, divis_by=divis_by)
        ph, pw = self._padder.padded_hw
        m = bucket_multiple or 1
        self.extra_h = (-ph) % m
        self.extra_w = (-pw) % m
        self.bucket_hw: Tuple[int, int] = (ph + self.extra_h,
                                           pw + self.extra_w)

    def pad(self, *inputs: jax.Array):
        out = self._padder.pad(*inputs)
        if len(inputs) == 1:
            out = [out]
        if self.extra_h or self.extra_w:
            out = [replicate_pad(x, (0, self.extra_w, 0, self.extra_h))
                   for x in out]
        return out if len(out) > 1 else out[0]

    def pad_into(self, out: np.ndarray, image: np.ndarray) -> None:
        """Host twin of ``pad`` for ONE (H, W, C) image: fills ``out``
        (bucket_h, bucket_w, C) with the padded image, the same bits
        (edge replication copies, and an edge replicated twice is that
        edge replicated once by the sum) and no device program — the
        serving engine stages a batch with it, row by row into one
        array (serve/engine.py ``_stage_pairs``)."""
        l, r, t, b = self._padder._pad
        h, w = image.shape[:2]
        assert out.shape[:2] == self.bucket_hw, (out.shape, self.bucket_hw)
        out[t:t + h, l:l + w] = image
        if l:
            out[t:t + h, :l] = out[t:t + h, l:l + 1]
        if l + w < out.shape[1]:
            out[t:t + h, l + w:] = out[t:t + h, l + w - 1:l + w]
        if t:
            out[:t] = out[t:t + 1]
        if t + h < out.shape[0]:
            out[t + h:] = out[t + h - 1:t + h]

    def unpad(self, x: jax.Array) -> jax.Array:
        if self.extra_h or self.extra_w:
            x = x[:, :x.shape[1] - self.extra_h,
                  :x.shape[2] - self.extra_w, :]
        return self._padder.unpad(x)


def coords_grid_x(batch: int, ht: int, wd: int, dtype=jnp.float32) -> jax.Array:
    """x-coordinate grid (B, H, W, 1).

    The reference carries a full 2-channel (x, y) grid (core/utils/utils.py:76-79)
    but zeroes the y update every iteration (core/raft_stereo.py:120) — for
    stereo only the x channel ever changes.  We carry x only and materialise a
    zero y channel where the motion encoder needs 2-channel flow.
    """
    x = jnp.arange(wd, dtype=dtype)
    return jnp.broadcast_to(x[None, None, :, None], (batch, ht, wd, 1))
