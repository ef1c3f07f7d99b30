"""Pallas TPU kernel for the correlation-pyramid lookup.

This is the TPU-native equivalent of the reference's CUDA extension
(reference: sampler/sampler.cpp, sampler/sampler_kernel.cu): per output pixel,
interpolate 2r+1 taps from its correlation row.  Where the CUDA kernel gathers
2r+2 integer taps and lerps (sampler_kernel.cu:19-60), a TPU kernel must avoid
per-lane gathers entirely — instead each (row-block, tap) output is computed as
a masked reduction over the whole W2 row with the hat weight

    w(j) = relu(1 - |j - x_k|)

which is algebraically identical to two-tap linear interpolation with zero
padding (see ops/sampler.linear_sample_1d_dense, the XLA oracle for this
kernel).  The reduction is pure VPU work: broadcast-compare-multiply-add over
a VMEM-resident row block, no scatter/gather anywhere.

The backward pass mirrors the CUDA scatter-add backward
(sampler_kernel.cu:63-105) but again as a dense product:
    dvol[w1, j] = sum_k g[w1, k] * w_k(j)
Gradients w.r.t. coordinates are not needed: the model detaches the disparity
at the top of every refinement iteration (reference: core/raft_stereo.py:109,
CorrSampler.backward likewise returns None for coords, core/corr.py:24-29).

Supports fp32 and bf16 volumes (the CUDA kernel's
AT_DISPATCH_FLOATING_TYPES_AND_HALF, sampler_kernel.cu:126); accumulation is
always fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Max rows (W1 pixels) per block; lane-width multiple keeps the VPU fully busy.
_BLOCK_W1 = 256

# (B*H) rows per grid step.  One row per step (round 1) made the flagship
# lookup grid 136 steps long and per-step overhead (~7 us: Mosaic grid
# bookkeeping + DMA issue latency through this chip's fabric) dominated the
# kernel — measured 0.97 ms/call while the pure matmul+VPU work costs ~0.3 ms.
# Batching rows per step amortizes that overhead; flat inputs are row-padded
# to this multiple (zero rows correlate/scatter to exactly zero, and padded
# outputs are sliced off).
_BLOCK_ROWS = 8


# Row-blocked grids need more scoped VMEM than Mosaic's 16 MB default
# (R=8 fp32 flagship blocks are ~44 MB across double buffers); v5e carries
# 128 MB of VMEM per core, so raise the scoped limit rather than shrink R.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _pad_rows(x: jax.Array, r: int = _BLOCK_ROWS) -> jax.Array:
    """Zero-pad axis 0 (flattened B*H rows) to a multiple of ``r``."""
    pad = (-x.shape[0]) % r
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

# None = auto (compile on TPU backends, interpret elsewhere).  Set True to
# force interpret mode, e.g. when debugging CPU-placed execution on a TPU host
# (auto-detection keys off the default backend, not actual placement).
interpret_override = None


def _interpret() -> bool:
    if interpret_override is not None:
        return interpret_override
    return jax.default_backend() not in ("tpu",)


def _block_w1(w1: int) -> int:
    """Row-block size: cap at _BLOCK_W1 but don't pad small W1 up to it —
    the dense reduction's FLOPs scale with the padded row count."""
    return min(_BLOCK_W1, -(-w1 // 8) * 8)


def _lookup_kernel(vol_ref, taps_ref, out_ref, *, bounds):
    """One (n, w1-block), ALL pyramid levels against a W2-concatenated
    volume: out[w1, l*K + k] = sum_j vol_l[w1, j] * hat(j - taps[w1, l*K+k]).

    ``bounds`` is a static (offset, padded-width) per level; levels are
    zero-padded to lane multiples so each slice is lane-aligned and a
    padded column contributes exactly zero (zero-outside semantics without
    masks — same construction as pallas_alt). Single-level callers use
    bounds=((0, w2),).
    """
    vol = vol_ref[...].astype(jnp.float32)        # (R, W1_t, W2cat)
    taps = taps_ref[...].astype(jnp.float32)      # (R, W1_t, L*K)
    kk = taps.shape[-1] // len(bounds)
    cols = []
    for li, (off, w2p) in enumerate(bounds):
        vl = vol[:, :, off:off + w2p]
        # Mosaic requires integer iota; cast to f32 for the hat weights.
        j = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w2p), 2).astype(jnp.float32)
        for ki in range(kk):                       # L*K is small: unrolled
            t = taps[:, :, li * kk + ki][..., None]
            w = jnp.maximum(0.0, 1.0 - jnp.abs(j - t))
            cols.append(jnp.sum(vl * w, axis=-1))  # (R, W1_t)
    out_ref[...] = jnp.stack(cols, axis=-1).astype(out_ref.dtype)


def _lookup_bwd_kernel(taps_ref, g_ref, dvol_ref, *, bounds):
    """dvol_l[w1, j] = sum_k g[w1, l*K + k] * hat(j - taps[w1, l*K + k])."""
    taps = taps_ref[...].astype(jnp.float32)      # (R, W1_t, L*K)
    g = g_ref[...].astype(jnp.float32)            # (R, W1_t, L*K)
    kk = taps.shape[-1] // len(bounds)
    parts = []
    for li, (off, w2p) in enumerate(bounds):
        j = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w2p), 2).astype(jnp.float32)
        acc = jnp.zeros(taps.shape[:2] + (w2p,), jnp.float32)
        for ki in range(kk):
            t = taps[:, :, li * kk + ki][..., None]
            w = jnp.maximum(0.0, 1.0 - jnp.abs(j - t))
            acc = acc + g[:, :, li * kk + ki][..., None] * w
        parts.append(acc)
    # Grad mass on padded columns lands in rows the caller's concat-pad
    # autodiff discards.
    dvol_ref[...] = jnp.concatenate(parts, axis=-1).astype(dvol_ref.dtype)


def _pad_w1(x, block):
    w1 = x.shape[1]
    pad = (-w1) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x, w1


def preflatten_volume(vol: jax.Array) -> jax.Array:
    """(B, H, W1, W2) -> (B*H, W1p, W2) flattened + W1-padded for the kernel.

    Do this ONCE per volume, outside any iteration loop: the pad is a real
    HBM copy of the whole volume.  Hoisting it here guarantees a single copy
    structurally instead of relying on XLA's loop-invariant code motion to
    lift it out of the GRU scan (measured: XLA does hoist it on TPU today,
    so this is neutral there — but interpret-mode/CPU callers and future
    compiler versions get the guarantee).
    """
    blk = _block_w1(vol.shape[2])
    v, _ = _pad_w1(vol.reshape(vol.shape[0] * vol.shape[1], *vol.shape[2:]),
                   blk)
    return _pad_rows(v)


LANE = 128


def pad_lane(x: jax.Array, axis: int) -> jax.Array:
    """Zero-pad ``axis`` to a lane-width multiple so static slices of a
    level concat are lane-aligned inside the fused kernels; zero columns
    contribute exactly zero to every lookup. Shared by both fused pyramid
    paths (this module's volume lookup and pallas_alt's on-demand one)."""
    pad = (-x.shape[axis]) % LANE
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def bounds_from_widths(w2s) -> tuple:
    """Per-level (offset, width) pairs for a W2-concatenated pyramid."""
    bounds = []
    off = 0
    for w2 in w2s:
        bounds.append((off, w2))
        off += w2
    return tuple(bounds)


def pad_vol_lane(vflat: jax.Array) -> jax.Array:
    """(B*H, W1p, W2) volume level -> lane-multiple W2 (see pad_lane)."""
    return pad_lane(vflat, 2)


def pallas_lookup_flat(vflat: jax.Array, taps: jax.Array) -> jax.Array:
    """Lookup against a :func:`preflatten_volume` result.  taps stays in
    model layout (B, H, W1, K); only the (small) taps tensor is reshaped and
    padded per call.  Single-level special case of the fused pyramid path."""
    return _make_lookup(vflat.shape, (vflat.shape[2],),
                        vflat.dtype.name)(vflat, taps)


def pallas_lookup_pyramid_flat(vcat: jax.Array, taps: jax.Array,
                               w2s: tuple) -> jax.Array:
    """All pyramid levels in ONE kernel call.

    vcat: per-level ``preflatten_volume`` + ``pad_vol_lane`` results
    concatenated along W2; taps: (B, H, W1, L*K) per-level LOCAL taps,
    level-major; w2s: static per-level PADDED widths.
    """
    return _make_lookup(vcat.shape, tuple(w2s), vcat.dtype.name)(vcat, taps)


def pallas_lookup(vol: jax.Array, taps: jax.Array) -> jax.Array:
    """Forward-equivalent of :func:`linear_sample_1d` running as a Pallas TPU
    kernel.  vol: (B, H, W1, W2); taps: (B, H, W1, K) -> (B, H, W1, K) f32.

    Autodiff divergence from the oracle, by design: gradients w.r.t. ``taps``
    are hard zeros (the model detaches disparity every iteration, and the
    reference CUDA op likewise returns no coords grad: core/corr.py:29), and
    forward-mode AD is unsupported (custom_vjp).  Use ``linear_sample_1d`` if
    you need either.  Loop callers should :func:`preflatten_volume` once and
    use :func:`pallas_lookup_flat` per iteration.
    """
    return pallas_lookup_flat(preflatten_volume(vol), taps)


@functools.lru_cache(maxsize=None)
def _make_lookup(vflat_shape, w2s, vol_dtype_name):
    """custom_vjp instance per static (flat shape, level widths, dtype) —
    residuals carry only the taps; the volume's shape/dtype ride in the
    closure."""
    bounds = bounds_from_widths(w2s)

    @jax.custom_vjp
    def f(vflat, taps):
        return _lookup_fwd_impl(vflat, taps, bounds)

    def fwd(vflat, taps):
        return _lookup_fwd_impl(vflat, taps, bounds), taps

    def bwd(taps, g):
        dvflat = _lookup_bwd_impl(taps, g, vflat_shape, vol_dtype_name,
                                  bounds)
        # No coordinate gradient by design (disparity is detached per
        # iteration; the reference kernel likewise returns None:
        # core/corr.py:29).
        return dvflat, jnp.zeros_like(taps)

    f.defvjp(fwd, bwd)
    return f


def _pad_taps(taps, nrows=None):
    """(B, H, W1, K) -> (nrows, W1p, K) matching the flat operand's row pad."""
    b, h, w1, kk = taps.shape
    blk = _block_w1(w1)
    t, _ = _pad_w1(taps.reshape(b * h, w1, kk), blk)
    t = _pad_rows(t)
    if nrows is not None and t.shape[0] != nrows:
        raise ValueError(f"taps rows {t.shape[0]} != flat rows {nrows}; "
                         "was the flat operand preflattened with a "
                         "different batch/height?")
    return t, blk


def _lookup_fwd_impl(vflat, taps, bounds):
    vflat = _pad_rows(vflat)  # no-op for preflatten_volume outputs
    n, w1p, w2 = vflat.shape
    b, h, w1, kk = taps.shape
    t, blk = _pad_taps(taps, n)
    r = _BLOCK_ROWS
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, bounds=bounds),
        out_shape=jax.ShapeDtypeStruct((n, w1p, kk), jnp.float32),
        grid=(n // r, w1p // blk),
        in_specs=[
            pl.BlockSpec((r, blk, w2), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, blk, kk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, blk, kk), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        name="corr_lookup_fwd",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(vflat, t)
    return out[:b * h, :w1].reshape(b, h, w1, kk)


def _lookup_bwd_impl(taps, g, vflat_shape, vol_dtype_name, bounds):
    n0, w1p, w2 = vflat_shape  # the primal's rows (maybe not block-padded)
    n = n0 + (-n0) % _BLOCK_ROWS
    b, h, w1, kk = taps.shape
    t, blk = _pad_taps(taps, n)
    gg, _ = _pad_w1(g.reshape(b * h, w1, kk), blk)
    gg = _pad_rows(gg)
    r = _BLOCK_ROWS
    dvol = pl.pallas_call(
        functools.partial(_lookup_bwd_kernel, bounds=bounds),
        out_shape=jax.ShapeDtypeStruct((n, w1p, w2), jnp.float32),
        grid=(n // r, w1p // blk),
        in_specs=[
            pl.BlockSpec((r, blk, kk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, blk, kk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, blk, w2), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        name="corr_lookup_bwd",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(t, gg)
    return dvol[:n0].astype(vol_dtype_name)
