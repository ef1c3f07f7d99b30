"""Pallas TPU kernel for ON-DEMAND correlation lookup (no precomputed volume).

The reference gestures at this capability twice and ships it broken/slow:
``AlternateCorrBlock`` (``alt_cuda``) raises NotImplementedError and its CUDA
extension is absent (reference: core/corr.py:159-188), while the pure-torch
``alt`` path works but is documented as significantly slower
(reference: README.md:121).  This module is the working TPU form.

Design: the correlation row for a block of W1 pixels is

    M[x1, j] = <fmap1[x1, :], fmap2[j, :]> / sqrt(C)

— a (blk x C) @ (C x W2) matmul that fits in VMEM and runs on the MXU.  Each
kernel invocation recomputes its block's rows on the fly, applies the same
hat-weight tap reduction as the precomputed-volume kernel (ops/pallas_corr.py)
and throws the rows away: HBM never holds more than the O(H*W) feature
pyramids, yet the inner loop is MXU matmul + VPU reduction instead of the
XLA gather chain the ``alt`` backend lowers to.

Backward (for completeness/training) fuses the volume-gradient expansion with
the feature-gradient matmuls per block:

    dM[x1, j]   = sum_k g[x1, k] * hat(j - t_k(x1)) * scale
    dfmap1      = dM @ fmap2            (per block, written directly)
    dfmap2     += dM^T @ fmap1_block    (accumulated across W1 blocks in the
                                         output block, relying on the TPU
                                         grid's sequential iteration order)

so the O(W1*W2) gradient also never reaches HBM.  Tap gradients are hard
zeros (disparity is detached every iteration; reference: core/raft_stereo.py:109).
Supports fp32 and bf16 feature maps; accumulation is always fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_corr import (_BLOCK_ROWS, _COMPILER_PARAMS, _block_w1,
                          _interpret, _pad_rows, _pad_taps, _pad_w1,
                          bounds_from_widths, pad_lane)


def _dot(a, b, dims, prec: str):
    """dot_general with a precision POLICY string, not a lax.Precision:
    Mosaic only lowers DEFAULT and HIGHEST, so the 3-pass "high" form
    (jax.lax.Precision.HIGH outside kernels) is built manually — split each
    fp32 operand into a bf16 head + bf16 residual and sum the three
    significant cross products (hi*hi + hi*lo + lo*hi), which is exactly
    XLA's bf16x3 emulation.  bf16 operands always take the native single
    pass regardless of the policy."""
    if a.dtype != jnp.float32 or prec == "default":
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.DEFAULT)
    if prec == "highest":
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.HIGHEST)
    a_hi = a.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def d(x, y):
        return jax.lax.dot_general(x, y, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.DEFAULT)

    return d(a_hi, b_hi) + d(a_hi, b_lo) + d(a_lo, b_hi)


def _alt_pyr_fwd_kernel(f1_ref, f2_ref, taps_ref, out_ref, *, scale, bounds,
                        prec="highest"):
    """Fused all-levels lookup: the fmap2 pyramid is concatenated along W2
    and every level's taps are resolved against one (blk x W2cat) matmul:
    out[x1, l*K + k] = sum_j M_l[x1, j] * hat(j - taps[x1, l*K + k]).

    One kernel launch per (row, w1-block) instead of one per level.
    ``bounds`` is a static tuple of (offset, width) per level; static
    lane-aligned slices of the matmul result keep each tap's hat reduction
    inside its own level (see the body comment), so zero-outside semantics
    at level edges are preserved exactly. The single-level
    ``pallas_alt_lookup`` path is this same kernel with bounds=((0, w2),).
    """
    # Feed the MXU the stored dtype directly: bf16 inputs take the native
    # bf16 path with fp32 accumulation (multi-pass emulation on bf16 inputs
    # would be pure waste); fp32 inputs use the requested emulation depth
    # ("highest" = exact 6-pass, "high" = 3-pass at half the MXU cost;
    # see _dot).
    f1 = f1_ref[...]                              # (R, blk, C)
    f2 = f2_ref[...]                              # (R, W2cat, C)
    taps = taps_ref[...].astype(jnp.float32)      # (R, blk, L*K)
    m = _dot(f1, f2, (((2,), (2,)), ((0,), (0,))),
             prec) * scale                        # (R, blk, W2cat)
    kk = taps.shape[-1] // len(bounds)
    cols = []
    for li, (off, w2p) in enumerate(bounds):
        # Static lane-aligned slice: each tap's hat reduction sweeps only
        # its own level's columns (masking the full concat row costs L x
        # the VPU work; unaligned slices cost lane-realignment copies —
        # both measured slower than per-level kernel launches). Levels are
        # zero-padded to lane multiples, and a padded column's m is exactly
        # zero, so no mask is needed for correct zero-outside semantics.
        ml = m[:, :, off:off + w2p]
        j = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w2p), 2).astype(jnp.float32)
        for ki in range(kk):                      # L*K is small: unrolled
            t = taps[:, :, li * kk + ki][..., None]
            w = jnp.maximum(0.0, 1.0 - jnp.abs(j - t))
            cols.append(jnp.sum(ml * w, axis=-1))  # (R, blk)
    out_ref[...] = jnp.stack(cols, axis=-1).astype(out_ref.dtype)


def _radial_cols(f1_ref, f2_ref, x_ref, *, scale, bounds, radius, prec,
                 level_scales):
    """Shared core of the radial kernels: the per-tap column list.

    Taps are x + k for k in [-radius, radius], so every tap of a level
    shares floor(x)/frac(x).  Instead of K dense hat sweeps (~6 VPU ops
    per column-visit), sweep K+1 integer WINDOWS
    win[d] = M[x1, floor(x)+d-radius] (~3 ops per visit: one shared integer
    offset, then compare + masked-accumulate per window) and lerp
    per-pixel:  out_k = (1-f)*win[k] + f*win[k+1].  Algebraically identical
    to the hat form — hat(j - (b0+f+k-r)) is nonzero exactly at
    j = b0+k-r (weight 1-f) and j+1 (weight f) — including zero-outside
    edges (out-of-range windows sum nothing) and NaN coords (f = NaN
    poisons the lerp).  ~1.7x fewer VPU ops on the kernel's dominant
    cost."""
    f1 = f1_ref[...]                              # (R, blk, C)
    f2 = f2_ref[...]                              # (R, W2cat, C)
    x = x_ref[...].astype(jnp.float32)            # (R, blk, L)
    m = _dot(f1, f2, (((2,), (2,)), ((0,), (0,))),
             prec) * scale                        # (R, blk, W2cat)
    kk = 2 * radius + 1
    cols = []
    for li, (off, w2p) in enumerate(bounds):
        ml = m[:, :, off:off + w2p]
        # level_scales (static): x carries only the LEVEL-0 center and the
        # per-level locals are derived in-register — the (B, H, W1, L)
        # center tensor cost 28 us/iter of 24 GB/s loop fusion outside.
        xl = (x[:, :, li] if level_scales is None
              else x[:, :, 0] * level_scales[li])
        b0 = jnp.floor(xl)
        f = xl - b0                               # (R, blk)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w2p), 2)
        z = j - b0.astype(jnp.int32)[..., None] + radius   # (R, blk, w2p)
        wins = [jnp.sum(jnp.where(z == d, ml, 0.0), axis=-1)
                for d in range(kk + 1)]           # each (R, blk)
        for ki in range(kk):
            cols.append(wins[ki] * (1.0 - f) + wins[ki + 1] * f)
    return cols


def _alt_pyr_radial_kernel(f1_ref, f2_ref, x_ref, out_ref, *, scale, bounds,
                           radius, prec="highest", level_scales=None):
    """Radial lookup emitting the raw correlation features."""
    cols = _radial_cols(f1_ref, f2_ref, x_ref, scale=scale, bounds=bounds,
                        radius=radius, prec=prec, level_scales=level_scales)
    # Zero channel padding up to the declared output width: a 36-lane
    # tensor makes the consuming 1x1 conv's fusion read at ~39 GB/s
    # (measured 60 us/iter); emitting a lane-friendly channel count is
    # free here and the consumer zero-pads its weights to match.
    while len(cols) < out_ref.shape[-1]:
        cols.append(jnp.zeros_like(cols[0]))
    out_ref[...] = jnp.stack(cols, axis=-1).astype(out_ref.dtype)


def _alt_pyr_radial_epi_kernel(f1_ref, f2_ref, x_ref, ew_ref, eb_ref,
                               out_ref, *, scale, bounds, radius,
                               prec="highest", level_scales=None):
    """Radial lookup with the motion encoder's convc1 fused as an
    epilogue: out = relu(cols @ W + b), the 1x1 (L*K -> 64) conv that
    otherwise re-reads the correlation features from HBM at 75 GB/s
    (60 us/iter, round-5 trace).  The dot runs in the consumer's compute
    dtype exactly like the module path (PointwisePaddedConv casts its
    input and kernel to the model dtype and adds bias in that dtype), so
    the fused numerics mirror the unfused ones; inference-only (the
    backward keeps the module conv — see make_pallas_alt_corr_fn)."""
    cols = _radial_cols(f1_ref, f2_ref, x_ref, scale=scale, bounds=bounds,
                        radius=radius, prec=prec, level_scales=level_scales)
    ew = ew_ref[...]                               # (L*K, Co) compute dtype
    z = jnp.stack(cols, axis=-1).astype(ew.dtype)  # (R, blk, L*K)
    pp = (jax.lax.Precision.HIGHEST if ew.dtype == jnp.float32 else None)
    y = jax.lax.dot_general(z, ew, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=pp)
    y = y.astype(ew.dtype) + eb_ref[...].astype(ew.dtype)  # eb (1, 1, Co)
    out_ref[...] = jnp.maximum(y, 0).astype(out_ref.dtype)


def _alt_pyr_bwd_kernel(f1_ref, f2_ref, taps_ref, g_ref, df1_ref, df2_ref, *,
                        scale, bounds, prec="highest"):
    f1 = f1_ref[...]                              # (R, blk, C)
    f2 = f2_ref[...]                              # (R, W2cat, C)
    taps = taps_ref[...].astype(jnp.float32)      # (R, blk, L*K)
    g = g_ref[...].astype(jnp.float32)            # (R, blk, L*K)
    kk = taps.shape[-1] // len(bounds)
    parts = []
    for li, (off, w2p) in enumerate(bounds):
        j = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w2p), 2).astype(jnp.float32)
        dml = jnp.zeros(taps.shape[:2] + (w2p,), jnp.float32)
        for ki in range(kk):
            t = taps[:, :, li * kk + ki][..., None]
            w = jnp.maximum(0.0, 1.0 - jnp.abs(j - t))
            dml = dml + g[:, :, li * kk + ki][..., None] * w
        parts.append(dml)
    # Gradient mass landing on a level's zero-padded columns (a tap within 1
    # of the level edge) flows into df2 rows that the caller's concat-pad
    # autodiff discards — matching the per-level kernels exactly.
    dm = (jnp.concatenate(parts, axis=-1) * scale).astype(f1.dtype)
    df1_ref[...] = _dot(dm, f2, (((2,), (1,)), ((0,), (0,))),
                        prec).astype(df1_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        df2_ref[...] = jnp.zeros_like(df2_ref[...])

    df2_ref[...] += _dot(dm, f1, (((1,), (1,)), ((0,), (0,))),
                         prec).astype(df2_ref.dtype)


def preflatten_fmap1(fmap1: jax.Array) -> jax.Array:
    """(B, H, W1, C) -> (B*H, W1p, C) flattened + W1-padded for the kernel.
    Do once outside any loop — the pad is an HBM copy; hoisting here makes
    the single copy structural (same rationale as
    pallas_corr.preflatten_volume)."""
    f1, _ = _pad_w1(
        fmap1.reshape(fmap1.shape[0] * fmap1.shape[1], *fmap1.shape[2:]),
        _block_w1(fmap1.shape[2]))
    return _pad_rows(f1)


def preflatten_fmap2(fmap2: jax.Array) -> jax.Array:
    """(B, H, W2, C) -> (B*Hp, W2, C); W2 unpadded (rides whole in VMEM),
    rows padded to the kernel row-block like preflatten_fmap1."""
    return _pad_rows(
        fmap2.reshape(fmap2.shape[0] * fmap2.shape[1], *fmap2.shape[2:]))


def pallas_alt_lookup_flat(f1flat: jax.Array, f2flat: jax.Array,
                           taps: jax.Array,
                           precision: str = "highest") -> jax.Array:
    """Lookup against preflattened feature maps; taps stay in model layout
    (B, H, W1, K) and are the only tensor reshaped per call. Single-level
    special case of the fused pyramid kernel."""
    return _make_alt_pyr(f1flat.shape, f2flat.shape, (f2flat.shape[1],),
                         f1flat.dtype.name, f2flat.dtype.name, precision)(
                             f1flat, f2flat, taps)


def pallas_alt_lookup(fmap1: jax.Array, fmap2: jax.Array,
                      taps: jax.Array) -> jax.Array:
    """On-demand correlation at the given taps.

    fmap1: (B, H, W1, C); fmap2: (B, H, W2, C) (same level resolution);
    taps: (B, H, W1, K) absolute x-coordinates into W2.
    Returns (B, H, W1, K) float32, scaled by 1/sqrt(C), zero outside
    [0, W2-1], align-corners linear interpolation — the exact semantics of
    the ``reg``/``alt`` backends (cross-checked in tests/test_pallas_alt.py).
    Loop callers should preflatten once and use the ``_flat`` variant.
    """
    return pallas_alt_lookup_flat(preflatten_fmap1(fmap1),
                                  preflatten_fmap2(fmap2), taps)


def pad_w2_lane(f2flat: jax.Array) -> jax.Array:
    """(B*H, W2, C) level -> lane-multiple W2 (pallas_corr.pad_lane); zero
    rows correlate to exactly zero, so padding never changes a lookup."""
    return pad_lane(f2flat, 1)


def pallas_alt_pyramid_flat(f1flat: jax.Array, f2cat: jax.Array,
                            taps: jax.Array, w2s: tuple,
                            precision: str = "highest",
                            out_dtype=jnp.float32) -> jax.Array:
    """All pyramid levels in ONE kernel call.

    f1flat: (B*H, W1p, C) from preflatten_fmap1; f2cat: (B*H, sum(w2s), C) —
    the per-level preflattened, ``pad_w2_lane``-padded fmap2 pyramid
    concatenated along W2; taps: (B, H, W1, L*K) per-level LOCAL tap
    coordinates, level-major; w2s: static per-level PADDED widths (each a
    lane multiple). Returns (B, H, W1, L*K) in ``out_dtype`` (fp32
    accumulation in-kernel; emitting bf16 directly saves the model's
    post-lookup convert + one HBM round trip) with the exact per-level
    ``pallas_alt_lookup`` semantics (equivalence pinned in
    tests/test_pallas_alt.py).
    """
    return _make_alt_pyr(f1flat.shape, f2cat.shape, tuple(w2s),
                         f1flat.dtype.name, f2cat.dtype.name, precision,
                         jnp.dtype(out_dtype).name)(f1flat, f2cat, taps)


def pallas_alt_pyramid_radial_flat(f1flat: jax.Array, f2cat: jax.Array,
                                   x_levels: jax.Array, w2s: tuple,
                                   radius: int,
                                   precision: str = "highest",
                                   out_dtype=jnp.float32,
                                   out_channels: int = 0,
                                   level_scales: tuple = None) -> jax.Array:
    """Model-pattern variant of :func:`pallas_alt_pyramid_flat`: instead of
    explicit per-tap coordinates it takes the per-level LOCAL center
    ``x_levels`` (B, H, W1, L) and the static ``radius``, and resolves the
    taps ``x + k, k in [-radius, radius]`` with the cheaper shared-fraction
    window kernel.  Output channel order and semantics are identical to the
    general entry with ``taps = x[..., None] + arange(-r, r+1)``
    (equivalence pinned in tests/test_pallas_alt.py).

    ``out_channels`` (when > L*K) zero-pads the channel axis in-kernel so
    consumers read a lane-friendly width (see the kernel comment).

    ``level_scales`` (static tuple of floats): when given, ``x_levels``
    carries a SINGLE channel — the level-0 center — and each level's
    local center is derived in-kernel as x * level_scales[l], removing
    the per-level center tensor from HBM entirely (the model's pattern:
    scales 2**-l)."""
    return _make_alt_pyr_radial(f1flat.shape, f2cat.shape, tuple(w2s),
                                radius, f1flat.dtype.name, f2cat.dtype.name,
                                precision, jnp.dtype(out_dtype).name,
                                out_channels,
                                tuple(level_scales)
                                if level_scales is not None
                                else None)(f1flat, f2cat, x_levels)


def pallas_alt_pyramid_radial_epi_flat(f1flat, f2cat, x_levels, w2s, radius,
                                       ew, eb,
                                       precision: str = "highest",
                                       out_dtype=jnp.float32,
                                       level_scales: tuple = None):
    """Radial pyramid lookup with a fused 1x1-conv + relu epilogue
    (the motion encoder's convc1): returns relu(corr @ ew + eb) directly,
    (B, H, W1, Co).  ``ew`` is (L*K, Co) in the compute dtype, ``eb``
    (1, 1, Co).  Inference-only — no VJP is defined (training keeps the
    module conv; the gate lives in the model, models/raft_stereo.py)."""
    bounds = bounds_from_widths(tuple(w2s))
    return _alt_pyr_radial_fwd_impl(
        f1flat, f2cat, x_levels, bounds, radius, precision,
        jnp.dtype(out_dtype), 0,
        tuple(level_scales) if level_scales is not None else None,
        epilogue=(ew, eb))


@functools.lru_cache(maxsize=None)
def _make_alt_pyr_radial(f1flat_shape, f2cat_shape, w2s, radius, f1_dtype,
                         f2_dtype, precision="highest", out_dtype="float32",
                         out_channels=0, level_scales=None):
    bounds = bounds_from_widths(w2s)
    odt = jnp.dtype(out_dtype)

    @jax.custom_vjp
    def f(f1flat, f2cat, x):
        return _alt_pyr_radial_fwd_impl(f1flat, f2cat, x, bounds, radius,
                                        precision, odt, out_channels,
                                        level_scales)

    def fwd(f1flat, f2cat, x):
        return _alt_pyr_radial_fwd_impl(
            f1flat, f2cat, x, bounds, radius, precision, odt,
            out_channels, level_scales), (f1flat, f2cat, x)

    def bwd(res, g):
        f1flat, f2cat, x = res
        # The general backward kernel already handles arbitrary taps; the
        # radial pattern is just its special case, so materialize the taps
        # (a small XLA broadcast-add on the backward path only).  Channel
        # padding carries no gradient: slice the cotangent back to L*K.
        if level_scales is not None:
            scales = jnp.asarray(level_scales, jnp.float32)
            xl = x.astype(jnp.float32)[..., 0:1] * scales
        else:
            xl = x.astype(jnp.float32)
        lk = xl.shape[-1] * (2 * radius + 1)
        offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
        taps = (xl[..., None] + offsets).reshape(*xl.shape[:-1], lk)
        df1, df2 = _alt_pyr_bwd_impl(f1flat, f2cat, taps, g[..., :lk],
                                     bounds, precision)
        return (df1[:f1flat.shape[0]].astype(f1_dtype),
                df2[:f2cat.shape[0]].astype(f2_dtype),
                jnp.zeros_like(x))

    f.defvjp(fwd, bwd)
    return f


def _alt_pyr_radial_fwd_impl(f1flat, f2cat, x, bounds, radius,
                             prec="highest", out_dtype=jnp.float32,
                             out_channels=0, level_scales=None,
                             epilogue=None):
    f1flat = _pad_rows(f1flat)  # no-ops for preflatten_* outputs
    f2cat = _pad_rows(f2cat)
    n, w1p, c = f1flat.shape
    b, h, w1, nl = x.shape
    t, blk = _pad_taps(x, n)
    scale = 1.0 / float(c) ** 0.5
    w2cat = f2cat.shape[1]
    n_lvl = len(bounds) if level_scales is not None else nl
    lk = max(n_lvl * (2 * radius + 1), out_channels)
    r = _BLOCK_ROWS
    operands = [f1flat, f2cat, t]
    in_specs = [
        pl.BlockSpec((r, blk, c), lambda i, j: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((r, w2cat, c), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((r, blk, nl), lambda i, j: (i, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    if epilogue is None:
        kernel = functools.partial(
            _alt_pyr_radial_kernel, scale=scale, bounds=bounds,
            radius=radius, prec=prec, level_scales=level_scales)
    else:
        ew, eb = epilogue                         # (L*K, Co), (1, 1, Co)
        lk = ew.shape[-1]
        kernel = functools.partial(
            _alt_pyr_radial_epi_kernel, scale=scale, bounds=bounds,
            radius=radius, prec=prec, level_scales=level_scales)
        operands += [ew, eb]
        in_specs += [
            pl.BlockSpec(ew.shape, lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(eb.shape, lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, w1p, lk), out_dtype),
        grid=(n // r, w1p // blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r, blk, lk), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        name="alt_lookup_fwd",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(*operands)
    return out[:b * h, :w1].reshape(b, h, w1, lk)


@functools.lru_cache(maxsize=None)
def _make_alt_pyr(f1flat_shape, f2cat_shape, w2s, f1_dtype, f2_dtype,
                  precision="highest", out_dtype="float32"):
    bounds = bounds_from_widths(w2s)
    prec = precision
    odt = jnp.dtype(out_dtype)

    @jax.custom_vjp
    def f(f1flat, f2cat, taps):
        return _alt_pyr_fwd_impl(f1flat, f2cat, taps, bounds, prec, odt)

    def fwd(f1flat, f2cat, taps):
        return _alt_pyr_fwd_impl(f1flat, f2cat, taps, bounds, prec, odt), (
            f1flat, f2cat, taps)

    def bwd(res, g):
        f1flat, f2cat, taps = res
        df1, df2 = _alt_pyr_bwd_impl(f1flat, f2cat, taps, g, bounds, prec)
        # Row-padding inside the impl is invisible to callers: cotangents
        # are sliced back to the primal row counts.
        return (df1[:f1flat.shape[0]].astype(f1_dtype),
                df2[:f2cat.shape[0]].astype(f2_dtype),
                jnp.zeros_like(taps))

    f.defvjp(fwd, bwd)
    return f


def _alt_pyr_fwd_impl(f1flat, f2cat, taps, bounds, prec="highest",
                      out_dtype=jnp.float32):
    f1flat = _pad_rows(f1flat)  # no-ops for preflatten_* outputs
    f2cat = _pad_rows(f2cat)
    n, w1p, c = f1flat.shape
    b, h, w1, lk = taps.shape
    t, blk = _pad_taps(taps, n)
    scale = 1.0 / float(c) ** 0.5
    w2cat = f2cat.shape[1]
    r = _BLOCK_ROWS
    out = pl.pallas_call(
        functools.partial(_alt_pyr_fwd_kernel, scale=scale, bounds=bounds,
                          prec=prec),
        out_shape=jax.ShapeDtypeStruct((n, w1p, lk), out_dtype),
        grid=(n // r, w1p // blk),
        in_specs=[
            pl.BlockSpec((r, blk, c), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, w2cat, c), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, blk, lk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, blk, lk), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        name="alt_lookup_taps_fwd",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(f1flat, f2cat, t)
    return out[:b * h, :w1].reshape(b, h, w1, lk)


def _alt_pyr_bwd_impl(f1flat, f2cat, taps, g, bounds, prec="highest"):
    f1flat = _pad_rows(f1flat)  # no-ops for preflatten_* outputs
    f2cat = _pad_rows(f2cat)
    n, w1p, c = f1flat.shape
    b, h, w1, lk = taps.shape
    t, blk = _pad_taps(taps, n)
    gg, _ = _pad_w1(g.reshape(b * h, w1, lk), blk)
    gg = _pad_rows(gg)
    scale = 1.0 / float(c) ** 0.5
    w2cat = f2cat.shape[1]
    r = _BLOCK_ROWS
    df1, df2 = pl.pallas_call(
        functools.partial(_alt_pyr_bwd_kernel, scale=scale, bounds=bounds,
                          prec=prec),
        out_shape=(jax.ShapeDtypeStruct((n, w1p, c), jnp.float32),
                   jax.ShapeDtypeStruct((n, w2cat, c), jnp.float32)),
        grid=(n // r, w1p // blk),
        in_specs=[
            pl.BlockSpec((r, blk, c), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, w2cat, c), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, blk, lk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, blk, lk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((r, blk, c), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, w2cat, c), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        name="alt_lookup_bwd",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(f1flat, f2cat, t, gg)
    return df1, df2
