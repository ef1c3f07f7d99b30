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

The served form (the radial kernels, ``alt_lookup_fwd``; _radial_cols) does
only the arithmetic that can change its answer (PR 27, measured on the v5e
at the served shape, PERF.md §5):

- *The windows are read, not summed.*  A pixel's 2r+2 integer windows are
  adjacent columns of its own row of M, so one per-row lane gather of each
  128-column chunk lands them on adjacent lanes.  The masked window sums
  this replaced (ten compare-select-reduce sweeps a level) were the whole
  kernel: 6.9 ms a call with or without any matmul behind them.  The
  centres come in pixels-on-lanes and are broadcast once, which leaves
  the sweep (1.6 ms alone) hidden behind the DMA of the two float32
  feature operands.
- *The matmul runs the passes that can be non-zero.*  A bf16 model hands
  over bf16 features which ops/corr.py widens to float32; a ``highest``
  float32 product is six bf16 passes, and five of them (level 0) or three
  (the pooled levels, float32 on the right only) multiply an all-zero low
  half.  For bf16-born features one native pass and three exact ones give
  the same float32-accumulated result (resolve_corr_matmul, _dot): 1.8 ms
  a call against 3.2 with all six.  Float32-born features keep the
  configured policy; nothing is rounded that is not already exact.

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

from .pallas_corr import (_BLOCK_ROWS, _COMPILER_PARAMS, LANE, _block_w1,
                          _interpret, _pad_rows, _pad_taps, _pad_w1,
                          bounds_from_widths, pad_lane)


EXACT_BF16 = "bf16_exact_1+3"
_LANE_BITS = LANE.bit_length() - 1


def resolve_corr_matmul(feature_dtype, operand_dtype, precision: str) -> str:
    """The form of the radial lookup's on-demand matmul — the ONE resolver:
    the kernel wrapper (_alt_pyr_radial_fwd_impl) asks it which passes to
    run and ``utils/platform.describe_runtime`` prints its answer as the
    ``runtime:`` line's ``corr_matmul``.

    ``feature_dtype`` is the dtype the features were BORN in (what the
    encoder handed over, before ops/corr.py widened them; None = the
    operands' own), ``operand_dtype`` the dtype the kernel's operands are
    stored in, ``precision`` the configured float32 policy.

    - bf16 operands: one native pass (``bf16_native``).
    - float32 operands that are bf16-born, policy ``highest``: fmap1 and
      level 0 of the fmap2 pyramid hold bf16 values exactly, so of the six
      bf16 passes that emulate a float32 product only one (level 0) or
      three (the pooled levels, whose RIGHT operand is a true float32) can
      be non-zero.  Only those run (``bf16_exact_1+3``, see _dot); the
      result is the ``highest`` one up to the order of float32 sums.
    - anything else: the configured policy on float32 (``f32_<policy>``).
    """
    if jnp.dtype(operand_dtype) == jnp.bfloat16:
        return "bf16_native"
    if (precision == "highest" and feature_dtype is not None
            and jnp.dtype(feature_dtype) == jnp.bfloat16):
        return EXACT_BF16
    return f"f32_{precision}"


def _split3(x):
    """float32 -> three bf16 pieces with hi + mid + lo == x exactly (8+8+8
    significand bits; each remainder is representable, so the subtractions
    do not round).  Holds for every float32 whose head does not round up
    to infinity and whose last bit is a normal bf16, 2**-102 <= |x| <
    3.39e38 or x == 0: correlation features are O(1)."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot(a, b, dims, prec: str):
    """dot_general with a precision POLICY string, not a lax.Precision:
    Mosaic only lowers DEFAULT and HIGHEST, so the 3-pass "high" form
    (jax.lax.Precision.HIGH outside kernels) is built manually — split each
    fp32 operand into a bf16 head + bf16 residual and sum the three
    significant cross products (hi*hi + hi*lo + lo*hi), which is exactly
    XLA's bf16x3 emulation.  bf16 operands always take the native single
    pass regardless of the policy.

    MIXED operands (bf16 against float32) are the EXACT form, whatever the
    policy: the float32 side is split three ways (_split3) and each piece
    takes one native pass against the bf16 side.  bf16 x bf16 products are
    exact in float32 and the MXU accumulates in float32, so this is the
    six-pass ``highest`` product of the same values with the three passes
    that would multiply the bf16 side's all-zero low half left out — equal
    to it up to the order of float32 sums."""
    def d(x, y):
        return jax.lax.dot_general(x, y, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.DEFAULT)

    if a.dtype != b.dtype:
        assert (a.dtype, b.dtype) == (jnp.bfloat16, jnp.float32), (a, b)
        hi, mid, lo = (d(a, p) for p in _split3(b))
        return (hi + mid) + lo
    if a.dtype != jnp.float32 or prec == "default":
        return d(a, b)
    if prec == "highest":
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.HIGHEST)
    a_hi = a.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)
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
                 level_scales, exact_bf16=False):
    """Shared core of the radial kernels: the (R*blk, LANE) slab whose lane
    l*K + k holds level l's tap k (K = 2*radius + 1), every other lane 0.

    Taps are x + k for k in [-radius, radius], so every tap of a level
    shares floor(x)/frac(x): out_k = (1-f)*win[k] + f*win[k+1] with the K+1
    integer WINDOWS win[d] = M[x1, floor(x)+d-radius].  Algebraically
    identical to the hat form — hat(j - (b0+f+k-r)) is nonzero exactly at
    j = b0+k-r (weight 1-f) and j+1 (weight f) — including zero-outside
    edges (an out-of-range window is 0) and NaN coords (f = NaN poisons
    the lerp).

    The windows are READ, not summed: a pixel's K+1 windows are adjacent
    columns of its own row of M, so one per-row lane gather of each
    128-column chunk (take_along_axis -> Mosaic's dynamic_gather) lands
    them on K+1 adjacent lanes, already at the level's place in the output
    (lane l*K + d reads column b0 - r + d).  That is ~10 VPU operations and
    one gather per (8 pixels x 128 columns) of M where the masked window
    sums this replaces spent ~31 and forty cross-lane reductions per
    8 pixels — all of the kernel's time (PERF.md §5, PR 27).  A window
    sum has one non-zero term, so reading it is the same float32 value.

    The matmul (``exact_bf16``; resolve_corr_matmul): when the features
    are bf16-born, fmap1 and the level-0 columns of fmap2 are cast back to
    bf16 (exact) and take ONE native pass; the pooled levels, true
    float32, take the exact three (_dot's mixed form)."""
    f1 = f1_ref[...]                              # (R, blk, C)
    f2 = f2_ref[...]                              # (R, W2cat, C)
    r, blk = f1.shape[:2]
    p = r * blk

    def centre(li):
        # The centres arrive pixels-on-lanes, (R, blk) a level: a block
        # with the pixels on sublanes and ONE lane is 1,920 four-byte DMA
        # rows a grid step, 0.63 ms of a 2.04-ms call (PERF.md §5).  One
        # small transpose puts them where the lane gather wants them, and
        # ONE lane broadcast serves everything derived from them below
        # (floor, fraction, columns, lerp weights: all full-width
        # elementwise; a broadcast per use kept the cross-lane unit busier
        # than the gathers did).
        xt = x_ref[li].astype(jnp.float32).T      # (blk, R)
        col = jnp.concatenate([xt[:, i:i + 1] for i in range(r)], axis=0)
        return jnp.broadcast_to(col, (p, LANE))

    dims = (((2,), (2,)), ((0,), (0,)))
    if exact_bf16:
        w0 = bounds[0][1]
        f1 = f1.astype(jnp.bfloat16)
        parts = [_dot(f1, f2[:, :w0].astype(jnp.bfloat16), dims, prec)]
        if len(bounds) > 1:
            parts.append(_dot(f1, f2[:, w0:], dims, prec))
    else:
        parts = [_dot(f1, f2, dims, prec)]        # (R, blk, W2cat)
    # M as LANE-column chunks, pixels flattened onto sublanes (blk is a
    # multiple of 8, so merging the leading axes moves nothing).
    chunks = [part[:, :, i:i + LANE].reshape(p, LANE)
              for part in parts for i in range(0, part.shape[-1], LANE)]
    kk = 2 * radius + 1
    assert len(bounds) * kk < LANE, (len(bounds), radius)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    out = jnp.zeros((p, LANE), jnp.float32)
    for li, (off, w2p) in enumerate(bounds):
        assert off % LANE == 0 and w2p % LANE == 0, bounds
        # level_scales (static): x carries only the LEVEL-0 center and the
        # per-level locals are derived in-register — the (B, H, W1, L)
        # center tensor cost 28 us/iter of 24 GB/s loop fusion outside.
        if level_scales is None:
            xl = centre(li)                       # (P, LANE), lanes equal
        else:
            if li == 0:
                x0 = centre(0)
            xl = x0 * level_scales[li]
        b0 = jnp.floor(xl)
        f = xl - b0
        # Column each lane reads.  A column outside [0, w2p) matches no
        # chunk below and stays 0; padded columns inside it hold m == 0.
        col = lane + (b0.astype(jnp.int32) - (radius + li * kk))
        src = col & (LANE - 1)
        win = jnp.zeros((p, LANE), jnp.float32)
        for c in range(w2p // LANE):
            g = jnp.take_along_axis(chunks[off // LANE + c], src, axis=1,
                                    mode="promise_in_bounds")
            win = jnp.where((col >> _LANE_BITS) == c, g, win)
        win = win * scale
        nxt = pltpu.roll(win, LANE - 1, 1)        # lane j <- win[j + 1]
        val = win * (1.0 - f) + nxt * f
        out = jnp.where((lane >= li * kk) & (lane < (li + 1) * kk), val, out)
    return out


def _alt_pyr_radial_kernel(f1_ref, f2_ref, x_ref, out_ref, *, scale, bounds,
                           radius, prec="highest", level_scales=None,
                           exact_bf16=False):
    """Radial lookup emitting the raw correlation features."""
    cols = _radial_cols(f1_ref, f2_ref, x_ref, scale=scale, bounds=bounds,
                        radius=radius, prec=prec, level_scales=level_scales,
                        exact_bf16=exact_bf16)
    # Zero channel padding up to the declared output width: a 36-lane
    # tensor makes the consuming 1x1 conv's fusion read at ~39 GB/s
    # (measured 60 us/iter); emitting a lane-friendly channel count is
    # free here (the slab's spare lanes are zeros) and the consumer
    # zero-pads its weights to match.
    out_ref[...] = cols[:, :out_ref.shape[-1]].reshape(
        out_ref.shape).astype(out_ref.dtype)


def _alt_pyr_radial_epi_kernel(f1_ref, f2_ref, x_ref, ew_ref, eb_ref,
                               out_ref, *, scale, bounds, radius,
                               prec="highest", level_scales=None,
                               exact_bf16=False):
    """Radial lookup with the motion encoder's convc1 fused as an
    epilogue: out = relu(cols @ W + b), the 1x1 (L*K -> 64) conv that
    otherwise re-reads the correlation features from HBM at 75 GB/s
    (60 us/iter, round-5 trace).  The dot runs in the consumer's compute
    dtype exactly like the module path (PointwisePaddedConv casts its
    input and kernel to the model dtype and adds bias in that dtype), so
    the fused numerics mirror the unfused ones; inference-only (the
    backward keeps the module conv — see make_pallas_alt_corr_fn)."""
    cols = _radial_cols(f1_ref, f2_ref, x_ref, scale=scale, bounds=bounds,
                        radius=radius, prec=prec, level_scales=level_scales,
                        exact_bf16=exact_bf16)
    ew = ew_ref[...]                               # (L*K, Co) compute dtype
    r, blk = out_ref.shape[:2]
    z = cols[:, :ew.shape[0]].astype(ew.dtype).reshape(r, blk, ew.shape[0])
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
                                   level_scales: tuple = None,
                                   feature_dtype=None) -> jax.Array:
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
    scales 2**-l).

    ``feature_dtype``: the dtype the features were born in, when the
    caller widened them to the operands' float32 (ops/corr.py).  bfloat16
    promises that ``f1flat`` and the level-0 columns of ``f2cat`` hold
    bf16 values exactly, and the forward matmul then runs only the passes
    that can be non-zero (resolve_corr_matmul); the backward is the
    configured ``precision`` either way."""
    return _make_alt_pyr_radial(f1flat.shape, f2cat.shape, tuple(w2s),
                                radius, f1flat.dtype.name, f2cat.dtype.name,
                                precision, jnp.dtype(out_dtype).name,
                                out_channels,
                                tuple(level_scales)
                                if level_scales is not None
                                else None,
                                feature_dtype and jnp.dtype(
                                    feature_dtype).name)(
                                    f1flat, f2cat, x_levels)


def pallas_alt_pyramid_radial_epi_flat(f1flat, f2cat, x_levels, w2s, radius,
                                       ew, eb,
                                       precision: str = "highest",
                                       out_dtype=jnp.float32,
                                       level_scales: tuple = None,
                                       feature_dtype=None):
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
        epilogue=(ew, eb), feature_dtype=feature_dtype)


@functools.lru_cache(maxsize=None)
def _make_alt_pyr_radial(f1flat_shape, f2cat_shape, w2s, radius, f1_dtype,
                         f2_dtype, precision="highest", out_dtype="float32",
                         out_channels=0, level_scales=None,
                         feature_dtype=None):
    bounds = bounds_from_widths(w2s)
    odt = jnp.dtype(out_dtype)

    @jax.custom_vjp
    def f(f1flat, f2cat, x):
        return _alt_pyr_radial_fwd_impl(f1flat, f2cat, x, bounds, radius,
                                        precision, odt, out_channels,
                                        level_scales,
                                        feature_dtype=feature_dtype)

    def fwd(f1flat, f2cat, x):
        return _alt_pyr_radial_fwd_impl(
            f1flat, f2cat, x, bounds, radius, precision, odt,
            out_channels, level_scales,
            feature_dtype=feature_dtype), (f1flat, f2cat, x)

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
                             epilogue=None, feature_dtype=None):
    f1flat = _pad_rows(f1flat)  # no-ops for preflatten_* outputs
    f2cat = _pad_rows(f2cat)
    n, w1p, c = f1flat.shape
    b, h, w1, nl = x.shape
    t, blk = _pad_taps(x, n)
    t = jnp.moveaxis(t, 2, 0)   # (L, rows, W1p): free for the one-channel form
    scale = 1.0 / float(c) ** 0.5
    w2cat = f2cat.shape[1]
    n_lvl = len(bounds) if level_scales is not None else nl
    lk = max(n_lvl * (2 * radius + 1), out_channels)
    r = _BLOCK_ROWS
    exact_bf16 = resolve_corr_matmul(feature_dtype, f1flat.dtype,
                                     prec) == EXACT_BF16
    operands = [f1flat, f2cat, t]
    in_specs = [
        pl.BlockSpec((r, blk, c), lambda i, j: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((r, w2cat, c), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((nl, r, blk), lambda i, j: (0, i, j),
                     memory_space=pltpu.VMEM),
    ]
    if epilogue is None:
        assert lk <= LANE, lk
        kernel = functools.partial(
            _alt_pyr_radial_kernel, scale=scale, bounds=bounds,
            radius=radius, prec=prec, level_scales=level_scales,
            exact_bf16=exact_bf16)
    else:
        ew, eb = epilogue                         # (L*K, Co), (1, 1, Co)
        lk = ew.shape[-1]
        kernel = functools.partial(
            _alt_pyr_radial_epi_kernel, scale=scale, bounds=bounds,
            radius=radius, prec=prec, level_scales=level_scales,
            exact_bf16=exact_bf16)
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
