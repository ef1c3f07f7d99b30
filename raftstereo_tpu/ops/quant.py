"""Int8 quantized correlation + the serving accuracy-tier vocabulary.

The arithmetic-intensity lever that is left once the convolutions are at
their ceiling is precision.  This module supplies the numeric core of the
quantized serving fast path (what a tier earns on the chip is not
measured: no benchmark cell runs one):

* **symmetric int8 row quantization** of the left/right feature maps.  One
  scale per correlation ROW (each (b, h, w) feature vector — the matmul
  row/column of the all-pairs product), NOT per contraction channel: a
  per-channel scale sits inside the channel sum and cannot be pulled out
  of the int32 accumulator, while per-row scales factor exactly —
  ``corr[w, v] = s1[w] * s2[v] * sum_c q1[w, c] * q2[v, c]`` — which is
  what lets the dequant run as a cheap epilogue on the int32 output.
* **int8 x int8 -> int32 all-pairs correlation** with that dequant
  epilogue, as a plain XLA einsum (CPU + fallback) and as a Pallas TPU
  kernel (MXU-native int8 pass, 4x the bf16 multiply rate).  Both paths
  apply the identical epilogue expression, so the kernel is
  bitwise-comparable to the XLA path in interpret mode
  (tests/test_quant.py).
* **the accuracy-tier vocabulary** shared by the serving engine, the
  certification harness (eval/certify.py) and the HTTP layer:
  per-request ``accuracy`` tiers resolve to a *precision mode* that joins
  every executable cache key (serve/engine.py):

      certified -> fp32   (the certified-parity path: fp32 everywhere)
      fast      -> bf16   (bf16 encoders/GRU + bf16 correlation)
      turbo     -> int8   (bf16 compute + int8-quantized correlation)

The quantization error is the int8 rounding only — the epilogue algebra
is exact (asserted bit-for-bit on exactly-representable inputs in
tests/test_quant.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_corr import _COMPILER_PARAMS, _interpret

__all__ = ["MODES", "TIERS", "TIER_MODES", "config_for_mode",
           "default_mode", "mode_for_accuracy", "pallas_int8_corr_volume",
           "quant_corr_volume", "quantize_rows"]


# --------------------------------------------------------------------- tiers

# Request-facing tier names, in decreasing accuracy order.
TIERS = ("certified", "fast", "turbo")

# tier -> the precision mode that joins the executable cache key.
TIER_MODES = {"certified": "fp32", "fast": "bf16", "turbo": "int8"}

# Every precision mode an engine can compile (the cache-key component).
MODES = ("fp32", "bf16", "int8")


def mode_for_accuracy(accuracy: str) -> str:
    """Precision mode for a request's ``accuracy`` tier; raises
    ``ValueError`` on an unknown tier (HTTP 400 at the front-end)."""
    try:
        return TIER_MODES[accuracy]
    except KeyError:
        raise ValueError(
            f"unknown accuracy tier {accuracy!r}; choose from "
            f"{list(TIERS)}") from None


def default_mode(config) -> str:
    """The precision-mode key component of a model config's OWN
    executables — the mode of every request that carries no ``accuracy``
    field, so the default path's executables (and numerics) are untouched
    by the tier system.

    A config aliases onto a tier mode ONLY when it is exactly that
    mode's canonical config (``config_for_mode`` round-trips) — then an
    explicit tier request may share the base executables.  Any other
    numeric mix (e.g. fp32 compute with a bf16 correlation volume)
    returns the distinct ``"base"`` token: its numerics match no
    certified tier, so e.g. ``accuracy="certified"`` must compile the
    true fp32 program rather than silently serving the base one."""
    if getattr(config, "corr_quant", False):
        mode = "int8"
    elif config.compute_dtype == "bfloat16":
        mode = "bf16"
    else:
        mode = "fp32"
    return mode if config_for_mode(config, mode) == config else "base"


def config_for_mode(config, mode: str):
    """The model config a precision mode compiles with: the ONLY fields a
    tier may change are the numeric-policy ones (compute/corr dtype and
    the int8-corr gate) — architecture, corr backend and GRU backend stay
    the base config's, so every tier shares the base model's weights and
    shape policy."""
    if mode == "fp32":
        return dataclasses.replace(config, compute_dtype="float32",
                                   corr_dtype="float32", corr_quant=False)
    if mode == "bf16":
        return dataclasses.replace(config, compute_dtype="bfloat16",
                                   corr_dtype="bfloat16", corr_quant=False)
    if mode == "int8":
        return dataclasses.replace(config, compute_dtype="bfloat16",
                                   corr_dtype="bfloat16", corr_quant=True)
    raise ValueError(f"unknown precision mode {mode!r}; choose from "
                     f"{list(MODES)}")


# -------------------------------------------------------------- quantization

def quantize_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization with one scale per row (all leading
    axes; the LAST axis is the contraction/feature axis).

    Returns ``(q, scale)`` with ``q`` int8 in [-127, 127] and ``scale``
    fp32 of ``x.shape[:-1]`` such that ``q * scale[..., None] ~= x``.
    All-zero rows get scale 1.0 (and q == 0), so the dequant epilogue
    never divides by or multiplies with a zero scale."""
    f = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(f / scale[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_epilogue(acc: jax.Array, s1: jax.Array, s2: jax.Array,
                      c: int) -> jax.Array:
    """The ONE dequant expression both the XLA and the Pallas paths apply
    to the int32 accumulator — shared so the two are bitwise-comparable:
    ``(acc * (s1 (x) s2)) * (1/sqrt(C))`` with the same association.

    The 1/sqrt(C) normalization is a host-constant MULTIPLY, not a
    divide: XLA's algebraic simplifier rewrites division by a constant
    into multiplication by its reciprocal inside fused programs (e.g.
    the interpret-mode Pallas kernel) but not across eager op
    boundaries, so a divide here would make the two paths differ by an
    ULP.  A multiply is never rewritten — both paths compute identical
    bits.  When sqrt(C) is a power of two (the model's feature dim 256:
    sqrt = 16) the multiply is also bit-identical to
    ``build_corr_volume``'s division."""
    inv = np.float32(1.0) / np.float32(np.sqrt(np.float32(c)))
    deq = acc.astype(jnp.float32) * (s1[..., :, None] * s2[..., None, :])
    return deq * inv


def _int8_volume_xla(q1: jax.Array, s1: jax.Array, q2: jax.Array,
                     s2: jax.Array) -> jax.Array:
    """(B, H, W1, C) x (B, H, W2, C) int8 -> (B, H, W1, W2) fp32 via an
    int8 x int8 -> int32 einsum (XLA lowers this to the MXU's native int8
    pass on TPU and to integer GEMM on CPU) + the dequant epilogue."""
    acc = jnp.einsum("bhwc,bhvc->bhwv", q1, q2,
                     preferred_element_type=jnp.int32)
    return _dequant_epilogue(acc, s1, s2, q1.shape[-1])


# ------------------------------------------------------------- Pallas kernel

# (B*H) rows per grid step — same amortization rationale as
# pallas_corr._BLOCK_ROWS (per-step Mosaic/DMA overhead dominates
# one-row grids).
_BLOCK_ROWS = 8
_LANE = 128


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


def _int8_volume_kernel(q1_ref, q2_ref, s1_ref, s2_ref, out_ref, *, c: int):
    """One R-row block: int8 x int8 -> int32 batched matmul on the MXU,
    dequant epilogue on the VPU.  ``c`` is the REAL (unpadded) channel
    count — the epilogue's 1/sqrt(C); padded channels are zero on both
    operands and contribute exactly nothing to the accumulator."""
    q1 = q1_ref[...]                       # (R, W1p, Cp) int8
    q2 = q2_ref[...]                       # (R, W2p, Cp) int8
    acc = jax.lax.dot_general(
        q1, q2, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)  # (R, W1p, W2p)
    s1 = s1_ref[...].astype(jnp.float32)   # (R, W1p)
    s2 = s2_ref[...].astype(jnp.float32)   # (R, W2p)
    out_ref[...] = _dequant_epilogue(acc, s1, s2, c).astype(out_ref.dtype)


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pallas_int8_corr_volume(q1: jax.Array, s1: jax.Array, q2: jax.Array,
                            s2: jax.Array,
                            out_dtype=jnp.float32) -> jax.Array:
    """Pallas form of :func:`_int8_volume_xla`: (B, H, W1, C) x
    (B, H, W2, C) int8 -> (B, H, W1, W2) ``out_dtype``.

    Grid is row blocks of the flattened (B*H) axis; operands are
    zero-padded to int8-friendly tiles (channels and W2 to lane
    multiples) — padded channels are zero on both sides (accumulate to
    exactly 0) and padded rows/columns carry scale 0 and are sliced off,
    so padding is numerically invisible.  Interpret mode runs the same
    program on CPU (tests/test_quant.py asserts bitwise equality with
    the XLA einsum path there)."""
    b, h, w1, c = q1.shape
    w2 = q2.shape[2]
    assert q2.shape[:2] == (b, h) and q2.shape[3] == c, (q1.shape, q2.shape)
    assert s1.shape == (b, h, w1) and s2.shape == (b, h, w2), (
        s1.shape, s2.shape)
    n = b * h
    cp = _roundup(c, _LANE)
    # W1 is lane-padded too (not just sublane-padded): it is the LAST
    # axis of the s1 scale block, and Mosaic wants lane-dim tiles.
    w1p = _roundup(w1, _LANE)
    w2p = _roundup(w2, _LANE)
    npad = _roundup(n, _BLOCK_ROWS)
    r = _BLOCK_ROWS

    def prep_q(q, wp):
        q = q.reshape(n, q.shape[2], c)
        q = _pad_axis(_pad_axis(q, 1, wp), 2, cp)
        return _pad_axis(q, 0, npad)

    def prep_s(s, wp):
        s = s.reshape(n, s.shape[2])
        return _pad_axis(_pad_axis(s, 1, wp), 0, npad)

    q1f, q2f = prep_q(q1, w1p), prep_q(q2, w2p)
    s1f, s2f = prep_s(s1, w1p), prep_s(s2, w2p)
    out = pl.pallas_call(
        functools.partial(_int8_volume_kernel, c=c),
        out_shape=jax.ShapeDtypeStruct((npad, w1p, w2p), out_dtype),
        grid=(npad // r,),
        in_specs=[
            pl.BlockSpec((r, w1p, cp), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, w2p, cp), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, w1p), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, w2p), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, w1p, w2p), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        name="quant_corr_volume_fwd",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(q1f, q2f, s1f, s2f)
    return out[:n, :w1, :w2].reshape(b, h, w1, w2)


# ---------------------------------------------------------------- public API

def quant_corr_volume(fmap1: jax.Array, fmap2: jax.Array,
                      dtype=jnp.float32,
                      kernel: Optional[bool] = None) -> jax.Array:
    """Quantized drop-in for ``ops/corr.build_corr_volume``: symmetric
    per-row int8 quantization of both feature maps, int8 x int8 -> int32
    all-pairs product, scales folded into the dequant epilogue
    (mathematically ``build_corr_volume`` up to the int8 rounding of the
    inputs — the epilogue itself is exact algebra).

    ``kernel``: None = the Pallas kernel on TPU backends, the XLA einsum
    elsewhere; True/False pin one path (tests pin True to run the kernel
    in interpret mode on CPU).  ``dtype`` is the emitted volume dtype,
    same contract as ``build_corr_volume``."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    q1, s1 = quantize_rows(fmap1)
    q2, s2 = quantize_rows(fmap2)
    if kernel:
        return pallas_int8_corr_volume(q1, s1, q2, s2, out_dtype=dtype)
    return _int8_volume_xla(q1, s1, q2, s2).astype(dtype)
