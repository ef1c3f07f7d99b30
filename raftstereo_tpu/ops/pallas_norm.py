"""Pallas TPU instance-norm: layout-preserving stats + apply kernels.

Why this exists (measured on an earlier chip, with a device trace): at
the feature encoder's hot shape
(272x480x64 bf16) EVERY XLA formulation of the cross-(H,W) reduction —
lane-packed view, direct reduce, fp32 reduce, even MXU ones-vector
matmuls — costs 4-11 ms per norm, 50-100x its ~80 us bandwidth floor,
because each forces layout transitions against the surrounding convs'
blocked layouts (the [544,2,8,123,64]-style "data formatting" storm in the
trace).  A Pallas kernel reads the conv output in its natural row-major
(B, H, W, C) form: pass 1 accumulates per-(B, C) sum / sum-of-squares in
fp32 across a sequential row-block grid, pass 2 normalizes (optionally
fusing the following relu).  Three streaming passes over the tensor,
no reshapes anywhere.

Semantics match models.layers.InstanceNorm (torch InstanceNorm2d, no
affine, eps 1e-5; reference: core/extractor.py:29): per-image,
per-channel statistics over (H, W).  Statistics are fp32 (MXU-grade
accumulation — tighter than the bf16 tree reduces of the XLA form).

Backward: the XLA instance-norm's VJP, via jax.custom_vjp re-linearizing
the reference formulation — the backward pass keeps its current cost;
this kernel targets the inference/fixed-stage time where the 20+ ms lived.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_corr import _COMPILER_PARAMS, _interpret


# Elements of one activation row block the encoder kernels are sized for:
# 32 rows of a 1,536-wide 64-channel image (768 packed pixels x 128).  A
# block's VMEM grows with rows x width, and 32 rows of a 3,008-wide image
# (``encoder_conv``: 107.9 MB against the 100 MB limit, PR 28) no longer fit.
_BLOCK_ELEMS = 32 * 768 * 128


def _row_block(h: int, cap: int = 32, row_elems: int = 0) -> int:
    """Largest power-of-two divisor of ``h`` up to ``cap`` (encoder heights
    are multiples of 16 at flagship shapes; odd heights degrade gracefully).
    ``row_elems`` (elements in one row of the widest block) lowers the cap
    so that a block holds at most ``_BLOCK_ELEMS``: widths up to 1,536 keep
    all 32 rows, a 3,008-wide image gets 16."""
    if row_elems:
        cap = min(cap, max(1, _BLOCK_ELEMS // row_elems))
    r = 1
    while r * 2 <= cap and h % (r * 2) == 0:
        r *= 2
    return r


def _in_stats_kernel(x_ref, s1_ref, s2_ref):
    """Accumulate per-(image, channel) sum and sum-of-squares in fp32.
    Grid (B, H/R) iterates row-blocks innermost; TPU grids are sequential,
    so the b-th output block is initialized at its first row-block and
    accumulated across the rest (same pattern as pallas_alt's df2)."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref[...])
        s2_ref[...] = jnp.zeros_like(s2_ref[...])

    x = x_ref[...].astype(jnp.float32)                  # (1, R, W, C)
    # Stats blocks are (1, 1, C): Mosaic requires the last two block dims
    # to divide (8, 128) or equal the array dims — (1, C) of a (B, 1, C)
    # array satisfies that for any C.
    s1_ref[...] += jnp.sum(x, axis=(1, 2))[:, None, :]  # (1, 1, C)
    s2_ref[...] += jnp.sum(x * x, axis=(1, 2))[:, None, :]


def _in_apply_kernel(x_ref, m_ref, s_ref, o_ref, *, relu):
    x = x_ref[...]                                # (1, R, W, C)
    m = m_ref[...][:, :, None, :].astype(x.dtype)   # (1, 1, C) -> broadcast
    s = s_ref[...][:, :, None, :].astype(x.dtype)
    y = (x - m) * s
    if relu:
        y = jnp.maximum(y, 0)
    o_ref[...] = y.astype(o_ref.dtype)


def _xla_instance_norm(x, relu):
    """Reference XLA formulation (models.layers.InstanceNorm semantics) —
    used for the backward linearization and as the non-TPU path's oracle."""
    m = jnp.mean(x.astype(jnp.float32), axis=(1, 2), keepdims=True)
    c = x.astype(jnp.float32) - m
    v = jnp.mean(jnp.square(c), axis=(1, 2), keepdims=True)
    y = (c * jax.lax.rsqrt(v + 1e-5)).astype(x.dtype)
    return jnp.maximum(y, 0) if relu else y


def _pallas_forward(x, relu):
    b, h, w, c = x.shape
    r = _row_block(h, row_elems=w * c)
    grid = (b, h // r)
    s1, s2 = pl.pallas_call(
        _in_stats_kernel,
        out_shape=(jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.float32)),
        grid=grid,
        in_specs=[pl.BlockSpec((1, r, w, c), lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.VMEM)),
        name="norm_stats",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(x)
    n = jnp.float32(h * w)
    mean = s1 / n
    # E[x^2] - m^2 in fp32: with bf16 inputs the input quantization
    # (~3e-3 relative) dominates any fp32 cancellation; clamped for the
    # pathological all-constant case.  Measured fp32 envelope
    # (tests/test_pallas_encoder.py::TestStatsPrecisionEnvelope): rstd
    # error < 1e-4 at |mean|/std=10, < 1% at |mean|/std=100 — encoder
    # activations stay under ~10; a centered second pass would cost a
    # full extra HBM read of the tensor for precision no consumer needs.
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + 1e-5)
    return pl.pallas_call(
        functools.partial(_in_apply_kernel, relu=relu),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, r, w, c), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r, w, c), lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.VMEM),
        name="norm_apply",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(x, mean, rstd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def instance_norm_act(x: jax.Array, relu: bool = False) -> jax.Array:
    """Instance norm (optionally fused with relu) via the Pallas kernels."""
    return _pallas_forward(x, relu)


def _fwd(x, relu):
    return _pallas_forward(x, relu), x


def _bwd(relu, x, g):
    _, vjp = jax.vjp(lambda a: _xla_instance_norm(a, relu), x)
    return (vjp(g)[0],)


instance_norm_act.defvjp(_fwd, _bwd)
