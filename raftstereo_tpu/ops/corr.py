"""All-pairs 1-D correlation engine — the perf-critical core.

The reference ships four interchangeable backends (reference: core/corr.py;
selected at core/raft_stereo.py:90-100).  Here the same capability surface is
four backends behind one functional API, designed TPU-first:

* ``reg``    — precompute the full (B, H, W1, W2) volume as one batched matmul
               over B*H rows (MXU), build a W2 pyramid by average pooling,
               look up 2r+1 taps per level with an XLA gather+lerp.
               Mirror of ``CorrBlock1D`` (core/corr.py:110-156).
* ``alt``    — no precomputed volume: per lookup, sample fmap2 at the taps and
               dot with fmap1.  O(H*W) memory; mirror of
               ``PytorchAlternateCorrBlock1D`` (core/corr.py:64-107).
* ``pallas`` — same precomputed pyramid as ``reg`` but the lookup runs in a
               Pallas TPU kernel (gather-free masked reduction), the analogue
               of the reference's CUDA ``corr_sampler`` (sampler/sampler_kernel.cu).
* ``pallas_alt`` — on-demand Pallas kernel: each W1-block's correlation rows
               are recomputed in VMEM (MXU matmul + hat reduction) and thrown
               away.  O(H*W) memory at Pallas-kernel speed; the working form
               of the reference's dead ``alt_cuda`` (core/corr.py:159-188).

All backends share exact semantics: 1/sqrt(C) scaling, align_corners linear
interpolation in x, zero outside [0, W2-1], floor-halving pyramid.  The
reference builds num_levels+1 pyramid entries but only reads num_levels
(core/corr.py:122-125 vs :133); we build exactly num_levels.

A lookup function takes absolute x-coordinates (B, H, W1, 1) at level-0
resolution and returns (B, H, W1, num_levels*(2r+1)) correlation features,
ordered [level0: dx=-r..r, level1: ..., ...] to match the reference's channel
concatenation (core/corr.py:133-146).
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .sampler import linear_sample_1d

CorrFn = Callable[[jax.Array], jax.Array]


_PRECISIONS = {
    "highest": jax.lax.Precision.HIGHEST,
    "high": jax.lax.Precision.HIGH,
    "default": jax.lax.Precision.DEFAULT,
}


def build_corr_volume(fmap1: jax.Array, fmap2: jax.Array,
                      dtype=jnp.float32, precision: str = "highest") -> jax.Array:
    """(B, H, W1, C) x (B, H, W2, C) -> (B, H, W1, W2), scaled by 1/sqrt(C).

    One einsum = a batched matmul over B*H rows, which XLA tiles directly onto
    the MXU (reference equivalent: core/corr.py:148-156).
    """
    c = fmap1.shape[-1]
    # fp32-accurate multiply precision: sub-pixel disparity refinement reads
    # tiny differences between neighbouring correlation values, so the MXU's
    # single-pass bf16 path is not the right default (the reference likewise
    # pins the volume to fp32: core/raft_stereo.py:92).  "highest" is exact
    # 6-pass emulation and stays the default: the cheaper forms measured NO
    # speedup on the flagship path, so there is
    # nothing to trade accuracy for.
    corr = jnp.einsum("bhwc,bhvc->bhwv", fmap1, fmap2,
                      preferred_element_type=jnp.float32,
                      precision=_PRECISIONS[precision])
    return (corr / jnp.sqrt(jnp.float32(c))).astype(dtype)


def build_corr_pyramid(corr: jax.Array, num_levels: int) -> List[jax.Array]:
    """Average-pool the W2 axis by 2 per level, floor-halving odd widths
    (reference: core/corr.py:117-125)."""
    pyramid = [corr]
    for _ in range(num_levels - 1):
        c = pyramid[-1]
        w2 = c.shape[-1]
        c = c[..., : (w2 // 2) * 2]
        c = c.reshape(*c.shape[:-1], w2 // 2, 2).mean(axis=-1)
        pyramid.append(c)
    return pyramid


def _tap_offsets(radius: int) -> jax.Array:
    return jnp.arange(-radius, radius + 1, dtype=jnp.float32)


def _reg_lookup(pyramid: Sequence[jax.Array], radius: int,
                coords: jax.Array) -> jax.Array:
    """Tap lookup over a precomputed volume pyramid — the shared body of
    ``make_reg_corr_fn`` and the state-passing ``corr_fn_from_state``, so
    the monolithic and phase-split executables run identical ops."""
    offsets = _tap_offsets(radius)
    x = coords[..., 0].astype(jnp.float32)          # (B, H, W1)
    out = []
    for i, vol in enumerate(pyramid):
        taps = x[..., None] / (2.0 ** i) + offsets  # (B, H, W1, K)
        out.append(linear_sample_1d(vol, taps))
    return jnp.concatenate(out, axis=-1)


def _build_volume(fmap1: jax.Array, fmap2: jax.Array, dtype, precision: str,
                  quant: bool) -> jax.Array:
    """The one volume-construction seam: fp32 einsum (``build_corr_volume``)
    or the int8-quantized product with its dequant epilogue (ops/quant.py).
    ``precision`` only applies to the fp32 path — the int8 accumulator is
    exact integer arithmetic, there is no multiply precision to pick."""
    if quant:
        from .quant import quant_corr_volume
        return quant_corr_volume(fmap1.astype(jnp.float32),
                                 fmap2.astype(jnp.float32), dtype=dtype)
    return build_corr_volume(fmap1.astype(jnp.float32),
                             fmap2.astype(jnp.float32), dtype=dtype,
                             precision=precision)


def make_reg_corr_fn(fmap1: jax.Array, fmap2: jax.Array, num_levels: int,
                     radius: int, dtype=jnp.float32,
                     precision: str = "highest",
                     quant: bool = False) -> CorrFn:
    """Precomputed-volume backend (reference: CorrBlock1D, core/corr.py:110-156)."""
    volume = _build_volume(fmap1, fmap2, dtype, precision, quant)
    pyramid = build_corr_pyramid(volume, num_levels)

    return lambda coords: _reg_lookup(pyramid, radius, coords)


def build_fmap2_pyramid(fmap2: jax.Array, num_levels: int) -> List[jax.Array]:
    """Pool fmap2's W axis (axis=2 in NHWC) by 2 per level, floor-halving.

    Pooling fmap2 then correlating equals pooling the correlation volume
    (both are linear in fmap2), so on-demand backends built on this pyramid
    match ``reg`` exactly (reference: core/corr.py:104)."""
    c = fmap2.shape[-1]
    pyramid = [fmap2]
    for _ in range(num_levels - 1):
        f2 = pyramid[-1]
        w = f2.shape[2]
        f2 = f2[:, :, : (w // 2) * 2, :]
        f2 = f2.reshape(f2.shape[0], f2.shape[1], w // 2, 2, c).mean(axis=3)
        pyramid.append(f2)
    return pyramid


def _alt_lookup(fmap1: jax.Array, f2_pyramid: Sequence[jax.Array],
                radius: int, precision: str,
                coords: jax.Array) -> jax.Array:
    """On-demand tap correlation over an fmap2 pyramid — the shared body of
    ``make_alt_corr_fn`` and the state-passing ``corr_fn_from_state``.
    ``fmap1``/``f2_pyramid`` must already be fp32."""
    c = fmap1.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(c))
    offsets = _tap_offsets(radius)
    x = coords[..., 0].astype(jnp.float32)          # (B, H, W1)
    out = []
    for i, f2 in enumerate(f2_pyramid):
        taps = x[..., None] / (2.0 ** i) + offsets  # (B, H, W1, K)
        w2 = f2.shape[2]
        x0 = jnp.floor(taps)
        dx = taps - x0
        i0 = x0.astype(jnp.int32)
        i1 = i0 + 1
        # Flatten the (W1, K) tap grid into the W axis for one gather.
        b, h, w1, k = taps.shape

        def take(idx):
            idxc = jnp.clip(idx, 0, w2 - 1).reshape(b, h, w1 * k)
            g = jnp.take_along_axis(f2, idxc[..., None], axis=2)
            return g.reshape(b, h, w1, k, c)
        v0 = take(i0)
        v1 = take(i1)
        v0 = jnp.where(((i0 >= 0) & (i0 <= w2 - 1))[..., None], v0, 0)
        v1 = jnp.where(((i1 >= 0) & (i1 <= w2 - 1))[..., None], v1, 0)
        f2_taps = v0 * (1.0 - dx)[..., None] + v1 * dx[..., None]
        corr = jnp.einsum("bhwc,bhwkc->bhwk", fmap1, f2_taps,
                          precision=_PRECISIONS[precision]) * scale
        out.append(corr)
    return jnp.concatenate(out, axis=-1)


def make_alt_corr_fn(fmap1: jax.Array, fmap2: jax.Array, num_levels: int,
                     radius: int, precision: str = "highest") -> CorrFn:
    """On-demand backend: O(H*W) memory, recomputes correlation only at the
    sampled taps (reference: PytorchAlternateCorrBlock1D, core/corr.py:64-107).
    """
    fmap1 = fmap1.astype(jnp.float32)
    f2_pyramid = build_fmap2_pyramid(fmap2.astype(jnp.float32), num_levels)
    return lambda coords: _alt_lookup(fmap1, f2_pyramid, radius, precision,
                                      coords)


@functools.lru_cache(maxsize=None)
def _warn_corr_unshardable(reason: str) -> None:
    """Trace-time warning, once per distinct shape/mesh mismatch."""
    warnings.warn(
        f"corr mesh is active but the Pallas corr backend cannot partition "
        f"over it ({reason}); falling back to replicated lowering",
        RuntimeWarning, stacklevel=4)


def _corr_shard_mesh(b: int, h: int):
    """The active (data, space) mesh if the Pallas backends can partition
    over it: B divisible by data, H (at corr resolution) by space.

    The kernels' grids are per-(B*H)-row independent — the same independence
    the reference's CUDA kernel exploits (one thread block per row,
    sampler/sampler_kernel.cu:19-60) — so batch/height sharding via
    ``shard_map`` needs no cross-shard communication.  Returns
    (mesh, row_spec, flat_spec) or None (plain single-device lowering).
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.context import active_corr_mesh
    from ..parallel.mesh import DATA_AXIS, SPACE_AXIS

    mesh = active_corr_mesh()
    if mesh is None:
        return None
    d = mesh.shape.get(DATA_AXIS, 1)
    s = mesh.shape.get(SPACE_AXIS, 1)
    if d * s == 1:
        return None
    if b % d or h % s:
        # Loud, not silent: on a real mesh a user with e.g. batch 12 on
        # data=8 would otherwise lose corr partitioning with no signal.
        reasons = []
        if b % d:
            reasons.append(f"batch {b} not divisible by '{DATA_AXIS}' "
                           f"mesh axis {d}")
        if h % s:
            reasons.append(f"corr-height {h} not divisible by "
                           f"'{SPACE_AXIS}' mesh axis {s}")
        _warn_corr_unshardable("; ".join(reasons))
        return None
    # Flat (B*H, ...) arrays shard over BOTH axes at once; each device's
    # rows are exactly the ones its (b-block, h-block) produced, because
    # construction and lookup run inside shard_map with the same specs.
    return (mesh, P(DATA_AXIS, SPACE_AXIS, None, None),
            P((DATA_AXIS, SPACE_AXIS), None, None))


def make_pallas_corr_fn(fmap1: jax.Array, fmap2: jax.Array, num_levels: int,
                        radius: int, dtype=jnp.float32,
                        precision: str = "highest",
                        quant: bool = False) -> CorrFn:
    """Precomputed-pyramid backend with the Pallas TPU lookup kernel.

    Each pyramid level is flattened + W1-padded to the kernel's layout ONCE
    here; per-iteration calls reshape only the taps (the volume pad is an HBM
    copy of the whole volume — done once structurally rather than relying on
    XLA's loop-invariant code motion).

    Under an active corr mesh (parallel/context.py) construction and lookups
    run inside ``shard_map`` over the (data, space) axes, so the backend
    partitions across chips like the XLA-native ones."""
    from .pallas_corr import (pad_vol_lane, pallas_lookup_pyramid_flat,
                              preflatten_volume)

    def construct(f1, f2):
        volume = _build_volume(f1, f2, dtype, precision, quant)
        # Lane-padded level concat along W2: every per-iteration lookup is
        # ONE kernel launch covering all levels (same as pallas_alt).
        pyr = [pad_vol_lane(preflatten_volume(v))
               for v in build_corr_pyramid(volume, num_levels)]
        return tuple(pyr)

    shard = _corr_shard_mesh(fmap1.shape[0], fmap1.shape[1])
    if shard is None:
        pyramid = construct(fmap1, fmap2)
        lookup_flat = pallas_lookup_pyramid_flat
    else:
        mesh, row_spec, flat_spec = shard
        fmap_spec = row_spec
        pyramid = jax.shard_map(
            construct, mesh=mesh, in_specs=(fmap_spec, fmap_spec),
            out_specs=tuple([flat_spec] * num_levels),
            check_vma=False)(fmap1, fmap2)

        def lookup_flat(vcat, taps, w2s):
            return jax.shard_map(
                lambda v, t: pallas_lookup_pyramid_flat(v, t, w2s),
                mesh=mesh, in_specs=(flat_spec, row_spec),
                out_specs=row_spec, check_vma=False)(vcat, taps)

    w2s = tuple(v.shape[2] for v in pyramid)
    vcat = jnp.concatenate(pyramid, axis=2)
    offsets = _tap_offsets(radius)

    def corr_fn(coords: jax.Array) -> jax.Array:
        x = coords[..., 0].astype(jnp.float32)          # (B, H, W1)
        taps = jnp.concatenate(
            [x[..., None] / (2.0 ** i) + offsets        # (B, H, W1, K)
             for i in range(len(w2s))], axis=-1)
        return lookup_flat(vcat, taps, w2s)

    return corr_fn


def make_pallas_alt_corr_fn(fmap1: jax.Array, fmap2: jax.Array,
                            num_levels: int, radius: int,
                            dtype=jnp.float32,
                            precision: str = "highest",
                            out_dtype=jnp.float32,
                            out_channels: int = 0,
                            epilogue=None) -> CorrFn:
    """On-demand Pallas backend: O(H*W) HBM like ``alt``, but each W1-block's
    correlation rows are recomputed inside a TPU kernel (MXU matmul + hat
    reduction in VMEM).  Working form of the reference's dead ``alt_cuda``
    backend (reference: core/corr.py:159-188 raises NotImplementedError).

    ``epilogue``: the motion encoder's convc1 parameters
    ({"kernel": (1, 1, L*K, Co), "bias": (Co,)}) — when given, the kernel
    emits relu(corr @ W + b) directly (one fused pass; the separate 1x1
    conv re-read the correlation features at 75 GB/s, 60 us/iter).
    Inference-only: the caller gates it on test_mode (no VJP)."""
    from .pallas_alt import (pad_w2_lane, pallas_alt_pyramid_radial_epi_flat,
                             pallas_alt_pyramid_radial_flat,
                             preflatten_fmap1, preflatten_fmap2)

    # Flatten/pad ONCE so each corr_fn call touches only the taps (the f1
    # pad is a full-fmap HBM copy; one copy guaranteed structurally). The
    # fmap2 pyramid is concatenated along W2 so every per-iteration lookup
    # is ONE kernel launch covering all levels — the per-level variant is
    # launch-overhead-bound (~4x slower at 1/4-res flagship shapes).
    # ``dtype`` selects the stored/matmul precision (the CUDA kernel's
    # fp32+fp16 dispatch, sampler_kernel.cu:126): bf16 halves the kernel's
    # DMA and takes the MXU's native bf16 path (fp32 accumulation). The
    # pyramid is always POOLED in fp32 first; only the kernel inputs are
    # rounded.
    #
    # With float32 operands the widening below loses what the kernel can
    # use: a bf16 model hands over bf16 features, so fmap1 and level 0 of
    # the pyramid are float32 values with an all-zero low half, and five
    # of the six bf16 passes that emulate their ``highest`` product
    # multiply zeros (three of six for the pooled levels, whose left
    # operand is still exact).  ``feature_dtype`` carries the dtype the
    # features were born in to the kernel wrapper, which then runs only
    # the passes that can be non-zero (pallas_alt.resolve_corr_matmul):
    # the same float32-accumulated result up to the order of float32
    # sums, no operand rounded that is not already exact.  Float32-born
    # features keep ``precision`` as it is.
    feature_dtype = jnp.result_type(fmap1.dtype, fmap2.dtype)

    def construct(f1, f2):
        f1flat = preflatten_fmap1(f1.astype(jnp.float32)).astype(dtype)
        f2p = [pad_w2_lane(preflatten_fmap2(x)).astype(dtype) for x in
               build_fmap2_pyramid(f2.astype(jnp.float32), num_levels)]
        return (f1flat,) + tuple(f2p)

    scales = tuple(1.0 / 2.0 ** i for i in range(num_levels))
    epi = None
    if epilogue is not None:
        # Prepared exactly as PointwisePaddedConv consumes them: compute
        # dtype for the dot and the bias add (out_dtype IS the model
        # compute dtype on this path).
        epi = (epilogue["kernel"][0, 0].astype(out_dtype),
               epilogue["bias"].reshape(1, 1, -1).astype(out_dtype))

    shard = _corr_shard_mesh(fmap1.shape[0], fmap1.shape[1])
    if shard is None:
        f1flat, *f2_pyramid = construct(fmap1, fmap2)

        def lookup_flat(f1, f2, xl, w2s):
            if epi is not None:
                return pallas_alt_pyramid_radial_epi_flat(
                    f1, f2, xl, w2s, radius, epi[0], epi[1],
                    precision=precision, out_dtype=out_dtype,
                    level_scales=scales, feature_dtype=feature_dtype)
            return pallas_alt_pyramid_radial_flat(f1, f2, xl, w2s, radius,
                                                  precision=precision,
                                                  out_dtype=out_dtype,
                                                  out_channels=out_channels,
                                                  level_scales=scales,
                                                  feature_dtype=feature_dtype)
    else:
        # Partition over the mesh (see _corr_shard_mesh): construction and
        # every lookup run per-shard inside shard_map; no collectives.
        mesh, row_spec, flat_spec = shard
        f1flat, *f2_pyramid = jax.shard_map(
            construct, mesh=mesh, in_specs=(row_spec, row_spec),
            out_specs=tuple([flat_spec] * (1 + num_levels)),
            check_vma=False)(fmap1, fmap2)

        def lookup_flat(f1, f2, xl, w2s):
            from jax.sharding import PartitionSpec as P

            if epi is not None:
                return jax.shard_map(
                    lambda a, b, t, w, bi: pallas_alt_pyramid_radial_epi_flat(
                        a, b, t, w2s, radius, w, bi, precision=precision,
                        out_dtype=out_dtype, level_scales=scales,
                        feature_dtype=feature_dtype),
                    mesh=mesh,
                    in_specs=(flat_spec, flat_spec, row_spec, P(), P()),
                    out_specs=row_spec, check_vma=False)(f1, f2, xl, *epi)
            return jax.shard_map(
                lambda a, b, t: pallas_alt_pyramid_radial_flat(
                    a, b, t, w2s, radius, precision=precision,
                    out_dtype=out_dtype, out_channels=out_channels,
                    level_scales=scales, feature_dtype=feature_dtype),
                mesh=mesh, in_specs=(flat_spec, flat_spec, row_spec),
                out_specs=row_spec, check_vma=False)(f1, f2, xl)

    w2s = tuple(f2.shape[1] for f2 in f2_pyramid)
    f2cat = jnp.concatenate(f2_pyramid, axis=1)

    def corr_fn(coords: jax.Array) -> jax.Array:
        x = coords[..., 0].astype(jnp.float32)          # (B, H, W1)
        # The kernel derives every level's local center in-register from
        # the level-0 center (static level_scales) and resolves the radius
        # taps itself (shared-fraction window form) — even the ONE
        # broadcast multiply that replaced round-2's per-level stack cost
        # 28 us/iter of 24 GB/s loop fusion (round-4 trace).
        return lookup_flat(f1flat, f2cat, x[..., None], w2s)

    return corr_fn


# Off switch for the fused convc1 epilogue: tests/test_pallas_alt.py runs
# the unfused form as the reference the fused one is held to.
corr_epilogue_enabled = True


def resolve_implementation(implementation: str, quant: bool = False) -> str:
    """'auto' -> the fastest backend for the active platform.  The ONE
    resolver — make_corr_fn and corr_epilogue_active must agree,
    or the model could set corr_preact for a backend that ignores the
    epilogue (skipping convc1 on raw features entirely).

    ``quant`` (the int8 corr volume, ops/quant.py) overrides the choice
    to a PRECOMPUTED-VOLUME backend regardless of the configured one: the
    int8 win is the one-shot volume matmul, and the on-demand backends
    would re-quantize (and re-pay the int8 pack) at every lookup.  On TPU
    that is the Pallas lookup kernel over the dequantized volume, the XLA
    gather path elsewhere."""
    if quant:
        return "pallas" if jax.default_backend() == "tpu" else "reg"
    if implementation == "auto":
        return "pallas_alt" if jax.default_backend() == "tpu" else "reg"
    return implementation


def corr_epilogue_active(implementation: str, quant: bool = False) -> bool:
    """Whether ``make_corr_fn`` would honor a convc1 ``epilogue`` for this
    implementation — the model consults this to decide if the motion
    encoder's convc1 is fused into the lookup kernel (pallas_alt only;
    never under the quantized volume path, which resolves away from
    pallas_alt)."""
    return (corr_epilogue_enabled
            and resolve_implementation(implementation, quant) == "pallas_alt")


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded_level_widths(w: int, num_levels: int) -> Tuple[int, ...]:
    """Per-level lane-padded W2 widths of a floor-halving pyramid whose
    level-0 width is ``w`` — the static shape info the pre-flattened
    Pallas corr states carry implicitly (level-0 W2 == the lookup
    coordinates' W1 for stereo, so it never needs to be stored)."""
    from .pallas_corr import LANE
    widths = [w]
    for _ in range(num_levels - 1):
        widths.append(widths[-1] // 2)
    return tuple(_roundup(x, LANE) for x in widths)


def _pack_state_rows(x: jax.Array, hp: int, w_axis: int,
                     w_to: int) -> jax.Array:
    """Zero-pad a batch-leading (B, H, ...) array to (B, Hp, ...) rows and
    ``w_axis`` to ``w_to`` — reshape/zero-pad only, so packed lookups are
    bitwise-equal to the unpacked ones (padded rows/columns correlate to
    exactly zero and are sliced off; asserted in tests/test_model.py)."""
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, hp - x.shape[1])
    widths[w_axis] = (0, w_to - x.shape[w_axis])
    return jnp.pad(x, widths)


def build_corr_state(implementation: str, fmap1: jax.Array,
                     fmap2: jax.Array, num_levels: int,
                     dtype=jnp.float32,
                     precision: str = "highest",
                     quant: bool = False) -> Tuple[jax.Array, ...]:
    """Backend-specific correlation state as a FLAT TUPLE of batch-leading
    arrays — the carried-state form of ``make_corr_fn``'s closure, for
    executables that split one request across several XLA programs (the
    iteration-level scheduler's prologue/step split, serve/sched/).

    Every leaf keeps the batch as its leading axis so per-slot selects
    (``jnp.where`` over a (B,) mask) compose requests into a running batch
    without touching other slots' values.  For the Pallas backends the
    kernels' flatten/lane-pad relayout is done HERE, once at the prologue:
    levels are lane-padded and concatenated along W2, rows padded to the
    kernel row block, all with the batch axis kept leading — so each
    lookup through ``corr_fn_from_state`` performs only free reshapes
    (merging the leading (B, Hp) axes) instead of re-copying the pyramid
    per step.  The packing is reshape/zero-pad only and therefore exact
    (asserted in tests/test_model.py); level widths are derived statically
    from the lookup coordinates' W1 (``_padded_level_widths``).

    The arrays are built by the SAME ops as ``make_corr_fn`` at the same
    dtypes, so a lookup through ``corr_fn_from_state`` is bitwise-equal to
    the monolithic closure's (asserted in tests/test_sched.py).
    """
    from .pallas_corr import _BLOCK_ROWS, _block_w1

    implementation = resolve_implementation(implementation, quant)
    if implementation == "reg":
        volume = _build_volume(fmap1, fmap2, jnp.float32, precision, quant)
        return tuple(build_corr_pyramid(volume, num_levels))
    if implementation == "alt":
        return ((fmap1.astype(jnp.float32),)
                + tuple(build_fmap2_pyramid(fmap2.astype(jnp.float32),
                                            num_levels)))
    if implementation == "pallas":
        volume = _build_volume(fmap1, fmap2, dtype, precision, quant)
        pyr = build_corr_pyramid(volume, num_levels)
        b, h, w1 = pyr[0].shape[:3]
        hp = _roundup(h, _BLOCK_ROWS)
        w1p = _roundup(w1, _block_w1(w1))
        w2s = _padded_level_widths(w1, num_levels)
        vcat = jnp.concatenate(
            [jnp.pad(v, ((0, 0), (0, hp - h), (0, w1p - w1),
                         (0, w2s[i] - v.shape[3])))
             for i, v in enumerate(pyr)], axis=3)
        return (vcat,)
    if implementation == "pallas_alt":
        # astype before the pack: elementwise, so the order swap vs
        # make_pallas_alt_corr_fn's construct() is exact.
        f1 = fmap1.astype(jnp.float32).astype(dtype)
        f2p = [x.astype(dtype) for x in
               build_fmap2_pyramid(fmap2.astype(jnp.float32), num_levels)]
        b, h, w1 = f1.shape[:3]
        hp = _roundup(h, _BLOCK_ROWS)
        w1p = _roundup(w1, _block_w1(w1))
        w2s = _padded_level_widths(w1, num_levels)
        f1p = _pack_state_rows(f1, hp, 2, w1p)
        f2cat = jnp.concatenate(
            [_pack_state_rows(f2, hp, 2, w2s[i])
             for i, f2 in enumerate(f2p)], axis=2)
        return (f1p, f2cat)
    raise ValueError(f"unknown corr implementation: {implementation}")


def corr_fn_from_state(implementation: str, state: Sequence[jax.Array],
                       num_levels: int, radius: int,
                       precision: str = "highest", out_dtype=jnp.float32,
                       out_channels: int = 0, epilogue=None,
                       quant: bool = False, feature_dtype=None) -> CorrFn:
    """Rebuild a lookup function over ``build_corr_state`` output.

    Static parameters (radius/precision/out_*/epilogue/quant) are passed
    per call — the state itself is a pure array pytree, so it can live on
    device between step executables.  Semantics match ``make_corr_fn``
    for the same backend (the epilogue/out_channels knobs are honored
    exactly where that function honors them: pallas_alt only).  ``quant``
    only steers implementation resolution — the state arrays are already
    the DEQUANTIZED volume pyramid, so the lookups are the stock ones.
    ``feature_dtype`` is the dtype the encoder handed the features over in
    (the state arrays are already float32, so the caller has to say): it
    selects the pallas_alt kernel's matmul form exactly as
    ``make_pallas_alt_corr_fn`` does from ``fmap1.dtype``.
    """
    implementation = resolve_implementation(implementation, quant)
    if implementation == "reg":
        pyramid = tuple(state)
        fn = lambda coords: _reg_lookup(pyramid, radius, coords)  # noqa: E731
    elif implementation == "alt":
        f1, f2p = state[0], tuple(state[1:])
        fn = lambda coords: _alt_lookup(f1, f2p, radius, precision,  # noqa: E731
                                        coords)
    elif implementation == "pallas":
        from .pallas_corr import pallas_lookup_pyramid_flat
        (vcat4,) = state     # (B, Hp, W1p, sum(w2s)) — pre-packed
        offsets = _tap_offsets(radius)

        def fn(coords):
            x = coords[..., 0].astype(jnp.float32)
            b, h, w1 = x.shape
            hp = vcat4.shape[1]
            w2s = _padded_level_widths(w1, num_levels)
            assert sum(w2s) == vcat4.shape[3], (w2s, vcat4.shape)
            taps = jnp.concatenate(
                [x[..., None] / (2.0 ** i) + offsets
                 for i in range(len(w2s))], axis=-1)
            if hp != h:   # row pad mirrors the packed state's
                taps = jnp.pad(taps, ((0, 0), (0, hp - h), (0, 0), (0, 0)))
            # Merging the leading (B, Hp) axes is a free row-major
            # reinterpretation — the only per-lookup "relayout" left.
            vflat = vcat4.reshape((-1,) + vcat4.shape[2:])
            out = pallas_lookup_pyramid_flat(vflat, taps, w2s)
            return out[:, :h] if hp != h else out
    elif implementation == "pallas_alt":
        from .pallas_alt import (pallas_alt_pyramid_radial_epi_flat,
                                 pallas_alt_pyramid_radial_flat)
        f1p4, f2cat4 = state  # (B, Hp, W1p, C), (B, Hp, sum(w2s), C)
        scales = tuple(1.0 / 2.0 ** i for i in range(num_levels))
        epi = None
        if epilogue is not None:
            epi = (epilogue["kernel"][0, 0].astype(out_dtype),
                   epilogue["bias"].reshape(1, 1, -1).astype(out_dtype))

        def fn(coords):
            x = coords[..., 0].astype(jnp.float32)
            b, h, w1 = x.shape
            hp = f1p4.shape[1]
            w2s = _padded_level_widths(w1, num_levels)
            assert sum(w2s) == f2cat4.shape[2], (w2s, f2cat4.shape)
            xl = x[..., None]
            if hp != h:
                xl = jnp.pad(xl, ((0, 0), (0, hp - h), (0, 0), (0, 0)))
            f1flat = f1p4.reshape((-1,) + f1p4.shape[2:])
            f2cat = f2cat4.reshape((-1,) + f2cat4.shape[2:])
            if epi is not None:
                out = pallas_alt_pyramid_radial_epi_flat(
                    f1flat, f2cat, xl, w2s, radius, epi[0], epi[1],
                    precision=precision, out_dtype=out_dtype,
                    level_scales=scales, feature_dtype=feature_dtype)
            else:
                out = pallas_alt_pyramid_radial_flat(
                    f1flat, f2cat, xl, w2s, radius, precision=precision,
                    out_dtype=out_dtype, out_channels=out_channels,
                    level_scales=scales, feature_dtype=feature_dtype)
            return out[:, :h] if hp != h else out
        return fn
    else:
        raise ValueError(f"unknown corr implementation: {implementation}")
    if jnp.dtype(out_dtype) == jnp.float32:
        return fn
    return lambda coords: fn(coords).astype(out_dtype)


def make_corr_fn(implementation: str, fmap1: jax.Array, fmap2: jax.Array,
                 num_levels: int, radius: int, dtype=jnp.float32,
                 precision: str = "highest", out_dtype=jnp.float32,
                 out_channels: int = 0, epilogue=None,
                 quant: bool = False) -> CorrFn:
    """Backend dispatch (reference: core/raft_stereo.py:90-100).

    ``auto`` resolves to the fastest backend for the active platform: the
    on-demand Pallas kernel on TPU (fastest measured AND O(H*W) memory),
    the XLA gather path elsewhere (the Pallas kernels are TPU-tuned; their
    interpret mode is for correctness tests, not speed).

    ``out_dtype`` is the dtype of the returned correlation features.  The
    lookup math is identical (fp32 accumulation everywhere); a bf16 model
    requests bf16 directly so the Pallas kernel emits it and the
    post-lookup convert + HBM round trip disappear from the loop.

    ``out_channels`` (> num_levels*(2r+1)) asks the pallas_alt backend to
    zero-pad the channel axis in-kernel to a lane-friendly width; other
    backends return the natural width (consumers must accept both — the
    motion encoder's padded 1x1 conv does).

    ``quant`` swaps the volume construction for the int8-quantized
    product (ops/quant.py) and forces a precomputed-volume backend (see
    ``resolve_implementation``) — lookups over the dequantized volume
    are the stock ones, so monolithic, stream and phase-split callers
    all share the same quantized numerics."""
    implementation = resolve_implementation(implementation, quant)
    if implementation == "reg":
        fn = make_reg_corr_fn(fmap1, fmap2, num_levels, radius,
                              dtype=jnp.float32, precision=precision,
                              quant=quant)
    elif implementation == "alt":
        fn = make_alt_corr_fn(fmap1, fmap2, num_levels, radius,
                              precision=precision)
    elif implementation == "pallas":
        fn = make_pallas_corr_fn(fmap1, fmap2, num_levels, radius,
                                 dtype=dtype, precision=precision,
                                 quant=quant)
    elif implementation == "pallas_alt":
        return make_pallas_alt_corr_fn(fmap1, fmap2, num_levels, radius,
                                       dtype=dtype, precision=precision,
                                       out_dtype=out_dtype,
                                       out_channels=out_channels,
                                       epilogue=epilogue)
    else:
        raise ValueError(f"unknown corr implementation: {implementation}")
    if jnp.dtype(out_dtype) == jnp.float32:
        return fn
    return lambda coords: fn(coords).astype(out_dtype)
