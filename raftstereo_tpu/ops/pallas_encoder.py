"""Fused Pallas pipeline for the feature encoder's instance-norm stage.

Why: at flagship resolution the stem + layer1 stage (five 64-channel convs
with instance norms at 544x960) costs ~27 ms of which ~21 ms is XLA layout
churn — every cross-(H,W) reduction forces ~4 full-tensor relayouts of
135 MB each between the convs' space-to-depth blocked layouts and the
reduce's, and NO XLA-side formulation escapes it (lane-packed views,
direct/fp32 reduces, MXU ones-vector matmuls, 128-channel padding ALL
measured 27-62 ms on an earlier chip).

The fix is to own the stage end-to-end in Pallas so every tensor stays in
row-major (B, H, W, C):

* The (H, W, 64) tensor is VIEWED as (H, W/2, 128) — a free row-major
  reinterpretation that packs adjacent pixel pairs into full MXU/VPU
  lanes (the same trick XLA's blocked layouts buy with relayouts).  A
  3x3x64->64 conv becomes a 3x3-tap 128->128 conv over packed columns
  whose (parity-in, parity-out) weight blocks embed the original taps:
  measured 90.8 TF/s packed (= ~45 TF/s of useful 64-ch flops) vs
  XLA's 29.8 TF/s row-major / ~70 TF/s blocked-plus-relayouts.
* Each kernel call fuses the whole conv INPUT preparation — instance-norm
  apply from precomputed stats, relu, optional residual add (itself
  normalized from a second raw tensor) — and accumulates the fp32
  per-channel sum/sum-of-squares of its raw OUTPUT for the next norm, so
  a norm never touches HBM as a separate op.
* dy taps read halo rows (built by cheap strided row slices, 2 rows per
  block); dx taps are resolved post-matmul by rolling the accumulated
  output one packed column and masking the wrap (operands stay
  contiguous: weights shift, never activations).

Semantics are exactly BasicEncoder's stem + layer1 (conv1-norm1-relu,
two ResidualBlocks; reference: core/extractor.py:122-197 structure) with
instance-norm statistics in fp32.  The backward pass is the XLA reference
formulation's VJP via jax.custom_vjp (training keeps its current cost;
this pipeline removes fixed-stage inference time).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_corr import _COMPILER_PARAMS, _interpret
from .pallas_norm import _row_block


# None = auto (fused on TPU backends); True/False force — tests force True
# to exercise the interpret-mode kernels on CPU, and config.fused_encoder
# forwards a per-model override (so evaluations can pin one numeric path).
# Thread-local: the override scopes a TRACE, and concurrent tracing from
# another thread must not see this thread's gate (the train step's
# override_fused_stem(False) is load-bearing for training numerics).
_tls = threading.local()

def make_override_scope(tls, attr):
    """(getter, contextmanager) pair over a thread-local override slot.

    Shared scaffolding for the fused-stage gates (stem here, layer2 in
    pallas_layer2): the override scopes a TRACE, and concurrent tracing
    from another thread must not see this thread's gate — so the slot
    lives in a ``threading.local``, and the scope restores the previous
    value on exit (nesting-safe)."""
    def get():
        return getattr(tls, attr, None)

    @contextlib.contextmanager
    def scope(value):
        prev = get()
        setattr(tls, attr, value)
        try:
            yield
        finally:
            setattr(tls, attr, prev)

    return get, scope


_get_override, _stem_scope = make_override_scope(_tls, "fused_stem_override")


def override_fused_stem(value):
    """Trace-time scope for the thread-local stem-gate override.  Since
    round 5 the train step no longer forces this off — the stage's
    backward consumes the forward's saved residuals (_stage_bwd_xla)
    instead of re-linearizing the XLA forward, and measures >= plain at
    the per-shard batches where the auto gate engages (train/step.py).
    Tests force True to pin the interpret-mode kernels on CPU; a
    per-model config.fused_encoder still wins over this scope
    (use_fused_stem checks the explicit override first)."""
    return _stem_scope(value)


def _stem_shard_mesh(shape, warn: bool = False):
    """The active (data, space) mesh if the fused stage can partition over
    it via ``shard_map``: B divisible by ``data``, H by ``space`` with >= 2
    rows per shard (each conv needs one real halo row per boundary).
    Returns (mesh, data, space) or None (plain single-device lowering).

    ``warn``: emit the partitionability warning — only use_fused_stem sets
    it, and only when the gate would otherwise have TAKEN the fused stage
    (a CPU/GPU multi-device run with an odd batch would otherwise get a
    misleading RuntimeWarning on a path it never wanted)."""
    import warnings

    from ..parallel.context import active_corr_mesh
    from ..parallel.mesh import DATA_AXIS, SPACE_AXIS

    mesh = active_corr_mesh()
    if mesh is None:
        return None
    b, h = shape[0], shape[1]
    d = mesh.shape.get(DATA_AXIS, 1)
    s = mesh.shape.get(SPACE_AXIS, 1)
    if d * s == 1:
        return None
    if b % d or h % s or (h // s) < 2:
        if warn:
            warnings.warn(
                f"fused encoder stage cannot partition over the active mesh "
                f"(batch {b} % data {d}, height {h} % space {s}); using the "
                f"plain XLA stage", RuntimeWarning, stacklevel=3)
        return None
    return mesh, d, s


def fused_stem_forced(override=None) -> bool:
    """True iff the fused stage is EXPLICITLY forced on — the same
    tri-state precedence use_fused_stem applies (per-model config override
    wins over the module-level one).  Single source of truth for callers
    that branch on forced-ness (encoders' BN-without-conv1 case)."""
    ov = override if override is not None else _get_override()
    return ov is True


def use_fused_stem(norm_fn: str, shape, override=None) -> bool:
    """Gate for the fused stage: instance or frozen-batch norm, even
    width, TPU backend (the kernels interpret on CPU for tests, but the
    plain XLA path is the sane CPU default).

    Sharding: a bare pallas_call cannot be SPMD-partitioned, so under an
    active corr mesh (the evaluator / train / dryrun paths) the stage runs
    inside ``shard_map`` over the mesh's (data, space) axes — see
    ``_fused_forward`` — and the gate only asks that the shapes divide.
    With >1 devices visible but NO mesh context the gate stays off: a user
    may jit with shardings directly, and the plain XLA stage (which XLA
    partitions with halo exchanges) must remain what they get.

    ``override`` (tri-state, from config.fused_encoder) wins over the
    thread-local ``override_fused_stem`` scope, which wins over backend auto.
    The auto path also gates on <= 4 images per shard: at batch 8 the XLA
    stage's blocked lowering amortizes over the batch and the fused
    pipeline measures a net loss (12.45 vs 12.32 pairs/sec same-session
    at flagship b8; the conv1 kernel shows the same crossover).

    ``batch`` norm also qualifies: frozen BatchNorm folds to a constant
    per-channel affine, which the kernels' prep form relu(x*s + t)
    represents exactly (bn_affine) — no stats kernels, no psum."""
    ok = norm_fn in ("instance", "batch") and shape[2] % 2 == 0
    if not ok:
        return False
    ov = override if override is not None else _get_override()
    # Warn about an unpartitionable mesh only if the gate would otherwise
    # have taken the fused stage (explicit True, or TPU auto).
    would_take = ov is True or (ov is None
                                and jax.default_backend() == "tpu")
    shard = _stem_shard_mesh(shape, warn=would_take)
    if shard is not None:
        if ov is not None:
            return ov
        return (jax.default_backend() == "tpu"
                and shape[0] // shard[1] <= 4)
    from ..parallel.context import active_corr_mesh

    if active_corr_mesh() is not None:
        return False  # mesh active but not partitionable (warned above)
    if ov is not None:
        return ov
    return (jax.default_backend() == "tpu" and len(jax.devices()) == 1
            and shape[0] <= 4)


# --------------------------------------------------------------- packing

def pack_view(x: jax.Array) -> jax.Array:
    """(B, H, W, C) -> (B, H, W/2, 2C): free row-major reinterpretation
    (adjacent pixel pair -> one packed column)."""
    b, h, w, c = x.shape
    return x.reshape(b, h, w // 2, 2 * c)


def unpack_view(x: jax.Array) -> jax.Array:
    b, h, wp, c2 = x.shape
    return x.reshape(b, h, wp * 2, c2 // 2)


def pack_weights(w: jax.Array) -> jax.Array:
    """(3, 3, C, C) HWIO conv weights -> (9, 2C, 2C) packed [dy*3 + dp].

    Output pixel w_out = 2p + po with tap dx reads input pixel
    2p + po + dx = packed column p + dp, parity pi, where
    dp = floor((po+dx)/2), pi = (po+dx) mod 2:
      dp=-1: (pi=1 -> po=0) = W[dy, dx=-1]
      dp= 0: full 2x2 parity block
      dp=+1: (pi=0 -> po=1) = W[dy, dx=+1]
    """
    c = w.shape[2]
    out = jnp.zeros((3, 3, 2 * c, 2 * c), w.dtype)
    for po in range(2):
        for dxi, dx in enumerate((-1, 0, 1)):
            dp = (po + dx) // 2
            pi = (po + dx) % 2
            out = out.at[:, dp + 1,
                         pi * c:(pi + 1) * c,
                         po * c:(po + 1) * c].set(w[:, dxi])
    return out.reshape(9, 2 * c, 2 * c)


def pack_vec(v: jax.Array) -> jax.Array:
    """Per-channel vector -> packed duplicate [v, v] (both parities)."""
    return jnp.concatenate([v, v], axis=-1)


def stats_from_packed(s1: jax.Array, s2: jax.Array, n: float
                      ) -> Tuple[jax.Array, jax.Array]:
    """Packed (B, 1, 2C) fp32 sums -> per-original-channel (B, 1, C)
    mean / rstd (parity halves sum exactly: they partition the pixels).
    E[x^2]-m^2 precision envelope: see pallas_norm._pallas_forward and
    tests/test_pallas_encoder.py::TestStatsPrecisionEnvelope."""
    c = s1.shape[-1] // 2
    t1 = s1[..., :c] + s1[..., c:]
    t2 = s2[..., :c] + s2[..., c:]
    mean = t1 / n
    var = jnp.maximum(t2 / n - mean * mean, 0.0)
    return mean, jax.lax.rsqrt(var + 1e-5)


# ---------------------------------------------------------------- kernels

def _prep(x, s_ref, t_ref):
    """Normalization apply + relu from packed AFFINE refs: relu(x*s + t).
    Instance norm passes (rstd, -mean*rstd); frozen batch norm passes its
    folded constants (gamma*rstd, beta - mean*gamma*rstd) — the affine
    form also represents gamma == 0 channels exactly, which (x - m)*s
    cannot."""
    s = s_ref[...][:, :, None, :].astype(x.dtype)
    t = t_ref[...][:, :, None, :].astype(x.dtype)
    return jnp.maximum(x * s + t, 0)


def _edge_mask_halo(th, hv_ref):
    """Zero the prepped halo rows that lie OUTSIDE the image: conv zero
    padding applies in the PREPPED domain, but prepping a zero-filled edge
    halo yields relu(-m*s) != 0.  Validity comes from an (nblk, 2) SMEM
    operand (whole array per block, row selected by program_id — Mosaic
    requires non-divisible block dims to equal the array dims) rather than
    a program_id comparison so that under space sharding a shard-boundary
    halo (a REAL neighbor row delivered by ppermute) is kept while a
    global image edge is still masked."""
    j = pl.program_id(1)
    # Scalar multiplies, not a stacked bool mask: Mosaic cannot shape-cast
    # a vector<2xi1> to the broadcast rank.  Edge halo values are finite
    # (prep of a zero row), so multiply-by-zero is exact.
    top = th[:, 0:1] * hv_ref[j, 0].astype(th.dtype)
    bot = th[:, 1:2] * hv_ref[j, 1].astype(th.dtype)
    return jnp.concatenate([top, bot], axis=1)


def _conv_packed(t, halo, w_ref, bias_ref, wp):
    """3x3 packed conv of the prepped tile.

    t: (1, R, Wp, 2C) prepped center rows; halo: (1, 2, Wp, 2C) prepped
    halo rows [above, below]; w_ref: (9, 2C, 2C); returns (1, R, Wp, 2C)
    fp32 + bias."""
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, wp, 1), 2)
    y = None
    for dpi in range(3):
        u = None
        for dyi in range(3):
            if dyi == 0:
                rows = jnp.concatenate([halo[:, 0:1], t[:, :-1]], axis=1)
            elif dyi == 1:
                rows = t
            else:
                rows = jnp.concatenate([t[:, 1:], halo[:, 1:2]], axis=1)
            m = jax.lax.dot_general(
                rows, w_ref[dyi * 3 + dpi],
                (((3,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            u = m if u is None else u + m
        o = dpi - 1
        if o == 0:
            shifted = u
        else:
            shifted = pltpu.roll(u, (-o) % wp, 2)
            if o == 1:
                shifted = jnp.where(col < wp - 1, shifted, 0.0)
            else:
                shifted = jnp.where(col > 0, shifted, 0.0)
        y = shifted if y is None else y + shifted
    return y + bias_ref[...][:, :, None, :]


def _acc_stats(y, stat_refs):
    """Accumulate packed fp32 (sum, sumsq) of the raw output — skipped
    entirely for affine (frozen-BN) pipelines, whose constant prep needs
    no statistics (stat_refs empty)."""
    if not stat_refs:
        return
    s1_ref, s2_ref = stat_refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref[...])
        s2_ref[...] = jnp.zeros_like(s2_ref[...])

    s1_ref[...] += jnp.sum(y, axis=(1, 2))[:, None, :]
    s2_ref[...] += jnp.sum(y * y, axis=(1, 2))[:, None, :]


def _enc_conv_kernel(x_ref, xh_ref, s_ref, t_ref, w_ref, b_ref, hv_ref,
                     y_ref, *stat_refs, wp):
    """prep(x) -> packed conv -> raw y (+ packed output stats)."""
    t = _prep(x_ref[...], s_ref, t_ref)
    th = _edge_mask_halo(_prep(xh_ref[...][:, 0], s_ref, t_ref), hv_ref)
    y = _conv_packed(t, th, w_ref, b_ref, wp)
    y_ref[...] = y.astype(y_ref.dtype)
    _acc_stats(y, stat_refs)


def _enc_conv_res_kernel(x_ref, xh_ref, s_ref, t_ref,
                         r_ref, rh_ref, rs_ref, rt_ref,
                         w_ref, b_ref, hv_ref, y_ref, *stat_refs, wp):
    """Residual-block boundary: the conv input is
    relu( prep(res_raw) + prep(x_raw) ) — both tensors arrive RAW with
    their affines and are normalized in-register."""
    t = jnp.maximum(_prep(r_ref[...], rs_ref, rt_ref)
                    + _prep(x_ref[...], s_ref, t_ref), 0)
    th = _edge_mask_halo(
        jnp.maximum(_prep(rh_ref[...][:, 0], rs_ref, rt_ref)
                    + _prep(xh_ref[...][:, 0], s_ref, t_ref), 0), hv_ref)
    y = _conv_packed(t, th, w_ref, b_ref, wp)
    y_ref[...] = y.astype(y_ref.dtype)
    _acc_stats(y, stat_refs)


def _enc_finish_kernel(y1_ref, s1_ref, t1_ref, c11_ref, s11_ref, t11_ref,
                       c21_ref, s21_ref, t21_ref, o_ref):
    """t2 = relu( relu( t0 + u2 ) + v2 ): the stage output in the final
    domain, from the three raw tensors + their affines."""
    t0 = _prep(y1_ref[...], s1_ref, t1_ref)
    u2 = _prep(c11_ref[...], s11_ref, t11_ref)
    v2 = _prep(c21_ref[...], s21_ref, t21_ref)
    o_ref[...] = jnp.maximum(jnp.maximum(t0 + u2, 0) + v2,
                             0).astype(o_ref.dtype)


# ------------------------------------------------------------- host side

def _halo_rows(x: jax.Array, r: int, boundary=None) -> jax.Array:
    """(B, H, Wp, C2) -> (B, H//r, 2, Wp, C2): rows above/below each
    r-row block; strided slices, ~2/r of a pass.  ``boundary`` provides the
    (above, below) rows at the local-array edges — the space-sharding path
    passes the neighbor shards' edge rows (from ppermute); default zeros
    (the image edge, masked in-kernel by the halo-validity operand)."""
    b, h, wp, c2 = x.shape
    nblk = h // r
    if boundary is None:
        above = below = jnp.zeros((b, 1, wp, c2), x.dtype)
    else:
        above, below = boundary
    top = jnp.concatenate([above, x[:, r - 1::r][:, : nblk - 1]], axis=1)
    bot = jnp.concatenate([x[:, r::r], below], axis=1)
    return jnp.stack([top, bot], axis=2)


def _default_hv(nblk: int) -> jax.Array:
    """Halo validity for the unsharded case: only the image edges invalid."""
    return (jnp.ones((nblk, 2), jnp.float32)
            .at[0, 0].set(0.0).at[nblk - 1, 1].set(0.0))


def _enc_conv(x, stats, w9, bias, res=None, res_stats=None,
              hv=None, boundary=None, res_boundary=None, want_stats=True):
    """One fused prep+conv(+stats) call on packed arrays.

    x: (B, H, Wp, C2) raw; stats: AFFINE (s, t) each (B, 1, C2) packed;
    w9: (9, C2, C2); bias: (1, 1, C2); hv: (H//r, 2) halo validity;
    boundary / res_boundary: neighbor edge rows under space sharding.
    ``want_stats=False`` (affine pipelines) skips the output-stats
    accumulation entirely.  Returns (y_raw fp-of-x, (s1, s2) or None)."""
    b, h, wp, c2 = x.shape
    r = _row_block(h, row_elems=wp * c2)
    grid = (b, h // r)
    xh = _halo_rows(x, r, boundary)
    if hv is None:
        hv = _default_hv(h // r)
    m, s = stats

    def row_spec():
        return pl.BlockSpec((1, r, wp, c2), lambda i, j: (i, j, 0, 0),
                            memory_space=pltpu.VMEM)

    def halo_spec():
        return pl.BlockSpec((1, 1, 2, wp, c2), lambda i, j: (i, j, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    def stat_spec():
        return pl.BlockSpec((1, 1, c2), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    wspec = pl.BlockSpec((9, c2, c2), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    bspec = pl.BlockSpec((1, 1, c2), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    hvspec = pl.BlockSpec(hv.shape, lambda i, j: (0, 0),
                          memory_space=pltpu.SMEM)

    if res is None:
        kernel = functools.partial(_enc_conv_kernel, wp=wp)
        operands = (x, xh, m, s, w9, bias[None, None, :], hv)
        in_specs = [row_spec(), halo_spec(), stat_spec(), stat_spec(),
                    wspec, bspec, hvspec]
    else:
        rm, rs = res_stats
        rh = _halo_rows(res, r, res_boundary)
        kernel = functools.partial(_enc_conv_res_kernel, wp=wp)
        operands = (x, xh, m, s, res, rh, rm, rs, w9, bias[None, None, :], hv)
        in_specs = [row_spec(), halo_spec(), stat_spec(), stat_spec(),
                    row_spec(), halo_spec(), stat_spec(), stat_spec(),
                    wspec, bspec, hvspec]

    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [row_spec()]
    if want_stats:
        out_shape += [jax.ShapeDtypeStruct((b, 1, c2), jnp.float32)] * 2
        out_specs += [stat_spec(), stat_spec()]
    out = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        name="encoder_conv",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(*operands)
    if want_stats:
        return out[0], (out[1], out[2])
    return out[0], None


def _packed_stats(x):
    """Packed per-channel fp32 (sum, sumsq) of a raw packed tensor via the
    layout-preserving stats kernel (pallas_norm)."""
    from .pallas_norm import _in_stats_kernel

    b, h, wp, c2 = x.shape
    r = _row_block(h, row_elems=wp * c2)
    return pl.pallas_call(
        _in_stats_kernel,
        out_shape=(jax.ShapeDtypeStruct((b, 1, c2), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c2), jnp.float32)),
        grid=(b, h // r),
        in_specs=[pl.BlockSpec((1, r, wp, c2), lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((1, 1, c2), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 1, c2), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.VMEM)),
        name="encoder_stats",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(x)


def _expand_stats(s1, s2, n, axis_name=None):
    """Packed sums -> packed prep AFFINE (rstd, -mean*rstd) duplicated
    over parities (the kernels apply relu(x*s + t)).
    ``axis_name``: psum the partial sums over that mesh axis first (space
    sharding — instance-norm statistics span the whole image height)."""
    if axis_name is not None:
        s1 = jax.lax.psum(s1, axis_name)
        s2 = jax.lax.psum(s2, axis_name)
    mean, rstd = stats_from_packed(s1, s2, n)
    return pack_vec(rstd), pack_vec(-mean * rstd)


def fused_stem_layer1(y1_raw: jax.Array, params: dict, n=None,
                      space_axis=None, space_size=1) -> jax.Array:
    """norm1 + relu + layer1 (two ResidualBlocks), fused, from conv1's RAW
    output (B, H, W, 64), any even W.

    Both split points were measured E2E: letting norm1 run in XLA (so
    conv1 keeps its fused blocked lowering) costs MORE than it saves —
    conv1 drops 1.4 -> 3.8 ms when its consumer is row-major, but the XLA
    norm1's own relayouts cost ~3 ms more (9.49 vs 9.77 pairs/sec), so
    the pipeline consumes conv1 raw and computes norm1's stats with the
    layout-preserving kernel.
    params: {"c10","c11","c20","c21"} -> {"kernel": (3,3,64,64),
    "bias": (64,)} — layer1_0.conv1/conv2, layer1_1.conv1/conv2.
    Returns the stage output in the final (post-relu) domain.

    Space sharding (``space_axis`` set, called inside shard_map): the
    array is an H-shard; stats psum over the axis, each conv's shard-edge
    halo row arrives from the neighbor by ppermute, and the halo-validity
    operand keeps those rows while still masking the global image edges.
    ``n`` is the GLOBAL H*W pixel count (defaults to the local shape's).
    """
    xp = pack_view(y1_raw)
    if n is None:
        n = float(y1_raw.shape[1] * y1_raw.shape[2])
    st1 = _expand_stats(*_packed_stats(xp), n, space_axis)
    return _stage_on_packed(xp, st1, params, n, space_axis, space_size)


def _shard_ctx(nblk: int, space_axis, space_size: int, rows: int = 1):
    """(halo-validity array, edge-row exchange fn) for one stage geometry.
    ``rows``: how many boundary rows each conv needs from the neighbor."""
    if space_axis is None:
        return _default_hv(nblk), lambda t: None
    idx = jax.lax.axis_index(space_axis)
    hv = (jnp.ones((nblk, 2), jnp.float32)
          .at[0, 0].set((idx > 0).astype(jnp.float32))
          .at[nblk - 1, 1].set((idx < space_size - 1)
                               .astype(jnp.float32)))
    fwd = [(i, i + 1) for i in range(space_size - 1)]
    bwd = [(i + 1, i) for i in range(space_size - 1)]

    def exch(t):
        # Neighbor edge rows: shards with no source (global image
        # edges) receive zeros, which the hv operand masks anyway
        # (or, for the raw-image conv1 path, ARE the zero padding).
        above = jax.lax.ppermute(t[:, -rows:], space_axis, fwd)
        below = jax.lax.ppermute(t[:, :rows], space_axis, bwd)
        return above, below

    return hv, exch


def _stage_on_packed(xp, st1, params, n, space_axis=None, space_size=1,
                     affines=None, want_residuals=False):
    """The four fused convs + finish kernel, from the packed raw stage
    input ``xp`` and its prep affine ``st1``.

    ``affines``: for affine norms (frozen batch norm) — a list of the four
    remaining packed (s, t) prep affines [after c10, c11, c20, c21]; the
    per-tensor statistics accumulated by the kernels are then ignored
    (constant affines need no stats and no psum).

    ``want_residuals``: also return the four raw conv outputs (packed) and
    the five prep affines — the backward's saved state.  The pipeline
    materializes all of these in HBM anyway (each _enc_conv is its own
    pallas_call), so saving them is free; the hand-written backward then
    never re-runs a forward (see _stage_bwd_xla)."""
    dt = xp.dtype
    b, h, wp, c2 = xp.shape
    r = _row_block(h, row_elems=wp * c2)
    nblk = h // r
    hv, exch = _shard_ctx(nblk, space_axis, space_size)

    def pw(name):
        return (pack_weights(params[name]["kernel"]).astype(dt),
                pack_vec(params[name]["bias"]).astype(dt))

    ws = affines is None

    def nxt(sums, i):
        if affines is not None:
            return affines[i]
        return _expand_stats(*sums, n, space_axis)

    xb = exch(xp)
    c10, s10 = _enc_conv(xp, st1, *pw("c10"), hv=hv, boundary=xb,
                         want_stats=ws)
    st10 = nxt(s10, 0)
    c11, s11 = _enc_conv(c10, st10, *pw("c11"), hv=hv, boundary=exch(c10),
                         want_stats=ws)
    st11 = nxt(s11, 1)
    # block boundary: input of layer1_1.conv1 is relu(t0 + u2)
    c20, s20 = _enc_conv(c11, st11, *pw("c20"), res=xp, res_stats=st1,
                         hv=hv, boundary=exch(c11), res_boundary=xb,
                         want_stats=ws)
    st20 = nxt(s20, 2)
    c21, s21 = _enc_conv(c20, st20, *pw("c21"), hv=hv, boundary=exch(c20),
                         want_stats=ws)
    st21 = nxt(s21, 3)

    def row_spec():
        return pl.BlockSpec((1, r, wp, c2), lambda i, j: (i, j, 0, 0),
                            memory_space=pltpu.VMEM)

    def stat_spec():
        return pl.BlockSpec((1, 1, c2), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        _enc_finish_kernel,
        out_shape=jax.ShapeDtypeStruct(xp.shape, dt),
        grid=(b, h // r),
        in_specs=[row_spec(), stat_spec(), stat_spec(),
                  row_spec(), stat_spec(), stat_spec(),
                  row_spec(), stat_spec(), stat_spec()],
        out_specs=row_spec(),
        name="encoder_finish",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(xp, *st1, c11, *st11, c21, *st21)
    if want_residuals:
        return (unpack_view(out), (c10, c11, c20, c21),
                (st1, st10, st11, st20, st21))
    return unpack_view(out)


# --------------------------------------------- fused 7x7 stem conv (conv1)

def pack_weights7(w: jax.Array) -> jax.Array:
    """(7, 7, 3, 64) HWIO conv1 weights -> (7, 5, 6, 128) packed
    [dy, dp+2]: output pixel 2p+po with tap dx reads packed column p+dp,
    parity pi, where dp = floor((po+dx)/2) in [-2, 2], pi = (po+dx) mod 2
    (same construction as pack_weights, 7 dx taps instead of 3)."""
    kh, kw, ci, co = w.shape
    out = jnp.zeros((kh, 5, 2 * ci, 2 * co), w.dtype)
    for po in range(2):
        for dxi, dx in enumerate(range(-3, 4)):
            dp = (po + dx) // 2
            pi = (po + dx) % 2
            out = out.at[:, dp + 2,
                         pi * ci:(pi + 1) * ci,
                         po * co:(po + 1) * co].set(w[:, dxi])
    return out


def _stem7_kernel(x_ref, xh_ref, w_ref, b_ref, y_ref, *stat_refs,
                  rows):
    """7x7 stride-1 packed conv of the RAW input image tile + fp32 output
    stats (for norm1).  No prep/halo masking: the input is the [-1, 1]
    image itself, so zero halo rows ARE the conv's zero padding.

    The 5 packed-column offsets are resolved by PRE-SHIFTING the
    6-channel input (roll + zero-mask on 6 lanes) and concatenating into
    one K=30 operand per dy tap — rolling/masking the 128-wide fp32
    accumulator per offset instead (the first formulation) made the
    whole kernel run at a ~38 GB/s effective write rate."""
    t = x_ref[...]                     # (1, R, Wp, 6)
    th = xh_ref[...][:, 0]             # (1, 6, Wp, 6): 3 above, 3 below
    full = jnp.concatenate([th[:, :3], t, th[:, 3:]], axis=1)
    w = w_ref[...]                     # (7, 5, 6, 128)
    zc = jnp.zeros_like(full[:, :, :2])
    shifts = []
    for dpi in range(5):
        o = dpi - 2
        if o == 0:
            shifts.append(full)
        elif o > 0:
            # xshift_o[p] = full[p + o], zero outside [0, wp); static
            # sublane-dim slices (Mosaic cannot rotate bf16 sublanes).
            shifts.append(jnp.concatenate(
                [full[:, :, o:], zc[:, :, :o]], axis=2))
        else:
            shifts.append(jnp.concatenate(
                [zc[:, :, :(-o)], full[:, :, :o]], axis=2))
    xcat = jnp.concatenate(shifts, axis=-1)         # (1, R+6, Wp, 30)
    wcat = w.reshape(7, 5 * w.shape[2], w.shape[3])
    y = None
    for dyi in range(7):
        m = jax.lax.dot_general(
            xcat[:, dyi:dyi + rows], wcat[dyi],
            (((3,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y = m if y is None else y + m
    y = y + b_ref[...][:, :, None, :]
    y_ref[...] = y.astype(y_ref.dtype)
    _acc_stats(y, stat_refs)


def pack_weights7s2(w: jax.Array) -> jax.Array:
    """(7, 7, 3, 64) HWIO conv1 weights -> (7, 3, 12, 128) packed for
    STRIDE 2: output pixel 2p+po reads input column 4p + u, u = 2*po + dx
    in [-3, 5] -> packed-4 column p + dq, sub-position pi, with
    dq = floor(u/4) in [-1, 1], pi = u mod 4."""
    kh, kw, ci, co = w.shape
    out = jnp.zeros((kh, 3, 4 * ci, 2 * co), w.dtype)
    for po in range(2):
        for dxi, dx in enumerate(range(-3, 4)):
            u = 2 * po + dx
            dq = u // 4
            pi = u % 4
            out = out.at[:, dq + 1,
                         pi * ci:(pi + 1) * ci,
                         po * co:(po + 1) * co].set(w[:, dxi])
    return out


def _stem7s2_kernel(x_ref, xh_ref, w_ref, b_ref, y_ref, *stat_refs,
                    rows):
    """7x7 STRIDE-2 packed conv of the raw input image + fp32 output
    stats.  x_ref: (1, 2R, Wq, 12) input rows for this block's R output
    rows; xh_ref: (1, 5, Wq, 12) = 3 rows above + 2 below.  Output row r
    (local) with tap dy' reads input full[2r + dy' + 3]; padding full to
    an even row count and viewing it as (R+3, 2, ...) turns each dy' into
    a CONTIGUOUS row slice at parity (dy'+3) % 2."""
    t = x_ref[...]
    th = xh_ref[...][:, 0]
    full = jnp.concatenate(
        [th[:, :3], t, th[:, 3:5],
         jnp.zeros_like(th[:, :1])], axis=1)        # (1, 2R+6, Wq, 12)
    # Pre-shift the 12-channel input (static sublane-dim slices) and fold
    # the 3 packed-column offsets into one K=36 operand per dy tap —
    # same rationale as _stem7_kernel (rolling the 128-wide accumulator
    # per offset dominated the kernel).
    zc = jnp.zeros_like(full[:, :, :1])
    shifts = []
    for dqi in range(3):
        o = dqi - 1
        if o == 0:
            shifts.append(full)
        elif o > 0:
            shifts.append(jnp.concatenate(
                [full[:, :, o:], zc[:, :, :o]], axis=2))
        else:
            shifts.append(jnp.concatenate(
                [zc[:, :, :(-o)], full[:, :, :o]], axis=2))
    xcat = jnp.concatenate(shifts, axis=-1)         # (1, 2R+6, Wq, 36)
    view = xcat.reshape(1, rows + 3, 2, xcat.shape[2], xcat.shape[3])
    w = w_ref[...]                                  # (7, 3, 12, 128)
    wcat = w.reshape(7, 3 * w.shape[2], w.shape[3])  # dq-major, like xcat
    y = None
    for dyi in range(7):
        e, par = divmod(dyi, 2)
        m = jax.lax.dot_general(
            view[:, e:e + rows, par], wcat[dyi],
            (((3,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y = m if y is None else y + m
    y = y + b_ref[...][:, :, None, :]
    y_ref[...] = y.astype(y_ref.dtype)
    _acc_stats(y, stat_refs)


def _halo_rows_s2(x: jax.Array, r: int, boundary=None) -> jax.Array:
    """(B, H, Wq, C) input -> (B, Hout//r, 5, Wq, C): the 3 rows above and
    2 below each 2r-input-row block (one block per r output rows)."""
    b, h, wq, c = x.shape
    nblk = (h // 2) // r
    if boundary is None:
        above = jnp.zeros((b, 3, wq, c), x.dtype)
        below = jnp.zeros((b, 2, wq, c), x.dtype)
    else:
        above, below = boundary
        below = below[:, :2]
    span = 2 * r
    xpad_t = jnp.concatenate([above, x[:, : (nblk - 1) * span]], axis=1)
    xpad_b = jnp.concatenate([x[:, span:], below], axis=1)
    tops = [xpad_t[:, k::span][:, :nblk] for k in range(3)]
    bots = [xpad_b[:, k::span][:, :nblk] for k in range(2)]
    return jnp.stack(tops + bots, axis=2)


def _stem_conv1_s2(img, c1_params, dt, boundary=None, want_stats=True):
    """Pallas stride-2 conv1: (B, H, W, 3) image -> packed raw conv1
    output (B, H/2, W/4, 128) + packed fp32 output stats.  Requires
    H % 2 == 0 and W % 4 == 0."""
    b, h, w, ci = img.shape
    xq = img.astype(dt).reshape(b, h, w // 4, 4 * ci)
    r = _row_block(h // 2, row_elems=(w // 4) * 128)
    grid = (b, (h // 2) // r)
    xh = _halo_rows_s2(xq, r, boundary)
    w7 = pack_weights7s2(c1_params["kernel"]).astype(dt)
    bias = pack_vec(c1_params["bias"]).astype(dt)[None, None, :]
    co2 = w7.shape[-1]
    wq = w // 4
    c4 = 4 * ci

    out_shape = [jax.ShapeDtypeStruct((b, h // 2, wq, co2), dt)]
    if want_stats:
        out_shape += [jax.ShapeDtypeStruct((b, 1, co2), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(_stem7s2_kernel, rows=r),
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2 * r, wq, c4), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 5, wq, c4), lambda i, j: (i, j, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(w7.shape, lambda i, j: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, co2), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(
            [pl.BlockSpec((1, r, wq, co2), lambda i, j: (i, j, 0, 0),
                          memory_space=pltpu.VMEM)]
            + [pl.BlockSpec((1, 1, co2), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)] * (2 * want_stats)),
        name="encoder_stem_s2",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(xq, xh, w7, bias)
    if want_stats:
        return out[0], (out[1], out[2])
    return out[0], None


def _halo_rows3(x: jax.Array, r: int, boundary=None) -> jax.Array:
    """(B, H, Wp, C) -> (B, H//r, 6, Wp, C): the 3 rows above and 3 below
    each r-row block (zeros at local-array edges unless ``boundary``
    provides the neighbor shards' 3 edge rows each way)."""
    b, h, wp, c = x.shape
    nblk = h // r
    if boundary is None:
        above = below = jnp.zeros((b, 3, wp, c), x.dtype)
    else:
        above, below = boundary
    xpad_t = jnp.concatenate([above, x[:, : (nblk - 1) * r]], axis=1)
    xpad_b = jnp.concatenate([x[:, r:], below], axis=1)
    tops = [xpad_t[:, k::r][:, :nblk] for k in range(3)]
    bots = [xpad_b[:, k::r][:, :nblk] for k in range(3)]
    return jnp.stack(tops + bots, axis=2)


def _stem_conv1(img, c1_params, dt, boundary=None, want_stats=True):
    """Pallas conv1: (B, H, W, 3) [-1,1] image -> packed raw conv1 output
    (B, H, Wp, 128) + packed fp32 (sum, sumsq) output stats, one pass.
    Requires stride 1 (downsample <= 2) and W % 2 == 0."""
    xp = pack_view(img.astype(dt))                 # (B, H, W/2, 6)
    b, h, wp, c2 = xp.shape
    r = _row_block(h, row_elems=wp * 128)          # the output row: 128 packed
    grid = (b, h // r)
    xh = _halo_rows3(xp, r, boundary)
    w7 = pack_weights7(c1_params["kernel"]).astype(dt)
    bias = pack_vec(c1_params["bias"]).astype(dt)[None, None, :]
    co2 = w7.shape[-1]

    out_shape = [jax.ShapeDtypeStruct((b, h, wp, co2), dt)]
    if want_stats:
        out_shape += [jax.ShapeDtypeStruct((b, 1, co2), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(_stem7_kernel, rows=r),
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, r, wp, c2), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 6, wp, c2), lambda i, j: (i, j, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(w7.shape, lambda i, j: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, co2), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(
            [pl.BlockSpec((1, r, wp, co2), lambda i, j: (i, j, 0, 0),
                          memory_space=pltpu.VMEM)]
            + [pl.BlockSpec((1, 1, co2), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)] * (2 * want_stats)),
        name="encoder_stem",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(xp, xh, w7, bias)
    if want_stats:
        return out[0], (out[1], out[2])
    return out[0], None


def _stem_conv1_any(im, c1p, dt, stride, boundary, want_stats=True):
    if stride == 2:
        return _stem_conv1_s2(im, c1p, dt, boundary=boundary,
                              want_stats=want_stats)
    return _stem_conv1(im, c1p, dt, boundary=boundary,
                       want_stats=want_stats)


def _conv1_pack_for_halo(im, dt, stride):
    """The packed view whose edge rows the space-sharding exchange
    ships: pixel pairs for stride 1, packed fours for stride 2."""
    if stride == 2:
        b, h, w, ci = im.shape
        return im.astype(dt).reshape(b, h, w // 4, 4 * ci)
    return pack_view(im.astype(dt))


def _fused_forward1(img, c1_params, params, dt, stride=1,
                    want_residuals=False):
    """conv1 + stage, fused end to end; shard_map'd like _fused_forward.
    The stage's stats span the conv1 OUTPUT resolution (H/stride).
    ``want_residuals`` additionally returns conv1's packed raw output, the
    stage raws, and the prep affines (the backward's saved state)."""
    n = float((img.shape[1] // stride) * (img.shape[2] // stride))

    def local(im, c1p, p, space_axis=None, space_size=1):
        _, exch3 = _shard_ctx(1, space_axis, space_size, rows=3)
        imp = _conv1_pack_for_halo(im, dt, stride)
        yb = exch3(imp) if space_axis is not None else None
        yp, sums = _stem_conv1_any(im, c1p, dt, stride, yb)
        st1 = _expand_stats(*sums, n, space_axis)
        if want_residuals:
            out, raws, affs = _stage_on_packed(
                yp, st1, p, n, space_axis, space_size, want_residuals=True)
            return out, yp, raws, affs
        return _stage_on_packed(yp, st1, p, n, space_axis, space_size)

    return _shard_wrapped(local, img.shape, (img, c1_params, params))


def _xla_conv1(img, c1_params, dt, stride=1):
    """Plain-XLA conv1 (7x7 SAME) — backward linearization.
    No preferred_element_type: a fp32-typed output from bf16 operands
    makes the conv transpose ill-typed (see PointwisePaddedConv), and this
    formulation exists exactly to be differentiated."""
    x = img.astype(dt)
    y = jax.lax.conv_general_dilated(
        x, c1_params["kernel"].astype(dt), (stride, stride),
        ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + c1_params["bias"].astype(dt)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def conv1_stem_layer1(img, c1_params, params, dt=jnp.float32, stride=1):
    """Fused conv1 + norm1 + layer1 from the normalized input image.
    Forward is all-Pallas (one boundary: the image read); backward is the
    XLA reference formulation's VJP on global arrays."""
    return _fused_forward1(img, c1_params, params, dt, stride)


def _fwd1(img, c1_params, params, dt, stride):
    out, yp, raws, affs = _fused_forward1(img, c1_params, params, dt,
                                          stride, want_residuals=True)
    return out, (img, c1_params, params, yp, raws, affs)


def _bwd1(dt, stride, residuals, g):
    img, c1_params, params, yp, raws, affs = residuals
    dy1, dparams = _stage_bwd_xla(unpack_view(yp), raws, affs, params, g)
    dimg, dc1 = _conv1_bwd(img, c1_params, dt, stride, dy1)
    return dimg, dc1, dparams


conv1_stem_layer1.defvjp(_fwd1, _bwd1)


# --------------------------------------- affine-norm (frozen BN) pipeline

def bn_affine(norm_params, norm_stats, eps: float = 1e-5):
    """Frozen BatchNorm (use_running_average) folded to the kernels' prep
    affine: relu(x*s + t) with s = gamma*rsqrt(var+eps),
    t = beta - mean*s.  Exact for gamma == 0 channels too."""
    s = norm_params["scale"].astype(jnp.float32) * jax.lax.rsqrt(
        norm_stats["var"].astype(jnp.float32) + eps)
    t = norm_params["bias"].astype(jnp.float32) - \
        norm_stats["mean"].astype(jnp.float32) * s
    return s, t


def _pack_affines(affines, b, c2):
    return [(jnp.broadcast_to(pack_vec(s)[None, None], (b, 1, c2)),
             jnp.broadcast_to(pack_vec(t)[None, None], (b, 1, c2)))
            for s, t in affines]


def _xla_reference_affine(y1_raw, params, affines):
    """Plain-XLA mirror of the affine-norm stage (oracle + backward)."""
    def nr(x, a):
        s, t = a
        return jnp.maximum(x * s.astype(x.dtype) + t.astype(x.dtype), 0)

    def conv(x, p):
        return jax.lax.conv_general_dilated(
            x, p["kernel"].astype(x.dtype), (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["bias"].astype(x.dtype)

    t0 = nr(y1_raw, affines[0])
    u2 = nr(conv(nr(conv(t0, params["c10"]), affines[1]), params["c11"]),
            affines[2])
    t1 = jnp.maximum(t0 + u2, 0)
    v2 = nr(conv(nr(conv(t1, params["c20"]), affines[3]), params["c21"]),
            affines[4])
    return jnp.maximum(t1 + v2, 0)


def _fused_forward_affine(y1_raw, params, affines, want_residuals=False):
    """Affine-norm fused stage over the active mesh.  No stats, no psum
    — constant affines replicate.  ``want_residuals`` also returns the
    four raw conv outputs (the affines are primals, not residuals)."""
    def local(y1, p, aff, space_axis=None, space_size=1):
        xp = pack_view(y1)
        pa = _pack_affines(aff, xp.shape[0], xp.shape[-1])
        if want_residuals:
            out, raws, _ = _stage_on_packed(
                xp, pa[0], p, n=1.0, space_axis=space_axis,
                space_size=space_size, affines=pa[1:], want_residuals=True)
            return out, raws
        return _stage_on_packed(xp, pa[0], p, n=1.0, space_axis=space_axis,
                                space_size=space_size, affines=pa[1:])

    return _shard_wrapped(local, y1_raw.shape, (y1_raw, params, affines))


@jax.custom_vjp
def bn_stem_layer1(y1_raw, params, affines):
    """Fused affine-norm stage from conv1's raw output (stride-2 conv1
    configs); hand-written backward from saved residuals
    (_stage_bwd_xla_affine).  ``affines``: five UNPACKED per-channel
    (s, t) fp32 pairs — [norm1, l1_0.norm1, l1_0.norm2, l1_1.norm1,
    l1_1.norm2] (see bn_affine) — through which gradients flow to the
    BatchNorm scale/bias."""
    return _fused_forward_affine(y1_raw, params, affines)


def _fwd_bn(y1_raw, params, affines):
    out, raws = _fused_forward_affine(y1_raw, params, affines,
                                      want_residuals=True)
    return out, (y1_raw, params, affines, raws)


def _bwd_bn(residuals, g):
    y1_raw, params, affines, raws = residuals
    return _stage_bwd_xla_affine(y1_raw, raws, params, affines, g)


bn_stem_layer1.defvjp(_fwd_bn, _bwd_bn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def bn_conv1_stem_layer1(img, c1_params, params, affines, dt=jnp.float32,
                         stride=1):
    """Pallas conv1 + affine-norm stage."""
    return _fused_forward1_affine(img, c1_params, params, affines, dt,
                                  stride)


def _fused_forward1_affine(img, c1_params, params, affines, dt, stride=1,
                           want_residuals=False):
    def local(im, c1p, p, aff, space_axis=None, space_size=1):
        _, exch3 = _shard_ctx(1, space_axis, space_size, rows=3)
        yb = (exch3(_conv1_pack_for_halo(im, dt, stride))
              if space_axis is not None else None)
        yp, _ = _stem_conv1_any(im, c1p, dt, stride, yb, want_stats=False)
        pa = _pack_affines(aff, yp.shape[0], yp.shape[-1])
        if want_residuals:
            out, raws, _ = _stage_on_packed(
                yp, pa[0], p, n=1.0, space_axis=space_axis,
                space_size=space_size, affines=pa[1:], want_residuals=True)
            return out, yp, raws
        return _stage_on_packed(yp, pa[0], p, n=1.0, space_axis=space_axis,
                                space_size=space_size, affines=pa[1:])

    return _shard_wrapped(local, img.shape,
                          (img, c1_params, params, affines))


def _fwd1_bn(img, c1_params, params, affines, dt, stride):
    out, yp, raws = _fused_forward1_affine(img, c1_params, params, affines,
                                           dt, stride, want_residuals=True)
    return out, (img, c1_params, params, affines, yp, raws)


def _bwd1_bn(dt, stride, residuals, g):
    img, c1_params, params, affines, yp, raws = residuals
    dy1, dparams, daff = _stage_bwd_xla_affine(unpack_view(yp), raws,
                                               params, affines, g)
    dimg, dc1 = _conv1_bwd(img, c1_params, dt, stride, dy1)
    return dimg, dc1, dparams, daff


bn_conv1_stem_layer1.defvjp(_fwd1_bn, _bwd1_bn)


# --------------------------------------- backward from saved residuals
#
# The round-4 backward re-linearized the full XLA reference forward
# (jax.vjp(_xla_reference, ...)), so training paid Pallas-fwd + XLA-fwd +
# XLA-bwd and gated the stage off (-1.3% measured).  The Pallas pipeline
# already materializes every backward residual in HBM — each _enc_conv is
# its own pallas_call writing its raw output, and the prep affines carry
# (mean, rstd) — so the backward below consumes THOSE and never re-runs a
# forward: elementwise mask/activation recomputes, 8 transposed convs
# (jax.linear_transpose — no primal evaluation), and the instance-norm
# VJP's per-image reductions.  Reference analogue: the CUDA sampler's
# dedicated backward kernel (/root/reference/sampler/sampler_kernel.cu:63-105)
# rather than autodiff through a re-run forward.

def _drelu(z):
    """Derivative of jnp.maximum(z, 0) under JAX's tie convention (0.5 at
    z == 0 — measured; exact zeros are COMMON here because both operands
    of the residual adds are post-relu).  Emitted in z's dtype (0/0.5/1
    are exact in bf16) so bf16 backward chains stay bf16."""
    return jnp.where(z > 0, 1.0,
                     jnp.where(z < 0, 0.0, 0.5)).astype(z.dtype)


def _aff_stats(st):
    """Packed prep affine (s, t) each (B, 1, 2C) -> broadcastable unpacked
    (mean, rstd) (B, 1, 1, C) fp32.  s IS rstd (> 0 always: rsqrt of
    var + 1e-5) and t = -mean * rstd, so the inversion is exact."""
    s, t = st
    c = s.shape[-1] // 2
    rstd = s[..., :c].astype(jnp.float32)[:, :, None, :]
    mean = -t[..., :c].astype(jnp.float32)[:, :, None, :] / rstd
    return mean, rstd


# Packed-domain reduction path for the backward's instance-norm means.
# Module-level override for tests/A-B: None = auto (TPU, no active mesh,
# even W), True/False force.
_bwd_packed_sums = None


def _dual_sum_kernel(u_ref, v_ref, s1_ref, s2_ref):
    """Accumulate per-(image, packed-channel) fp32 (sum(u), sum(u*v)) —
    the two reductions of the instance-norm VJP, computed layout-preserving
    like the forward's stats kernels (same accumulation pattern as
    pallas_norm._in_stats_kernel)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref[...])
        s2_ref[...] = jnp.zeros_like(s2_ref[...])

    u = u_ref[...].astype(jnp.float32)          # in-register upcast: fp32
    v = v_ref[...].astype(jnp.float32)          # accumulation, any input dt
    s1_ref[...] += jnp.sum(u, axis=(1, 2))[:, None, :]
    s2_ref[...] += jnp.sum(u * v, axis=(1, 2))[:, None, :]


def _in_bwd_means(u, xhat):
    """(mean_HW(u), mean_HW(u * xhat)) as (B, 1, 1, C) fp32.

    On single-device TPU these run as ONE layout-preserving Pallas kernel
    over the packed row-major view: a plain XLA cross-(H,W) reduce of a
    conv-adjacent tensor forces full-tensor blocked<->row-major relayouts,
    and NO XLA-side formulation escapes that (measured exhaustively) — the
    exact storm that motivated this module.
    Under an active mesh the XLA form stays: the backward runs on GLOBAL
    arrays that GSPMD partitions, where a bare pallas_call cannot."""
    from ..parallel.context import active_corr_mesh

    use_packed = _bwd_packed_sums
    if use_packed is None:
        use_packed = (jax.default_backend() == "tpu"
                      and active_corr_mesh() is None
                      and u.shape[2] % 2 == 0)
    if not use_packed:
        # dtype=f32: fp32 accumulation without materializing fp32 copies.
        return (jnp.mean(u, axis=(1, 2), keepdims=True, dtype=jnp.float32),
                jnp.mean(u * xhat, axis=(1, 2), keepdims=True,
                         dtype=jnp.float32))
    # Operands stay in their storage dtype (bf16 under training) — the
    # kernel upcasts in-register; .astype(f32) here would MATERIALIZE a
    # ~1 GB fp32 copy per tensor at recipe shapes (measured: HBM OOM).
    up = pack_view(u)
    vp = pack_view(xhat)
    b, h, wp, c2 = up.shape
    r = _row_block(h, row_elems=wp * c2)
    s1, s2 = pl.pallas_call(
        _dual_sum_kernel,
        out_shape=(jax.ShapeDtypeStruct((b, 1, c2), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c2), jnp.float32)),
        grid=(b, h // r),
        in_specs=[pl.BlockSpec((1, r, wp, c2), lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.VMEM)] * 2,
        out_specs=(pl.BlockSpec((1, 1, c2), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 1, c2), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.VMEM)),
        name="encoder_norm_bwd_sums",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(up, vp)
    n = float(u.shape[1] * u.shape[2])
    c = c2 // 2
    m1 = (s1[..., :c] + s1[..., c:])[:, :, None, :] / n
    m2 = (s2[..., :c] + s2[..., c:])[:, :, None, :] / n
    return m1, m2


def _in_bwd(xhat, rstd, u):
    """VJP of x -> xhat = (x - mean(x)) * rstd(x) through the per-image
    statistics: dx = rstd * (u - mean_HW(u) - xhat * mean_HW(u * xhat)),
    exact including the 1e-5 epsilon (xhat carries it).  The large
    tensors stay in their storage dtype (bf16 under training — the
    reference backward rounds comparably); only the means are fp32."""
    mu, mux = _in_bwd_means(u, xhat)
    dt = u.dtype
    return rstd.astype(dt) * (u - mu.astype(dt) - xhat * mux.astype(dt))


def _conv_bwd(t, kernel, dy):
    """(dt, dkernel, dbias) of y = conv3x3_same(t, kernel) + bias via
    linear transposition — unlike jax.vjp, never evaluates the primal."""
    def conv_in(a):
        return jax.lax.conv_general_dilated(
            a, kernel, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def conv_k(k):
        return jax.lax.conv_general_dilated(
            t, k, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    dt = jax.linear_transpose(conv_in, t)(dy)[0]
    dk = jax.linear_transpose(conv_k, kernel)(dy)[0]
    return dt, dk, dy.sum((0, 1, 2), dtype=jnp.float32)


def _stage_bwd_xla(y1_raw, raws, affs, params, g):
    """Hand-written backward of the instance-norm stage from saved
    residuals.  Returns (dy1_raw, dparams).  Mask/activation recomputes
    are elementwise (XLA fuses them) and STAY in the storage dtype —
    fp32 upcasts here materialize ~1 GB per tensor at recipe shapes
    (measured HBM OOM); the reference backward rounds in bf16 the same
    way.  The tiny per-image statistics are fp32 throughout."""
    cdt = y1_raw.dtype
    c10, c11, c20, c21 = [unpack_view(r) for r in raws]
    y1 = y1_raw

    def nh(c, st):
        m, r = st
        return (c - m.astype(cdt)) * r.astype(cdt)

    stats = [_aff_stats(a) for a in affs]
    r1, r10, r11, r20, r21 = [s[1] for s in stats]

    x0 = nh(y1, stats[0])
    t0 = jnp.maximum(x0, 0)
    x10 = nh(c10, stats[1])
    t10 = jnp.maximum(x10, 0)
    x11 = nh(c11, stats[2])
    u2 = jnp.maximum(x11, 0)
    z1 = t0 + u2
    t1 = jnp.maximum(z1, 0)
    x20 = nh(c20, stats[3])
    t20 = jnp.maximum(x20, 0)
    x21 = nh(c21, stats[4])
    v2 = jnp.maximum(x21, 0)

    def kp(name):
        return params[name]["kernel"].astype(cdt)

    go = g.astype(cdt) * _drelu(t1 + v2)
    dc21 = _in_bwd(x21, r21, go * _drelu(x21))
    dt20, dk21, db21 = _conv_bwd(t20, kp("c21"), dc21)
    dc20 = _in_bwd(x20, r20, dt20 * _drelu(x20))
    dt1c, dk20, db20 = _conv_bwd(t1, kp("c20"), dc20)
    dz1 = (go + dt1c) * _drelu(z1)
    dc11 = _in_bwd(x11, r11, dz1 * _drelu(x11))
    dt10, dk11, db11 = _conv_bwd(t10, kp("c11"), dc11)
    dc10 = _in_bwd(x10, r10, dt10 * _drelu(x10))
    dt0c, dk10, db10 = _conv_bwd(t0, kp("c10"), dc10)
    dy1 = _in_bwd(x0, r1, (dz1 + dt0c) * _drelu(x0))

    def dparam(name, dk, db):
        p = params[name]
        return {"kernel": dk.astype(p["kernel"].dtype),
                "bias": db.astype(p["bias"].dtype)}

    dparams = {"c10": dparam("c10", dk10, db10),
               "c11": dparam("c11", dk11, db11),
               "c20": dparam("c20", dk20, db20),
               "c21": dparam("c21", dk21, db21)}
    return dy1.astype(y1_raw.dtype), dparams


def _stage_bwd_xla_affine(y1_raw, raws, params, affines, g):
    """Backward of the affine-norm (frozen BN) stage from saved residuals.
    Returns (dy1_raw, dparams, daffines) — gradients flow into the folded
    BatchNorm scale/bias pairs like the reference backward."""
    cdt = y1_raw.dtype
    c10, c11, c20, c21 = [unpack_view(r) for r in raws]
    y1 = y1_raw
    aff = [(s.astype(cdt), t.astype(cdt)) for s, t in affines]

    def pre(c, i):
        s, t = aff[i]
        return c * s + t

    z0 = pre(y1, 0)
    t0 = jnp.maximum(z0, 0)
    z10 = pre(c10, 1)
    t10 = jnp.maximum(z10, 0)
    z11 = pre(c11, 2)
    u2 = jnp.maximum(z11, 0)
    z1 = t0 + u2
    t1 = jnp.maximum(z1, 0)
    z20 = pre(c20, 3)
    t20 = jnp.maximum(z20, 0)
    z21 = pre(c21, 4)
    v2 = jnp.maximum(z21, 0)

    daff = [None] * 5

    def aff_bwd(dact, z, c, i):
        u = dact * _drelu(z)
        s, _ = aff[i]
        # fp32 accumulation via the reduce dtype — no fp32 materialization.
        daff[i] = ((u * c).sum((0, 1, 2), dtype=jnp.float32)
                   .astype(affines[i][0].dtype),
                   u.sum((0, 1, 2), dtype=jnp.float32)
                   .astype(affines[i][1].dtype))
        return u * s

    def kp(name):
        return params[name]["kernel"].astype(cdt)

    go = g.astype(cdt) * _drelu(t1 + v2)
    dc21 = aff_bwd(go, z21, c21, 4)
    dt20, dk21, db21 = _conv_bwd(t20, kp("c21"), dc21)
    dc20 = aff_bwd(dt20, z20, c20, 3)
    dt1c, dk20, db20 = _conv_bwd(t1, kp("c20"), dc20)
    dz1 = (go + dt1c) * _drelu(z1)
    dc11 = aff_bwd(dz1, z11, c11, 2)
    dt10, dk11, db11 = _conv_bwd(t10, kp("c11"), dc11)
    dc10 = aff_bwd(dt10, z10, c10, 1)
    dt0c, dk10, db10 = _conv_bwd(t0, kp("c10"), dc10)
    dy1 = aff_bwd(dz1 + dt0c, z0, y1, 0)

    def dparam(name, dk, db):
        p = params[name]
        return {"kernel": dk.astype(p["kernel"].dtype),
                "bias": db.astype(p["bias"].dtype)}

    dparams = {"c10": dparam("c10", dk10, db10),
               "c11": dparam("c11", dk11, db11),
               "c20": dparam("c20", dk20, db20),
               "c21": dparam("c21", dk21, db21)}
    return (dy1.astype(y1_raw.dtype), dparams,
            [tuple(d) for d in daff])


def _conv1_bwd(img, c1_params, dt, stride, dy1):
    """(dimg, dc1_params) of the 7x7 stem conv via linear transposition
    (the astype casts transpose to casts back, so cotangent dtypes match
    the primals')."""
    k = c1_params["kernel"]

    def f_im(im):
        return jax.lax.conv_general_dilated(
            im.astype(dt), k.astype(dt), (stride, stride), ((3, 3), (3, 3)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def f_k(kk):
        return jax.lax.conv_general_dilated(
            img.astype(dt), kk.astype(dt), (stride, stride),
            ((3, 3), (3, 3)), dimension_numbers=("NHWC", "HWIO", "NHWC"))

    g = dy1.astype(dt)
    dimg = jax.linear_transpose(f_im, img)(g)[0]
    dk = jax.linear_transpose(f_k, k)(g)[0]
    db = (dy1.sum((0, 1, 2), dtype=jnp.float32)
          .astype(c1_params["bias"].dtype))
    return dimg, {"kernel": dk, "bias": db}


# ------------------------------------------------- reference + custom VJP

def _xla_reference(y1_raw, params):
    """Plain-XLA mirror of fused_stem_layer1 (oracle + backward)."""
    from .pallas_norm import _xla_instance_norm

    def norm_relu(x):
        return _xla_instance_norm(x, relu=True)

    def conv(x, p):
        # No preferred_element_type — this mirror IS the backward
        # formulation, and a fp32-typed output from bf16 operands makes
        # the conv transpose ill-typed (see PointwisePaddedConv).
        return jax.lax.conv_general_dilated(
            x, p["kernel"].astype(x.dtype), (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["bias"].astype(x.dtype)

    t0 = norm_relu(y1_raw)
    u2 = norm_relu(conv(norm_relu(conv(t0, params["c10"])), params["c11"]))
    t1 = jnp.maximum(t0 + u2, 0)
    v2 = norm_relu(conv(norm_relu(conv(t1, params["c20"])), params["c21"]))
    return jnp.maximum(t1 + v2, 0)


def _shard_wrapped(local, shape, operands):
    """Run ``local(*operands, space_axis=..., space_size=...)`` inside
    shard_map over the active (data, space) mesh when one is set
    (parallel/context.py) and partitionable, else directly.  The FIRST
    operand is batch/height-sharded; the rest replicate.  Single home for
    the wrapper plumbing all four fused entry points share."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, SPACE_AXIS

    shard = _stem_shard_mesh(shape)
    if shard is None:
        return local(*operands)
    mesh, d, s = shard
    spec = P(DATA_AXIS, SPACE_AXIS, None, None)
    # Residual-returning locals produce a pytree mixing (B, H, Wp, C2)
    # tensors (shard like the input) and (B, 1, 2C) prep affines (psum'd
    # inside, so replicated over space: shard over data only).  The output
    # structure comes from an eval_shape of the UNSHARDED local — identical
    # pytree, zero compute.
    stat = P(DATA_AXIS, None, None)
    outs = jax.eval_shape(lambda *a: local(*a), *operands)
    out_specs = jax.tree.map(lambda l: spec if l.ndim == 4 else stat, outs)
    fn = functools.partial(local, space_axis=SPACE_AXIS if s > 1 else None,
                           space_size=s)
    in_specs = (spec,) + (P(),) * (len(operands) - 1)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*operands)


def _fused_forward(y1_raw, params):
    """The fused pipeline over the active mesh.  Batch sharding needs no
    communication (instance-norm stats are per-image); space sharding adds
    a stats psum + 2 ppermute'd halo rows per conv — both tiny next to the
    conv work.  The trace-time mesh consult mirrors ops/corr.py."""
    n = float(y1_raw.shape[1] * y1_raw.shape[2])
    return _shard_wrapped(
        functools.partial(fused_stem_layer1, n=n),
        y1_raw.shape, (y1_raw, params))


def _fused_forward_res(y1_raw, params):
    """_fused_forward that also returns the backward residuals (raw conv
    outputs + all five prep affines) as global arrays."""
    n = float(y1_raw.shape[1] * y1_raw.shape[2])

    def local(y1, p, space_axis=None, space_size=1):
        xp = pack_view(y1)
        st1 = _expand_stats(*_packed_stats(xp), n, space_axis)
        return _stage_on_packed(xp, st1, p, n, space_axis, space_size,
                                want_residuals=True)

    return _shard_wrapped(local, y1_raw.shape, (y1_raw, params))


@jax.custom_vjp
def stem_layer1(y1_raw: jax.Array, params: dict) -> jax.Array:
    """Fused forward; hand-written backward from the forward's saved
    residuals (_stage_bwd_xla — no forward re-linearization).  The
    backward runs on the GLOBAL arrays as plain XLA ops, so under a mesh
    GSPMD partitions it (conv halo exchanges included) without any manual
    collectives."""
    return _fused_forward(y1_raw, params)


def _fwd(y1_raw, params):
    out, raws, affs = _fused_forward_res(y1_raw, params)
    return out, (y1_raw, raws, affs, params)


def _bwd(residuals, g):
    y1_raw, raws, affs, params = residuals
    return _stage_bwd_xla(y1_raw, raws, affs, params, g)


stem_layer1.defvjp(_fwd, _bwd)
