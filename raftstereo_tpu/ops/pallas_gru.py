"""Fused Pallas megakernel for the finest-level GRU update block.

One kernel call per refinement iteration computes the whole finest-level
update — motion encoder (convc1/convc2/convf1/convf2/conv), the gru0
z/r/q gate convs, the hidden-state blend and the flow head — with every
intermediate (gate pre-activations, r*h, the motion-feature concat, the
flow-head hidden) living only in VMEM.  The XLA scan body materializes
each of those in HBM every iteration (~1000 channel-equivalents per
pixel per step, profiled round 5); the fused step's HBM traffic is the
carried state itself (h, disparity) plus the sampled correlation
features and the loop invariants — roughly a 4x reduction on the loop's
memory traffic at flagship shapes (docs/perf_notes_r06.md).

Design, built on the data-stationary 3x3-conv formulation validated by
scripts/mb_gru_kernel.py (90.8 TF/s packed vs XLA's 74.8 at GRU shapes):

* weights shift, not activations: dy taps are row slices on the untiled
  outer axis (free), the per-tap matmuls take contiguous operands, and
  only the three accumulated outputs are realigned (2 rolls + masks);
* the ``_sliced_conv`` kernel-splits of models/update.py become weight
  SLICES inside the kernel: the gate convs run one dot per (tap,
  operand) over h / motion features / the upsampled coarser state and
  accumulate — the [h, x] concats never exist anywhere;
* grid is (B,); each batch row's full arrays ride in VMEM and a static
  Python loop walks row slabs (overlapping halo recompute, receptive
  field 9 rows end-to-end), so intermediates stay slab-sized and VMEM
  scales with H*W*C of the INPUTS, not the intermediates;
* the 7x7 flow conv contracts only the disparity channel (the y-flow is
  structurally zero) as 49 shifted copies -> one (49 -> 64) matmul,
  the tap-matmul trick from models/update.tap_conv3x3.

Semantics mirror ``BasicMultiUpdateBlock`` for the finest level in test
mode (no mask head — the model computes the final mask once after the
scan).  The backward is the XLA reference formulation's VJP via
``jax.custom_vjp`` (same policy as ops/pallas_encoder.py); the kernel
gates off under device meshes and on CPU (``use_fused_gru``).
"""

from __future__ import annotations

import functools
import threading
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_corr import _COMPILER_PARAMS, _interpret
from .pallas_encoder import make_override_scope

# Receptive-field depths (rows each side of a slab's center rows) of the
# fused chain, counted back from its two outputs:
#   delta <- fh2(1) <- fh1(1) <- h_new(+-2)
#   h_new <- z/q convs(1) <- r conv(1)        => h, x at +-4
#   x = [motion features, ext]                => ext at +-4
#   mf <- me conv(1) <- convc2(1) <- c1(1x1)  => corr at +-6
#   mf <- me conv(1) <- convf2(1) <- convf1(3)=> disp at +-9
_D_H = 4
_D_X = 4
_D_CORR = 6
_D_DISP = 9

# Weight-pack key order == kernel operand order (ext entries dropped for
# single-level GRUs).  Values are (9, Cin, Cout) taps for 3x3 convs,
# (49, 64) for the 7x7 flow conv, (Ck, 64) for the 1x1 corr conv and
# (1, 1, C) biases.
_WKEYS = ("wzr_h", "wzr_m", "wzr_e", "bzr",
          "wq_h", "wq_m", "wq_e", "bq",
          "wc1", "bc1", "wc2", "bc2",
          "wf1", "bf1", "wf2", "bf2",
          "wmc", "wmf", "bme",
          "wfh1", "bfh1", "wfh2", "bfh2")


_tls = threading.local()
_get_override, override_fused_gru = make_override_scope(
    _tls, "fused_gru_override")


def use_fused_gru(backend: str, test_mode: bool) -> bool:
    """Gate for the fused GRU step.

    ``backend`` is config.gru_backend: "auto" resolves to the XLA
    reference step on every backend — Mosaic does not lower the kernel
    at flagship size in a time a server start can bear (PR 24,
    ROADMAP.md Speed 2); "fused"/"xla" force one path (tests force
    "fused" on CPU to exercise the interpret-mode kernel; on a TPU an
    explicit "fused" raises whatever the compiler raises).  The kernel covers
    the test-mode step only (no per-iteration mask head), so train-mode
    tracing always takes the XLA step.  A bare pallas_call cannot be
    SPMD-partitioned, so any active corr mesh (parallel/context.py)
    gates the kernel off — loudly if it was explicitly requested.
    The thread-local ``override_fused_gru`` scope sits between the two:
    an explicit config backend wins over it (same precedence as
    ops/pallas_encoder.use_fused_stem)."""
    if not test_mode:
        return False
    ov: Optional[bool] = None
    if backend != "auto":
        ov = backend == "fused"
    elif _get_override() is not None:
        ov = _get_override()
    from ..parallel.context import active_corr_mesh

    if active_corr_mesh() is not None:
        if ov:
            warnings.warn(
                "fused GRU backend cannot run under an active corr mesh; "
                "using the XLA reference step", RuntimeWarning, stacklevel=2)
        return False
    if ov is not None:
        return ov
    return False


def resolve_gru_backend(config) -> str:
    """The backend string a test-mode executable compiles with — the
    serving engine's cache-key component (serve/engine.py): everything
    that selects a distinct compiled program must reach the key."""
    return "fused" if use_fused_gru(config.gru_backend, True) else "xla"


# ---------------------------------------------------------------- packing

def _w9(k, dt):
    """(3, 3, Cin, Cout) HWIO -> (9, Cin, Cout), dy-major."""
    return k.reshape(9, k.shape[2], k.shape[3]).astype(dt)


def _b(v, dt):
    return v.reshape(1, 1, -1).astype(dt)


def pack_update_params(params: Dict, corr_channels: int, ext_dim: int,
                       dtype) -> Dict[str, jax.Array]:
    """Kernel weight pack from the update block's parameter tree
    (models/update.BasicMultiUpdateBlock variables["params"]).

    The gate convs' fused-input kernels are SLICED along the input axis
    exactly like models/update._sliced_conv — [0:hd] convolves h,
    [hd:hd+128] the motion features, [hd+128:] the upsampled coarser
    state — so the parameter tree is untouched and checkpoints stay
    bit-compatible.  ``corr_channels`` is the width the correlation
    lookup actually emits (the pallas_alt backend's lane-friendly pad);
    convc1's kernel is zero-row-padded to match, the same arithmetic
    identity PointwisePaddedConv applies.  ``ext_dim`` is 0 for
    single-level GRUs (the ext entries are dropped from the pack)."""
    enc, gru, fh = params["encoder"], params["gru0"], params["flow_head"]
    kzr = gru["convzr"]["kernel"]
    hd = kzr.shape[-1] // 2
    assert kzr.shape[2] == hd + 128 + ext_dim, (kzr.shape, hd, ext_dim)
    kq = gru["convq"]["kernel"]
    kc1 = enc["convc1"]["kernel"][0, 0]          # (cor_planes, 64)
    pad = corr_channels - kc1.shape[0]
    assert pad >= 0, (corr_channels, kc1.shape)
    if pad:
        kc1 = jnp.pad(kc1, ((0, pad), (0, 0)))
    kme = enc["conv"]["kernel"]                  # (3, 3, 128, 126)
    me_out = kme.shape[-1]
    w = {
        "wzr_h": _w9(kzr[:, :, :hd], dtype),
        "wzr_m": _w9(kzr[:, :, hd:hd + 128], dtype),
        "bzr": _b(gru["convzr"]["bias"], dtype),
        "wq_h": _w9(kq[:, :, :hd], dtype),
        "wq_m": _w9(kq[:, :, hd:hd + 128], dtype),
        "bq": _b(gru["convq"]["bias"], dtype),
        "wc1": kc1.astype(dtype),
        "bc1": _b(enc["convc1"]["bias"], dtype),
        "wc2": _w9(enc["convc2"]["kernel"], dtype),
        "bc2": _b(enc["convc2"]["bias"], dtype),
        # The y-flow channel is structurally zero (the model builds
        # flow = [d, 0] every iteration): contract only the x slice.
        "wf1": enc["convf1"]["kernel"][:, :, 0].reshape(49, -1).astype(dtype),
        "bf1": _b(enc["convf1"]["bias"], dtype),
        "wf2": _w9(enc["convf2"]["kernel"], dtype),
        "bf2": _b(enc["convf2"]["bias"], dtype),
        # me conv split along its [cor, flo] input concat; output padded
        # 126 -> 128 with zero columns (the flow channels are injected
        # on top of the zero lanes in-kernel).
        "wmc": _w9(jnp.pad(kme[:, :, :64], ((0, 0), (0, 0), (0, 0),
                                            (0, 128 - me_out))), dtype),
        "wmf": _w9(jnp.pad(kme[:, :, 64:], ((0, 0), (0, 0), (0, 0),
                                            (0, 128 - me_out))), dtype),
        "bme": _b(jnp.pad(enc["conv"]["bias"], (0, 128 - me_out)), dtype),
        "wfh1": _w9(fh["conv1"]["kernel"], dtype),
        "bfh1": _b(fh["conv1"]["bias"], dtype),
        "wfh2": _w9(fh["conv2"]["kernel"], dtype),
        "bfh2": _b(fh["conv2"]["bias"], dtype),
    }
    if ext_dim:
        w["wzr_e"] = _w9(kzr[:, :, hd + 128:], dtype)
        w["wq_e"] = _w9(kq[:, :, hd + 128:], dtype)
    return w


def _slab_plan(h: int) -> Tuple[int, Tuple[int, ...]]:
    """(slab rows, static slab starts): bounded unroll (<= 8 slabs), the
    last slab clamped so every start + R <= H (overlapping rows are
    recomputed identically — pure function of the inputs)."""
    if h <= 32:
        return h, (0,)
    r = max(32, -(-h // 8))
    starts = list(range(0, h - r, r)) + [h - r]
    return r, tuple(starts)


# ----------------------------------------------------------------- kernel

def _roll_w(u, o, wd):
    """shift_o(u)[:, w] = u[:, w + o], zero outside [0, wd) — the
    data-stationary dx realignment (scripts/mb_gru_kernel.py)."""
    if o == 0:
        return u
    col = jax.lax.broadcasted_iota(jnp.int32, (1, wd, 1), 1)
    s = pltpu.roll(u, (-o) % wd, 1)
    if o > 0:
        return jnp.where(col < wd - o, s, jnp.zeros_like(s))
    return jnp.where(col >= -o, s, jnp.zeros_like(s))


def _conv3(ops, bias, wd):
    """Data-stationary SAME 3x3 conv over row slabs, fp32 accumulation.

    ``ops`` is a list of (window, w9) pairs summed over — the in-kernel
    form of models/update._sliced_conv's channel partition.  Windows are
    (rows_out + 2, wd, Cin); returns (rows_out, wd, Cout) fp32 + bias."""
    rows_out = ops[0][0].shape[0] - 2
    y = None
    for dxi in range(3):
        u = None
        for x_win, w9 in ops:
            for dyi in range(3):
                m = jax.lax.dot_general(
                    x_win[dyi:dyi + rows_out], w9[dyi * 3 + dxi],
                    (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                u = m if u is None else u + m
        s = _roll_w(u, dxi - 1, wd)
        y = s if y is None else y + s
    return y + bias.astype(jnp.float32)


def _conv7x1(d_win, w49, bias, wd):
    """7x7 SAME conv of the 1-channel disparity window: 49 shifted
    copies of the scalar field concatenated along lanes, one
    (49 -> Cout) matmul (the tap-matmul trick, models/update.py)."""
    rows_out = d_win.shape[0] - 6
    taps = []
    for dyi in range(7):
        rows = d_win[dyi:dyi + rows_out]
        for dxi in range(7):
            taps.append(_roll_w(rows, dxi - 3, wd))
    z = jnp.concatenate(taps, axis=-1).astype(w49.dtype)  # (rows_out, wd, 49)
    y = jax.lax.dot_general(z, w49, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y + bias.astype(jnp.float32)


def _gru_update_kernel(*refs, hgt, wd, rr, starts, has_ext, hd):
    """One batch row's full fused update: static slab loop, all
    intermediates slab-resident in VMEM."""
    it = iter(refs)
    h_ref = next(it)
    ext_ref = next(it) if has_ext else None
    corr_ref, disp_ref, cz_ref, cr_ref, cq_ref = (next(it) for _ in range(5))
    w = {}
    for k in _WKEYS:
        if not has_ext and k in ("wzr_e", "wq_e"):
            continue
        w[k] = next(it)[...]
    hnew_ref, delta_ref = next(it), next(it)
    ct = h_ref.dtype

    h = h_ref[0]
    ext = ext_ref[0] if has_ext else None
    corr = corr_ref[0]
    disp = disp_ref[0]
    cz, cr, cq = cz_ref[0], cr_ref[0], cq_ref[0]

    def win(x, s, d):
        """Rows [s - d, s + rr + d) with zeros outside the image — the
        conv zero padding, materialized only at edge slabs (interior
        slabs are plain static slices)."""
        lo, hi = s - d, s + rr + d
        a, b2 = max(lo, 0), min(hi, hgt)
        parts = []
        if a > lo:
            parts.append(jnp.zeros((a - lo,) + x.shape[1:], x.dtype))
        parts.append(x[a:b2])
        if hi > b2:
            parts.append(jnp.zeros((hi - b2,) + x.shape[1:], x.dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    def mask(t, s, d):
        """Zero rows outside the image: a conv output at such rows is
        its bias, but the NEXT conv's zero padding needs exact zeros.
        Static no-op for interior slabs."""
        lo = s - d
        if lo >= 0 and lo + t.shape[0] <= hgt:
            return t
        i = jax.lax.broadcasted_iota(jnp.int32, (t.shape[0], 1, 1), 0) + lo
        return jnp.where((i >= 0) & (i < hgt), t, jnp.zeros_like(t))

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 128), 2)
    onehot126 = (lane == 126).astype(jnp.float32)

    for s in starts:
        # ---- motion encoder (fixed 64/128-channel geometry)
        c1 = mask(jnp.maximum(
            (jax.lax.dot_general(win(corr, s, _D_CORR), w["wc1"],
                                 (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + w["bc1"].astype(jnp.float32)).astype(ct), 0), s, _D_CORR)
        cor = mask(jnp.maximum(
            _conv3([(c1, w["wc2"])], w["bc2"], wd).astype(ct), 0), s, 5)
        d9f = win(disp, s, _D_DISP)
        d9 = d9f.astype(ct)
        f1 = mask(jnp.maximum(
            _conv7x1(d9f, w["wf1"], w["bf1"], wd).astype(ct), 0), s, 6)
        flo = mask(jnp.maximum(
            _conv3([(f1, w["wf2"])], w["bf2"], wd).astype(ct), 0), s, 5)
        me = mask(jnp.maximum(
            _conv3([(cor, w["wmc"]), (flo, w["wmf"])],
                   w["bme"], wd).astype(ct), 0), s, _D_X)
        # motion features = [me(126, zero-padded to 128), d, 0]: the
        # disparity rides on lane 126 (lane 127 stays the zero y-flow).
        mf = me + (d9f[5:-5] * onehot126).astype(ct)

        # ---- gru0 gates: one dot per (tap, operand), no concats
        h4 = win(h, s, _D_H)
        zr_ops = [(h4, w["wzr_h"]), (mf, w["wzr_m"])]
        if has_ext:
            e4 = win(ext, s, _D_X)
            zr_ops.append((e4, w["wzr_e"]))
        zr = _conv3(zr_ops, w["bzr"], wd).astype(ct)
        f32 = jnp.float32
        # Transcendentals in f32 and cast back: Mosaic verifies neither
        # sigmoid nor tanh on bf16 operands (PR 24).
        z = jax.nn.sigmoid(
            (zr[..., :hd] + win(cz, s, 3)).astype(f32)).astype(ct)
        r = jax.nn.sigmoid(
            (zr[..., hd:] + win(cr, s, 3)).astype(f32)).astype(ct)
        rh = r * h4[1:-1]
        q_ops = [(rh, w["wq_h"]), (mf[1:-1], w["wq_m"])]
        if has_ext:
            q_ops.append((e4[1:-1], w["wq_e"]))
        q = jnp.tanh((_conv3(q_ops, w["bq"], wd).astype(ct)
                      + win(cq, s, 2)).astype(f32)).astype(ct)
        z2 = z[1:-1]
        hn = mask((1 - z2) * h4[2:-2] + z2 * q, s, 2)

        # ---- flow head
        fh = mask(jnp.maximum(
            _conv3([(hn, w["wfh1"])], w["bfh1"], wd).astype(ct), 0), s, 1)
        delta = _conv3([(fh, w["wfh2"])], w["bfh2"], wd).astype(ct)

        hnew_ref[0, s:s + rr] = hn[2:-2]
        delta_ref[0, s:s + rr] = delta


def _fused_forward(h, ext, corr, disp, cz, cr, cq, wpack):
    b, hgt, wd, hd = h.shape
    has_ext = ext is not None
    ct = h.dtype
    rr, starts = _slab_plan(hgt)

    def full(x):
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda i: (i,) + (0,) * (x.ndim - 1),
                            memory_space=pltpu.VMEM)

    def const(x):
        return pl.BlockSpec(x.shape, lambda i: (0,) * x.ndim,
                            memory_space=pltpu.VMEM)

    operands = [h] + ([ext] if has_ext else []) + [
        corr.astype(ct), disp.astype(jnp.float32), cz, cr, cq]
    in_specs = [full(x) for x in operands]
    for k in _WKEYS:
        if not has_ext and k in ("wzr_e", "wq_e"):
            continue
        operands.append(wpack[k])
        in_specs.append(const(wpack[k]))

    hn, delta = pl.pallas_call(
        functools.partial(_gru_update_kernel, hgt=hgt, wd=wd, rr=rr,
                          starts=starts, has_ext=has_ext, hd=hd),
        out_shape=(jax.ShapeDtypeStruct((b, hgt, wd, hd), ct),
                   jax.ShapeDtypeStruct((b, hgt, wd, 2), ct)),
        grid=(b,),
        in_specs=in_specs,
        out_specs=(full(h), pl.BlockSpec(
            (1, hgt, wd, 2), lambda i: (i, 0, 0, 0),
            memory_space=pltpu.VMEM)),
        name="gru_fused_step",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(*operands)
    return hn, delta


# ------------------------------------------------- XLA reference + VJP

def _xla_reference_update(h, ext, corr, disp, cz, cr, cq, wpack):
    """Plain-XLA mirror of the fused step on the SAME packed weights —
    the kernel's parity oracle (tests/test_pallas_gru.py) and the
    backward formulation (its VJP is the custom_vjp's bwd, the
    pallas_encoder policy: training cost unchanged, no kernel VJP)."""
    ct = h.dtype

    def conv(x, w, bias, kh=3, kw=3):
        # w: (kh*kw, Cin, Cout) taps, or (kh*kw, Cout) for the 1-channel
        # flow conv — reshaped back to HWIO.
        cin = 1 if w.ndim == 2 else w.shape[1]
        k = w.reshape(kh, kw, cin, w.shape[-1])
        p = ((kh // 2, kh // 2), (kw // 2, kw // 2))
        y = jax.lax.conv_general_dilated(
            x, k.astype(ct), (1, 1), p,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + bias.astype(ct)

    c1 = jax.nn.relu(jnp.tensordot(corr.astype(ct), wpack["wc1"], 1)
                     + wpack["bc1"].astype(ct))
    cor = jax.nn.relu(conv(c1, wpack["wc2"], wpack["bc2"]))
    dct = disp.astype(ct)
    f1 = jax.nn.relu(conv(dct, wpack["wf1"], wpack["bf1"], kh=7, kw=7))
    flo = jax.nn.relu(conv(f1, wpack["wf2"], wpack["bf2"]))
    me = jax.nn.relu(conv(cor, wpack["wmc"], wpack["bme"])
                     + conv(flo, wpack["wmf"],
                            jnp.zeros_like(wpack["bme"])))
    mf = me + jnp.pad(dct, ((0, 0), (0, 0), (0, 0), (126, 1)))
    hd = h.shape[-1]
    zr = (conv(h, wpack["wzr_h"], wpack["bzr"])
          + conv(mf, wpack["wzr_m"], jnp.zeros_like(wpack["bzr"])))
    qp = (conv(mf, wpack["wq_m"], wpack["bq"]))
    if ext is not None:
        zr = zr + conv(ext, wpack["wzr_e"], jnp.zeros_like(wpack["bzr"]))
        qp = qp + conv(ext, wpack["wq_e"], jnp.zeros_like(wpack["bq"]))
    z = jax.nn.sigmoid(zr[..., :hd] + cz)
    r = jax.nn.sigmoid(zr[..., hd:] + cr)
    q = jnp.tanh(qp + conv(r * h, wpack["wq_h"],
                           jnp.zeros_like(wpack["bq"])) + cq)
    hn = (1 - z) * h + z * q
    fh = jax.nn.relu(conv(hn, wpack["wfh1"], wpack["bfh1"]))
    delta = conv(fh, wpack["wfh2"], wpack["bfh2"])
    return hn, delta


@jax.custom_vjp
def fused_update(h, ext, corr, disp, cz, cr, cq, wpack):
    """Fused finest-level update step: ``(h_new, delta)`` from the
    hidden state, the upsampled coarser state (``ext``, None for
    single-level GRUs), the sampled correlation features, the carried
    disparity and the precomputed context biases.  Forward is the
    Pallas megakernel (interpret mode off-TPU); backward is the XLA
    reference VJP."""
    return _fused_forward(h, ext, corr, disp, cz, cr, cq, wpack)


def _fused_fwd(h, ext, corr, disp, cz, cr, cq, wpack):
    out = _fused_forward(h, ext, corr, disp, cz, cr, cq, wpack)
    return out, (h, ext, corr, disp, cz, cr, cq, wpack)


def _fused_bwd(res, g):
    _, vjp = jax.vjp(_xla_reference_update, *res)
    return vjp(g)


fused_update.defvjp(_fused_fwd, _fused_bwd)
