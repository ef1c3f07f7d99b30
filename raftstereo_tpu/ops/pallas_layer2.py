"""Fused Pallas pipeline for the encoder's layer2 (stride-2) stage.

Extends the stem..layer1 pipeline (ops/pallas_encoder.py) one stage
deeper: round-5 profiling puts ~15 ms of the 23.6 ms flagship fixed stage
in XLA's layer2/layer3 convs and the blocked-layout relayouts around them
— the same storm the stem pipeline removed.

Semantics are exactly BasicEncoder's layer2 (two ResidualBlocks, first
stride 2 with a 1x1 projection shortcut; reference:
core/extractor.py:6-60,122-197 structure) with instance-norm statistics
in fp32:

    c1  = conv3x3_s2(t_in)           p  = conv1x1_s2(t_in)   [projection]
    t_y = relu(in1(c1))              pn = in_p(p)            [no relu]
    c2  = conv3x3(t_y)
    out0 = relu(pn + relu(in2(c2)))
    c3  = conv3x3(out0);  t3 = relu(in3(c3))
    c4  = conv3x3(t3);    out = relu(out0 + relu(in4(c4)))

Layout: the 64-channel input arrives as the stage's packed pixel-pair
view (B, H, W/2, 128); outputs live at half resolution as plain row-major
(B, H/2, W/2, 96) — 96 lanes, no column packing (the halved width still
fills sublanes).  The stride-2 entry kernel resolves its taps against the
packed columns: output col j reads input pixels 2j+dx, i.e. packed cols
{j-1, j}, and the 1x1 stride-2 projection is FREE in this view — input
pixel (2r, 2j) is the dy=0 row view's parity-0 lanes.

Single-device, inference-first: the backward is the XLA reference
formulation's VJP (training keeps the plain XLA layer2 by default, like
the stem stage before round 5), and the gate declines under an active
mesh (shard_map plumbing not yet built for this stage).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import threading

from .pallas_corr import _COMPILER_PARAMS, _interpret
from .pallas_norm import _row_block
from .pallas_encoder import make_override_scope, pack_view

# Thread-local trace scope, like pallas_encoder.override_fused_stem: the
# train step forces this stage OFF under differentiation (its backward
# re-linearizes the full XLA layer2 forward — the exact pattern measured
# as a net training loss on the stem in round 4); an explicit per-model
# config.fused_encoder still wins over the scope.
_tls = threading.local()

# Same trace-scope mechanism as the stem gate — one shared implementation
# (pallas_encoder.make_override_scope) so a fix to one cannot desync the
# other.  The train step holds this one at False (the layer2 backward
# still re-linearizes the XLA stage, a measured training loss).
_get_l2_override, override_fused_layer2 = make_override_scope(
    _tls, "fused_layer2_override")


# ------------------------------------------------------------- weights

def pack_weights3s2(w: jax.Array) -> jax.Array:
    """(3, 3, 64, 96) HWIO stride-2 conv weights -> (3, 2, 128, 96)
    packed [dy, dq+1]: output col j with tap dx reads packed col j + dq,
    parity pi, where dq = floor(dx/2) in {-1, 0}, pi = dx mod 2."""
    kh, kw, ci, co = w.shape
    out = jnp.zeros((kh, 2, 2 * ci, co), w.dtype)
    for dxi, dx in enumerate((-1, 0, 1)):
        dq = dx // 2
        pi = dx % 2
        out = out.at[:, dq + 1, pi * ci:(pi + 1) * ci, :].set(w[:, dxi])
    return out


def pack_weights3(w: jax.Array) -> jax.Array:
    """(3, 3, C, C) HWIO -> (3, 3C, C): per-dy concat over dx taps in
    operand order [dx=-1, 0, +1]."""
    kh, kw, ci, co = w.shape
    return jnp.concatenate([w[:, dxi] for dxi in range(3)],
                           axis=1).reshape(kh, 3 * ci, co)


def _flat_affine(s1, s2, n):
    """(B, 1, C) fp32 sums -> instance-norm prep affine (rstd, -mean*rstd).
    Same E[x^2]-m^2 form and measured precision envelope as the stem
    stage (pallas_encoder.stats_from_packed)."""
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + 1e-5)
    return rstd, -mean * rstd


# -------------------------------------------------------------- kernels

def _acc_flat_stats(y, s1_ref, s2_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref[...])
        s2_ref[...] = jnp.zeros_like(s2_ref[...])

    s1_ref[...] += jnp.sum(y, axis=(1, 2))[:, None, :]
    s2_ref[...] += jnp.sum(y * y, axis=(1, 2))[:, None, :]


def _l2_entry_kernel(x_ref, xh_ref, w_ref, b_ref, wp_ref, bp_ref,
                     c1_ref, p_ref, s1a_ref, s1b_ref, spa_ref, spb_ref,
                     *, rows):
    """Stride-2 3x3 conv (64->96) + free 1x1 stride-2 projection (64->96)
    + fp32 output stats for both, from the packed t-domain input.

    x_ref: (1, 2R, Wp, 128) input rows for this block's R output rows;
    xh_ref: (1, 1, 1, Wp, 128) the one halo row ABOVE (input row 2rb-1;
    zeros at the image edge — the input is an activation, so zero padding
    is exact).  Output row r reads input rows 2r-1, 2r, 2r+1 =
    full[2r], full[2r+1], full[2r+2] with full = [above; x]."""
    t = x_ref[...]
    above = xh_ref[...][:, 0]
    # Pad to an even row count and view as (R+1, 2, ...) so every dy tap
    # is a CONTIGUOUS slice at a parity (strided row slices lower to >2D
    # gathers, which Mosaic rejects — same trick as _stem7s2_kernel).
    full = jnp.concatenate([above, t, jnp.zeros_like(above)],
                           axis=1)                  # (1, 2R+2, Wp, 128)
    view = full.reshape(1, rows + 1, 2, full.shape[2], full.shape[3])
    views = [view[:, :rows, 0],                     # dy=-1: full[2r]
             view[:, :rows, 1],                     # dy= 0: full[2r+1]
             view[:, 1:, 0]]                        # dy=+1: full[2r+2]
    zc = jnp.zeros_like(views[0][:, :, :1])
    parts = []
    for v in views:
        # dq=-1: output col j reads packed col j-1 (zero at col 0 = the
        # conv's own zero padding); dq=0: col j.
        parts += [jnp.concatenate([zc, v[:, :, :-1]], axis=2), v]
    xcat = jnp.concatenate(parts, axis=-1)          # (1, R, Wp, 768)
    w = w_ref[...]                                  # (3, 2, 128, 96)
    wcat = w.reshape(3 * 2 * w.shape[2], w.shape[3])
    y = jax.lax.dot_general(xcat, wcat, (((3,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + b_ref[...][:, :, None, :]
    c1_ref[...] = y.astype(c1_ref.dtype)
    _acc_flat_stats(y, s1a_ref, s1b_ref)
    # Projection: input pixel (2r, 2j) = dy=0 row view, parity-0 lanes.
    pj = views[1][..., :w.shape[2] // 2]
    p = jax.lax.dot_general(pj, wp_ref[...], (((3,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = p + bp_ref[...][:, :, None, :]
    p_ref[...] = p.astype(p_ref.dtype)
    _acc_flat_stats(p, spa_ref, spb_ref)


def _prep_f(x, s_ref, t_ref, relu=True):
    s = s_ref[...][:, :, None, :].astype(x.dtype)
    t = t_ref[...][:, :, None, :].astype(x.dtype)
    y = x * s + t
    return jnp.maximum(y, 0) if relu else y


def _edge_mask(th, hv_ref):
    j = pl.program_id(1)
    top = th[:, 0:1] * hv_ref[j, 0].astype(th.dtype)
    bot = th[:, 1:2] * hv_ref[j, 1].astype(th.dtype)
    return jnp.concatenate([top, bot], axis=1)


def _conv3_flat(t, halo, w_ref, b_ref):
    """3x3 same-channel conv of the prepped (1, R, W2, C) tile; halo
    (1, 2, W2, C) prepped rows [above, below]; w_ref (3, 3C, C)."""
    zc = jnp.zeros_like(t[:, :, :1])
    y = None
    for dyi in range(3):
        if dyi == 0:
            rows = jnp.concatenate([halo[:, 0:1], t[:, :-1]], axis=1)
        elif dyi == 1:
            rows = t
        else:
            rows = jnp.concatenate([t[:, 1:], halo[:, 1:2]], axis=1)
        xcat = jnp.concatenate(
            [jnp.concatenate([zc, rows[:, :, :-1]], axis=2),   # dx=-1
             rows,                                             # dx= 0
             jnp.concatenate([rows[:, :, 1:], zc], axis=2)],   # dx=+1
            axis=-1)
        m = jax.lax.dot_general(xcat, w_ref[dyi],
                                (((3,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = m if y is None else y + m
    return y + b_ref[...][:, :, None, :]


def _l2_conv_kernel(x_ref, xh_ref, s_ref, t_ref, w_ref, b_ref, hv_ref,
                    y_ref, s1_ref, s2_ref):
    """prep(x) -> 3x3 conv -> raw y + stats (layer2_0.conv2 /
    layer2_1.conv2)."""
    t = _prep_f(x_ref[...], s_ref, t_ref)
    th = _edge_mask(_prep_f(xh_ref[...][:, 0], s_ref, t_ref), hv_ref)
    y = _conv3_flat(t, th, w_ref, b_ref)
    y_ref[...] = y.astype(y_ref.dtype)
    _acc_flat_stats(y, s1_ref, s2_ref)


def _l2_conv_res_kernel(p_ref, ph_ref, sp_ref, tp_ref,
                        c_ref, ch_ref, sc_ref, tc_ref,
                        w_ref, b_ref, hv_ref, y_ref, s1_ref, s2_ref):
    """layer2_1.conv1: its input is out0 = relu(pn + u) with
    pn = p*sp+tp (projection norm, NO relu) and u = relu(c*sc+tc)."""
    t = jnp.maximum(_prep_f(p_ref[...], sp_ref, tp_ref, relu=False)
                    + _prep_f(c_ref[...], sc_ref, tc_ref), 0)
    th = _edge_mask(
        jnp.maximum(_prep_f(ph_ref[...][:, 0], sp_ref, tp_ref, relu=False)
                    + _prep_f(ch_ref[...][:, 0], sc_ref, tc_ref), 0),
        hv_ref)
    y = _conv3_flat(t, th, w_ref, b_ref)
    y_ref[...] = y.astype(y_ref.dtype)
    _acc_flat_stats(y, s1_ref, s2_ref)


def _l2_finish_kernel(p_ref, sp_ref, tp_ref, c2_ref, s2_ref, t2_ref,
                      c4_ref, s4_ref, t4_ref, o_ref):
    """out = relu( relu(pn + u2) + y4 ): the stage output from the three
    raw tensors + their affines."""
    out0 = jnp.maximum(
        _prep_f(p_ref[...], sp_ref, tp_ref, relu=False)
        + _prep_f(c2_ref[...], s2_ref, t2_ref), 0)
    y4 = _prep_f(c4_ref[...], s4_ref, t4_ref)
    o_ref[...] = jnp.maximum(out0 + y4, 0).astype(o_ref.dtype)


# ------------------------------------------------------------ host side

def _halo1_above_s2(xp, r):
    """(B, H, Wp, 128) -> (B, Hout//r, 1, Wp, 128): input row 2*r_out0 - 1
    for each block (zeros above the image)."""
    b, h, wp, c = xp.shape
    nblk = (h // 2) // r
    span = 2 * r
    above = jnp.concatenate(
        [jnp.zeros((b, 1, wp, c), xp.dtype),
         xp[:, span - 1::span][:, :nblk - 1]], axis=1)
    return above[:, :, None]


def _halo2(x, r):
    """(B, H2, W2, C) -> (B, H2//r, 2, W2, C): rows above/below each
    block (zeros at edges; unsharded)."""
    b, h, w2, c = x.shape
    nblk = h // r
    z = jnp.zeros((b, 1, w2, c), x.dtype)
    top = jnp.concatenate([z, x[:, r - 1::r][:, :nblk - 1]], axis=1)
    bot = jnp.concatenate([x[:, r::r], z], axis=1)
    return jnp.stack([top, bot], axis=2)


def _default_hv2(nblk):
    return (jnp.ones((nblk, 2), jnp.float32)
            .at[0, 0].set(0.0).at[nblk - 1, 1].set(0.0))


def _specs(r, w2, c):
    row = pl.BlockSpec((1, r, w2, c), lambda i, j: (i, j, 0, 0),
                       memory_space=pltpu.VMEM)
    halo = pl.BlockSpec((1, 1, 2, w2, c), lambda i, j: (i, j, 0, 0, 0),
                        memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    return row, halo, stat


def _l2_entry(xp, w3, b3, wp1, bp1, dt):
    b, h, wpk, c2 = xp.shape
    h2 = h // 2
    r = _row_block(h2, row_elems=wpk * c2)
    grid = (b, h2 // r)
    xh = _halo1_above_s2(xp, r)
    co = w3.shape[-1]
    w2 = wpk  # output width == packed input width
    row, _, stat = _specs(r, w2, co)
    out = pl.pallas_call(
        functools.partial(_l2_entry_kernel, rows=r),
        out_shape=(jax.ShapeDtypeStruct((b, h2, w2, co), dt),
                   jax.ShapeDtypeStruct((b, h2, w2, co), dt),
                   jax.ShapeDtypeStruct((b, 1, co), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, co), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, co), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, co), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2 * r, wpk, c2), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1, wpk, c2), lambda i, j: (i, j, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(w3.shape, lambda i, j: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, co), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(wp1.shape, lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, co), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(row, row, stat, stat, stat, stat),
        name="layer2_entry",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(xp, xh, w3, b3[None, None, :], wp1, bp1[None, None, :])
    return out


def _l2_conv(x, aff, w, bias, dt, res=None, res_aff=None):
    b, h2, w2, c = x.shape
    r = _row_block(h2, row_elems=w2 * c)
    grid = (b, h2 // r)
    hv = _default_hv2(h2 // r)
    row, halo, stat = _specs(r, w2, c)
    hvspec = pl.BlockSpec(hv.shape, lambda i, j: (0, 0),
                          memory_space=pltpu.SMEM)
    wspec = pl.BlockSpec(w.shape, lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    # Bias is SHARED (1, 1, C): its own spec — the per-image stat spec
    # indexes block i on dim 0, out of bounds for batch > 1.
    bspec = pl.BlockSpec((1, 1, c), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    s, t = aff
    if res is None:
        kernel = _l2_conv_kernel
        operands = (x, _halo2(x, r), s, t, w, bias[None, None, :], hv)
        in_specs = [row, halo, stat, stat, wspec, bspec, hvspec]
    else:
        rs, rt = res_aff
        kernel = _l2_conv_res_kernel
        operands = (res, _halo2(res, r), rs, rt, x, _halo2(x, r), s, t,
                    w, bias[None, None, :], hv)
        in_specs = [row, halo, stat, stat, row, halo, stat, stat,
                    wspec, bspec, hvspec]
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(x.shape, dt),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(row, stat, stat),
        name="layer2_conv",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(*operands)


def _l2_finish(p, ap, c2, a2, c4, a4, dt):
    b, h2, w2, c = p.shape
    r = _row_block(h2, row_elems=w2 * c)
    row, _, stat = _specs(r, w2, c)
    return pl.pallas_call(
        _l2_finish_kernel,
        out_shape=jax.ShapeDtypeStruct(p.shape, dt),
        grid=(b, h2 // r),
        in_specs=[row, stat, stat, row, stat, stat, row, stat, stat],
        out_specs=row,
        name="layer2_finish",
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(p, *ap, c2, *a2, c4, *a4)


# ---------------------------------------------------------- entry point

def _params_of(params, key):
    return params[key]["kernel"], params[key]["bias"]


def _fused_layer2_fwd(t_in, params, dt, affines=None):
    """t_in: (B, H, W, 64) stage activation.  params keys: c1 (3,3,64,96
    stride-2), proj (1x1: (64, 96)), c2, c3, c4 (3,3,96,96).
    Returns (B, H/2, W/2, 96).

    ``affines``: None for instance norm (per-image stats computed by the
    kernels' fused accumulators), or 5 constant (s, t) pairs — folded
    frozen-BatchNorm affines (pallas_encoder.bn_affine) in stage order
    (norm1, projection norm, norm2, layer2_1.norm1, layer2_1.norm2);
    the kernels' prep form relu(x*s + t) expresses both exactly."""
    xp = pack_view(t_in)
    b = t_in.shape[0]
    n = float(t_in.shape[1] // 2 * (t_in.shape[2] // 2))

    def aff(stats_pair, i):
        if affines is None:
            return _flat_affine(*stats_pair, n)
        s, t = affines[i]
        return (jnp.broadcast_to(s.astype(jnp.float32)[None, None],
                                 (b, 1, s.shape[-1])),
                jnp.broadcast_to(t.astype(jnp.float32)[None, None],
                                 (b, 1, t.shape[-1])))

    k1, b1 = _params_of(params, "c1")
    kp, bp = _params_of(params, "proj")
    c1, p, s1a, s1b, spa, spb = _l2_entry(
        xp, pack_weights3s2(k1).astype(dt), b1.astype(dt),
        kp.reshape(kp.shape[-2:]).astype(dt), bp.astype(dt), dt)
    a1 = aff((s1a, s1b), 0)
    ap = aff((spa, spb), 1)
    k2, b2 = _params_of(params, "c2")
    c2, s2a, s2b = _l2_conv(c1, a1, pack_weights3(k2).astype(dt),
                            b2.astype(dt), dt)
    a2 = aff((s2a, s2b), 2)
    k3, b3 = _params_of(params, "c3")
    c3, s3a, s3b = _l2_conv(c2, a2, pack_weights3(k3).astype(dt),
                            b3.astype(dt), dt, res=p, res_aff=ap)
    a3 = aff((s3a, s3b), 3)
    k4, b4 = _params_of(params, "c4")
    c4, s4a, s4b = _l2_conv(c3, a3, pack_weights3(k4).astype(dt),
                            b4.astype(dt), dt)
    a4 = aff((s4a, s4b), 4)
    return _l2_finish(p, ap, c2, a2, c4, a4, dt)


def _xla_layer2_reference(t_in, params):
    """Plain-XLA mirror (oracle + backward linearization)."""
    from .pallas_norm import _xla_instance_norm

    def conv(x, k, b, stride=1):
        pad = 1 if k.shape[0] == 3 else 0
        return jax.lax.conv_general_dilated(
            x, k.astype(x.dtype), (stride, stride),
            ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + b.astype(x.dtype)

    c1 = conv(t_in, *_params_of(params, "c1"), stride=2)
    t_y = _xla_instance_norm(c1, relu=True)
    c2 = conv(t_y, *_params_of(params, "c2"))
    u2 = _xla_instance_norm(c2, relu=True)
    p = conv(t_in, *_params_of(params, "proj"), stride=2)
    pn = _xla_instance_norm(p, relu=False)
    out0 = jnp.maximum(pn + u2, 0)
    c3 = conv(out0, *_params_of(params, "c3"))
    t3 = _xla_instance_norm(c3, relu=True)
    c4 = conv(t3, *_params_of(params, "c4"))
    y4 = _xla_instance_norm(c4, relu=True)
    return jnp.maximum(out0 + y4, 0)


def _xla_layer2_reference_affine(t_in, params, affines):
    """Plain-XLA mirror of the frozen-BN (constant-affine) stage."""
    def nr(x, i, relu=True):
        s, t = affines[i]
        y = x * s.astype(x.dtype) + t.astype(x.dtype)
        return jnp.maximum(y, 0) if relu else y

    def conv(x, k, b, stride=1):
        pad = 1 if k.shape[0] == 3 else 0
        return jax.lax.conv_general_dilated(
            x, k.astype(x.dtype), (stride, stride),
            ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + b.astype(x.dtype)

    c1 = conv(t_in, *_params_of(params, "c1"), stride=2)
    u2 = nr(conv(nr(c1, 0), *_params_of(params, "c2")), 2)
    pn = nr(conv(t_in, *_params_of(params, "proj"), stride=2), 1,
            relu=False)
    out0 = jnp.maximum(pn + u2, 0)
    c3 = conv(out0, *_params_of(params, "c3"))
    y4 = nr(conv(nr(c3, 3), *_params_of(params, "c4")), 4)
    return jnp.maximum(out0 + y4, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_layer2(t_in, params, dt=jnp.float32):
    """Fused forward; XLA-reference backward (inference-first — the gate
    in models/encoders.py keeps training on the plain XLA layer2)."""
    return _fused_layer2_fwd(t_in, params, dt)


def _fwd_l2(t_in, params, dt):
    return _fused_layer2_fwd(t_in, params, dt), (t_in, params)


def _bwd_l2(dt, residuals, g):
    t_in, params = residuals
    _, vjp = jax.vjp(_xla_layer2_reference, t_in, params)
    return vjp(g)


fused_layer2.defvjp(_fwd_l2, _bwd_l2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer2_bn(t_in, params, affines, dt=jnp.float32):
    """Frozen-BatchNorm layer2 stage: the same Pallas pipeline with the
    five norm affines constant (pallas_encoder.bn_affine) instead of
    in-kernel instance stats.  Covers the context encoder's layer2 (the
    reference's cnet uses batch norm, core/extractor.py:199-300) and the
    realtime config's shared trunk.  Fused forward; XLA-reference
    backward (training keeps the plain XLA stage via the gate)."""
    return _fused_layer2_fwd(t_in, params, dt, affines=affines)


def _fwd_l2_bn(t_in, params, affines, dt):
    return (_fused_layer2_fwd(t_in, params, dt, affines=affines),
            (t_in, params, affines))


def _bwd_l2_bn(dt, residuals, g):
    t_in, params, affines = residuals
    _, vjp = jax.vjp(_xla_layer2_reference_affine, t_in, params, affines)
    return vjp(g)


fused_layer2_bn.defvjp(_fwd_l2_bn, _bwd_l2_bn)


def use_fused_layer2(norm_fn, stride, shape, override=None) -> bool:
    """Gate: instance or frozen-batch norm, stride-2 layer2, even W, no
    active mesh (shard plumbing not built), single-device TPU unless
    forced.

    Precedence mirrors use_fused_stem: ``override`` (per-model
    config.fused_encoder) > the override_fused_layer2 thread-local scope
    (the train step forces False — the backward re-linearizes) > the
    stem's own scope (tests forcing the fused forms get layer2 too) >
    backend auto.  The auto batch bound mirrors the stem gate's
    <=4-images crossover; auto also requires ONE visible device — a bare
    pallas_call cannot be GSPMD-partitioned, and a user jitting with
    explicit shardings must keep the plain XLA stage."""
    if norm_fn not in ("instance", "batch") or stride != 2 or shape[2] % 2:
        return False
    if shape[1] % 2:
        return False
    from ..parallel.context import active_corr_mesh

    if active_corr_mesh() is not None:
        return False
    if override is not None:
        return override
    ov = _get_l2_override()
    if ov is not None:
        return ov
    from .pallas_encoder import _get_override

    ov = _get_override()
    if ov is not None:
        return ov
    return (jax.default_backend() == "tpu" and len(jax.devices()) == 1
            and shape[0] <= 4)
