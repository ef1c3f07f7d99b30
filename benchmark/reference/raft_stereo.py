"""Plain RAFT-Stereo forward pass: the yardstick's reference.

Written from the paper (Lipson, Teed, Deng: "RAFT-Stereo: Multilevel Recurrent
Field Transforms for Stereo Matching", 3DV 2021) and the published model's
layer list.  float32 ``jax.numpy``, every contraction at
``Precision.HIGHEST``, no flax, no kernels, no batching tricks.  It imports nothing of ``raftstereo_tpu``.

Parameters are a flat dict keyed by the published checkpoint's state-dict
names (``cnet.layer1.0.conv1.weight`` ...), conv weights in the published
OIHW layout; ``param_spec`` lists them for a configuration.  Tensors are
NHWC.  The output follows the published convention: the x-flow from the left
to the right image, so disparities come out negative.

``operand_dtype`` computes every contraction with both operands rounded to a
narrower type (accumulating in float32): ``None`` is the reference proper,
``"bfloat16"`` the precision the benchmark's configurations state, and
``"float8_e4m3fn"`` the control one step below it (see ``check.py``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FEATURE_DIM = 256       # correlation features (published: output_dim=256)
_ENC_OUT = 128          # motion encoder's output channels, flow included


# ----------------------------------------------------------------- parameters

def _block_spec(spec: Dict, prefix: str, cin: int, cout: int, stride: int,
                norm: str) -> None:
    spec[f"{prefix}.conv1.weight"] = (cout, cin, 3, 3)
    spec[f"{prefix}.conv1.bias"] = (cout,)
    spec[f"{prefix}.conv2.weight"] = (cout, cout, 3, 3)
    spec[f"{prefix}.conv2.bias"] = (cout,)
    norms = ["norm1", "norm2"]
    if stride != 1 or cin != cout:
        spec[f"{prefix}.downsample.0.weight"] = (cout, cin, 1, 1)
        spec[f"{prefix}.downsample.0.bias"] = (cout,)
        norms.append("downsample.1")
    if norm == "batch":
        for n in norms:
            for leaf in ("weight", "bias", "running_mean", "running_var"):
                spec[f"{prefix}.{n}.{leaf}"] = (cout,)


def _trunk_spec(spec: Dict, prefix: str, norm: str, d: int) -> None:
    spec[f"{prefix}conv1.weight"] = (64, 3, 7, 7)
    spec[f"{prefix}conv1.bias"] = (64,)
    if norm == "batch":
        for leaf in ("weight", "bias", "running_mean", "running_var"):
            spec[f"{prefix}norm1.{leaf}"] = (64,)
    _block_spec(spec, f"{prefix}layer1.0", 64, 64, 1, norm)
    _block_spec(spec, f"{prefix}layer1.1", 64, 64, 1, norm)
    _block_spec(spec, f"{prefix}layer2.0", 64, 96, 1 + (d > 1), norm)
    _block_spec(spec, f"{prefix}layer2.1", 96, 96, 1, norm)
    _block_spec(spec, f"{prefix}layer3.0", 96, 128, 1 + (d > 0), norm)
    _block_spec(spec, f"{prefix}layer3.1", 128, 128, 1, norm)


def param_spec(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    """State-dict name -> shape for a configuration (a dict with the
    published flags: n_downsample, n_gru_layers, hidden_dims, corr_levels,
    corr_radius, shared_backbone, slow_fast_gru, context_norm)."""
    d, n = cfg["n_downsample"], cfg["n_gru_layers"]
    hd = list(cfg["hidden_dims"])
    norm = cfg.get("context_norm", "batch")
    spec: Dict[str, Tuple[int, ...]] = {}
    _trunk_spec(spec, "cnet.", norm, d)
    if n >= 2:
        _block_spec(spec, "cnet.layer4.0", 128, 128, 2, norm)
        _block_spec(spec, "cnet.layer4.1", 128, 128, 1, norm)
    if n >= 3:
        _block_spec(spec, "cnet.layer5.0", 128, 128, 2, norm)
        _block_spec(spec, "cnet.layer5.1", 128, 128, 1, norm)
    for hi in range(2):       # head 0: GRU hidden state, head 1: context
        _block_spec(spec, f"cnet.outputs08.{hi}.0", 128, 128, 1, norm)
        spec[f"cnet.outputs08.{hi}.1.weight"] = (hd[0], 128, 3, 3)
        spec[f"cnet.outputs08.{hi}.1.bias"] = (hd[0],)
        if n >= 2:
            _block_spec(spec, f"cnet.outputs16.{hi}.0", 128, 128, 1, norm)
            spec[f"cnet.outputs16.{hi}.1.weight"] = (hd[1], 128, 3, 3)
            spec[f"cnet.outputs16.{hi}.1.bias"] = (hd[1],)
        if n >= 3:
            spec[f"cnet.outputs32.{hi}.weight"] = (hd[2], 128, 3, 3)
            spec[f"cnet.outputs32.{hi}.bias"] = (hd[2],)
    if cfg.get("shared_backbone"):
        _block_spec(spec, "conv2.0", 128, 128, 1, "instance")
        spec["conv2.1.weight"] = (FEATURE_DIM, 128, 3, 3)
        spec["conv2.1.bias"] = (FEATURE_DIM,)
    else:
        _trunk_spec(spec, "fnet.", "instance", d)
        spec["fnet.conv2.weight"] = (FEATURE_DIM, 128, 1, 1)
        spec["fnet.conv2.bias"] = (FEATURE_DIM,)
    for i in range(n):
        spec[f"context_zqr_convs.{i}.weight"] = (3 * hd[i], hd[i], 3, 3)
        spec[f"context_zqr_convs.{i}.bias"] = (3 * hd[i],)
    planes = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1)
    u = "update_block."
    for name, shape in (("convc1", (64, planes, 1, 1)),
                        ("convc2", (64, 64, 3, 3)),
                        ("convf1", (64, 2, 7, 7)),
                        ("convf2", (64, 64, 3, 3)),
                        ("conv", (_ENC_OUT - 2, 128, 3, 3))):
        spec[f"{u}encoder.{name}.weight"] = shape
        spec[f"{u}encoder.{name}.bias"] = (shape[0],)
    gru_in = {"gru08": _ENC_OUT + (hd[1] if n > 1 else 0)}
    if n >= 2:
        gru_in["gru16"] = hd[0] + (hd[2] if n == 3 else 0)
    if n == 3:
        gru_in["gru32"] = hd[1]
    for lvl, (g, cin) in enumerate(gru_in.items()):
        for c in ("convz", "convr", "convq"):
            spec[f"{u}{g}.{c}.weight"] = (hd[lvl], hd[lvl] + cin, 3, 3)
            spec[f"{u}{g}.{c}.bias"] = (hd[lvl],)
    spec[f"{u}flow_head.conv1.weight"] = (256, hd[0], 3, 3)
    spec[f"{u}flow_head.conv1.bias"] = (256,)
    spec[f"{u}flow_head.conv2.weight"] = (2, 256, 3, 3)
    spec[f"{u}flow_head.conv2.bias"] = (2,)
    f = 2 ** d
    spec[f"{u}mask.0.weight"] = (256, hd[0], 3, 3)
    spec[f"{u}mask.0.bias"] = (256,)
    spec[f"{u}mask.2.weight"] = (9 * f * f, 256, 1, 1)
    spec[f"{u}mask.2.bias"] = (9 * f * f,)
    return spec


# ------------------------------------------------------------------ primitives

class _Ops:
    """The contractions, with operands optionally rounded to a narrower
    type first (the rest of the arithmetic stays float32)."""

    def __init__(self, operand_dtype: Optional[str]):
        self.dt = None if operand_dtype in (None, "float32") \
            else jnp.dtype(operand_dtype)

    def q(self, x):
        if self.dt is None:
            return x
        if self.dt.itemsize == 1:      # fp8: saturate, a cast would give NaN
            lim = float(jnp.finfo(self.dt).max)
            x = jnp.clip(x, -lim, lim)
        return x.astype(self.dt).astype(jnp.float32)

    def conv(self, p, name, x, stride=1, pad=None):
        w = p[f"{name}.weight"]
        pad = w.shape[-1] // 2 if pad is None else pad
        y = jax.lax.conv_general_dilated(
            self.q(x), self.q(w), (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "OIHW", "NHWC"), precision=HI)
        return y + p[f"{name}.bias"]

    def corr_volume(self, f1, f2):
        c = f1.shape[-1]
        v = jnp.einsum("bhwc,bhvc->bhwv", self.q(f1), self.q(f2),
                       precision=HI)
        return v / jnp.sqrt(jnp.float32(c))


def _norm(p, name, x, kind):
    if kind == "instance":
        m = x.mean(axis=(1, 2), keepdims=True)
        v = jnp.square(x - m).mean(axis=(1, 2), keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5)
    if kind == "batch":          # frozen statistics, as published
        s = p[f"{name}.weight"] * jax.lax.rsqrt(
            p[f"{name}.running_var"] + 1e-5)
        return (x - p[f"{name}.running_mean"]) * s + p[f"{name}.bias"]
    raise ValueError(kind)


def _block(ops, p, name, x, stride, kind):
    y = jax.nn.relu(_norm(p, f"{name}.norm1",
                          ops.conv(p, f"{name}.conv1", x, stride), kind))
    y = jax.nn.relu(_norm(p, f"{name}.norm2",
                          ops.conv(p, f"{name}.conv2", y), kind))
    if f"{name}.downsample.0.weight" in p:
        x = _norm(p, f"{name}.downsample.1",
                  ops.conv(p, f"{name}.downsample.0", x, stride, 0), kind)
    return jax.nn.relu(x + y)


def _trunk(ops, p, prefix, x, kind, d):
    x = jax.nn.relu(_norm(p, f"{prefix}norm1", ops.conv(
        p, f"{prefix}conv1", x, 1 + (d > 2), 3), kind))
    for name, stride in (("layer1.0", 1), ("layer1.1", 1),
                         ("layer2.0", 1 + (d > 1)), ("layer2.1", 1),
                         ("layer3.0", 1 + (d > 0)), ("layer3.1", 1)):
        x = _block(ops, p, prefix + name, x, stride, kind)
    return x


def _pool2x(x):
    """3x3 / stride 2 / pad 1 average, padding counted in the divisor."""
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    return s / 9.0


def _resize_axis(x, axis, out):
    n = x.shape[axis]
    if n == out:
        return x
    if out == 1 or n == 1:
        return jnp.take(x, np.zeros(out, np.int32), axis=axis)
    pos = np.arange(out, dtype=np.float64) * (n - 1) / (out - 1)
    i0 = np.minimum(np.floor(pos).astype(np.int32), n - 1)
    i1 = np.minimum(i0 + 1, n - 1)
    shape = [1] * x.ndim
    shape[axis] = out
    w = jnp.asarray((pos - i0).astype(np.float32)).reshape(shape)
    return jnp.take(x, i0, axis=axis) * (1 - w) + jnp.take(x, i1, axis=axis) * w


def _interp(x, like):
    """Bilinear, corners aligned."""
    return _resize_axis(_resize_axis(x, 1, like.shape[1]), 2, like.shape[2])


def _sample_w(vol, x):
    """vol (..., W) at fractional x (..., K), zero outside [0, W-1]: the
    two-tap linear read, written as a sum over the whole row with the hat
    weight max(0, 1 - |j - x|) (the same numbers; a gather crawls on a
    TPU and the reference has to fit inside a run)."""
    j = jnp.arange(vol.shape[-1], dtype=jnp.float32)
    hat = jnp.maximum(0.0, 1.0 - jnp.abs(j - x[..., None]))
    return jnp.einsum("...w,...kw->...k", vol, hat, precision=HI)


def _gru(ops, p, name, h, ctx, *xs):
    cz, cr, cq = ctx
    x = jnp.concatenate(xs, axis=-1)
    hx = jnp.concatenate([h, x], axis=-1)
    z = jax.nn.sigmoid(ops.conv(p, f"{name}.convz", hx) + cz)
    r = jax.nn.sigmoid(ops.conv(p, f"{name}.convr", hx) + cr)
    q = jnp.tanh(ops.conv(p, f"{name}.convq",
                          jnp.concatenate([r * h, x], axis=-1)) + cq)
    return (1 - z) * h + z * q


def convex_upsample(flow, mask, f):
    """(B,H,W,1) x (B,H,W,9*f*f) -> (B,f*H,f*W,1): softmax-weighted sum of
    each coarse pixel's 3x3 neighbourhood, values scaled by f."""
    b, h, w, _ = flow.shape
    m = jax.nn.softmax(mask.reshape(b, h, w, 9, f, f), axis=3)
    fp = jnp.pad(flow[..., 0] * f, ((0, 0), (1, 1), (1, 1)))
    nb = jnp.stack([fp[:, ky:ky + h, kx:kx + w]
                    for ky in range(3) for kx in range(3)], axis=-1)
    up = jnp.einsum("bhwk,bhwkyx->bhywx", nb, m, precision=HI)
    return up.reshape(b, h * f, w * f, 1)


# --------------------------------------------------------------------- forward

def encode(p, cfg, image1, image2, operand_dtype=None):
    """Images (B,H,W,3) in [0,255] -> GRU states, context biases, fmaps."""
    ops = _Ops(operand_dtype)
    d, n = cfg["n_downsample"], cfg["n_gru_layers"]
    hd = list(cfg["hidden_dims"])
    kind = cfg.get("context_norm", "batch")
    b = image1.shape[0]
    i1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    i2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    if cfg.get("shared_backbone"):
        trunk = _trunk(ops, p, "cnet.", jnp.concatenate([i1, i2], 0), kind, d)
        f = ops.conv(p, "conv2.1",
                     _block(ops, p, "conv2.0", trunk, 1, "instance"))
        x = trunk[:b]
    else:
        x = _trunk(ops, p, "cnet.", i1, kind, d)
        f = ops.conv(p, "fnet.conv2", _trunk(
            ops, p, "fnet.", jnp.concatenate([i1, i2], 0), "instance", d),
            1, 0)
    fmap1, fmap2 = f[:b], f[b:]

    def heads(lvl, y):
        if lvl == "32":
            return [ops.conv(p, f"cnet.outputs32.{hi}", y) for hi in (0, 1)]
        return [ops.conv(p, f"cnet.outputs{lvl}.{hi}.1", _block(
            ops, p, f"cnet.outputs{lvl}.{hi}.0", y, 1, kind))
            for hi in (0, 1)]

    outs = [heads("08", x)]
    if n >= 2:
        y = _block(ops, p, "cnet.layer4.1",
                   _block(ops, p, "cnet.layer4.0", x, 2, kind), 1, kind)
        outs.append(heads("16", y))
    if n >= 3:
        z = _block(ops, p, "cnet.layer5.1",
                   _block(ops, p, "cnet.layer5.0", y, 2, kind), 1, kind)
        outs.append(heads("32", z))
    nets = [jnp.tanh(o[0]) for o in outs]
    ctx = []
    for i, o in enumerate(outs):
        c = ops.conv(p, f"context_zqr_convs.{i}", jax.nn.relu(o[1]))
        ctx.append((c[..., :hd[i]], c[..., hd[i]:2 * hd[i]],
                    c[..., 2 * hd[i]:]))
    return nets, ctx, fmap1, fmap2


def corr_pyramid(ops, fmap1, fmap2, levels):
    pyr = [ops.corr_volume(fmap1, fmap2)]
    for _ in range(levels - 1):
        v = pyr[-1]
        w2 = v.shape[-1] // 2
        pyr.append(v[..., :2 * w2].reshape(*v.shape[:-1], w2, 2).mean(-1))
    return pyr


def lookup(pyr, x, radius):
    """x (B,H,W) -> (B,H,W, levels*(2r+1)), level-major, taps ascending."""
    off = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    return jnp.concatenate(
        [_sample_w(v, x[..., None] / (2.0 ** i) + off)
         for i, v in enumerate(pyr)], axis=-1)


def update(ops, p, cfg, nets, ctx, corr, disp):
    """One refinement iteration: new states and the disparity step."""
    n, sf = cfg["n_gru_layers"], cfg.get("slow_fast_gru", False)
    nets = list(nets)
    u = "update_block."

    def level(i):
        if i == 2:
            nets[2] = _gru(ops, p, u + "gru32", nets[2], ctx[2],
                           _pool2x(nets[1]))
        elif i == 1:
            xs = [_pool2x(nets[0])]
            if n == 3:
                xs.append(_interp(nets[2], nets[1]))
            nets[1] = _gru(ops, p, u + "gru16", nets[1], ctx[1], *xs)

    if n == 3 and sf:
        level(2)
    if n >= 2 and sf:
        if n == 3:
            level(2)
        level(1)
    if n == 3:
        level(2)
    if n >= 2:
        level(1)
    flow = jnp.concatenate([disp, jnp.zeros_like(disp)], axis=-1)
    e = u + "encoder."
    cor = jax.nn.relu(ops.conv(p, e + "convc1", corr, 1, 0))
    cor = jax.nn.relu(ops.conv(p, e + "convc2", cor))
    flo = jax.nn.relu(ops.conv(p, e + "convf1", flow, 1, 3))
    flo = jax.nn.relu(ops.conv(p, e + "convf2", flo))
    mot = jax.nn.relu(ops.conv(p, e + "conv",
                               jnp.concatenate([cor, flo], axis=-1)))
    xs = [jnp.concatenate([mot, flow], axis=-1)]
    if n > 1:
        xs.append(_interp(nets[1], nets[0]))
    nets[0] = _gru(ops, p, u + "gru08", nets[0], ctx[0], *xs)
    delta = ops.conv(p, u + "flow_head.conv2", jax.nn.relu(
        ops.conv(p, u + "flow_head.conv1", nets[0])))
    return nets, delta[..., :1]


def upsample_mask(ops, p, net0):
    """Logits of the convex upsampling, from the finest state."""
    return 0.25 * ops.conv(p, "update_block.mask.2", jax.nn.relu(
        ops.conv(p, "update_block.mask.0", net0)), 1, 0)


def forward(p, cfg, image1, image2, iters: int, operand_dtype=None,
            all_iters: bool = False):
    """(low-resolution disparity, full-resolution disparity) after
    ``iters`` refinements; with ``all_iters`` the list of every
    iteration's full-resolution field (what training's loss reads)."""
    ops = _Ops(operand_dtype)
    nets, ctx, fmap1, fmap2 = encode(p, cfg, image1, image2, operand_dtype)
    pyr = corr_pyramid(ops, fmap1, fmap2, cfg["corr_levels"])
    b, h, w, _ = nets[0].shape
    grid = jnp.broadcast_to(jnp.arange(w, dtype=jnp.float32)[None, None, :],
                            (b, h, w))
    disp = jnp.zeros((b, h, w, 1), jnp.float32)
    f = 2 ** cfg["n_downsample"]

    def step(carry, _):
        nets, disp = carry
        disp = jax.lax.stop_gradient(disp)
        corr = lookup(pyr, grid + disp[..., 0], cfg["corr_radius"])
        nets, delta = update(ops, p, cfg, nets, ctx, corr, disp)
        disp = disp + delta
        up = (convex_upsample(disp, upsample_mask(ops, p, nets[0]), f)
              if all_iters else None)
        return (tuple(nets), disp), up

    # one traced body, so the float32 program compiles in seconds
    (nets, disp), ups = jax.lax.scan(step, (tuple(nets), disp), None,
                                     length=iters)
    if all_iters:
        return ups                        # (iters, B, f*H, f*W, 1)
    return disp, convex_upsample(disp, upsample_mask(ops, p, nets[0]), f)


# ----------------------------------------------------- the server's pad policy

def bucket_pad(hw: Tuple[int, int], divis_by: int = 32,
               bucket_multiple: int = 64):
    """(top, bottom, left, right) edge-replicate padding of a served pair:
    first to a multiple of ``divis_by`` split around the image (the
    published ``InputPadder``), then down/right to the bucket grid."""
    h, w = hw
    ph, pw = (-h) % divis_by, (-w) % divis_by
    t, b, l, r = ph // 2, ph - ph // 2, pw // 2, pw - pw // 2
    b += (-(h + ph)) % bucket_multiple
    r += (-(w + pw)) % bucket_multiple
    return t, b, l, r


def serve_reference(p, cfg, left, right, iters, divis_by=32,
                    bucket_multiple=64, operand_dtype=None):
    """What a server with this pad policy owes for one (H,W,3) pair:
    the (H,W) full-resolution disparity."""
    h, w = left.shape[:2]
    t, b, l, r = bucket_pad((h, w), divis_by, bucket_multiple)
    pad = ((0, 0), (t, b), (l, r), (0, 0))
    i1 = jnp.pad(jnp.asarray(left, jnp.float32)[None], pad, mode="edge")
    i2 = jnp.pad(jnp.asarray(right, jnp.float32)[None], pad, mode="edge")
    _, up = forward(p, cfg, i1, i2, iters, operand_dtype)
    return up[0, t:t + h, l:l + w, 0]
