"""Iterative refinement: motion encoder + multilevel ConvGRU stack + heads.

Capability mirror of the reference's update module (reference: core/update.py),
NHWC + flax.linen.  Differences by design:

* The GRU context biases (cz, cr, cq) are precomputed once outside the loop
  (reference does the same: core/raft_stereo.py:32,88) and passed in.
* Disparity is carried as a single channel; the 2-channel flow the motion
  encoder expects (its 7x7 conv has 2 input channels) is materialised with a
  zero y channel, preserving converted-weight compatibility while halving the
  recurrent flow state.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..config import RAFTStereoConfig
from ..ops.image import avg_pool2x, resize_bilinear_align_corners
from .layers import conv, kaiming_out


# Tri-state override of the tap-matmul head gate (tests force both paths).
tap_head_override = None


def _use_tap_head() -> bool:
    """The tap-matmul form of the narrow 3x3 head conv is a TPU fix (N=2
    output channels waste the MXU's 128 N-lanes — measured 3.5 TF/s,
    costing as much as a 256->128 conv).  CPU/GPU
    keep the plain conv.  The tap combination has two epilogues chosen by
    per-shard batch inside tap_conv3x3 (both A/B-measured, the tap form
    wins at every batch size with the right epilogue)."""
    if tap_head_override is not None:
        return tap_head_override
    return jax.default_backend() == "tpu"


def _local_batch(batch: int) -> int:
    from ..parallel.context import active_corr_mesh
    from ..parallel.mesh import DATA_AXIS

    mesh = active_corr_mesh()
    if mesh is not None:  # per-shard batch, like the conv1 gate
        batch = max(1, batch // mesh.shape.get(DATA_AXIS, 1))
    return batch


def tap_conv3x3(conv_mod, y):
    """A bound SAME-padded 3x3 nn.Conv with FEW output channels, computed
    as one 1x1 matmul into kh*kw*co per-tap channels + a tiny constant
    SELECTOR conv that shifts-and-sums the taps.

    o[p] = sum_t K[t] . y[p + t - 1]  ==  sum_t z_t[p + t - 1] where
    z_t = y . K[t] is pointwise — so one (ci -> 9*co) matmul (padded to a
    full MXU N-tile instead of 2/128 lanes) replaces the narrow conv.
    Two epilogues combine the taps, chosen by per-shard batch
    (alternating same-process A/Bs):

    * batch <= 2: 9 shifted adds of the 28x-smaller z (batch 1
      9.80 -> 10.45 pairs/sec vs plain; realtime +2.8%; the selector
      conv's launch overhead costs ~10% at realtime's tiny spatial);
    * batch > 2: a 3x3 conv with CONSTANT block-identity weights
      S[dy, dx, tin, c] = [tin == (dy*3+dx)*co + c] (batch 8
      12.58/12.69 -> 12.71/12.90 vs plain; the co=2 strided slices of
      the other epilogue are lane-hostile at batch amortization)."""
    _assert_default_conv_geometry(conv_mod)
    p = conv_mod.variables["params"]
    k = p["kernel"]
    kh, kw, ci, co = k.shape
    assert (kh, kw) == (3, 3), (kh, kw)
    assert tuple(conv_mod.padding) == ((1, 1), (1, 1)), conv_mod.padding
    w = k.transpose(2, 0, 1, 3).reshape(ci, kh * kw * co).astype(y.dtype)
    z = jnp.tensordot(y, w, 1)
    if _local_batch(y.shape[0]) <= 2:
        zp = jnp.pad(z, ((0, 0), (1, 1), (1, 1), (0, 0)))
        h, wd = y.shape[1], y.shape[2]
        o = None
        for t in range(kh * kw):
            dy, dx = divmod(t, kw)
            s = zp[:, dy:dy + h, dx:dx + wd, t * co:(t + 1) * co]
            o = s if o is None else o + s
        return o + p["bias"].astype(y.dtype)
    sel = np.zeros((kh, kw, kh * kw * co, co), np.float32)
    for t in range(kh * kw):
        dy, dx = divmod(t, kw)
        for c in range(co):
            # lax.conv is cross-correlation: tap (a, b) reads
            # in[p + (a-1, b-1)], and o[p] needs z_t[p + (dy-1, dx-1)].
            sel[dy, dx, t * co + c, c] = 1.0
    # HIGHEST for fp32 inputs: the selector's weights are exact 0/1 and its
    # output feeds the certified-parity delta-flow, so the default-precision
    # bf16 pass would round the taps once more than the plain conv (the
    # batch<=2 shift-add epilogue has no such extra rounding).  co=2 makes
    # the fp32 multiply passes free; bf16 inputs keep the default.
    prec = (jax.lax.Precision.HIGHEST if y.dtype == jnp.float32 else None)
    o = jax.lax.conv_general_dilated(
        z, jnp.asarray(sel, y.dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
    return o + p["bias"].astype(y.dtype)


class FlowHead(nn.Module):
    """3x3 conv -> relu -> 3x3 conv (reference: core/update.py:6-14).
    Output stays 2-channel for weight parity; the model uses channel 0."""

    hidden_dim: int = 256
    output_dim: int = 2
    dtype: Any = jnp.float32

    def setup(self):
        self.conv1 = conv(self.hidden_dim, 3, dtype=self.dtype)
        self.conv2 = conv(self.output_dim, 3, dtype=self.dtype)

    def __call__(self, x):
        y = nn.relu(self.conv1(x))
        if self.is_initializing() or not _use_tap_head():
            return self.conv2(y)
        return tap_conv3x3(self.conv2, y)

    def from_hidden(self, y):
        """Head output from an already-computed relu(conv1(x)) activation
        (the merged-head path in BasicMultiUpdateBlock)."""
        if _use_tap_head():
            return tap_conv3x3(self.conv2, y)
        return self.conv2(y)


def _assert_default_conv_geometry(conv_mod):
    """Fail loudly if a wrapped nn.Conv ever stops being a stride-1,
    undilated, default-precision conv — the fast paths below re-implement
    exactly that geometry and would otherwise silently diverge."""
    def _pair(v):
        return (v, v) if v is None or isinstance(v, int) else tuple(v)

    assert _pair(conv_mod.strides) in ((1, 1), (None, None)), conv_mod.strides
    assert _pair(conv_mod.kernel_dilation) in ((1, 1), (None, None)), \
        conv_mod.kernel_dilation
    assert conv_mod.precision is None, conv_mod.precision
    assert conv_mod.feature_group_count == 1


def _sliced_conv(conv_mod, x, lo, hi, bias=True):
    """Apply a bound nn.Conv on an input-channel SLICE of its kernel:
    out = conv(x; kernel[:, :, lo:hi]) (+ bias).  Summing the slices over
    a channel partition equals the conv of the concatenated input.

    Assumes the wrapped conv's default geometry/precision — asserted so a
    future nn.Conv change fails loudly instead of silently diverging.
    No ``preferred_element_type``, matching the flax path it replaces: in
    bf16 mode both emit bf16 gate pre-activations (MXU-internal fp32
    accumulation, rounded at the output) — intentional, covered by the
    bf16 torch-parity configs in tests/test_torch_parity.py."""
    _assert_default_conv_geometry(conv_mod)
    p = conv_mod.variables["params"]
    k = p["kernel"][:, :, lo:hi]
    pad = conv_mod.padding
    y = jax.lax.conv_general_dilated(
        x, k.astype(x.dtype), (1, 1), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias and "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


class ConvGRU(nn.Module):
    """Conv gated recurrent unit with external context biases
    (reference: core/update.py:16-32).  Concat order [h, x] and [r*h, x]
    is preserved for checkpoint conversion.

    The z and r gates read the same input, so their convs (the reference's
    separate ``convz``/``convr``) are one fused conv producing 2*hidden
    channels — per output channel the arithmetic is identical (the fusion
    only concatenates along the *output* axis), so converted checkpoints
    stay bit-compatible; the converter concatenates the torch weights
    (utils/convert.py).  One fewer HBM read of ``hx`` per GRU per iteration.
    ``convq``'s input differs (r gates h first) and stays separate."""

    hidden_dim: int
    kernel_size: int = 3
    dtype: Any = jnp.float32

    def setup(self):
        k = self.kernel_size

        def split_fan_out_init(key, shape, dtype=jnp.float32):
            # From-scratch init must match the reference's two SEPARATE
            # kaiming fan_out convs (core/extractor.py:155-162 semantics):
            # per-gate fan_out is hidden*k*k, not the fused 2*hidden*k*k —
            # plain kaiming on the fused shape would under-scale by sqrt(2).
            kh, kw, _, o = shape
            std = (2.0 / (o // 2 * kh * kw)) ** 0.5
            return std * jax.random.normal(key, shape, dtype)

        self.convzr = nn.Conv(2 * self.hidden_dim, (k, k),
                              padding=((k // 2, k // 2), (k // 2, k // 2)),
                              kernel_init=split_fan_out_init,
                              dtype=self.dtype, name="convzr")
        self.convq = conv(self.hidden_dim, k, dtype=self.dtype)

    def __call__(self, h, cz, cr, cq, *x_list):
        hd = self.hidden_dim
        x = jnp.concatenate(x_list, axis=-1)
        if self.is_initializing():
            # Plain concat form once, so the parameter tree is the
            # reference-compatible fused-input conv.
            zr = self.convzr(jnp.concatenate([h, x], axis=-1))
            z = nn.sigmoid(zr[..., :hd] + cz)
            r = nn.sigmoid(zr[..., hd:] + cr)
            q = nn.tanh(self.convq(jnp.concatenate([r * h, x], axis=-1)) + cq)
            return (1 - z) * h + z * q
        # Apply each conv as two kernel-sliced convs instead of
        # materializing the [h, x] concats: kernel[:, :, :hd] convolves h,
        # kernel[:, :, hd:] convolves x, summed — arithmetically identical
        # (a conv is linear in its input channels), parameters unchanged.
        # The concats are real HBM round trips inside the scan loop
        # (~1.3 ms/iter at batch 8, profiled).
        zr = (_sliced_conv(self.convzr, h, 0, hd, bias=False)
              + _sliced_conv(self.convzr, x, hd, None))
        z = nn.sigmoid(zr[..., :hd] + cz)
        r = nn.sigmoid(zr[..., hd:] + cr)
        q = (_sliced_conv(self.convq, r * h, 0, hd, bias=False)
             + _sliced_conv(self.convq, x, hd, None))
        q = nn.tanh(q + cq)
        return (1 - z) * h + z * q


class SepConvGRU(nn.Module):
    """Separable (1x5 then 5x1) ConvGRU (reference: core/update.py:34-62;
    capability parity — unused by the default path)."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32

    def setup(self):
        def c(name, kh, kw, ph, pw):
            return nn.Conv(self.hidden_dim, (kh, kw),
                           padding=((ph, ph), (pw, pw)), dtype=self.dtype,
                           name=name)
        self.convz1 = c("convz1", 1, 5, 0, 2)
        self.convr1 = c("convr1", 1, 5, 0, 2)
        self.convq1 = c("convq1", 1, 5, 0, 2)
        self.convz2 = c("convz2", 5, 1, 2, 0)
        self.convr2 = c("convr2", 5, 1, 2, 0)
        self.convq2 = c("convq2", 5, 1, 2, 0)

    def __call__(self, h, *x_list):
        x = jnp.concatenate(x_list, axis=-1)
        for convz, convr, convq in ((self.convz1, self.convr1, self.convq1),
                                    (self.convz2, self.convr2, self.convq2)):
            hx = jnp.concatenate([h, x], axis=-1)
            z = nn.sigmoid(convz(hx))
            r = nn.sigmoid(convr(hx))
            q = nn.tanh(convq(jnp.concatenate([r * h, x], axis=-1)))
            h = (1 - z) * h + z * q
        return h


class PointwisePaddedConv(nn.Module):
    """1x1 conv whose PARAMETER keeps the declared ``in_features`` shape
    (checkpoint-compatible with the reference's conv) but whose input may
    arrive with extra trailing ZERO channels — the kernel is zero-padded
    to match at apply time, which is arithmetically identical.  Lets the
    Pallas corr backend emit a lane-friendly channel count (36 correlation
    lanes made the consuming fusion read at ~39 GB/s, measured
    60 us/iteration at flagship shapes)."""

    features: int
    in_features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        k = self.param("kernel", kaiming_out,
                       (1, 1, self.in_features, self.features))
        b = self.param("bias", nn.initializers.zeros, (self.features,))
        pad = x.shape[-1] - self.in_features
        if pad:
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        x = x.astype(self.dtype)  # flax-Conv-style compute-dtype cast
        # No preferred_element_type: the MXU accumulates bf16 operands in
        # fp32 internally either way, and a fp32-typed OUTPUT from bf16
        # operands makes the conv's transpose ill-typed (cotangent fp32 vs
        # kernel bf16 — lax.conv requires matching dtypes), breaking every
        # bf16 backward through this op.  Cost: one bf16 rounding before
        # the bias add.
        y = jax.lax.conv_general_dilated(
            x, k.astype(self.dtype), (1, 1), ((0, 0), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + b.astype(x.dtype)


class BasicMotionEncoder(nn.Module):
    """Fuses correlation features and current flow into 128 motion channels,
    the last 2 being the raw flow (reference: core/update.py:64-85).

    ``corr`` may arrive zero-channel-padded past ``cor_planes`` (the
    Pallas backend's lane-friendly emission); convc1 handles it with an
    unchanged parameter shape."""

    cor_planes: int
    dtype: Any = jnp.float32

    def setup(self):
        self.convc1 = PointwisePaddedConv(64, self.cor_planes,
                                          dtype=self.dtype)
        self.convc2 = conv(64, 3, dtype=self.dtype)
        self.convf1 = conv(64, 7, padding=3, dtype=self.dtype)
        self.convf2 = conv(64, 3, dtype=self.dtype)
        self.conv = conv(128 - 2, 3, dtype=self.dtype)

    def __call__(self, flow, corr, preact: bool = False):
        # ``preact``: corr already IS relu(convc1(raw_corr)) — the
        # pallas_alt lookup kernel's fused epilogue (ops/pallas_alt.py);
        # convc1's parameters are consumed by the kernel, not here.
        c1 = corr if preact else nn.relu(self.convc1(corr))
        cor = nn.relu(self.convc2(c1))
        if self.is_initializing() or self.dtype != jnp.bfloat16:
            f1 = self.convf1(flow)
        else:
            # Stereo flow's y channel is STRUCTURALLY zero — the model
            # builds flow = [d, 0] every iteration (raft_stereo.py step;
            # delta y is zeroed, flow_init folds into the 1-channel d) —
            # so the kernel's y input-slice only ever multiplies zeros.
            # Contract only the x slice: algebraically exact (the dropped
            # products are exact fp zeros; convf1's K halves 98 -> 49,
            # +0.5-0.7% b1 x3 alternating), but the compiled contraction
            # ORDER differs, so outputs shift at rounding level — gated to
            # bf16 compute; fp32 keeps the certified-parity conv form
            # (same policy as the corr epilogue, ops/pallas_alt.py).
            f1 = _sliced_conv(self.convf1, flow[..., :1], 0, 1)
        flo = nn.relu(self.convf2(nn.relu(f1)))
        # The [cor, flo] concat feeding self.conv measured FREE here —
        # slicing it like the GRU gates was a wash (alternating b1 pairs
        # 1.00/0.999; XLA fuses this concat into the conv read, unlike
        # the GRU's carry concats) — committed negative, keep the
        # reference form.
        out = nn.relu(self.conv(jnp.concatenate([cor, flo], axis=-1)))
        return jnp.concatenate([out, flow], axis=-1)


def _interp_to(x, dest):
    return resize_bilinear_align_corners(x, dest.shape[1:3])


class BasicMultiUpdateBlock(nn.Module):
    """Coupled multilevel GRU update (reference: core/update.py:97-138).

    Levels are indexed finest-first: net[0] is the 1/2^n_downsample state
    (the reference's net_list ordering, core/raft_stereo.py:84).  GRU call
    order is coarsest -> finest, with avg-pooled finer state and bilinearly
    upsampled coarser state as cross-level inputs.
    """

    config: RAFTStereoConfig
    dtype: Any = jnp.float32

    def setup(self):
        cfg = self.config
        hd = cfg.hidden_dims
        n = cfg.n_gru_layers
        self.encoder = BasicMotionEncoder(cfg.cor_planes, dtype=self.dtype)
        encoder_output_dim = 128
        # Input widths mirror reference wiring (core/update.py:104-106).
        self.gru0 = ConvGRU(hd[0], dtype=self.dtype)   # finest ("gru08")
        if n >= 2:
            self.gru1 = ConvGRU(hd[1], dtype=self.dtype)   # mid ("gru16")
        if n == 3:
            self.gru2 = ConvGRU(hd[2], dtype=self.dtype)   # coarsest ("gru32")
        self.flow_head = FlowHead(hidden_dim=256, output_dim=2, dtype=self.dtype)
        factor = cfg.factor
        self.mask_conv1 = conv(256, 3, dtype=self.dtype)
        self.mask_conv2 = conv(factor * factor * 9, 1, padding=0, dtype=self.dtype)

    def __call__(self, net: Sequence[jax.Array], inp: Sequence[Tuple],
                 corr: Optional[jax.Array] = None,
                 flow: Optional[jax.Array] = None,
                 iter0: bool = True, iter1: bool = True, iter2: bool = True,
                 update: bool = True, with_mask: bool = True,
                 corr_preact: bool = False):
        cfg = self.config
        n = cfg.n_gru_layers
        net = list(net)

        # One scope per level inside the caller's ``gru`` stage
        # (models/raft_stereo.py STAGES): gru/level32, gru/level16,
        # gru/level08 in a device trace's op names.
        if n == 3 and iter2:
            with jax.named_scope("level32"):
                net[2] = self.gru2(net[2], *inp[2], avg_pool2x(net[1]))
        if n >= 2 and iter1:
            with jax.named_scope("level16"):
                if n > 2:
                    net[1] = self.gru1(net[1], *inp[1], avg_pool2x(net[0]),
                                       _interp_to(net[2], net[1]))
                else:
                    net[1] = self.gru1(net[1], *inp[1], avg_pool2x(net[0]))
        if iter0:
            motion_features = self.encoder(flow, corr, preact=corr_preact)
            with jax.named_scope("level08"):
                if n > 1:
                    net[0] = self.gru0(net[0], *inp[0], motion_features,
                                       _interp_to(net[1], net[0]))
                else:
                    net[0] = self.gru0(net[0], *inp[0], motion_features)

        if not update:
            return net

        if with_mask and not self.is_initializing():
            # Train mode: flow_head.conv1 and mask_conv1 are both 3x3
            # 128->256 convs on net[0]; one merged 128->512 conv (kernels
            # concatenated along the output axis — per-channel arithmetic
            # unchanged, parameters untouched) halves the net[0] HBM reads
            # and conv launches in the loop body.
            y = self._merged_head_hidden(net[0])
            hd = self.flow_head.hidden_dim
            delta = self.flow_head.from_hidden(y[..., :hd])
            with jax.named_scope("upsample"):
                mask = 0.25 * self.mask_conv2(y[..., hd:])
            return net, mask, delta

        delta = self.flow_head(net[0])
        if not with_mask:
            # Test-mode scan bodies skip the mask head: only the FINAL
            # iteration's mask is consumed, and it depends only on net[0],
            # so the model computes it once after the loop (upsample_mask)
            # — measured ~0.18 ms/iter of conv + f32 cast + carry traffic
            # at flagship shapes.
            return net, None, delta
        with jax.named_scope("upsample"):
            mask = self.upsample_mask(net[0])
        return net, mask, delta

    def _merged_head_hidden(self, net0: jax.Array) -> jax.Array:
        """relu of the concatenated flow/mask first-stage convs on net[0],
        as ONE conv: [relu(flow.conv1(x)), relu(mask_conv1(x))]."""
        _assert_default_conv_geometry(self.flow_head.conv1)
        _assert_default_conv_geometry(self.mask_conv1)
        assert self.flow_head.conv1.padding == self.mask_conv1.padding
        pf = self.flow_head.conv1.variables["params"]
        pm = self.mask_conv1.variables["params"]
        x = net0
        k = jnp.concatenate([pf["kernel"], pm["kernel"]], axis=-1)
        b = jnp.concatenate([pf["bias"], pm["bias"]])
        y = jax.lax.conv_general_dilated(
            x, k.astype(x.dtype), (1, 1), self.mask_conv1.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return nn.relu(y + b.astype(x.dtype))

    def upsample_mask(self, net0: jax.Array) -> jax.Array:
        """Convex-upsampling mask from the finest GRU state.  0.25 scaling
        balances mask-head gradients (reference: core/update.py:137)."""
        return 0.25 * self.mask_conv2(nn.relu(self.mask_conv1(net0)))
