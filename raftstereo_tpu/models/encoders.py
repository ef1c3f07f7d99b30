"""Feature and context encoders.

Capability mirror of the reference's ``BasicEncoder``/``MultiBasicEncoder``
(reference: core/extractor.py:122-300), NHWC + flax.linen.  Stride placement
follows the reference's downsample-factor logic: conv1 strides iff
downsample>2, layer2 iff downsample>1, layer3 iff downsample>0, so the trunk
output sits at 1/2^downsample resolution.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from .layers import ResidualBlock, conv, make_norm


def _plain_stem(enc, x):
    """The ordinary flax stem: conv1 -> norm1 -> relu -> layer1."""
    x = nn.relu(enc.norm1(enc.conv1(x)))
    return enc.layer1_1(enc.layer1_0(x))


def _stem_layer1(enc, x):
    """conv1 + norm1 + relu + layer1, with the fused Pallas fast path on
    TPU.  ``x`` is the normalized input image.

    The plain path's four layer1 instance norms at flagship resolution
    cost ~21 ms of XLA layout churn (measured);
    the fused pipeline (ops/pallas_encoder.py) keeps the whole stage in
    row-major packed form.  When conv1 is stride 1 (downsample <= 2) it
    joins the pipeline as a packed Pallas 7x7 kernel too — removing the
    XLA-conv <-> row-major boundary relayouts and the 14 TF/s stem conv
    (round-3 trace) — otherwise the stage consumes conv1's raw XLA output
    directly.  Numerically pinned against this exact module path in
    tests/test_pallas_encoder.py; init always takes the plain path so the
    parameter tree is identical either way."""
    from ..ops.pallas_encoder import (bn_affine, bn_conv1_stem_layer1,
                                      bn_stem_layer1, conv1_stem_layer1,
                                      stem_layer1, use_fused_stem)

    stride = 1 + (enc.downsample > 2)
    oshape = (x.shape[0], -(-x.shape[1] // stride),
              -(-x.shape[2] // stride), 64)
    if (not enc.is_initializing()
            and use_fused_stem(enc.norm_fn, oshape, enc.fused_stem)):
        params = {
            "c10": enc.layer1_0.conv1.variables["params"],
            "c11": enc.layer1_0.conv2.variables["params"],
            "c20": enc.layer1_1.conv1.variables["params"],
            "c21": enc.layer1_1.conv2.variables["params"],
        }
        if enc.norm_fn == "batch":
            # Frozen BN folds to constant prep affines (bn_affine).
            affines = [
                bn_affine(m.variables["params"], m.variables["batch_stats"])
                for m in (enc.norm1, enc.layer1_0.norm1, enc.layer1_0.norm2,
                          enc.layer1_1.norm1, enc.layer1_1.norm2)]
        else:
            affines = None
        # Pallas conv1 only at small per-shard image counts: measured
        # same-session A/B at flagship shapes — batch 1 (2 images)
        # 9.56 -> 9.84 pairs/sec, batch 2 a wash, batch 8 11.87 -> 12.31
        # for the XLA conv (its blocked lowering amortizes over batch
        # while the packed K=6 kernel scales linearly).  The 7x7 conv also
        # needs 3 halo rows from each space-shard neighbor, so each shard
        # must hold >= 3 rows (ppermute reaches one neighbor only).
        from ..ops.pallas_encoder import _stem_shard_mesh

        shard = _stem_shard_mesh(oshape)
        local_imgs = x.shape[0] // (shard[1] if shard is not None else 1)
        local_h = oshape[1] // (shard[2] if shard is not None else 1)
        # Stride 2 (downsample 3 / realtime) uses the packed-fours kernel;
        # it needs W % 4 == 0 and even H.  Both conv1 kernels pre-shift
        # the narrow input and fold the column offsets into one dot per
        # row tap — the first formulation rolled the 128-wide fp32
        # accumulator per offset and measured a net LOSS; restructured,
        # the stride-2 path flips to a +2.5-4% realtime win (alternating
        # same-process A/B — the chip drifts).
        ok_geom = (x.shape[-1] == 3 and local_imgs <= 4 and local_h >= 3
                   and (stride == 1
                        or (x.shape[1] % 2 == 0 and x.shape[2] % 4 == 0)))
        if ok_geom:
            c1p = enc.conv1.variables["params"]
            if affines is not None:
                return bn_conv1_stem_layer1(x, c1p, params, affines,
                                            enc.dtype, stride)
            return conv1_stem_layer1(x, c1p, params, enc.dtype, stride)
        if affines is not None:
            # BN stage WITHOUT the Pallas conv1 re-pays the XLA-conv ->
            # row-major boundary relayout and measures a net loss
            # (same-session realtime: 101 vs 111.5 pairs/sec plain),
            # unlike the instance stage whose XLA alternative is the
            # 21 ms relayout storm.  Auto keeps the plain XLA stage here;
            # an explicit True override still forces the fused form (the
            # CPU equivalence tests and forced-path evaluations).
            from ..ops.pallas_encoder import fused_stem_forced
            if fused_stem_forced(enc.fused_stem):
                return bn_stem_layer1(enc.conv1(x), params, affines)
            return _plain_stem(enc, x)
        return stem_layer1(enc.conv1(x), params)
    return _plain_stem(enc, x)


def _trunk_layer2(enc, x):
    """layer2 (two ResidualBlocks, first stride-2 + projection), with the
    fused Pallas fast path on TPU: round-5 profiling puts ~15 ms of the
    flagship fixed stage in XLA's layer2+ convs and their blocked-layout
    relayouts; the fused stage keeps everything
    row-major (ops/pallas_layer2.py).  Numerically pinned against this
    exact module path in tests/test_pallas_layer2.py."""
    from ..ops.pallas_layer2 import (fused_layer2, fused_layer2_bn,
                                     use_fused_layer2)

    stride2 = 1 + (enc.downsample > 1)
    if (not enc.is_initializing()
            and use_fused_layer2(enc.norm_fn, stride2, x.shape,
                                 override=enc.fused_stem)):
        params = {
            "c1": enc.layer2_0.conv1.variables["params"],
            "c2": enc.layer2_0.conv2.variables["params"],
            "proj": enc.layer2_0.downsample_conv.variables["params"],
            "c3": enc.layer2_1.conv1.variables["params"],
            "c4": enc.layer2_1.conv2.variables["params"],
        }
        if enc.norm_fn == "batch":
            # Frozen BN folds to constant prep affines, exactly like the
            # stem stage (pallas_encoder.bn_affine); stage order:
            # norm1, projection norm, norm2, layer2_1.norm1/norm2.
            from ..ops.pallas_encoder import bn_affine
            affines = [
                bn_affine(m.variables["params"], m.variables["batch_stats"])
                for m in (enc.layer2_0.norm1, enc.layer2_0.downsample_norm,
                          enc.layer2_0.norm2, enc.layer2_1.norm1,
                          enc.layer2_1.norm2)]
            return fused_layer2_bn(x, params, affines, enc.dtype)
        return fused_layer2(x, params, enc.dtype)
    return enc.layer2_1(enc.layer2_0(x))


class BasicEncoder(nn.Module):
    """Residual trunk -> ``output_dim`` feature maps at 1/2^downsample res
    (reference: core/extractor.py:122-197).  The reference's list-input
    batching trick (stack both images into the batch axis) is the caller's
    job here — pass (2B, H, W, 3)."""

    output_dim: int = 128
    norm_fn: str = "batch"
    downsample: int = 3
    dtype: Any = jnp.float32
    # Tri-state override of the fused-stem gate (config.fused_encoder):
    # None = auto (TPU backend), True/False = force one numeric path.
    fused_stem: Optional[bool] = None

    def setup(self):
        d = self.downsample
        self.conv1 = conv(64, 7, stride=1 + (d > 2), padding=3, dtype=self.dtype)
        self.norm1 = make_norm(self.norm_fn, 64, self.dtype, num_groups=8)
        self.layer1_0 = ResidualBlock(64, 64, self.norm_fn, 1, self.dtype)
        self.layer1_1 = ResidualBlock(64, 64, self.norm_fn, 1, self.dtype)
        self.layer2_0 = ResidualBlock(64, 96, self.norm_fn, 1 + (d > 1), self.dtype)
        self.layer2_1 = ResidualBlock(96, 96, self.norm_fn, 1, self.dtype)
        self.layer3_0 = ResidualBlock(96, 128, self.norm_fn, 1 + (d > 0), self.dtype)
        self.layer3_1 = ResidualBlock(128, 128, self.norm_fn, 1, self.dtype)
        self.conv2 = conv(self.output_dim, 1, padding=0, dtype=self.dtype)

    def __call__(self, x):
        x = _stem_layer1(self, x)
        x = _trunk_layer2(self, x)
        for blk in (self.layer3_0, self.layer3_1):
            x = blk(x)
        return self.conv2(x)


class MultiBasicEncoder(nn.Module):
    """Context encoder: shared trunk + two extra stride-2 stages, with
    per-GRU-level output heads (reference: core/extractor.py:199-300).

    ``output_dims`` is a sequence of channel tuples, one per output head
    group (the model passes (hidden_dims, hidden_dims) for the GRU hidden
    state and the context stream).  Each tuple is indexed finest-first:
    dims[level] is the head width at GRU level ``level`` (0 = finest).

    Returns ``(levels, heads)``-nested lists: ``out[level][head]``, finest
    level first, plus the trunk features when ``dual_inp`` (shared-backbone
    mode, reference: core/raft_stereo.py:78-80).
    """

    output_dims: Sequence[Tuple[int, ...]] = ((128, 128, 128), (128, 128, 128))
    norm_fn: str = "batch"
    downsample: int = 3
    dtype: Any = jnp.float32
    fused_stem: Optional[bool] = None  # see BasicEncoder.fused_stem

    def setup(self):
        d = self.downsample
        self.conv1 = conv(64, 7, stride=1 + (d > 2), padding=3, dtype=self.dtype)
        self.norm1 = make_norm(self.norm_fn, 64, self.dtype, num_groups=8)
        self.layer1_0 = ResidualBlock(64, 64, self.norm_fn, 1, self.dtype)
        self.layer1_1 = ResidualBlock(64, 64, self.norm_fn, 1, self.dtype)
        self.layer2_0 = ResidualBlock(64, 96, self.norm_fn, 1 + (d > 1), self.dtype)
        self.layer2_1 = ResidualBlock(96, 96, self.norm_fn, 1, self.dtype)
        self.layer3_0 = ResidualBlock(96, 128, self.norm_fn, 1 + (d > 0), self.dtype)
        self.layer3_1 = ResidualBlock(128, 128, self.norm_fn, 1, self.dtype)
        self.layer4_0 = ResidualBlock(128, 128, self.norm_fn, 2, self.dtype)
        self.layer4_1 = ResidualBlock(128, 128, self.norm_fn, 1, self.dtype)
        self.layer5_0 = ResidualBlock(128, 128, self.norm_fn, 2, self.dtype)
        self.layer5_1 = ResidualBlock(128, 128, self.norm_fn, 1, self.dtype)

        # Heads: level 0 (finest, trunk res) gets a ResidualBlock + 3x3 conv,
        # level 1 the same, level 2 (coarsest) a bare 3x3 conv — mirroring the
        # reference's outputs08/outputs16/outputs32 structure
        # (core/extractor.py:227-250).
        heads08, heads16, heads32 = [], [], []
        for hi, dims in enumerate(self.output_dims):
            heads08.append((
                ResidualBlock(128, 128, self.norm_fn, 1, self.dtype,
                              name=f"head08_{hi}_res"),
                conv(dims[0], 3, dtype=self.dtype, name=f"head08_{hi}_conv"),
            ))
            if len(dims) >= 2:
                heads16.append((
                    ResidualBlock(128, 128, self.norm_fn, 1, self.dtype,
                                  name=f"head16_{hi}_res"),
                    conv(dims[1], 3, dtype=self.dtype, name=f"head16_{hi}_conv"),
                ))
            if len(dims) >= 3:
                heads32.append(conv(dims[2], 3, dtype=self.dtype,
                                    name=f"head32_{hi}_conv"))
        self.heads08 = heads08
        self.heads16 = heads16
        self.heads32 = heads32

    def __call__(self, x, dual_inp: bool = False, num_layers: int = 3):
        x = _stem_layer1(self, x)
        x = _trunk_layer2(self, x)
        for blk in (self.layer3_0, self.layer3_1):
            x = blk(x)
        trunk = None
        if dual_inp:
            trunk = x
            x = x[: x.shape[0] // 2]

        out08 = [head_conv(head_res(x)) for head_res, head_conv in self.heads08]
        outputs = [out08]
        if num_layers >= 2:
            y = self.layer4_1(self.layer4_0(x))
            outputs.append([hc(hr(y)) for hr, hc in self.heads16])
        if num_layers >= 3:
            z = self.layer5_1(self.layer5_0(y))
            outputs.append([hc(z) for hc in self.heads32])
        if dual_inp:
            return outputs, trunk
        return outputs
