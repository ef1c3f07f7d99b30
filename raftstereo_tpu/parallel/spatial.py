"""Spatial sharding: ONE inference split over the ``space`` mesh axis.

Every serving path before this file is single-chip per request (replicas,
sessions, tiers all schedule WHOLE engines); this module runs a single
stereo pair with image height H sharded across the ``space`` axis of a
``(1, N)`` mesh under ``shard_map`` — the path for pairs whose correlation
pyramid and activations exceed one chip's HBM.  RAFT-Stereo's structure
makes H the free axis: the all-pairs correlation is 1-D along W (each H
row's epipolar line is self-contained, so corr build AND lookups are
row-local per shard), and everything else is convs with small receptive
fields.  Feature extraction, the corr volume, and the whole GRU iteration
loop therefore stay sharded end to end; the only data that ever crosses
shard boundaries is

* receptive-field-sized halo rows, exchanged by ``ppermute`` before each
  conv (``halo_exchange``): every shard sends its top/bottom ``pad`` rows
  to its neighbors, convolves VALID-in-H over the extended slab, and gets
  back exactly its own output rows.  ``ppermute`` zero-fills the shards
  with no neighbor, which reproduces the reference conv's zero padding at
  the global image edges bit-for-bit — one mechanism covers interior and
  edge slabs;
* full-height all-gathers for the two genuinely global ops: instance-norm
  statistics (a mean over all of H x W — stats are computed on the
  gathered activation via ``models.layers.instance_norm_stats`` and
  applied to the local slab, the exact split that function exists for)
  and the cross-GRU-level bilinear resizes (align-corners row weights
  couple distant rows; v1 gathers the COARSE level, which is 1/64th of
  the finest activation, and slices the local slab from the exact
  reference resize);
* full-height all-gathers for convs whose LOCAL output is tiny
  (``SPATIAL_REPLICATE_BELOW``): XLA:CPU's Eigen contraction shards the
  reduction dimension across threads when a gemm's output is small,
  combining per-thread partial sums whose rounding depends on the output
  shape — so a slab-height conv can round differently from the
  full-height conv even though every window sees identical inputs.
  Those convs run replicated at full height (reference-identical shape
  forces reference-identical accumulation) and slice the shard's rows
  back out; coarse pyramid levels are 1/4..1/64 of the trunk pixels, so
  the replicated compute is noise at serving resolutions.

Bitwise contract: on the CPU fp32 path the sharded forward is
bit-identical to ``RAFTStereo.jitted_infer`` / ``jitted_infer_init`` at
the same resolution (asserted on a real ``(1, 4)`` virtual-device mesh in
tests/test_spatial_sharding.py).  Per-op equivalences: a halo-exchanged
VALID-in-H conv equals the zero-padded full conv at stride 1 and at
stride 2 (even local H); frozen batch norm is elementwise, so the real
flax module applied to the slab matches; the 3x3/s2/p1 average pool over
a halo-extended slab matches; convex upsampling reads a 3x3 coarse
neighbourhood, one halo row.

v1 scope (validated in ``validate_spatial_config``):

* The Pallas corr backends remap to their XLA twins (pallas -> reg,
  pallas_alt -> alt: same math, different kernels), and the plain conv
  flow head / plain stem are always used — so on TPU the spatial path's
  numerics match the CPU certified-parity path, not the single-chip TPU
  fast paths (tap head, fused stem, corr epilogue).
* no int8 corr (``corr_quant``), no ``shared_backbone``, no GroupNorm
  context (the default "batch" and "instance"/"none" are covered).

Geometry: each shard's slab must stay evenly divisible through every
stride-2 stage and the convex upsample, i.e. H % (shards *
``spatial_row_multiple(cfg)``) == 0 — the serving layer sizes its
spatial buckets to this (serve/spatial/).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import RAFTStereoConfig
from ..models.layers import instance_norm_apply, instance_norm_stats
from ..ops.corr import make_corr_fn, resolve_implementation
from ..ops.image import coords_grid_x, resize_bilinear_align_corners
from .mesh import SPACE_AXIS, make_mesh


# Below this many LOCAL conv-output elements, the slab conv is computed on
# the all-gathered full-height input instead of the halo-extended slab
# (module docstring: Eigen shards the gemm reduction dimension for small
# outputs, making the rounding output-shape-dependent).  Empirically the
# slab/full split is bitwise-stable from 12288 elements up and diverges at
# <= 6144 on an 8-virtual-device host; 32768 gives > 5x margin.  Env
# override for hosts whose Eigen heuristics draw the line elsewhere.
SPATIAL_REPLICATE_BELOW = int(os.environ.get(
    "RAFTSTEREO_SPATIAL_REPLICATE_BELOW", "32768"))


class SpatialShardingUnsupported(ValueError):
    """A config/shape the spatial v1 path cannot run.  Raised at setup or
    trace time, never mid-inference — the serving admission layer maps it
    to a 400 (serve/spatial/admission.py), so an unsupported request can
    never trigger a compile."""


# --------------------------------------------------------------- validation

def spatial_row_multiple(cfg: RAFTStereoConfig) -> int:
    """Per-shard slab-height granularity: the local trunk rows must divide
    evenly through every context-encoder stride-2 stage (2^(n_gru_layers-1))
    and the slab image rows through the trunk downsample (``factor``)."""
    return cfg.factor * 2 ** (cfg.n_gru_layers - 1)


def validate_spatial_config(cfg: RAFTStereoConfig) -> None:
    """Reject configs the v1 sharded forward does not cover (module
    docstring).  Cheap and pure — admission calls it per request."""
    if cfg.shared_backbone:
        raise SpatialShardingUnsupported(
            "spatial sharding does not support shared_backbone")
    if cfg.context_norm == "group":
        raise SpatialShardingUnsupported(
            "spatial sharding supports context_norm batch/instance/none, "
            "not group")
    if cfg.corr_quant:
        raise SpatialShardingUnsupported(
            "spatial sharding does not support the int8 corr volume "
            "(corr_quant); use an unquantized config")


def check_spatial_shape(cfg: RAFTStereoConfig, shards: int, h: int,
                        w: int) -> None:
    """Static shape admission: H must split into ``shards`` equal slabs,
    each a multiple of ``spatial_row_multiple``."""
    if shards < 1:
        raise SpatialShardingUnsupported(f"shards must be >= 1, got {shards}")
    m = spatial_row_multiple(cfg) * shards
    if h % m:
        raise SpatialShardingUnsupported(
            f"spatial sharding needs H % {m} == 0 "
            f"({shards} shards x row multiple {spatial_row_multiple(cfg)}); "
            f"got H={h}")
    if w % cfg.factor:
        raise SpatialShardingUnsupported(
            f"W must be divisible by factor={cfg.factor}; got W={w}")


def spatial_corr_implementation(cfg: RAFTStereoConfig) -> str:
    """The corr backend the sharded forward uses: the config's resolved
    implementation with the Pallas kernels remapped to their XLA twins
    (identical math; the kernels are bare pallas_calls — module
    docstring)."""
    resolved = resolve_implementation(cfg.corr_implementation, quant=False)
    return {"pallas": "reg", "pallas_alt": "alt"}.get(resolved, resolved)


def spatial_mesh(shards: int, devices: Optional[Sequence] = None) -> Mesh:
    """The canonical spatial mesh: ``(1, shards)`` over the first
    ``shards`` devices — batch stays whole, H splits over ``space``
    (mesh.spatial_sharded is the matching NamedSharding)."""
    devices = list(devices if devices is not None else jax.devices())
    return make_mesh(data=1, space=shards, devices=devices[:shards])


# ------------------------------------------------------------ halo exchange

def halo_exchange(x: jax.Array, pad: int, n_shards: int,
                  axis_name: str = SPACE_AXIS) -> jax.Array:
    """Extend a local H slab (B, h, W, C) -> (B, h + 2*pad, W, C) with the
    neighbors' edge rows: shard i receives shard i-1's bottom ``pad`` rows
    above its slab and shard i+1's top rows below.  The boundary shards
    have no neighbor on one side; ``ppermute`` zero-fills unaddressed
    outputs, which is EXACTLY the reference conv's zero padding at the
    global top/bottom edge — so a VALID-in-H conv over the extended slab
    reproduces the padded full-image conv's rows bit-for-bit on every
    shard.  ``n_shards == 1`` degenerates to plain zero padding."""
    if pad == 0:
        return x
    if n_shards == 1:
        return jnp.pad(x, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    down = [(i, i + 1) for i in range(n_shards - 1)]  # i's bottom -> i+1's top
    up = [(i + 1, i) for i in range(n_shards - 1)]    # i+1's top -> i's bottom
    top = lax.ppermute(x[:, -pad:], axis_name, down)
    bot = lax.ppermute(x[:, :pad], axis_name, up)
    return jnp.concatenate([top, x, bot], axis=1)


# ------------------------------------------------- sharded layer primitives
#
# Each helper mirrors ONE module apply from models/ as the raw lax call the
# flax module lowers to (fp32: promote_dtype is a no-op and flax's conv IS
# lax.conv_general_dilated at default precision + a bias broadcast), with
# the H padding moved from the conv into the halo exchange.  Parameters are
# indexed straight off the model's params tree — same names, same trees.

def _replicate_rows(x: jax.Array, n_sh: int,
                    fn: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """Run ``fn`` on the full-height gather of a local slab and slice this
    shard's output rows back out.  ``fn`` sees the exact global array the
    reference forward sees, so its result is reference-bitwise no matter
    how the backend lowers it."""
    full = lax.all_gather(x, SPACE_AXIS, axis=1, tiled=True)
    y = fn(full)
    h_loc = y.shape[1] // n_sh
    i = lax.axis_index(SPACE_AXIS)
    return lax.dynamic_slice_in_dim(y, i * h_loc, h_loc, axis=1)


def _small_conv_output(x: jax.Array, k: jax.Array, stride: int, pad_h: int,
                       pad_w: int, n_sh: int) -> bool:
    """True when the LOCAL output of a slab conv falls under
    ``SPATIAL_REPLICATE_BELOW`` — the regime where Eigen's
    reduction-dimension sharding makes slab and full convs round
    differently (module docstring)."""
    if n_sh == 1:
        return False
    b, h, w = x.shape[:3]
    out_h = (h + 2 * pad_h - k.shape[0]) // stride + 1
    out_w = (w + 2 * pad_w - k.shape[1]) // stride + 1
    return b * out_h * out_w * k.shape[3] < SPATIAL_REPLICATE_BELOW


def _conv(p: Dict, x: jax.Array, stride: int, pad: int,
          n_sh: int) -> jax.Array:
    """``layers.conv`` (torch-geometry nn.Conv) on an H slab: halo rows in,
    VALID-in-H / symmetric-W conv out.  Stride 2 requires even local H
    (enforced by ``check_spatial_shape``); the slab's output rows then
    align exactly with the full conv's (first window of shard i starts at
    global row i*h_loc - pad, the same alignment the padded full conv
    gives row i*h_loc/stride).  Small outputs replicate at full height
    instead (``_small_conv_output``)."""
    k = p["kernel"].astype(x.dtype)
    b_ = p["bias"].astype(x.dtype)
    dn = ("NHWC", "HWIO", "NHWC")
    if _small_conv_output(x, k, stride, pad, pad, n_sh):
        return _replicate_rows(x, n_sh, lambda full: lax.conv_general_dilated(
            full, k, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=dn) + b_)
    y = lax.conv_general_dilated(
        halo_exchange(x, pad, n_sh), k, (stride, stride),
        ((0, 0), (pad, pad)), dimension_numbers=dn)
    return y + b_


def _conv_slice(p: Dict, x: jax.Array, lo: int, hi: Optional[int],
                pad: int, bias: bool, n_sh: int) -> jax.Array:
    """``update._sliced_conv`` on a local H slab: conv by an input-channel
    slice of the kernel (the GRU's concat-free gate form), halo rows in /
    VALID-in-H out, with the same small-output replication as ``_conv``."""
    k = p["kernel"][:, :, lo:hi].astype(x.dtype)
    b_ = p["bias"].astype(x.dtype) if bias else None
    dn = ("NHWC", "HWIO", "NHWC")

    def apply(a: jax.Array, pad_h) -> jax.Array:
        y = lax.conv_general_dilated(a, k, (1, 1), (pad_h, (pad, pad)),
                                     dimension_numbers=dn)
        return y + b_ if bias else y

    if _small_conv_output(x, k, 1, pad, pad, n_sh):
        return _replicate_rows(x, n_sh, lambda full: apply(full, (pad, pad)))
    return apply(halo_exchange(x, pad, n_sh), (0, 0))


def _norm(nf: str, p: Dict, s: Dict, name: str, dtype, x: jax.Array,
          n_sh: int) -> jax.Array:
    """One norm site from ``layers.make_norm``.  Frozen batch norm is
    elementwise, so the real flax module on the local slab matches the
    full-image rows; instance norm gathers the full-height activation for
    its (H, W) statistics and normalizes the slab locally — the
    stats/apply split in models/layers.py exists for exactly this call
    (the lane-group factor k depends only on (C, W), so the slab shares
    the full image's view geometry)."""
    if nf == "none":
        return x
    if nf == "batch":
        return nn.BatchNorm(use_running_average=True, epsilon=1e-5,
                            dtype=dtype).apply(
            {"params": p[name], "batch_stats": s[name]}, x)
    if nf == "instance":
        full = (lax.all_gather(x, SPACE_AXIS, axis=1, tiled=True)
                if n_sh > 1 else x)
        k, mw, sw = instance_norm_stats(full)
        return instance_norm_apply(x, k, mw, sw)
    raise SpatialShardingUnsupported(f"unsupported norm under spatial: {nf}")


def _res_block(p: Dict, s: Dict, nf: str, dtype, x: jax.Array, stride: int,
               n_sh: int) -> jax.Array:
    """``layers.ResidualBlock``; the projection shortcut exists iff the
    params tree has one (stride != 1 or a channel change — mirrors
    ``has_projection``)."""
    y = nn.relu(_norm(nf, p, s, "norm1", dtype,
                      _conv(p["conv1"], x, stride, 1, n_sh), n_sh))
    y = nn.relu(_norm(nf, p, s, "norm2", dtype,
                      _conv(p["conv2"], y, 1, 1, n_sh), n_sh))
    if "downsample_conv" in p:
        x = _norm(nf, p, s, "downsample_norm", dtype,
                  _conv(p["downsample_conv"], x, stride, 0, n_sh), n_sh)
    return nn.relu(x + y)


def _trunk(p: Dict, s: Dict, nf: str, dtype, d: int, x: jax.Array,
           n_sh: int) -> jax.Array:
    """The shared encoder trunk (encoders._plain_stem + layer2/layer3),
    stride placement per the downsample-factor logic.  Always the PLAIN
    module path — the fused Pallas stem is single-chip-only, and plain is
    what the CPU reference runs, so the bitwise contract holds."""
    x = nn.relu(_norm(nf, p, s, "norm1", dtype,
                      _conv(p["conv1"], x, 1 + (d > 2), 3, n_sh), n_sh))
    x = _res_block(p["layer1_0"], s.get("layer1_0", {}), nf, dtype, x, 1, n_sh)
    x = _res_block(p["layer1_1"], s.get("layer1_1", {}), nf, dtype, x, 1, n_sh)
    x = _res_block(p["layer2_0"], s.get("layer2_0", {}), nf, dtype, x,
                   1 + (d > 1), n_sh)
    x = _res_block(p["layer2_1"], s.get("layer2_1", {}), nf, dtype, x, 1, n_sh)
    x = _res_block(p["layer3_0"], s.get("layer3_0", {}), nf, dtype, x,
                   1 + (d > 0), n_sh)
    x = _res_block(p["layer3_1"], s.get("layer3_1", {}), nf, dtype, x, 1, n_sh)
    return x


def _basic_encoder(p: Dict, s: Dict, nf: str, dtype, d: int, x: jax.Array,
                   n_sh: int) -> jax.Array:
    """``encoders.BasicEncoder`` (the feature net, instance norm)."""
    x = _trunk(p, s, nf, dtype, d, x, n_sh)
    return _conv(p["conv2"], x, 1, 0, n_sh)


def _multi_encoder(p: Dict, s: Dict, nf: str, dtype, d: int, x: jax.Array,
                   num_layers: int, n_heads: int,
                   n_sh: int) -> List[List[jax.Array]]:
    """``encoders.MultiBasicEncoder`` (the context net): trunk + per-level
    heads, finest first — out[level][head]."""
    x = _trunk(p, s, nf, dtype, d, x, n_sh)

    def head_rc(prefix: str, hi: int, y: jax.Array) -> jax.Array:
        y = _res_block(p[f"{prefix}_{hi}_res"],
                       s.get(f"{prefix}_{hi}_res", {}), nf, dtype, y, 1, n_sh)
        return _conv(p[f"{prefix}_{hi}_conv"], y, 1, 1, n_sh)

    outputs = [[head_rc("head08", hi, x) for hi in range(n_heads)]]
    if num_layers >= 2:
        y = _res_block(p["layer4_0"], s.get("layer4_0", {}), nf, dtype, x, 2,
                       n_sh)
        y = _res_block(p["layer4_1"], s.get("layer4_1", {}), nf, dtype, y, 1,
                       n_sh)
        outputs.append([head_rc("head16", hi, y) for hi in range(n_heads)])
    if num_layers >= 3:
        z = _res_block(p["layer5_0"], s.get("layer5_0", {}), nf, dtype, y, 2,
                       n_sh)
        z = _res_block(p["layer5_1"], s.get("layer5_1", {}), nf, dtype, z, 1,
                       n_sh)
        outputs.append([_conv(p[f"head32_{hi}_conv"], z, 1, 1, n_sh)
                        for hi in range(n_heads)])
    return outputs


def _gru(p: Dict, h: jax.Array, cz, cr, cq, x: jax.Array,
         n_sh: int) -> jax.Array:
    """``update.ConvGRU``'s apply-time sliced form (kernel[:, :, :hd] on h,
    the rest on x, summed), each conv halo-exchanged."""
    hd = h.shape[-1]
    zr = (_conv_slice(p["convzr"], h, 0, hd, 1, False, n_sh)
          + _conv_slice(p["convzr"], x, hd, None, 1, True, n_sh))
    z = nn.sigmoid(zr[..., :hd] + cz)
    r = nn.sigmoid(zr[..., hd:] + cr)
    q = (_conv_slice(p["convq"], r * h, 0, hd, 1, False, n_sh)
         + _conv_slice(p["convq"], x, hd, None, 1, True, n_sh))
    q = nn.tanh(q + cq)
    return (1 - z) * h + z * q


def _motion_encoder(p: Dict, flow: jax.Array, corr: jax.Array, dtype,
                    n_sh: int) -> jax.Array:
    """``update.BasicMotionEncoder`` (no corr-epilogue preact — spatial
    never fuses convc1 into a lookup kernel).  convc1 is the pointwise
    padded conv (kernel zero-padded to the corr width); convf1 keeps the
    bf16 x-slice contraction gate."""
    k = p["convc1"]["kernel"]
    padc = corr.shape[-1] - k.shape[2]
    if padc:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, padc), (0, 0)))
    xc = corr.astype(dtype)
    kc = k.astype(dtype)
    bc = p["convc1"]["bias"].astype(xc.dtype)

    def c1_fn(a: jax.Array) -> jax.Array:
        y = lax.conv_general_dilated(
            a, kc, (1, 1), ((0, 0), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + bc

    if _small_conv_output(xc, kc, 1, 0, 0, n_sh):
        c1 = nn.relu(_replicate_rows(xc, n_sh, c1_fn))
    else:
        c1 = nn.relu(c1_fn(xc))
    cor = nn.relu(_conv(p["convc2"], c1, 1, 1, n_sh))
    if dtype == jnp.bfloat16:
        f1 = _conv_slice(p["convf1"], flow[..., :1], 0, 1, 3, True, n_sh)
    else:
        f1 = _conv(p["convf1"], flow, 1, 3, n_sh)
    flo = nn.relu(_conv(p["convf2"], nn.relu(f1), 1, 1, n_sh))
    out = nn.relu(_conv(p["conv"], jnp.concatenate([cor, flo], axis=-1),
                        1, 1, n_sh))
    return jnp.concatenate([out, flow], axis=-1)


def _avg_pool2x(x: jax.Array, n_sh: int) -> jax.Array:
    """``image.avg_pool2x`` (3x3/s2/p1, zeros in the divisor) on a slab:
    one halo row each way, VALID-in-H windows."""
    ext = halo_exchange(x, 1, n_sh)
    s = lax.reduce_window(
        ext, 0.0, lax.add,
        window_dimensions=(1, 3, 3, 1), window_strides=(1, 2, 2, 1),
        padding=((0, 0), (0, 0), (1, 1), (0, 0)))
    return s / jnp.asarray(9.0, dtype=x.dtype)


def _interp_to(x: jax.Array, dest: jax.Array, n_sh: int) -> jax.Array:
    """``update._interp_to`` (align-corners bilinear to dest's (H, W)):
    align-corners row weights couple rows across slab boundaries with
    H-dependent (not receptive-field) reach, so v1 gathers the COARSE
    source level (1/4 the rows of dest, itself already 1/factor of the
    image), runs the exact reference resize at full height, and slices
    this shard's rows — bitwise by construction.  A halo-based resize is
    the documented follow-up alongside the sharded megakernel."""
    h_loc, w = dest.shape[1:3]
    if n_sh == 1:
        return resize_bilinear_align_corners(x, (h_loc, w))
    full = lax.all_gather(x, SPACE_AXIS, axis=1, tiled=True)
    out = resize_bilinear_align_corners(full, (h_loc * n_sh, w))
    i = lax.axis_index(SPACE_AXIS)
    return lax.dynamic_slice_in_dim(out, i * h_loc, h_loc, axis=1)


def _flow_head(p: Dict, x: jax.Array, n_sh: int) -> jax.Array:
    """``update.FlowHead``, always the plain-conv form (the tap-matmul
    head is a single-chip TPU layout fix; plain is the CPU certified
    path)."""
    y = nn.relu(_conv(p["conv1"], x, 1, 1, n_sh))
    return _conv(p["conv2"], y, 1, 1, n_sh)


def _convex_upsample(flow: jax.Array, mask: jax.Array, factor: int,
                     n_sh: int) -> jax.Array:
    """``ops.upsample.convex_upsample``: softmax over each pixel's 3x3
    coarse neighbourhood — one halo row of the scaled flow replaces the
    H zero-pad of ``extract_3x3_patches``; the mask softmax is
    pixel-local."""
    b, h, w, d = flow.shape
    mask = mask.reshape(b, h, w, 9, factor, factor).astype(jnp.float32)
    mask = jax.nn.softmax(mask, axis=3)
    ext = halo_exchange(flow.astype(jnp.float32) * factor, 1, n_sh)
    pw = jnp.pad(ext, ((0, 0), (0, 0), (1, 1), (0, 0)))
    rows = [pw[:, ky:ky + h, kx:kx + w, :]
            for ky in range(3) for kx in range(3)]
    patches = jnp.stack(rows, axis=3)
    up = jnp.einsum("bhwkd,bhwkyx->bhywxd", patches, mask)
    return up.reshape(b, h * factor, w * factor, d)


# ------------------------------------------------------- sharded forward

def _update_block(up: Dict, cfg: RAFTStereoConfig, dtype, n_sh: int,
                  net: Sequence[jax.Array], zqr: Sequence[Tuple],
                  corr: Optional[jax.Array] = None,
                  flow: Optional[jax.Array] = None,
                  iter0: bool = True, iter1: bool = True, iter2: bool = True,
                  update: bool = True):
    """``update.BasicMultiUpdateBlock.__call__`` (test-mode, no in-loop
    mask head), coarsest -> finest with pooled finer / upsampled coarser
    cross-level inputs."""
    n = cfg.n_gru_layers
    net = list(net)
    if n == 3 and iter2:
        net[2] = _gru(up["gru2"], net[2], *zqr[2],
                      _avg_pool2x(net[1], n_sh), n_sh)
    if n >= 2 and iter1:
        if n > 2:
            x1 = jnp.concatenate([_avg_pool2x(net[0], n_sh),
                                  _interp_to(net[2], net[1], n_sh)], axis=-1)
        else:
            x1 = _avg_pool2x(net[0], n_sh)
        net[1] = _gru(up["gru1"], net[1], *zqr[1], x1, n_sh)
    if iter0:
        mf = _motion_encoder(up["encoder"], flow, corr, dtype, n_sh)
        if n > 1:
            x0 = jnp.concatenate([mf, _interp_to(net[1], net[0], n_sh)],
                                 axis=-1)
        else:
            x0 = mf
        net[0] = _gru(up["gru0"], net[0], *zqr[0], x0, n_sh)
    if not update:
        return net, None
    return net, _flow_head(up["flow_head"], net[0], n_sh)


def _local_forward(model, n_sh: int, iters: int, variables: Dict,
                   image1: jax.Array, image2: jax.Array,
                   flow_init: jax.Array):
    """The per-shard body under ``shard_map``: the exact op sequence of
    ``RAFTStereo.forward(test_mode=True)`` with every module apply
    replaced by its slab-local mirror above.  All inputs/outputs are
    local H slabs; ``variables`` is replicated."""
    cfg = model.config
    dtype = model.dtype
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    b = image1.shape[0]

    img1 = (2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
    img2 = (2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
    if cfg.input_mode == "sl":
        img1 = _conv(params["sl_proj"]["proj"], img1, 1, 1, n_sh)
        img2 = _conv(params["sl_proj"]["proj"], img2, 1, 1, n_sh)

    outputs = _multi_encoder(params["cnet"], stats.get("cnet", {}),
                             cfg.context_norm, dtype, cfg.n_downsample,
                             img1, cfg.n_gru_layers, 2, n_sh)
    fmaps = _basic_encoder(params["fnet"], stats.get("fnet", {}),
                           "instance", dtype, cfg.n_downsample,
                           jnp.concatenate([img1, img2], axis=0), n_sh)
    fmap1, fmap2 = fmaps[:b], fmaps[b:]

    net_list = [jnp.tanh(o[0]) for o in outputs]
    inp_list = [nn.relu(o[1]) for o in outputs]
    zqr_list = []
    for i, x in enumerate(inp_list):
        hd = cfg.hidden_dims[i]
        y = _conv(params["zqr"][f"zqr{i}"], x, 1, 1, n_sh)
        zqr_list.append((y[..., :hd], y[..., hd:2 * hd], y[..., 2 * hd:]))

    # Corr build AND lookups are H-row-local (the 1-D correlation is
    # along W), so the stock backend runs unchanged on the slab fmaps.
    corr_dtype = (jnp.bfloat16 if cfg.corr_dtype == "bfloat16"
                  else jnp.float32)
    corr_fn = make_corr_fn(spatial_corr_implementation(cfg), fmap1, fmap2,
                           cfg.corr_levels, cfg.corr_radius,
                           dtype=corr_dtype, precision=cfg.corr_precision,
                           out_dtype=dtype)

    up = params["update"]
    h0, w0 = net_list[0].shape[1:3]
    grid = coords_grid_x(b, h0, w0)  # x-only: identical on every row slab
    disp = (jnp.zeros((b, h0, w0, 1), jnp.float32)
            + flow_init.astype(jnp.float32))

    sf = cfg.slow_fast_gru
    n = cfg.n_gru_layers

    def step(carry, _):
        nets, d = carry
        d = lax.stop_gradient(d)
        corr = corr_fn(grid + d)
        flow = jnp.concatenate([d, jnp.zeros_like(d)], axis=-1).astype(dtype)
        nets = list(nets)
        if n == 3 and sf:
            nets, _ = _update_block(up, cfg, dtype, n_sh, nets, zqr_list,
                                    iter2=True, iter1=False, iter0=False,
                                    update=False)
        if n >= 2 and sf:
            nets, _ = _update_block(up, cfg, dtype, n_sh, nets, zqr_list,
                                    iter2=(n == 3), iter1=True, iter0=False,
                                    update=False)
        nets, delta = _update_block(up, cfg, dtype, n_sh, nets, zqr_list,
                                    corr=corr, flow=flow,
                                    iter2=(n == 3), iter1=(n >= 2))
        d = d + delta[..., :1].astype(jnp.float32)
        return (tuple(nets), d), None

    (nets, disp), _ = lax.scan(step, (tuple(net_list), disp), None,
                               length=iters)

    mask = 0.25 * _conv(up["mask_conv2"],
                        nn.relu(_conv(up["mask_conv1"], nets[0], 1, 1, n_sh)),
                        1, 0, n_sh)
    disp_up = _convex_upsample(disp, mask.astype(jnp.float32), cfg.factor,
                               n_sh)
    return disp, disp_up


# ------------------------------------------------------------- public API

def build_spatial_forward(model, mesh: Mesh, iters: int):
    """The sharded forward over ``mesh``: (variables, img1, img2,
    flow_init) -> (disp_low, disp_up), all image-space arguments GLOBAL
    arrays sharded P(None, "space") (mesh.spatial_sharded), variables
    replicated.  Not jitted — wrap with ``jax.jit`` or use the
    ``jitted_spatial_*`` builders."""
    validate_spatial_config(model.config)
    n_sh = int(mesh.shape[SPACE_AXIS])

    def local_fn(variables, image1, image2, flow_init):
        return _local_forward(model, n_sh, iters, variables, image1, image2,
                              flow_init)

    spec = P(None, SPACE_AXIS)
    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(P(), spec, spec, spec),
                         out_specs=(spec, spec), check_vma=False)


def jitted_spatial_infer(model, mesh: Mesh, iters: int = 32):
    """Compiled sharded test-mode forward, signature-compatible with
    ``RAFTStereo.jitted_infer``: (variables, img1, img2) -> (low, up)."""
    fwd = build_spatial_forward(model, mesh, iters)
    cfg = model.config
    shards = int(mesh.shape[SPACE_AXIS])

    def fn(v, i1, i2):
        b, h, w = i1.shape[:3]
        check_spatial_shape(cfg, shards, h, w)
        f = jnp.zeros((b, h // cfg.factor, w // cfg.factor, 1), jnp.float32)
        return fwd(v, i1, i2, f)

    return jax.jit(fn)


def jitted_spatial_infer_init(model, mesh: Mesh, iters: int = 32):
    """Compiled warm-start sharded forward, signature-compatible with
    ``RAFTStereo.jitted_infer_init``: (variables, img1, img2, flow_init)
    -> (low, up).  Zeros ``flow_init`` reproduces ``jitted_spatial_infer``
    bitwise (same property as the single-device pair)."""
    fwd = build_spatial_forward(model, mesh, iters)
    cfg = model.config
    shards = int(mesh.shape[SPACE_AXIS])

    def fn(v, i1, i2, flow_init):
        check_spatial_shape(cfg, shards, i1.shape[1], i1.shape[2])
        return fwd(v, i1, i2, flow_init)

    return jax.jit(fn)
