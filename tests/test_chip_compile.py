"""Described-chip compiles of the main path's kernels at flagship widths.

The TPU compiler is installed on chip-less machines and compiles for a chip
that is described, not attached (``jax.experimental.topologies``).  Nothing
runs — these tests say nothing about results or times — but Mosaic refuses
here exactly what it would refuse on the chip: a misaligned slice, too much
VMEM, an op it has no lowering for.  Interpret-mode tests cannot see any of
that (the GRU megakernel passed all of them and does not lower, PR 24).

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU library, and under pytest-xdist every
worker imports every test file.  Keep these tests in this ONE file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Flagship geometry: 544x960 padded input, 1/4-resolution 136x240 field,
# 256-channel correlation features, 4 levels x radius 4.
H, W = 544, 960
H4, W4, C = H // 4, W // 4, 256
LEVELS, RADIUS = 4, 4
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Pallas kernels lower through Mosaic instead of interpreting (the
    auto rule would interpret: the default backend here is the CPU)."""
    from raftstereo_tpu.ops import pallas_corr

    monkeypatch.setattr(pallas_corr, "interpret_override", False)


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _tree_sds(one_chip, tree):
    return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text().count("tpu_custom_call")


def _fmaps(one_chip, dtype, batch=1):
    f = _sds(one_chip, (batch, H4, W4, C), dtype)
    coords = _sds(one_chip, (batch, H4, W4, 1))
    return f, f, coords


def test_pallas_alt_lookup_with_epilogue(one_chip, compiled_kernels):
    """What ``auto`` serves with in bf16: all four levels in one launch,
    the motion encoder's convc1 fused in as a relu epilogue."""
    from raftstereo_tpu.ops.corr import make_corr_fn

    planes = LEVELS * (2 * RADIUS + 1)

    def lookup(f1, f2, coords, kernel, bias):
        fn = make_corr_fn("pallas_alt", f1, f2, LEVELS, RADIUS, dtype=BF16,
                          out_dtype=BF16, out_channels=64,
                          epilogue={"kernel": kernel, "bias": bias})
        return fn(coords)

    compiled, kernels = _compile(
        lookup, *_fmaps(one_chip, BF16),
        _sds(one_chip, (1, 1, planes, 64), BF16), _sds(one_chip, (64,), BF16))
    assert kernels >= 1
    assert compiled.output_shardings == one_chip


# What the benchmark's cells serve (PERF.md §4): the 576x960 bucket at batch 8,
# float32 correlation operands under --mixed_precision.
SERVED_BATCH, SERVED_H4 = 8, 576 // 4
SERVED_COMPILE_LIMIT_S = 90.0    # 4-7 s alone; the old body took 25-30 s


@pytest.mark.parametrize("born", [BF16, jnp.float32],
                         ids=["bf16_born", "f32_born"])
def test_pallas_alt_lookup_as_served(one_chip, compiled_kernels, born):
    """The served form of the lookup, for both feature origins: float32
    operands, ``highest``, the convc1 epilogue, bf16 out, 1,152 rows x 240
    x 256 against the 640 lane-padded pyramid columns.  bf16-born features
    take the exact one- and three-pass matmul, float32-born the six-pass
    one (ops/pallas_alt.resolve_corr_matmul); both read their windows by
    lane gather.  Held to a time limit of its own — three dots in place of
    one must not turn the server's start into minutes — and to the operand
    signature the benchmark's roofline reader finds the call by."""
    import time

    from raftstereo_tpu.ops.corr import make_corr_fn

    planes = LEVELS * (2 * RADIUS + 1)

    def lookup(f1, f2, coords, kernel, bias):
        fn = make_corr_fn("pallas_alt", f1, f2, LEVELS, RADIUS,
                          dtype=jnp.float32, precision="highest",
                          out_dtype=BF16, out_channels=64,
                          epilogue={"kernel": kernel, "bias": bias})
        return fn(coords)

    f = _sds(one_chip, (SERVED_BATCH, SERVED_H4, W4, C), born)
    coords = _sds(one_chip, (SERVED_BATCH, SERVED_H4, W4, 1))
    t0 = time.monotonic()
    compiled, kernels = _compile(
        lookup, f, f, coords, _sds(one_chip, (1, 1, planes, 64), BF16),
        _sds(one_chip, (64,), BF16))
    took = time.monotonic() - t0
    assert kernels == 1
    assert took < SERVED_COMPILE_LIMIT_S, took
    # benchmark/metrics/kernel.corr_lookup_roofline.serve.json finds the
    # call in a trace by its result and its FIRST operand.
    (call,) = [ln for ln in compiled.as_text().splitlines()
               if "tpu_custom_call" in ln]
    rows = SERVED_BATCH * SERVED_H4
    assert f"= bf16[{rows},{W4},64]" in call
    assert f"operand_layout_constraints={{f32[{rows},{W4},{C}]" in call
    assert "alt_lookup_fwd" in call


def test_pallas_alt_lookup_train_forward_and_backward(one_chip,
                                                      compiled_kernels):
    """What ``auto`` trains with: the raw lookup and its backward kernel
    (320x720 crops: an 80x180 field)."""
    from raftstereo_tpu.ops.corr import make_corr_fn

    f = _sds(one_chip, (1, 80, 180, C), BF16)
    coords = _sds(one_chip, (1, 80, 180, 1))

    def loss(f1, f2, coords):
        fn = make_corr_fn("pallas_alt", f1, f2, LEVELS, RADIUS, dtype=BF16,
                          out_dtype=BF16, out_channels=64)
        return (fn(coords).astype(jnp.float32) ** 2).sum()

    _, kernels = _compile(jax.grad(loss, argnums=(0, 1)), f, f, coords)
    assert kernels >= 2  # forward + backward


def test_pallas_lookup_over_precomputed_volume(one_chip, compiled_kernels):
    from raftstereo_tpu.ops.corr import make_corr_fn

    def lookup(f1, f2, coords):
        return make_corr_fn("pallas", f1, f2, LEVELS, RADIUS)(coords)

    _, kernels = _compile(lookup, *_fmaps(one_chip, jnp.float32))
    assert kernels >= 1


# The full-resolution Middlebury bucket (PERF.md §4): a 1988x2964 pair pads
# to 2048x3008, a 512x752 field against 1,536 lane-padded pyramid columns.
FULL_H, FULL_W = 2048, 3008


def test_pallas_alt_lookup_at_full_resolution(one_chip, compiled_kernels):
    """The served form at a 752-pixel row (rows cut to 64: a grid step
    does not know how many there are): (8, 256) against 1,536 pyramid
    columns are blocks Mosaic takes, and the kernel keeps the name and the
    operands the benchmark's roofline readers find it by."""
    from raftstereo_tpu.ops.corr import make_corr_fn

    planes = LEVELS * (2 * RADIUS + 1)
    w1 = FULL_W // 4

    def lookup(f1, f2, coords, kernel, bias):
        fn = make_corr_fn("pallas_alt", f1, f2, LEVELS, RADIUS,
                          dtype=jnp.float32, precision="highest",
                          out_dtype=BF16, out_channels=64,
                          epilogue={"kernel": kernel, "bias": bias})
        return fn(coords)

    f = _sds(one_chip, (1, 64, w1, C), BF16)
    compiled, kernels = _compile(
        lookup, f, f, _sds(one_chip, (1, 64, w1, 1)),
        _sds(one_chip, (1, 1, planes, 64), BF16), _sds(one_chip, (64,), BF16))
    assert kernels == 1
    (call,) = [ln for ln in compiled.as_text().splitlines()
               if "tpu_custom_call" in ln]
    assert "= bf16[64,768,64]" in call
    assert "operand_layout_constraints={f32[64,768,256]" in call
    assert "f32[64,1536,256]" in call and "alt_lookup_fwd" in call


@pytest.mark.parametrize("hw", [(H, W), (FULL_H, FULL_W)],
                         ids=["544x960", "2048x3008"])
@pytest.mark.parametrize("which", ["feature_instance", "context_batch"])
def test_fused_stem_and_layer2_stage(one_chip, compiled_kernels, which, hw):
    """The fused encoder stages at 544x960 and at the full-resolution
    bucket (where 32 rows a block no longer fit the kernels' VMEM and the
    row block follows from the width, ops/pallas_norm._row_block): conv1 +
    norm + layer1 (stem) and
    the stride-2 layer2 stage, in both norm forms the default model uses —
    instance norm with in-kernel statistics (feature encoder, both images
    of a pair) and frozen batch norm folded to affines (context encoder)."""
    from raftstereo_tpu.models.encoders import BasicEncoder, MultiBasicEncoder

    if which == "feature_instance":
        enc = BasicEncoder(output_dim=C, norm_fn="instance", downsample=2,
                           dtype=BF16, fused_stem=True)
        batch = 2
    else:
        enc = MultiBasicEncoder(norm_fn="batch", downsample=2, dtype=BF16,
                                fused_stem=True)
        batch = 1
    # init takes the plain path (shapes only; the parameter tree is the
    # same either way), apply takes the forced fused path.
    variables = jax.eval_shape(
        enc.init, jax.random.key(0), jnp.zeros((1, 64, 64, 3), BF16))
    _, kernels = _compile(
        enc.apply, _tree_sds(one_chip, variables),
        _sds(one_chip, (batch, *hw, 3), BF16))
    assert kernels >= 8, kernels  # stem + layer2 are several launches each


def test_instance_norm_stats_kernel(one_chip, compiled_kernels):
    from raftstereo_tpu.ops.pallas_norm import instance_norm_act

    _, kernels = _compile(lambda x: instance_norm_act(x, True),
                          _sds(one_chip, (2, H4, W4, 128), BF16))
    assert kernels == 2  # statistics + apply
