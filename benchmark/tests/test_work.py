"""benchmark/work.py against hand counts."""

import json
import os

import pytest

from benchmark import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_one_conv():
    # 3x3, 64 -> 96 channels, 10x20 outputs: 2 * 9 * 64 * 96 * 200
    assert work.conv_flops(3, 64, 96, 10, 20) == 2 * 9 * 64 * 96 * 200 \
        == 22118400


def test_one_gru_level():
    # hidden 128 over [h, x] of 128 + 256 channels at 4x5: three 3x3 convs
    assert work.gru_flops(128, 256, (4, 5)) == 3 * 2 * 9 * 384 * 128 * 20


def test_one_lookup_call():
    c = dict(cfg("raftstereo_default"), compute_dtype="bfloat16")
    # a 16x32 image: field 4x8, right-row widths 8+4+2+1 = 15, 9 taps
    k = work.kernel_corr_lookup(c, (16, 32), batch=2)
    rows = 2 * 4
    assert k["flops"] == 2 * rows * 8 * 15 * 256 + 4 * rows * 8 * 9 * 15
    assert k["bytes"] == (rows * 8 * 256 * 4 + rows * 15 * 256 * 4
                          + rows * 8 * 4 + rows * 8 * 36 * 2)


def test_level_shapes_follow_the_strides():
    assert work.level_shapes(cfg("raftstereo_default"), (540, 960)) == [
        (540, 960), (270, 480), (135, 240), (68, 120), (34, 60)]
    assert work.level_shapes(cfg("raftstereo_realtime"), (540, 960))[2] == (
        68, 120)


def test_pair_flops_magnitude_and_peaks():
    assert 5.0e12 < work.pair_flops(cfg("raftstereo_default"),
                                    (540, 960), 32) < 6.5e12
    assert 0.3e12 < work.pair_flops(cfg("raftstereo_realtime"),
                                    (540, 960), 7) < 0.7e12
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    least, bound = work.least_seconds({"flops": 197e12, "bytes": 1.0}, pk)
    assert (least, bound) == (1.0, "compute")
