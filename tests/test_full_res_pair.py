"""A full-resolution pair on one chip (PR 28): what a 6-megapixel bucket asks
of admission, of the on-demand lookup's blocks and of the encoder gates, held
on the CPU at sizes that take seconds.

(a) a wide, short pair whose 1/4-resolution row is longer than one pixel
    block and whose pyramid is wider than the served 640 columns, through
    ``BatchEngine`` with the interpreted kernel, against the benchmark's plain
    reference; (b) the lookup's blocks a program reports
    (``utils/platform.describe_program``) at the served, the full-resolution
    and the training shape; (c) admission follows the configured buckets;
    (d) the fused encoder stages' ``auto`` answers.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raftstereo_tpu.config import RAFTStereoConfig, ServeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- (a) a wide row, end to end

WIDE_HW = (64, 1216)    # bucket 64x1216: a 16x304 field, 896 pyramid columns


@pytest.fixture(scope="module")
def wide_pair_fields():
    """The served disparity of one wide pair — float32 model, the
    ``pallas_alt`` lookup interpreted — and the plain reference's."""
    import sys
    sys.path.insert(0, REPO)
    from benchmark.loadgen.pairs import make_pair
    from benchmark.reference import raft_stereo as R
    from benchmark.weights import make_weights
    from raftstereo_tpu.models import RAFTStereo
    from raftstereo_tpu.obs.trace import Tracer
    from raftstereo_tpu.serve import BatchEngine
    from raftstereo_tpu.utils.convert import torch_to_variables

    with open(os.path.join(REPO, "benchmark", "configs",
                           "raftstereo_default.json")) as f:
        cfg = json.load(f)["model"]
    w = make_weights(cfg, 7)
    pc = RAFTStereoConfig(corr_implementation="pallas_alt")
    model = RAFTStereo(pc)
    v = torch_to_variables(w, model.init(jax.random.key(0)), pc)
    iters = 3
    tracer = Tracer(capacity=64)
    engine = BatchEngine(model, v, ServeConfig(
        port=0, buckets=(WIDE_HW,), warmup=False, max_batch_size=1,
        iters=iters, degraded_iters=iters), tracer=tracer)
    left, right = make_pair(3, 0, WIDE_HW, max_disp=24)
    pair = (left.astype(np.float32), right.astype(np.float32))
    (served,) = engine.infer_batch([pair], iters)
    ref = np.asarray(jax.jit(lambda p, l, r: R.serve_reference(
        p, cfg, l, r, iters))({k: jnp.asarray(x) for k, x in w.items()},
                              left, right))
    return served, ref, engine, tracer


def test_wide_row_matches_the_plain_reference(wide_pair_fields):
    """Tolerance: float32 model, float32 correlation at ``highest``: the
    program and the reference differ by the order of float32 sums, 7e-7
    of the field's mean after 3 iterations (measured here, CPU).  The same
    pair with bf16 correlation operands (``corr_dtype="bfloat16"``) reads
    3.7e-4, and with the kernel's products rounded to bf16 — a row
    accumulated in bf16 — 1.9e-3: 1e-4 passes the one with two orders of
    room and fails the other two."""
    served, ref, _, _ = wide_pair_fields
    assert served.shape == ref.shape == WIDE_HW
    scale = float(np.abs(ref).mean())
    assert scale > 0.01                       # the field moved
    assert float(np.abs(served - ref).mean()) / scale < 1e-4


def test_wide_row_took_more_than_one_pixel_block(wide_pair_fields):
    """The row (304 pixels, padded to 512) is two blocks of 256 against
    896 pyramid columns, and the bucket's ``compile`` span and
    ``compiled_programs`` say so."""
    from raftstereo_tpu.ops.corr import _padded_level_widths

    _, _, engine, tracer = wide_pair_fields
    assert sum(_padded_level_widths(WIDE_HW[1] // 4, 4)) == 896 > 640
    (facts,) = engine.compiled_programs.values()
    assert facts["corr_block"] == {"rows": 8, "pixels": 256}
    assert facts["fused_stages"] == {
        "stem_cnet": False, "layer2_cnet": False,
        "stem_fnet": False, "layer2_fnet": False}       # the CPU's answer
    (span,) = [s for s in tracer.spans() if s.name == "compile"]
    assert span.attrs["kind"] == "bucket"
    assert span.attrs["bucket"] == "64x1216"
    assert span.attrs["corr_block"] == facts["corr_block"]
    seg = engine.last_segments
    assert seg["pad_px"] == {"rows": 1, "real_px": 64 * 1216,
                             "bucket_px": 64 * 1216}


# ------------------------------------------------ (b) the lookup's blocks

@pytest.mark.parametrize("hw,corr,block", [
    ((576, 960), "pallas_alt", {"rows": 8, "pixels": 240}),    # 540x960
    ((2048, 3008), "pallas_alt", {"rows": 8, "pixels": 256}),  # 1988x2964
    ((320, 720), "pallas_alt", {"rows": 8, "pixels": 184}),    # training
    (WIDE_HW, "pallas_alt", {"rows": 8, "pixels": 256}),
    ((2048, 3008), "reg", None),                # no on-demand lookup
], ids=str)
def test_lookup_blocks_a_program_reports(hw, corr, block):
    """The blocks are two constants of ``ops/pallas_corr.py``: eight rows,
    and the row's pixels rounded up to eight and capped at 256.  The served
    shape keeps (8, 240), so the eight-row cells run the program they ran;
    a 752-pixel row takes three blocks of 256, which Mosaic accepts on the
    v5e (tests/test_chip_compile.py) and the microbenchmark found fastest
    (PERF.md section 5)."""
    from raftstereo_tpu.utils.platform import describe_program

    got = describe_program(RAFTStereoConfig(corr_implementation=corr), 1, hw)
    assert got["corr_block"] == block


# ------------------------------------------------------- (c) admission

def test_a_configured_bucket_raises_the_body_cap_only():
    from raftstereo_tpu.cli.serve import build_parser
    from raftstereo_tpu.config import bucket_body_mb, serve_config_from_args

    cfg = serve_config_from_args(build_parser().parse_args(
        ["--buckets", "1988x2964", "--max_batch_size", "1"]))
    # the bound on cold compiles stays the operator's
    assert cfg.max_image_dim == 2048
    assert cfg.max_body_mb == bucket_body_mb(((1988, 2964),)) > 160.0
    # under the constant nothing moves, and the operator's own caps stand
    small = ServeConfig(port=0, max_body_mb=0.1)
    assert (small.max_image_dim, small.max_body_mb) == (2048, 0.1)
    both = ServeConfig(port=0, buckets=((540, 960), (1988, 2964)),
                       max_image_dim=4000)
    assert (both.max_image_dim, both.max_body_mb) == (4000, 160.0)


@pytest.mark.parametrize("hw,admitted", [
    ((1988, 2964), True),      # the listed shape, with no --max_image_dim
    ((1980, 2960), True),      # inside it
    ((1988, 3000), False),     # wider than every listed shape
    ((2900, 2900), False),     # no side above 2964, 8.4 Mpx: fits nothing
    ((2048, 2048), True),      # under the constant, as before
    ((2049, 100), False),
], ids=str)
def test_above_the_constant_a_pair_is_admitted_by_bucket(hw, admitted):
    cfg = ServeConfig(port=0, buckets=((540, 960), (1988, 2964)),
                      max_batch_size=1)
    assert cfg.admits(*hw) is admitted


def test_admission_follows_the_configured_bucket_over_http():
    """A bucket wider than the constant is served with no other setting;
    a wider shape, and a tall one whose longest side is the bucket's, are
    400s at admission (cold buckets are allowed here, so nothing but the
    ceiling refuses them)."""
    from raftstereo_tpu.models import RAFTStereo
    from raftstereo_tpu.serve import ServeClient, ServeError, build_server

    model = RAFTStereo(RAFTStereoConfig(
        n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2, corr_radius=2))
    variables = model.init(jax.random.key(0), (64, 96))
    cfg = ServeConfig(port=0, bucket_multiple=32, buckets=((32, 2100),),
                      warmup=False, max_batch_size=1, iters=1,
                      degraded_iters=1, request_timeout_ms=120000.0)
    assert cfg.max_image_dim == 2048
    server = build_server(model, variables, cfg)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        client = ServeClient("127.0.0.1", server.port, timeout=120.0)
        img = np.zeros((32, 2100, 3), np.float32)
        disp, _ = client.predict(img, img)
        assert disp.shape == (32, 2100)
        for hw in ((32, 2200), (2100, 32)):
            other = np.zeros((*hw, 3), np.float32)
            with pytest.raises(ServeError) as ei:
                client.predict(other, other)
            assert ei.value.status == 400
            assert "fits no shape listed in --buckets" in str(ei.value)
        assert len(server.engine.compiled_keys) == 1
    finally:
        server.shutdown()
        server.close()
        thread.join(10.0)


# ------------------------------------------------ (d) the encoder gates

@pytest.fixture
def one_tpu(monkeypatch):
    """The gates' view of a one-chip TPU host (they ask the backend's name
    and the device count, nothing else)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [object()])


@pytest.mark.parametrize("rows,hw,fused", [
    (8, (576, 960), False),       # the eight-row cells: 16 / 8 images
    (1, (576, 960), True),        # one row of the served bucket
    (1, (2048, 3008), True),      # one 6-Mpx row (PERF.md section 6, PR 28)
])
def test_fused_stage_gates(one_tpu, rows, hw, fused):
    from raftstereo_tpu.ops.pallas_encoder import use_fused_stem
    from raftstereo_tpu.ops.pallas_layer2 import use_fused_layer2

    h, w = hw
    assert use_fused_stem("instance", (2 * rows, h, w, 64)) is fused
    assert use_fused_stem("batch", (rows, h, w, 64)) is fused
    assert use_fused_layer2("instance", 2, (2 * rows, h, w, 64)) is fused
    assert use_fused_layer2("batch", 2, (rows, h, w, 64)) is fused


@pytest.mark.parametrize("rows,fused", [(8, False), (1, True)])
def test_a_program_reports_its_fused_stages(one_tpu, rows, fused):
    """What the bucket's ``compile`` span and ``/debug/vars`` carry, and
    the ``runtime:`` line's two gates with it: all off at eight rows, all
    on at one 6-Mpx row."""
    from raftstereo_tpu.utils.platform import describe_program

    got = describe_program(RAFTStereoConfig(), rows, (2048, 3008))
    assert got["fused_stages"] == dict.fromkeys(
        ("stem_cnet", "layer2_cnet", "stem_fnet", "layer2_fnet"), fused)


@pytest.mark.parametrize("width,rows", [(960, 32), (1536, 32), (3008, 16)])
def test_encoder_row_blocks_follow_from_the_width(width, rows):
    """32 rows a block up to 1,536 wide as before; a 3,008-wide image gets
    16 (32 need 107.9 MB of VMEM in ``encoder_conv``, PR 28)."""
    from raftstereo_tpu.ops.pallas_norm import _row_block

    assert _row_block(2048, row_elems=(width // 2) * 128) == rows
    assert _row_block(2048) == 32
