"""The readers PR 26 added, over hand-made data and over the recorded trace of
two whole dispatches kept beside this file (``make_stage_trace.py``); and that
each of them returns nothing, without raising, on the trace of a program that
has neither scopes nor annotations (the older recorded trace)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import stages as st
from benchmark.readers import (cycle_gap, host_idle_unattributed, span_median,
                               stage_time)

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = os.path.join(HERE, "data", "serve_default_b8_stages.xplane.pb.gz")
OLD = os.path.join(HERE, "data", "serve_default_b8.xplane.pb.gz")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


def params(metric):
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return json.load(f)["params"]


def test_stage_of_takes_the_innermost_stage_and_strips_transforms():
    stages = ("encoders", "lookup", "gru", "upsample", "loss")
    assert st.stage_of("jit(f)/while/body/closed_call/gru/B/level08/add:",
                       stages) == "gru"
    assert st.stage_of("jit(f)/while/body/gru/B/upsample/mul", stages) \
        == "upsample"
    assert st.stage_of("jit(step)/transpose(jvp(gru))/B/conv", stages) == "gru"
    assert st.stage_of("jit(step)/jvp(loss)/abs", stages) == "loss"
    assert st.stage_of("jit(f)/while/body/closed_call", stages) == "unnamed"
    assert st.stage_of("", stages) == "unnamed"


def test_stage_metrics_over_the_recorded_trace():
    run = {"xplane": NEW}
    got = {s: stage_time.read(None, run, params(f"model_step.{s}_ms.serve"))
           for s in ("encoders", "corr_build", "lookup", "gru", "upsample")}
    # my chip run, PR 26 (PERF.md section 5)
    assert got["gru"] == pytest.approx(313.4, abs=0.5)
    assert got["lookup"] == pytest.approx(215.0, abs=0.5)
    assert got["encoders"] == pytest.approx(158.3, abs=0.5)
    assert got["gru"] > got["lookup"] > got["encoders"] > got["corr_build"] \
        > got["upsample"] > 0
    share = stage_time.read(None, run,
                            params("model_step.unnamed_share.serve"))
    assert 0 < share < 10
    bd = stage_time.breakdown(run, params("model_step.gru_ms.serve")["stages"])
    assert bd["dispatches"] == 2
    # the stages and `unnamed` account for a dispatch's busy time
    total = sum(bd["stage_s"].values()) + bd["unnamed_s"]
    assert total == pytest.approx(bd["busy_s"], rel=0.02)
    assert bd["busy_s"] <= bd["burst_s"] * 1.02


def test_idle_is_attributed_to_the_programs_phases():
    p = params("device.idle_unattributed_share.sat")
    assert p == params("device.idle_unattributed_share.rate")
    run = {"xplane": NEW, "spans": []}
    share = host_idle_unattributed.read(None, run, p)
    assert 0 <= share < 10
    spans, clocks = st.host_annotations(
        NEW, [n for names in p["phases"].values() for n in names])
    assert len(clocks) == 2 and clocks[0]["unix_ns"] < clocks[1]["unix_ns"]
    # the capture lasted what the clocks say, on both of their clocks
    for key in ("unix_ns", "perf_counter_ns"):
        assert (clocks[1][key] - clocks[0][key]) * 1e-9 == pytest.approx(
            clocks[1]["t"] - clocks[0]["t"], abs=1e-3)
    names = {s["name"] for s in spans}
    assert {"pad_bucket", "launch", "device_wait", "host_fetch",
            "reply_handoff", "batch_form", "wire_decode", "reply"} <= names


def test_attribute_gaps_ranks_threads_then_takes_the_shortest():
    spans = [{"name": "pad_bucket", "t0": 0.0, "t1": 4.0},     # the worker
             {"name": "reply", "t0": 1.0, "t1": 2.0},          # HTTP thread
             {"name": "queue_empty", "t0": 4.0, "t1": 9.0},
             {"name": "wire_decode", "t0": 5.0, "t1": 6.0}]
    rank = {"pad_bucket": 0, "reply": 1, "wire_decode": 1, "queue_empty": 2}
    by = st.attribute_gaps([(0.5, 3.0), (4.5, 7.0), (9.5, 10.0)], spans,
                           (0.0, 10.0), rank)
    assert by == pytest.approx({"pad_bucket": 2.5, "wire_decode": 1.0,
                                "queue_empty": 1.5, "unattributed": 0.5})
    # without ranks the shortest span over an instant takes it
    by = st.attribute_gaps([(0.5, 3.0)], spans, (0.0, 10.0))
    assert by == pytest.approx({"pad_bucket": 1.5, "reply": 1.0})


def _span(name, ts, dur, trace_id):
    return {"name": name, "ts": ts, "dur": dur, "args": {"trace_id": trace_id}}


def test_cycle_gap_and_span_medians_over_ring_spans():
    spans = []
    for i in range(4):          # a dispatch every 1000 us: wait, then a gap
        t = 1e6 + i * 1000.0
        spans += [_span("launch", t, 10.0, f"batch:{i}"),
                  _span("device_wait", t + 10.0, 900.0, f"batch:{i}"),
                  _span("device_wait", t + 10.0, 900.0, "some-request")]
    run = {"spans": spans, "t0": 0.5, "t_end": 2.0}
    assert cycle_gap.read(None, run, {}) == pytest.approx(0.09)   # 90 us
    assert span_median.read(None, run, params("engine.launch_ms.sat")) \
        == pytest.approx(0.01)
    assert cycle_gap.read(None, {"spans": [], "t0": 0, "t_end": 1}, {}) is None
    for metric in ("batcher.batch_form_ms.rate", "wire.decode_ms.sat",
                   "wire.decode_ms.rate", "wire.reply_ms.sat",
                   "wire.reply_ms.rate"):
        assert span_median.read(None, run, params(metric)) is None


def test_a_program_without_scopes_or_phases_gives_nothing():
    run = {"xplane": OLD, "spans": [], "t0": 0, "t_end": 1}
    ctx = SimpleNamespace()
    assert stage_time.read(ctx, run, params("model_step.gru_ms.serve")) is None
    assert stage_time.read(
        ctx, run, params("model_step.unnamed_share.serve")) is None
    assert host_idle_unattributed.read(
        ctx, run, params("device.idle_unattributed_share.sat")) is None
    assert cycle_gap.read(ctx, run, {}) is None
    assert stage_time.read(ctx, {}, params("model_step.gru_ms.serve")) is None
