"""``span_load`` and ``span_thread_time`` over a hand-made run: what they
read, what they leave out (spans outside the window, per-request copies of a
dispatch's span, spans without the attr), and that each returns nothing,
without raising, on the ring of a program that records none of it."""

import json
import os

import pytest

from benchmark.readers import span_load, span_thread_time

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def params(metric):
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return json.load(f)["params"]


def span(name, t_s, dur_ms, **args):
    return {"name": name, "ts": t_s * 1e6, "dur": dur_ms * 1e3,
            "args": args}


def run(*spans):
    return {"t0": 100.0, "t_end": 110.0, "spans": list(spans)}


def test_span_load_sums_the_window_once_a_dispatch():
    r = run(
        # one dispatch: its batch copy and two per-request copies
        span("pad_bucket", 101.0, 500.0, trace_id="batch:1"),
        span("pad_bucket", 101.0, 500.0, trace_id="req0"),
        span("pad_bucket", 101.0, 500.0, trace_id="req1"),
        span("launch", 101.5, 100.0, trace_id="batch:1"),
        span("launch", 101.5, 100.0, trace_id="req0"),
        span("pad_bucket", 105.0, 400.0, trace_id="batch:2"),
        # starts before the window, and after it: not counted
        span("pad_bucket", 99.9, 300.0, trace_id="batch:0"),
        span("launch", 110.5, 100.0, trace_id="batch:3"),
        span("reply_encode", 102.0, 80.0),
        span("reply_encode", 103.0, 120.0),
    )
    launcher = span_load.read(None, r, params("engine.launcher_load.rt"))
    assert launcher == pytest.approx(100.0 * 1.0 / 10.0)
    lock = span_load.read(None, r, params("wire.reply_lock_load.rt"))
    assert lock == pytest.approx(100.0 * 0.2 / 10.0)
    # every copy counts where the spans are not per dispatch
    assert span_load.read(None, r, {"spans": ["pad_bucket"]}) \
        == pytest.approx(100.0 * 1.9 / 10.0)


def test_span_thread_time_is_cpu_or_runq_over_the_wall():
    r = run(
        span("stage_copy", 101.0, 40.0, cpu_ms=30.0, runq_ms=6.0),
        span("h2d_put", 101.1, 60.0, cpu_ms=20.0, runq_ms=4.0),
        span("body_read", 102.0, 100.0, cpu_ms=10.0),  # no runq read
        span("widen", 102.2, 20.0, cpu_ms=18.0, runq_ms=1.0),
        span("reply_encode", 103.0, 80.0, cpu_ms=72.0, runq_ms=4.0),
        span("reply_wait", 103.0, 500.0, cpu_ms=0.0, runq_ms=0.0),
        span("widen", 99.0, 20.0, cpu_ms=20.0, runq_ms=0.0),  # outside
        span("stage_copy", 104.0, 50.0),    # a program without the attrs
    )
    cpu = span_thread_time.read(None, r, params("host.cpu_share.rt"))
    assert cpu == pytest.approx(100.0 * 150.0 / 300.0)
    runq = span_thread_time.read(None, r, dict(
        params("host.cpu_share.rt"), part="runq"))
    assert runq == pytest.approx(100.0 * 15.0 / 200.0)


@pytest.mark.parametrize("metric", [
    "engine.launcher_load.rt", "wire.reply_lock_load.rt",
    "host.cpu_share.rt"])
def test_nothing_to_read_gives_nothing(metric):
    reader = {"span_load": span_load,
              "span_thread_time": span_thread_time}[
        json.load(open(os.path.join(METRICS, metric + ".json")))["reader"]]
    # an older program's ring: the spans carry no thread times, and
    # nothing is called reply_encode
    old = run(span("wire_decode", 101.0, 100.0),
              span("reply", 102.0, 90.0))
    assert reader.read(None, old, params(metric)) is None
    assert reader.read(None, run(), params(metric)) is None


def test_a_host_without_schedstat_gives_no_runq_share():
    # cpu_ms on every phase, runq_ms on none: the cpu share reads
    r = run(span("stage_copy", 101.0, 40.0, cpu_ms=30.0),
            span("reply_encode", 103.0, 80.0, cpu_ms=72.0))
    cpu = params("host.cpu_share.rt")
    assert span_thread_time.read(None, r, cpu) \
        == pytest.approx(100.0 * 102.0 / 120.0)
    assert span_thread_time.read(None, r, dict(cpu, part="runq")) is None
