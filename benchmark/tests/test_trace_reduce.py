"""The trace reduction on hand-made device events, and on the small recorded
trace kept beside this file."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur, detail=""):
    return {"name": name, "start_s": start, "dur_s": dur, "detail": detail}


def test_busy_union_gaps_and_kernel_time():
    planes = {"/device:TPU:0": [
        ev("fusion.1", 0.0, 1.0), ev("fusion.2", 0.5, 1.0),     # overlap
        ev("custom-call.7", 2.0, 0.5, "corr_kernel tpu_custom_call"),
        ev("fusion.1", 4.0, 1.0)]}
    spans = [{"name": "host_fetch", "t0": 1.4, "t1": 2.1},
             {"name": "request", "t0": 0.0, "t1": 5.0},
             {"name": "queue_wait", "t0": 2.6, "t1": 3.9}]
    r = tr.reduce_events(planes, spans)
    assert r["busy_s"] == pytest.approx(3.0)       # [0,1.5] + [2,2.5] + [4,5]
    assert r["window_s"] == pytest.approx(5.0)
    assert r["idle_share"] == pytest.approx(0.4)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"host_fetch": 0.5, "queue_wait": 1.5})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    k = tr.kernel_time(planes, ["corr_kernel"])
    assert k == {"seconds": pytest.approx(0.5), "calls": 1}
    assert tr.kernel_time(planes, ["no_such"])["calls"] == 0


def test_busy_and_gaps_from_the_host_tracers_end_on():
    # the host tracer ran until 2.2: the gaps before it are ten times as
    # long as those after, and only the seconds after it are read
    planes = {"/device:TPU:0": [
        ev("f", 0.0, 1.0), ev("f", 2.0, 1.0),           # 1.0 idle between
        ev("f", 3.1, 1.0), ev("f", 4.2, 1.0)]}          # 0.1 idle between
    r = tr.reduce_events(planes, [{"name": "pad", "t0": 3.0, "t1": 3.1}],
                         free_from=2.2)
    assert r["tracer_free"]
    assert r["busy_s"] == pytest.approx(2.8)            # [2.2,3] [3.1,4.1] [4.2,5.2]
    assert r["window_s"] == pytest.approx(3.0)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"pad": 0.1, "uncovered": 0.1})
    assert r["traced"] == {"busy_s": pytest.approx(4.0),
                           "window_s": pytest.approx(5.2)}
    assert r["device_ops"] == [["f", pytest.approx(4.0)]]   # the whole trace
    # idle when the tracer stops: the wait for the next operation counts
    r = tr.reduce_events(planes, free_from=1.5)
    assert r["window_s"] == pytest.approx(3.7)
    assert r["busy_s"] == pytest.approx(3.0)
    # less than a second recorded past the tracer's end: the whole trace,
    # marked as such; the idle share is then the whole trace's
    r = tr.reduce_events(planes, free_from=4.5)
    assert not r["tracer_free"] and r["window_s"] == pytest.approx(5.2)
    from benchmark.readers import device_idle_share
    assert device_idle_share.read(None, {"trace": r}, {}) == pytest.approx(
        100 * 1.2 / 5.2)
    assert device_idle_share.read(None, {"trace": tr.reduce_events({})},
                                  {}) is None
    assert not tr.reduce_events(planes)["tracer_free"]


def test_no_device_plane_reads_nothing():
    r = tr.reduce_events({})
    assert r["idle_share"] is None and r["device_ops"] == []


def test_recorded_trace():
    """The first 7,000 device operations of a traced run of
    default_serve_saturated on the v5e (make_small_trace.py): the staging
    copies of one dispatch, the encoders, and the first iterations of the
    loop with the lookup kernel in them."""
    import json

    path = os.path.join(HERE, "data", "serve_default_b8.xplane.pb.gz")
    planes, free_from = tr.load_trace(path)
    assert list(planes) == ["/device:TPU:0"]
    assert free_from is None                # the host threads were cut away
    r = tr.reduce_events(planes, free_from=free_from)
    assert not r["tracer_free"]
    assert 0 < r["busy_s"] < r["window_s"] < 2.0
    assert 0.0 < r["idle_share"] < 0.5
    assert all(not n.startswith("%while") for n, _ in r["device_ops"])
    with open(os.path.join(os.path.dirname(HERE), "metrics",
                           "kernel.corr_lookup_roofline.serve.json")) as f:
        patterns = json.load(f)["params"]["patterns"]
    k = tr.kernel_time(planes, patterns)
    assert k["calls"] >= 3
    assert 0.004 < k["seconds"] / k["calls"] < 0.010    # 6.5 ms a call
    assert r["device_ops"][0][0].endswith("[tpu_custom_call]")
    long = [b - a for a, b in tr.bursts(planes) if b - a > 0.1]
    assert long, "part of one dispatched program is in the file"


def test_clock_offset_from_burst_ends():
    # bursts of 0.5 s at uneven starts on the trace's clock; the host saw
    # each end 1000.003 s later on its own clock, and other ends besides
    starts = [0.1, 0.8, 1.62, 2.3, 3.05, 3.7]
    planes = {"/device:TPU:0": [ev("f", t, 0.5) for t in starts]}
    sync = [1000.003 + t + 0.5 for t in [-1.3, -0.6] + starts + [4.4, 5.1]]
    assert tr.clock_offset(planes, sync) == pytest.approx(1000.003, abs=1e-6)
    assert tr.clock_offset(planes, sync, started_at=999.9) == pytest.approx(
        1000.003, abs=1e-6)
    assert tr.clock_offset(planes, sync, started_at=900.0) is None
    assert tr.clock_offset(planes, []) is None
