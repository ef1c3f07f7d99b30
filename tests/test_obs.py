"""Observability subsystem (raftstereo_tpu/obs, docs/observability.md).

Unit coverage for the span tracer, the Prometheus format validator, the
labeled metric families and the locked Gauge, plus the subsystem's
acceptance gate: an HTTP e2e that drives ``/predict`` and asserts the
response carries an ``X-Request-Id`` whose queue-wait / dispatch /
host-fetch spans appear in ``/debug/trace`` as valid Chrome trace-event
JSON with durations summing to at most the observed request latency,
``/metrics`` passes the format validator, span recording overhead stays
under 2% of request latency, and tracing adds zero XLA compiles.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

from raftstereo_tpu.config import RAFTStereoConfig, ServeConfig, StreamConfig
from raftstereo_tpu.obs import (TelemetryServer, Tracer, dump_threads,
                                lint_registry, parse_sample, parse_text,
                                to_chrome_trace, validate_prometheus)
from raftstereo_tpu.serve import ServeClient, ServeError, ServeMetrics, \
    build_server
from raftstereo_tpu.serve.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)


# ------------------------------------------------------------------- tracer

class TestTracer:
    def test_nesting_inherits_trace_and_parent(self):
        tr = Tracer()
        with tr.span("root") as root:
            with tr.span("child") as child:
                assert child.trace_id == root.trace_id
        spans = {s.name: s for s in tr.spans()}
        assert spans["child"].parent_id == spans["root"].span_id
        assert spans["root"].parent_id is None
        # Children record before parents (they close first) but share the
        # trace; durations nest.
        assert spans["child"].duration_s <= spans["root"].duration_s

    def test_record_explicit_window_and_parenting(self):
        tr = Tracer()
        rid = tr.new_trace_id()
        parent = tr.record("dispatch", 1.0, 3.0, rid, attrs={"iters": 8})
        tr.record("device_compute", 1.5, 2.5, rid, parent_id=parent)
        a, b = tr.spans()
        assert a.duration_s == 2.0 and b.parent_id == a.span_id
        assert a.attrs["iters"] == 8 and b.trace_id == rid

    def test_ring_bound_and_drop_count(self):
        tr = Tracer(capacity=8)
        rid = tr.new_trace_id()
        for i in range(20):
            tr.record(f"s{i}", 0.0, 1.0, rid)
        assert len(tr.spans()) == 8
        assert tr.recorded == 20 and tr.dropped == 12
        assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]
        assert tr.spans(last=3)[0].name == "s17"

    def test_thread_safety_under_contention(self):
        tr = Tracer(capacity=10000)

        def hammer(k):
            for i in range(500):
                with tr.span(f"t{k}"):
                    pass

        ts = [threading.Thread(target=hammer, args=(k,)) for k in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert tr.recorded == 2000

    def test_chrome_export_shape(self):
        tr = Tracer()
        rid = tr.new_trace_id()
        tr.record("x", 10.0, 10.5, rid)
        doc = tr.to_chrome()
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(events) == 1 and len(meta) == 1
        (e,) = events
        assert e["dur"] == pytest.approx(0.5e6)
        assert e["args"]["trace_id"] == rid
        assert meta[0]["name"] == "thread_name"
        json.dumps(doc)  # serializable as-is

    def test_trace_id_filter(self):
        tr = Tracer()
        tr.record("a", 0, 1, "rid-1")
        tr.record("b", 0, 1, "rid-2")
        assert [s.name for s in tr.spans(trace_id="rid-1")] == ["a"]


# -------------------------------------------------------- format validator

GOOD = """\
# HELP x_total a counter
# TYPE x_total counter
x_total{endpoint="predict",outcome="ok"} 3
# HELP h_seconds a histogram
# TYPE h_seconds histogram
h_seconds_bucket{le="0.1"} 1
h_seconds_bucket{le="+Inf"} 2
h_seconds_sum 0.5
h_seconds_count 2
"""


class TestValidator:
    def test_accepts_valid_exposition(self):
        assert validate_prometheus(GOOD) == []

    def test_parse_sample_unescapes_structure(self):
        name, labels, value = parse_sample(
            'm_total{a="x\\\\y",b="q\\"z",c="n\\nl"} 4')
        assert name == "m_total" and value == 4.0
        assert dict(labels) == {"a": "x\\\\y", "b": 'q\\"z', "c": "n\\nl"}

    @pytest.mark.parametrize("bad, why", [
        ("x_total 1\n", "no TYPE"),
        ("# TYPE x_total counter\nx_total{le=} 1\n", "bad label"),
        ("# TYPE x_total counter\nx_total oops\n", "bad value"),
        ("# TYPE x_total counter\nx_total 1\nx_total 2\n", "dup series"),
        ("# TYPE x_total wat\nx_total 1\n", "bad type"),
        ('# TYPE h histogram\nh_bucket{le="+Inf"} 3\nh_sum 1\nh_count 2\n',
         "+Inf != count"),
        ('# TYPE x_total counter\nx_total{v="a\\qb"} 1\n', "bad escape"),
        ("# HELP x_total bad \\q escape\n# TYPE x_total counter\n"
         "x_total 1\n", "bad HELP escape"),
    ])
    def test_rejects_malformed(self, bad, why):
        assert validate_prometheus(bad) != [], why

    def test_fully_populated_serve_render_validates(self):
        """Every ServeMetrics instrument populated — including labeled
        families with hostile label values — renders valid 0.0.4."""
        m = ServeMetrics()
        m.requests.labels(endpoint="predict", outcome="ok").inc(2)
        m.requests.labels(endpoint="stream", outcome="shed").inc()
        m.responses.inc()
        m.shed.inc()
        m.timeouts.inc()
        m.errors.inc()
        m.degraded_batches.inc()
        m.compile_hits.labels(bucket="64x96", iters="8", mode="batch",
                              tier="fp32").inc()
        m.compile_misses.labels(bucket="64x96", iters="8",
                                mode="stream", tier="bf16").inc()
        m.queue_depth.set(3)
        m.batch_size.observe(4)
        m.latency.observe(0.02)
        m.batch_latency.observe(0.01)
        m.stream_active.add(2)
        m.stream_warm_frames.inc()
        # Hostile label values: backslash, quote, newline must escape.
        m.stream_cold_frames.labels(reason='a\\b"c\nd').inc()
        m.stream_evicted.inc()
        m.stream_expired.inc()
        m.stream_frame_iters.observe(8)
        m.stream_frame_latency.observe(0.05)
        text = m.render()
        assert validate_prometheus(text) == []
        # The hostile value round-trips through the parser's escape rules.
        line = [l for l in text.splitlines()
                if l.startswith("stream_cold_frames_total{")][0]
        _, labels, v = parse_sample(line)
        assert v == 1.0
        assert dict(labels)["reason"] == 'a\\\\b\\"c\\nd'

    def test_family_label_validation(self):
        r = MetricsRegistry()
        fam = r.counter("f_total", "f", labels=("a", "b"))
        with pytest.raises(ValueError, match="labels"):
            fam.labels(a="1")
        with pytest.raises(ValueError, match="labels"):
            fam.labels(a="1", b="2", c="3")
        assert fam.labels(a="1", b="2") is fam.labels(b="2", a="1")

    def test_lint_flags_bad_names(self):
        r = MetricsRegistry()
        r.counter("requests", "missing suffix")
        r.gauge("depth_total", "total on a gauge")
        r.histogram("req_latency", "time histogram without unit")
        r.counter("ok_total", "")
        errs = "\n".join(lint_registry(r.entries()))
        assert "requests: counter names" in errs
        assert "depth_total: _total suffix" in errs
        assert "req_latency: time histogram" in errs
        assert "ok_total: empty HELP" in errs

    def test_repo_bundles_pass_check_metrics(self):
        """scripts/check_metrics.py is the tier-1 gate: serve + train
        bundles coexist on one registry, lint-clean, render-valid."""
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from scripts.check_metrics import check

        assert check() == []


# ------------------------------------------------------------ scrape parser

class TestParseText:
    def test_structured_lookups(self):
        scrape = parse_text(GOOD)
        assert "x_total" in scrape and "nope_total" not in scrape
        assert scrape["x_total"].kind == "counter"
        assert scrape["x_total"].help == "a counter"
        assert scrape.value("x_total", endpoint="predict",
                            outcome="ok") == 3.0
        # Label order never matters; absent series/metrics read as 0.
        assert scrape.value("x_total", outcome="ok",
                            endpoint="predict") == 3.0
        assert scrape.value("x_total", outcome="shed",
                            endpoint="predict") == 0.0
        assert scrape.value("nope_total") == 0.0
        assert scrape.get("nope_total") is None

    def test_total_sums_across_label_sets(self):
        text = ("# TYPE r_total counter\n"
                'r_total{tier="fast"} 2\n'
                'r_total{tier="certified"} 5\n')
        assert parse_text(text).total("r_total") == 7.0
        assert parse_text(text).total("absent_total") == 0.0

    def test_histogram_series_group_under_base(self):
        scrape = parse_text(GOOD)
        h = scrape["h_seconds"]
        assert h.kind == "histogram"
        assert h.value("h_seconds_bucket", le="0.1") == 1.0
        assert h.value("h_seconds_bucket", le="+Inf") == 2.0
        assert h.value("h_seconds_sum") == 0.5
        assert h.value("h_seconds_count") == 2.0
        assert len(h.series("h_seconds_bucket")) == 2
        # _bucket/_sum/_count never surface as metrics of their own.
        assert "h_seconds_bucket" not in scrape

    def test_delta_between_scrapes(self):
        before = parse_text("# TYPE s_total counter\ns_total 3\n")
        after = parse_text("# TYPE s_total counter\ns_total 11\n")
        assert after.delta(before, "s_total") == 8.0

    def test_help_after_type_is_backfilled(self):
        text = ("# TYPE late_total counter\n"
                "late_total 1\n"
                "# HELP late_total documented below its samples\n")
        assert parse_text(text)["late_total"].help == \
            "documented below its samples"

    def test_rejects_invalid_exposition(self):
        with pytest.raises(ValueError, match="malformed exposition"):
            parse_text("x_total 1\n")       # sample without TYPE
        with pytest.raises(ValueError, match="malformed exposition"):
            parse_text("# TYPE x_total counter\nx_total oops\n")


# --------------------------------------------------------- bounded Gauge

class TestBoundedInstruments:
    def test_gauge_concurrent_add_loses_nothing(self):
        m = ServeMetrics()

        def bump():
            for _ in range(1000):
                m.stream_active.add(1)
                m.stream_active.add(-1)

        ts = [threading.Thread(target=bump) for _ in range(4)]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        assert m.stream_active.value == 0.0


# --------------------------------------------------------- logger satellite

class TestLoggerJsonl:
    def test_write_scalar_survives_without_tensorboard(self, tmp_path,
                                                       monkeypatch):
        from raftstereo_tpu.train import logger as logger_mod

        monkeypatch.setattr(logger_mod, "_make_tb_writer", lambda d: None)
        log = logger_mod.Logger(log_dir=str(tmp_path),
                                jsonl_path=str(tmp_path / "m.jsonl"))
        log.write_scalar("live_loss", 1.5, step=3)
        log.write_scalar("lr", 2e-4, step=3)
        log.close()
        records = [json.loads(l) for l in
                   (tmp_path / "m.jsonl").read_text().splitlines()]
        assert {"step": 3, "live_loss": 1.5} in records
        assert any(r.get("lr") == 2e-4 for r in records)


# ------------------------------------------------------- telemetry exporter

class TestTelemetryServer:
    def test_endpoints(self):
        from raftstereo_tpu.train.telemetry import TrainMetrics

        tm = TrainMetrics()
        tm.observe_step(step_s=0.1, data_s=0.05)
        tm.observe_health({"data_samples_retried": 2.0,
                           "watchdog_slow": 1.0})
        tracer = Tracer()
        tracer.record("step", 0.0, 0.1, tracer.new_trace_id(),
                      attrs={"step": 1})
        srv = TelemetryServer(tm.registry, tracer,
                              vars_fn=lambda: {"config": {"name": "x"}},
                              host="127.0.0.1").start()
        try:
            client = ServeClient("127.0.0.1", srv.port)
            text = client.metrics_text()
            assert validate_prometheus(text) == []
            assert "train_steps_total 1" in text
            assert "data_samples_retried 2" in text
            assert "train_watchdog_slow_total 1" in text
            trace = client.debug_trace(last=10)
            names = [e["name"] for e in trace["traceEvents"]
                     if e["ph"] == "X"]
            assert names == ["step"]
            threads = client.debug_threads()
            assert "telemetry-http" in threads or "MainThread" in threads
            dvars = client.debug_vars()
            assert dvars["config"]["name"] == "x"
            assert dvars["build"]["pid"] > 0
            with pytest.raises(ServeError) as ei:
                client._get_json("/nope")
            assert ei.value.status == 404
            client.close()
        finally:
            srv.close()

    def test_data_wait_fraction_math(self):
        from raftstereo_tpu.train.telemetry import TrainMetrics

        tm = TrainMetrics()
        tm.observe_step(step_s=0.3, data_s=0.1)
        tm.observe_step(step_s=0.3, data_s=0.1)
        assert tm.data_wait_frac.value == pytest.approx(0.25)
        assert tm.steps.value == 2
        assert tm.steps_per_sec.value > 0

    def test_dump_threads_sees_this_thread(self):
        out = dump_threads()
        assert "test_dump_threads_sees_this_thread" in out


# ----------------------------------------------------------- stream spans

class _StubStreamEngine:
    """StreamRunner contract stand-in: no model, no compiles."""

    low = (16, 24)

    def bucket_of(self, shape):
        return (64, 96)

    def low_hw(self, hw):
        return self.low

    def infer_stream_batch(self, pairs, iters, inits, mode=None):
        return [(np.zeros(p[0].shape[:2], np.float32),
                 np.zeros(self.low, np.float32), False) for p in pairs]


class TestStreamSpans:
    def test_warp_forward_spans_and_cold_reasons(self):
        from raftstereo_tpu.stream.runner import StreamRunner

        cfg = StreamConfig(ladder=(8, 4), session_limit=4)
        metrics = ServeMetrics()
        tracer = Tracer()
        runner = StreamRunner(_StubStreamEngine(), cfg, metrics,
                              tracer=tracer)
        img = np.zeros((60, 90, 3), np.float32)
        r0 = runner.step("cam", 0, img, img, trace_id="rid-0")
        r1 = runner.step("cam", 1, img, img, trace_id="rid-1")
        assert not r0.warm and r1.warm
        names0 = [s.name for s in tracer.spans(trace_id="rid-0")]
        names1 = [s.name for s in tracer.spans(trace_id="rid-1")]
        assert names0 == ["forward"]            # cold: no warp
        assert names1 == ["warp", "forward"]    # warm: warp then forward
        # Cold reasons land as labels; out-of-order re-runs cold.
        runner.step("cam", 7, img, img)
        text = metrics.render()
        assert 'stream_cold_frames_total{reason="new"} 1' in text
        assert 'stream_cold_frames_total{reason="out_of_order"} 1' in text


# ------------------------------------------------------------------ end2end

@pytest.fixture(scope="module")
def obs_server():
    """Tiny real server, warmed (one executable: iters == degraded_iters),
    shared by the e2e tests so the XLA compile is paid once."""
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), (64, 96))
    cfg = ServeConfig(port=0, bucket_multiple=32, buckets=((60, 90),),
                      warmup=True, max_batch_size=2, max_wait_ms=5.0,
                      queue_limit=16, request_timeout_ms=60000.0, iters=3,
                      degraded_iters=3, degrade_queue_depth=16,
                      trace_buffer=512)
    metrics = ServeMetrics()
    server = build_server(model, variables, cfg, metrics)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server
    server.close()
    thread.join(10)


def _img(h=60, w=90, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


class TestEndToEnd:
    def test_request_trace_roundtrip(self, obs_server, retrace_guard):
        """Acceptance gate: X-Request-Id on /predict; /debug/trace returns
        valid Chrome trace-event JSON containing that id with queue-wait,
        dispatch and host-fetch spans whose durations sum to <= the
        observed request latency; /metrics passes the format validator;
        span overhead < 2% of request latency; zero new XLA compiles —
        enforced by the shared retrace guard (budget 0: warmup paid the
        only model compile) on top of the engine-level cache-key check."""
        server = obs_server
        compiled_before = set(server.engine.compiled_keys)
        client = ServeClient("127.0.0.1", server.port, timeout=120)
        with retrace_guard(0, what="tracing adds zero XLA compiles "
                                   "(PR 5 invariant)",
                           min_duration_s=0.5):
            t0 = time.perf_counter()
            disp, meta = client.predict(_img(), _img(seed=1))
            observed_latency = time.perf_counter() - t0
            assert disp.shape == (60, 90)
            rid = meta["request_id"]
            assert rid  # header + meta both carry it

            trace = client.debug_trace()
            events = [e for e in trace["traceEvents"]
                      if e["ph"] == "X"
                      and e["args"].get("trace_id") == rid]
            by_name = {e["name"]: e for e in events}
            for required in ("admission", "queue_wait", "dispatch",
                             "host_fetch", "request"):
                assert required in by_name, sorted(by_name)
            core = ["queue_wait", "dispatch", "host_fetch"]
            total_s = sum(by_name[n]["dur"] for n in core) / 1e6
            assert 0 < total_s <= observed_latency
            # Phases are consistent: the engine phases sit inside the
            # server's request window.
            assert by_name["request"]["dur"] / 1e6 <= observed_latency

            # /metrics: parse_text both validates the exposition and
            # replaces the old hand-regexed substring assertions with
            # structured lookups.
            scrape = parse_text(client.metrics_text())
            assert scrape.value("serve_requests_total",
                                endpoint="predict", outcome="ok") >= 1
            hits = scrape["serve_compile_cache_hits_total"]
            assert any(dict(litems).get("bucket") == "64x96"
                       and dict(litems).get("iters") == "3"
                       for litems, v in hits.series() if v > 0)

            # Bad request -> 400 with its own request id, counted by
            # outcome.
            with pytest.raises(ServeError) as ei:
                client.predict(_img(), _img(70, 100))
            assert ei.value.request_id  # error replies keep their trace key
            after = parse_text(client.metrics_text())
            assert after.value("serve_requests_total", endpoint="predict",
                               outcome="bad_request") == 1
            assert after.delta(scrape, "serve_requests_total",
                               endpoint="predict", outcome="bad_request") == 1

        # The engine-level view of the same invariant: warmup paid the
        # only compiles (one a row count), traffic added no cache keys.
        assert set(server.engine.compiled_keys) == compiled_before
        assert server.metrics.compile_misses.value \
            == len(compiled_before) == 2

        # Overhead: per-span record cost x spans-per-request under 2% of
        # the observed latency (measured, not assumed).
        bench_tracer = Tracer(capacity=256)
        bid = bench_tracer.new_trace_id()
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            bench_tracer.record("bench", 0.0, 1.0, bid,
                                attrs={"bucket": "64x96"})
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 200e-6  # sanity: recording is microseconds
        spans_per_request = len(events)
        assert spans_per_request * per_span < 0.02 * observed_latency
        client.close()

    def test_spans_and_counter_say_the_row_count_and_no_row_is_zero(
            self, obs_server):
        """``rows`` rides on the ``dispatch`` and ``pad_bucket`` spans and
        on the bucket's ``compile`` span, ``serve_batch_rows_total``
        counts the dispatches by it, and no plain dispatch holds a row
        nobody sent: ``rows == batch_size`` and, for bucket-sized pairs,
        ``real_px == bucket_px``."""
        server = obs_server
        assert server.engine.row_counts == (1, 2)
        counted = {lv[0]: c.value
                   for lv, c in server.metrics.batch_rows.series()}
        pair = (_img(64, 96, 5), _img(64, 96, 6))  # bucket-sized: 64x96
        rids = []

        def send():
            client = ServeClient("127.0.0.1", server.port, timeout=120)
            rids.append(client.predict(*pair)[1]["request_id"])
            client.close()

        send()  # alone: one row
        threads = [threading.Thread(target=send) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert len(rids) == 5
        spans = server.tracer.spans()
        mine = [s for s in spans if s.trace_id in rids]
        dispatches = [s for s in mine if s.name == "dispatch"]
        pads = [s for s in mine if s.name == "pad_bucket"]
        assert len(dispatches) == len(pads) == 5
        for s in dispatches:
            assert s.attrs["rows"] == s.attrs["batch_size"]
            assert s.attrs["rows"] in (1, 2)
        for s in pads:
            rows = s.attrs["rows"]
            assert s.attrs == {"rows": rows, "real_px": rows * 64 * 96,
                               "bucket_px": rows * 64 * 96}
        first = [s for s in dispatches if s.trace_id == rids[0]]
        assert first[0].attrs["rows"] == 1
        # once a dispatch, under the batch's own trace, with the same facts
        batch_pads = [s for s in spans if s.name == "pad_bucket"
                      and (s.trace_id or "").startswith("batch:")
                      and set(s.attrs["request_ids"]) & set(rids)]
        assert sum(s.attrs["rows"] for s in batch_pads) == 5
        for s in batch_pads:
            assert s.attrs["rows"] == len(s.attrs["request_ids"])
            assert s.attrs["real_px"] == s.attrs["bucket_px"]
        after = {lv[0]: c.value
                 for lv, c in server.metrics.batch_rows.series()}
        for rows in (1, 2):
            assert after.get(str(rows), 0) - counted.get(str(rows), 0) \
                == sum(s.attrs["rows"] == rows for s in batch_pads)
        # the programs' own compile spans name their row count
        compiled = {s.attrs["rows"] for s in spans if s.name == "compile"
                    and s.attrs.get("kind") == "bucket"}
        assert compiled == {1, 2}
        programs = server.engine.compiled_programs
        assert sorted(p["rows"] for p in programs.values()) == [1, 2]

    def test_debug_vars_threads_profile(self, obs_server):
        server = obs_server
        client = ServeClient("127.0.0.1", server.port, timeout=120)
        dvars = client.debug_vars()
        assert dvars["config"]["max_batch_size"] == 2
        assert dvars["config"]["iters"] == 3
        assert dvars["trace"]["capacity"] == 512
        assert dvars["build"]["pid"] > 0
        threads = client.debug_threads()
        assert "serve-batcher" in threads  # the deadlock-debug surface

        # On-demand profiler: second capture while one runs -> 409;
        # after it finishes a new one is accepted.
        info = client.debug_profile(seconds=0.4)
        assert info["seconds"] == 0.4
        with pytest.raises(ServeError) as ei:
            client.debug_profile(seconds=0.4)
        assert ei.value.status == 409
        deadline = time.time() + 10
        while server.profiler.running and time.time() < deadline:
            time.sleep(0.05)
        assert not server.profiler.running
        with pytest.raises(ServeError) as ei:
            client.debug_profile(seconds=0)  # out of bounds -> 400
        assert ei.value.status == 400
        client.close()

    def test_trace_query_filters(self, obs_server):
        server = obs_server
        client = ServeClient("127.0.0.1", server.port, timeout=120)
        _, meta = client.predict(_img(), _img(seed=1))
        rid = meta["request_id"]
        only = client.debug_trace(trace_id=rid)
        ids = {e["args"]["trace_id"] for e in only["traceEvents"]
               if e["ph"] == "X"}
        assert ids == {rid}
        last2 = client.debug_trace(last=2)
        assert len([e for e in last2["traceEvents"]
                    if e["ph"] == "X"]) == 2
        client.close()

    def test_chrome_export_helper_matches_endpoint(self, obs_server):
        spans = obs_server.tracer.spans(last=5)
        doc = to_chrome_trace(spans)
        assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 5


# ------------------------------------------- phases, clocks, light captures

def _profile_events(log_dir):
    """{name: [(start_ns, duration_ns, stats dict)]} of a capture's host
    plane, read with what JAX brings."""
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


def _without_thread_ms(attrs):
    return {k: v for k, v in attrs.items()
            if k not in ("cpu_ms", "runq_ms")}


class TestPhase:
    def test_phase_is_one_ring_span_and_one_profiler_event(self, tmp_path):
        """Inside a capture ``phase()`` leaves one span in the ring and one
        host event of the same name in the profiler's own trace, and the
        ``obs.clock`` pair maps the span's start onto the event's within
        1 ms."""
        from raftstereo_tpu.utils.profiling import (_start_capture,
                                                    _stop_capture)

        tracer = Tracer(capacity=8)
        _start_capture(str(tmp_path))
        try:
            with tracer.phase("unit_phase", trace_id="batch:7", rows=3):
                time.sleep(0.01)
        finally:
            _stop_capture()
        (span,) = tracer.spans()
        assert span.name == "unit_phase" and span.trace_id == "batch:7"
        assert _without_thread_ms(span.attrs) == {"rows": 3}
        # a sleeping phase: next to no CPU time
        assert 0.0 <= span.attrs["cpu_ms"] < 0.5 * span.duration_s * 1e3
        events = _profile_events(str(tmp_path))
        ((start_ns, dur_ns, stats),) = events["unit_phase"]
        assert stats["rows"] == 3
        assert dur_ns * 1e-9 == pytest.approx(span.duration_s, abs=1e-3)
        clocks = sorted(events["obs.clock"])
        assert len(clocks) == 2     # capture start and capture stop
        for c_start, _, c in clocks:
            on_trace = c_start + (span.t0 * 1e9 - c["perf_counter_ns"])
            assert abs(on_trace - start_ns) < 1e6
        # unix_ns is the same instant as the ring's Chrome export writes it
        ts_us = to_chrome_trace([span])["traceEvents"][0]["ts"]
        c_start, _, c = clocks[0]
        assert abs(c_start + (ts_us * 1e3 - c["unix_ns"]) - start_ns) < 1e6

    def test_phase_outside_a_capture_formats_nothing_and_is_cheap(self):
        class Hostile:
            def __repr__(self):
                raise AssertionError("formatted with no capture running")
            __str__ = __repr__

        from raftstereo_tpu.obs.trace import timed_phase

        tracer = Tracer(capacity=64)
        h = Hostile()
        with tracer.phase("p", trace_id="t", obj=h) as live:
            live.attrs["late"] = 1
        with timed_phase("q", obj=h) as ph:
            pass
        assert ph.t1 >= ph.t0 > 0 and ph.window == (ph.t0, ph.t1)
        (span,) = tracer.spans()
        assert _without_thread_ms(span.attrs) == {"obj": h, "late": 1}
        n = 5000
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.phase("bench", trace_id="t", batch_size=8,
                              bucket="576x960"):
                pass
        per_phase = (time.perf_counter() - t0) / n
        assert per_phase < 200e-6
        # A dispatch adds sixteen phases (three live in the batcher, seven
        # recorded, six timed in the engine) and each of its binary
        # requests eight (wire_decode and reply, three children each):
        # under the contract's 2 % of even a 250 ms dispatch of eight.
        assert (16 + 8 * 8) * per_phase < 0.02 * 0.25

    def test_cpu_ms_is_the_wall_when_spinning_and_none_when_asleep(self):
        """``cpu_ms`` never exceeds the phase's wall time: it is about
        the wall for a phase that spins and about 0 for one that sleeps,
        in ``Tracer.phase`` and ``timed_phase`` alike."""
        from raftstereo_tpu.obs.trace import timed_phase

        def spin(s):        # s seconds of this thread's CPU
            t_end = time.thread_time() + s
            while time.thread_time() < t_end:
                pass

        tracer = Tracer(capacity=8)
        with tracer.phase("spin", trace_id="t"):
            spin(0.05)
        with tracer.phase("sleep", trace_id="t"):
            time.sleep(0.05)
        with timed_phase("spin") as ph:
            spin(0.05)
        spun, slept = tracer.spans()
        for wall_s, cpu_ms in ((spun.duration_s, spun.attrs["cpu_ms"]),
                               (ph.t1 - ph.t0, ph.thread_ms["cpu_ms"])):
            # a loaded host may keep a spinning thread off its core a
            # while: the wall then outgrows the CPU time, never under it
            assert 50.0 <= cpu_ms <= wall_s * 1e3
            assert cpu_ms > 0.25 * wall_s * 1e3
        assert 0.0 <= slept.attrs["cpu_ms"] < 0.1 * slept.duration_s * 1e3
        # a plain span and an after-the-fact record time nothing
        with tracer.span("plain", trace_id="t"):
            pass
        tracer.record("window", 0.0, 1.0, "t")
        assert all("cpu_ms" not in s.attrs for s in tracer.spans()[2:])

    def test_runq_ms_where_schedstat_reads_and_absent_where_not(
            self, monkeypatch):
        """``runq_ms`` is on a phase where ``/proc/thread-self/schedstat``
        can be read (Linux), between 0 and the wall; where it cannot be
        read the phase leaves it out and raises nothing."""
        from raftstereo_tpu.obs import trace

        def one_phase(out):
            tracer = Tracer(capacity=2)
            with tracer.phase("p", trace_id="t"):
                time.sleep(0.002)
            out.append(tracer.spans()[0])

        def in_new_thread():        # the file is opened once per thread
            out = []
            t = threading.Thread(target=one_phase, args=(out,))
            t.start()
            t.join(10)
            return out[0]

        span = in_new_thread()
        if os.path.exists("/proc/thread-self/schedstat"):
            assert 0.0 <= span.attrs["runq_ms"] <= span.duration_s * 1e3
        monkeypatch.setattr(trace, "_SCHEDSTAT", "/nonexistent/schedstat")
        span = in_new_thread()
        assert "runq_ms" not in span.attrs and "cpu_ms" in span.attrs

    def test_unsampled_span_guard_still_holds_for_record(self):
        tracer = Tracer(capacity=4)
        assert tracer.record("x", 0.0, 1.0, None) == ""
        assert not hasattr(Tracer, "_new_span_id")   # the alias is gone
        assert len(Tracer.new_span_id()) == 16


def _stage_engine(cfg, hold=None):
    """The real engine's two halves over a stand-in program (the left
    image's first channel; no model), so every phase window is the
    engine's own.  ``hold`` (an Event) keeps every ``finish_batch`` from
    starting until it is set."""
    from raftstereo_tpu.serve.engine import BatchEngine

    eng = BatchEngine(None, {}, cfg)
    eng._fn = lambda iters, mode: lambda v, a, b: (None, a[..., :1])
    if hold is not None:
        finish = eng.finish_batch

        def held(launched):
            assert hold.wait(10.0)
            return finish(launched)

        eng.finish_batch = held
    return eng


def _holes(spans):
    return sum(max(b.t0 - a.t1, 0.0) for a, b in zip(spans, spans[1:]))


class TestWorkerPhases:
    def _serve(self, n_requests, max_wait_ms):
        from raftstereo_tpu.serve.batcher import DynamicBatcher

        tracer = Tracer(capacity=256)
        cfg = ServeConfig(max_batch_size=2, max_wait_ms=max_wait_ms,
                          queue_limit=8)
        img = np.zeros((60, 90, 3), np.float32)
        with DynamicBatcher(_stage_engine(cfg), cfg, tracer=tracer) as b:
            futs = [b.submit(img, img, trace_id=f"req{i}")
                    for i in range(n_requests)]
            for f in futs:
                f.result(10)
        return tracer.spans()

    def test_phases_tile_each_thread_once_per_dispatch(self):
        """One dispatch of two rows: the launcher's phases ``batch_form
        -> pad_bucket -> launch`` tile its thread, the finisher's
        ``device_wait -> host_fetch -> reply_handoff`` tile its own,
        each recorded ONCE under the batch's trace."""
        spans = self._serve(2, max_wait_ms=2000.0)
        batch = [s for s in spans if s.trace_id.startswith("batch:")
                 and s.name != "queue_empty"]
        btid = {s.trace_id for s in batch if s.name == "launch"}
        assert len(btid) == 1                   # one dispatch of two rows
        by = {}
        for s in batch:
            if s.trace_id in btid:
                assert s.name not in by, s.name  # each ONCE
                by[s.name] = s
        launcher = [by[n] for n in ("batch_form", "pad_bucket", "launch")]
        finisher = [by[n] for n in ("device_wait", "host_fetch",
                                    "reply_handoff")]
        assert sorted(by) == sorted(
            [s.name for s in launcher + finisher]
            + ["device_queued", "stage_copy", "h2d_put"])
        for thread in (launcher, finisher):
            assert _holes(thread) < 1e-3, [s.name for s in thread]
            assert all(b.t0 >= a.t0 for a, b in zip(thread, thread[1:]))
        assert finisher[0].t0 >= launcher[-1].t1  # handed over, in order
        # nothing was in flight: the device was free at launch's end
        assert by["device_queued"].t0 == by["device_queued"].t1 \
            == by["launch"].t1
        assert by["batch_form"].attrs["closed_by"] == "full"
        assert by["batch_form"].attrs["batch_size"] == 2
        assert by["batch_form"].attrs["ahead"] is False
        assert by["launch"].attrs["request_ids"] == ["req0", "req1"]
        assert by["batch_form"].attrs["bucket"] == "64x128"
        assert by["launch"].attrs["ahead"] is False
        # the per-request copies: one set a request, and launch +
        # device_wait are what device_compute spans
        for rid in ("req0", "req1"):
            names = sorted(s.name for s in spans if s.trace_id == rid)
            assert names == ["device_compute", "device_queued", "dispatch",
                             "host_fetch", "launch", "pad_bucket",
                             "queue_wait"]
        dc = next(s for s in spans if s.name == "device_compute")
        assert dc.t0 == by["launch"].t0 and dc.t1 == by["device_wait"].t1

    def test_staging_parts_tile_pad_bucket_once_per_dispatch(self):
        """``stage_copy`` (the host copies) and ``h2d_put`` (the
        transfers) tile ``pad_bucket``, once a dispatch under the batch's
        trace, with the row count; every phase the engine and the
        launcher timed carries its thread's CPU time, within its wall."""
        spans = self._serve(3, max_wait_ms=2000.0)
        batch = [s for s in spans if s.trace_id.startswith("batch:")]
        pads = [s for s in batch if s.name == "pad_bucket"]
        assert sorted(s.attrs["rows"] for s in pads) == [1, 2]
        for pad in pads:
            parts = sorted((s for s in batch if s.trace_id == pad.trace_id
                            and s.name in ("stage_copy", "h2d_put")),
                           key=lambda s: s.t0)
            assert [s.name for s in parts] == ["stage_copy", "h2d_put"]
            assert all(s.attrs["rows"] == pad.attrs["rows"] for s in parts)
            assert parts[0].t0 >= pad.t0 and parts[-1].t1 <= pad.t1
            holes = (parts[0].t0 - pad.t0) + _holes(parts) \
                + (pad.t1 - parts[-1].t1)
            assert holes < 1e-3
        # no request trace holds the two parts: they are the batch's
        assert not [s for s in spans if not s.trace_id.startswith("batch:")
                    and s.name in ("stage_copy", "h2d_put")]
        timed = ("batch_form", "pad_bucket", "stage_copy", "h2d_put",
                 "launch", "host_fetch", "reply_handoff")
        for s in batch:
            if s.name in timed:
                assert 0.0 <= s.attrs["cpu_ms"] <= s.duration_s * 1e3, s
            if s.name == "device_queued":     # a window no phase timed
                assert "cpu_ms" not in s.attrs

    def test_a_request_launched_ahead_sums_and_fills(self):
        """req0 rides alone and is held in ``finish``; req1 and req2
        fill a batch behind it and are launched ahead.  For them too
        ``queue_wait + dispatch + host_fetch`` is the server-side latency
        (enqueue to the result on the host), and ``pad_bucket + launch +
        device_queued + device_compute`` fill ``dispatch``: the device's
        time for THIS dispatch starts where the one before it ended."""
        from raftstereo_tpu.serve.batcher import DynamicBatcher

        tracer, hold = Tracer(capacity=256), threading.Event()
        cfg = ServeConfig(max_batch_size=2, max_wait_ms=1.0, queue_limit=8)
        img = np.zeros((60, 90, 3), np.float32)
        b = DynamicBatcher(_stage_engine(cfg, hold), cfg,
                           tracer=tracer).start()
        try:
            futs = [b.submit(img, img, trace_id="req0")]
            deadline = time.time() + 10
            while not b._flying and time.time() < deadline:
                time.sleep(0.002)
            t_sub = time.perf_counter()
            futs += [b.submit(img, img, trace_id=f"req{i}") for i in (1, 2)]
            while len(b._flying) < 2 and time.time() < deadline:
                time.sleep(0.002)
            assert len(b._flying) == 2 and not futs[0].done()
            time.sleep(0.01)  # the batch lies queued behind req0 a while
            hold.set()
            for f in futs:
                f.result(10)
        finally:
            hold.set()
            b.stop()
        spans = tracer.spans()
        first = {s.name: s for s in spans if s.trace_id == "req0"}
        for rid in ("req1", "req2"):
            by = {s.name: s for s in spans if s.trace_id == rid}
            assert by["dispatch"].attrs["ahead"] is True
            core = [by[n] for n in ("queue_wait", "dispatch", "host_fetch")]
            assert _holes(core) == 0.0 and core[0].t0 >= t_sub
            assert sum(s.duration_s for s in core) == pytest.approx(
                core[-1].t1 - core[0].t0, abs=1e-9)
            parts = [by[n] for n in ("pad_bucket", "launch",
                                     "device_queued", "device_compute")]
            assert all(s.parent_id == by["dispatch"].span_id
                       for s in parts)
            assert parts[0].t0 >= by["dispatch"].t0
            assert parts[-1].t1 == by["dispatch"].t1
            assert _holes(parts) < 1e-3
            assert all(b.t0 >= a.t1 for a, b in zip(parts, parts[1:]))
            # behind req0: queued until ITS device_compute ended
            assert by["device_queued"].duration_s >= 0.01
            assert by["device_queued"].t1 == by["device_compute"].t0 \
                == first["device_compute"].t1
        assert first["dispatch"].attrs["ahead"] is False
        assert first["device_queued"].duration_s == 0.0
        waits = sorted((s for s in spans if s.name == "device_wait"),
                       key=lambda s: s.t0)
        assert len(waits) == 2 and waits[1].t0 == waits[0].t1
        forms = sorted((s for s in spans if s.name == "batch_form"),
                       key=lambda s: s.t0)
        assert [s.attrs["closed_by"] for s in forms] == ["deadline",
                                                         "full_ahead"]

    def test_deadline_closes_a_short_batch_and_the_idle_wait_is_named(self):
        spans = self._serve(1, max_wait_ms=20.0)
        form = next(s for s in spans if s.name == "batch_form")
        assert form.attrs["closed_by"] == "deadline"
        assert form.attrs["batch_size"] == 1
        assert form.duration_s >= 0.015
        # before the first request the worker waited with nothing queued
        empty = [s for s in spans if s.name == "queue_empty"]
        assert empty and empty[0].trace_id == form.trace_id
        assert empty[0].t1 <= form.t0 + 1e-4
        # one clock read, the request's enqueue, ends one and starts the
        # other: the deadline counts from there
        assert empty[0].t1 == form.t0


class _AskedLock:
    """A lock or semaphore that says when someone first asked for it."""

    def __init__(self, lock):
        self.lock = lock
        self.asked = threading.Event()

    def acquire(self):
        self.asked.set()
        return self.lock.acquire()

    def release(self):
        self.lock.release()


def _predict_into(server, out):
    client = ServeClient("127.0.0.1", server.port, timeout=120)
    out["rid"] = client.predict(_img(), _img(seed=1))[1]["request_id"]
    client.close()


class TestRequestPhases:
    def test_request_contains_admission_contains_wire_decode(self,
                                                             obs_server):
        client = ServeClient("127.0.0.1", obs_server.port, timeout=120)
        _, meta = client.predict(_img(), _img(seed=1))
        client.close()
        rid = meta["request_id"]
        deadline = time.time() + 5      # `reply` closes after the write
        while time.time() < deadline:
            by = {s.name: s for s in obs_server.tracer.spans(trace_id=rid)}
            if "reply" in by:
                break
            time.sleep(0.01)
        req, adm, dec, reply = (by[n] for n in (
            "request", "admission", "wire_decode", "reply"))
        assert req.t0 <= adm.t0 <= dec.t0 and dec.t1 <= adm.t1 <= req.t1
        assert dec.parent_id == adm.span_id
        # `reply` starts inside the request (encode) and outlasts it (write)
        assert req.t0 < reply.t0 <= req.t1 <= reply.t1
        # the batch's own trace names this request
        launches = [s for s in obs_server.tracer.spans()
                    if s.name == "launch"
                    and rid in s.attrs.get("request_ids", ())]
        assert len(launches) == 1
        assert launches[0].trace_id.startswith("batch:")

    @staticmethod
    def _spans_of(server, rid, last="reply"):
        deadline = time.time() + 5      # `reply` closes after the write
        while time.time() < deadline:
            spans = server.tracer.spans(trace_id=rid)
            if any(s.name == last for s in spans):
                return spans
            time.sleep(0.01)
        raise AssertionError(f"no {last} span for {rid}")

    def test_binary_children_tile_wire_decode_and_reply(self, obs_server):
        """On a binary request ``decode_slot_wait -> body_read -> widen``
        tile ``wire_decode`` and ``reply_wait -> reply_encode ->
        reply_write`` tile ``reply``, holes under 1 ms in total; each
        child is a phase with its thread's times."""
        client = ServeClient("127.0.0.1", obs_server.port, timeout=120)
        _, meta = client.predict(_img(), _img(seed=1))
        client.close()
        spans = self._spans_of(obs_server, meta["request_id"])
        by = {s.name: s for s in spans}
        for parent, names in (
                ("wire_decode", ("decode_slot_wait", "body_read", "widen")),
                ("reply", ("reply_wait", "reply_encode", "reply_write"))):
            top = by[parent]
            kids = [by[n] for n in names]
            assert all(k.parent_id == top.span_id for k in kids)
            assert all(b.t0 >= a.t1 for a, b in zip(kids, kids[1:]))
            assert kids[0].t0 >= top.t0 and kids[-1].t1 <= top.t1
            holes = (kids[0].t0 - top.t0) + _holes(kids) \
                + (top.t1 - kids[-1].t1)
            assert holes < 1e-3, (parent, holes)
            for k in (top, *kids):
                assert 0.0 <= k.attrs["cpu_ms"] <= k.duration_s * 1e3, k

    def test_reply_wait_reads_the_lock_held(self, obs_server):
        """With the reply lock held 50 ms after the handler asked for it,
        ``reply_wait`` reads at least that, and so does
        ``serve_host_wait_seconds_total{point="reply_lock"}``."""
        spy = _AskedLock(obs_server.reply_encode)
        obs_server.reply_encode = spy
        before = obs_server.metrics.host_wait.labels(
            point="reply_lock").value
        out = {}
        try:
            with spy.lock:
                t = threading.Thread(target=_predict_into,
                                     args=(obs_server, out))
                t.start()
                assert spy.asked.wait(60)
                time.sleep(0.05)
            t.join(60)
        finally:
            obs_server.reply_encode = spy.lock
        spans = self._spans_of(obs_server, out["rid"])
        (wait,) = [s for s in spans if s.name == "reply_wait"]
        assert wait.duration_s >= 0.05
        after = obs_server.metrics.host_wait.labels(point="reply_lock").value
        assert after - before >= 0.05

    def test_decode_slot_wait_grows_when_every_slot_is_taken(
            self, obs_server):
        """A request that finds a decode slot free waits next to nothing;
        with every slot taken its ``decode_slot_wait`` holds the time
        until one is given back, and ``serve_host_wait_seconds_total``
        counts both points and renders validator-clean."""
        free = {}
        _predict_into(obs_server, free)
        (quick,) = [s for s in self._spans_of(obs_server, free["rid"])
                    if s.name == "decode_slot_wait"]
        spy = _AskedLock(obs_server.decode_slots)
        obs_server.decode_slots = spy
        slots = max(4, obs_server.config.max_batch_size)
        out = {}
        try:
            for _ in range(slots):
                spy.lock.acquire()
            t = threading.Thread(target=_predict_into,
                                 args=(obs_server, out))
            t.start()
            assert spy.asked.wait(60)
            time.sleep(0.05)
            for _ in range(slots):
                spy.lock.release()
            t.join(60)
        finally:
            obs_server.decode_slots = spy.lock
        (slow,) = [s for s in self._spans_of(obs_server, out["rid"])
                   if s.name == "decode_slot_wait"]
        assert slow.duration_s >= 0.05 > quick.duration_s
        scrape = parse_text(obs_server.metrics.render())
        for point in ("decode_slot", "reply_lock"):
            assert scrape.value("serve_host_wait_seconds_total",
                                point=point) >= 0.0
        assert scrape.value("serve_host_wait_seconds_total",
                            point="decode_slot") >= 0.05
        assert validate_prometheus(obs_server.metrics.render()) == []
        assert lint_registry(obs_server.metrics.registry.entries()) == []

    def test_reply_closes_when_the_client_hangs_up(self, obs_server):
        from raftstereo_tpu.serve.server import _Handler

        handler = _Handler.__new__(_Handler)    # no socket: _send is ours
        handler.server = obs_server
        handler._wire_ctx = None
        handler._trace = ("hangup-rid", None)

        def hang_up(*a, **k):
            raise BrokenPipeError("client went away")

        handler._send = hang_up
        with pytest.raises(BrokenPipeError):
            handler._finish_ok(obs_server, np.zeros((4, 4), np.float32),
                               {"iters": 3}, "predict", "hangup-rid",
                               time.perf_counter())
        names = [s.name for s in
                 obs_server.tracer.spans(trace_id="hangup-rid")]
        assert sorted(names) == ["reply", "request"]


class TestLightCapture:
    @pytest.mark.parametrize("which", ["on_demand", "step"])
    def test_captures_pass_the_light_options(self, which, tmp_path,
                                             monkeypatch):
        from raftstereo_tpu.utils import profiling

        seen = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda log_dir, **kw: seen.append((log_dir, kw)))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        if which == "on_demand":
            prof = profiling.OnDemandProfiler(log_dir=str(tmp_path))
            prof.start(0.05)
            with pytest.raises(profiling.ProfilerBusy):   # the 409
                prof.start(0.05)
            deadline = time.time() + 5
            while prof.running and time.time() < deadline:
                time.sleep(0.01)
            assert not prof.running
        else:
            prof = profiling.StepProfiler(str(tmp_path), start=1, stop=2)
            for i in range(3):
                with prof.step(i):
                    pass
        ((log_dir, kw),) = seen
        options = kw["profiler_options"]
        assert options.host_tracer_level == 1
        assert options.python_tracer_level == 0

    def test_every_start_trace_in_the_package_is_the_light_one(self):
        import subprocess

        out = subprocess.run(
            ["grep", "-rn", "jax.profiler.start_trace(", "raftstereo_tpu"],
            capture_output=True, text=True, cwd=REPO).stdout
        calls = [ln for ln in out.splitlines() if '"""' not in ln
                 and "``" not in ln]
        assert len(calls) == 1 and "profiler_options=options" in calls[0]


class TestCompileAndMemoryInstruments:
    def test_compile_counter_sees_every_program_and_staging_has_none(self):
        """``serve_xla_compiles_total`` counts every program the process
        builds — the eager ones too, which the engine's own hit/miss
        counters never see — and the engine's staging is not among them:
        a batch is staged on the host (row by row into one array, one
        transfer a side), whatever the row count, the occupancy or the
        pair's size (until PR 35 staging ran ~6 eager programs a row,
        and a new row count met them under traffic)."""
        from raftstereo_tpu.serve.engine import BatchEngine
        from raftstereo_tpu.serve.server import watch_xla_compiles

        metrics, tracer = ServeMetrics(), Tracer(capacity=64)
        # a bucket (64x160) no other test of this process stages at
        cfg = ServeConfig(max_batch_size=2, bucket_multiple=32,
                          buckets=((40, 136),))
        engine = BatchEngine(None, {}, cfg, metrics)
        pair = (np.ones((40, 136, 3), np.float32),) * 2
        unwatch = watch_xla_compiles(metrics, tracer)
        try:
            engine._pad_pairs([pair], 1)        # one row
            engine._pad_pairs([pair], 2)        # two rows, one of them real
            engine._pad_pairs([pair, pair], 2)  # another occupancy
            engine._pad_pairs([(np.ones((33, 130, 3), np.float32),) * 2], 1)
            assert metrics.xla_compiles.value == 0
            # an eager program of a shape nobody has built: counted once
            jax.numpy.pad(jax.numpy.ones((3, 41, 137)), 1).block_until_ready()
            staged = metrics.xla_compiles.value
            assert staged >= 1
            jax.numpy.pad(jax.numpy.ones((3, 41, 137)), 1).block_until_ready()
            assert metrics.xla_compiles.value == staged
        finally:
            unwatch()
        jax.numpy.pad(jax.numpy.ones((3, 43, 139)), 1).block_until_ready()
        assert metrics.xla_compiles.value == staged     # unsubscribed
        compiles = [s for s in tracer.spans() if s.name == "compile"]
        assert len(compiles) == staged
        assert {s.attrs["kind"] for s in compiles} == {"compile"}
        assert 'serve_xla_compiles_total{kind="compile"}' in metrics.render()

    def test_memory_gauges_render_lint_and_show_in_debug_vars(self,
                                                              obs_server):
        from raftstereo_tpu.serve.metrics import (DEVICE_MEMORY_KEYS,
                                                  device_memory)
        from raftstereo_tpu.train.telemetry import TrainMetrics

        registry = MetricsRegistry()
        ServeMetrics(registry)
        TrainMetrics(registry)
        assert lint_registry(registry.entries()) == []
        scrape = parse_text(registry.render())      # validates as well
        for prefix in ("serve", "train"):
            for key in DEVICE_MEMORY_KEYS:
                assert scrape.value(f"{prefix}_device_{key}") >= 0
        assert set(device_memory()) == set(DEVICE_MEMORY_KEYS)
        client = ServeClient("127.0.0.1", obs_server.port, timeout=120)
        dvars = client.debug_vars()
        client.close()
        assert set(dvars["memory"]) == set(DEVICE_MEMORY_KEYS)
