"""Temporal warm-start streaming subsystem (raftstereo_tpu/stream,
docs/streaming.md).

Store/controller policy tests are pure host logic (no model cost); engine
and end-to-end tests share one tiny real model + engine so each stream
executable compiles once per module.  The acceptance gates:

* warm-start plumbing is a NO-OP at zero init — the stream executable with
  ``flow_init=zeros`` is bitwise-identical to the plain serving executable;
* on a synthetic sequence, warm-start at <= half the iterations per frame
  reaches final-frame EPE within 5% of the cold full-iteration baseline;
* a session driven over real HTTP is bitwise-identical to the offline
  ``cli/stream.py`` runner on the same frames (same bucket, same ladder) —
  the serve<->eval parity guarantee from PR 1, extended to streaming;
* the session store is bounded: LRU eviction and TTL expiry both fall back
  to a cold frame (never an error) and are visible in ``/metrics``.
"""

import json
import threading

import numpy as np
import pytest

import jax

from raftstereo_tpu.config import (RAFTStereoConfig, ServeConfig,
                                   StreamConfig)
from raftstereo_tpu.serve import ServeClient, ServeMetrics, build_server, \
    run_load
from raftstereo_tpu.stream import (AdaptiveIterController, SessionStore,
                                   StreamRunner, build_stream_engine,
                                   compare_warm_cold, run_sequence)



TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)

# Ladder/thresholds used by every device test in this module: warm frames
# run 6 = half the cold 12 iterations, and the thresholds are sized to the
# RANDOM-weights update magnitudes (several px/frame) so the controller
# neither cold-resets nor needs a trained checkpoint.
STREAM_CFG = StreamConfig(ladder=(12, 6), promote_threshold=2.0,
                          demote_threshold=0.1, cold_reset_threshold=50.0)


@pytest.fixture(scope="module")
def stream_model():
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), (64, 96))
    return model, variables


@pytest.fixture(scope="module")
def stream_engine(stream_model):
    """Offline engine under the serving shape policy (60x90 -> 64x96
    bucket); compiles lazily, shared across the module's device tests."""
    model, variables = stream_model
    return build_stream_engine(model, variables, (60, 90), STREAM_CFG,
                               max_batch_size=1, divis_by=32,
                               bucket_multiple=32)


def _img(h=60, w=90, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


def _sequence(n=6, hw=(60, 90), seed=0):
    from raftstereo_tpu.data.synthetic import StereoVideoSequence

    return StereoVideoSequence(n_frames=n, hw=hw, d0=4.0, drift=0.25,
                               pan=1, seed=seed)


# ------------------------------------------------------------------- config

class TestStreamConfig:
    def test_validation(self):
        with pytest.raises(AssertionError):
            StreamConfig(ladder=(8, 16))         # not descending
        with pytest.raises(AssertionError):
            StreamConfig(ladder=(32,))           # no warm level
        with pytest.raises(AssertionError, match="half"):
            StreamConfig(ladder=(8, 5))          # warm > cold/2
        with pytest.raises(AssertionError):
            StreamConfig(promote_threshold=0.1,  # disordered thresholds
                         demote_threshold=1.0)
        assert StreamConfig(ladder=[16, 8, 4]).ladder == (16, 8, 4)

    def test_arg_roundtrip(self):
        import argparse

        from raftstereo_tpu.config import add_stream_args, \
            stream_config_from_args

        p = argparse.ArgumentParser()
        add_stream_args(p)
        args = p.parse_args(["--stream_ladder", "16", "8", "4",
                             "--session_limit", "7",
                             "--session_ttl_s", "12.5"])
        cfg = stream_config_from_args(args)
        assert cfg.ladder == (16, 8, 4)
        assert cfg.session_limit == 7 and cfg.session_ttl_s == 12.5


# ------------------------------------------------------------ session store

class TestSessionStore:
    def test_lru_eviction_bounded_and_counted(self):
        m = ServeMetrics()
        store = SessionStore(limit=2, ttl_s=100.0, metrics=m)
        a, created = store.get_or_create("a")
        assert created and len(store) == 1
        store.get_or_create("b")
        store.get_or_create("a")           # touch: b is now LRU
        store.get_or_create("c")           # evicts b
        assert len(store) == 2
        assert m.stream_evicted.value == 1
        _, created = store.get_or_create("a")
        assert not created                 # a survived (was touched)
        _, created = store.get_or_create("b")
        assert created                     # b was the one evicted
        assert m.stream_active.value == 2

    def test_ttl_expiry_falls_back_to_fresh_session(self):
        clock = [0.0]
        m = ServeMetrics()
        store = SessionStore(limit=8, ttl_s=10.0, metrics=m,
                             now_fn=lambda: clock[0])
        s1, _ = store.get_or_create("s")
        s1.frame_idx = 3
        clock[0] = 5.0
        s2, created = store.get_or_create("s")
        assert s2 is s1 and not created    # within TTL
        clock[0] = 16.0
        s3, created = store.get_or_create("s")
        assert created and s3 is not s1    # expired -> fresh (cold), no
        assert s3.frame_idx == 0           # error surfaced anywhere
        assert m.stream_expired.value == 1

    def test_drop(self):
        store = SessionStore(limit=2, ttl_s=100.0)
        store.get_or_create("x")
        assert store.drop("x") and not store.drop("x")
        assert len(store) == 0

    def test_a_parent_builds_snapshot_imports_warm(self, stream_engine):
        """Only the schema keys this build knows gate a handoff: a
        snapshot whose schema still carries an older build's
        ``gru_backend`` installs warm (a rolling restart keeps its
        sessions), while a key that does matter still falls back cold."""
        ours = stream_engine.session_schema()
        assert ours == {"factor": 4, "input_mode": "passive"}
        src = SessionStore(limit=2, ttl_s=100.0)
        sess, _ = src.get_or_create("cam0")
        with sess.lock:
            sess.prev_disp_low = np.arange(6, dtype=np.float32).reshape(2, 3)
            sess.next_seq = sess.frame_idx = 2
        snap = src.export_state("cam0", schema=dict(ours,
                                                    gru_backend="xla"))
        assert snap["schema"]["gru_backend"] == "xla"
        dst = SessionStore(limit=2, ttl_s=100.0)
        assert dst.import_state(snap, schema=dict(ours, input_mode="sl")) \
            == "cold_schema"
        assert dst.import_state(snap, schema=ours) == "warm"
        got, created = dst.get_or_create("cam0")
        assert not created and got.next_seq == 2
        np.testing.assert_array_equal(got.prev_disp_low, sess.prev_disp_low)


# -------------------------------------------------------------- controller

class TestController:
    CFG = StreamConfig(ladder=(16, 8, 4, 2))  # default thresholds

    def test_ladder_walk(self):
        c = AdaptiveIterController(self.CFG)
        assert c.cold_iters == 16
        assert c.warm_iters(c.first_warm_level) == 8
        # Promote on large EMA, clamped at the first warm level (never 0).
        assert c.next_level(2, ema=2.0) == (1, False)
        assert c.next_level(1, ema=2.0) == (1, False)
        # Demote on small EMA, clamped at the last rung.
        assert c.next_level(1, ema=0.1) == (2, False)
        assert c.next_level(3, ema=0.1) == (3, False)
        # Hold between thresholds.
        assert c.next_level(2, ema=0.5) == (2, False)
        # Cold reset when the warp lost the scene.
        assert c.next_level(2, ema=5.0) == (1, True)

    def test_ema(self):
        c = AdaptiveIterController(self.CFG)
        assert c.update_ema(0.0, 1.0) == pytest.approx(0.4)   # decay 0.6
        assert c.update_ema(1.0, 1.0) == pytest.approx(1.0)


# ------------------------------------------------------------------ engine

class TestEngineStream:
    def test_zero_flow_init_bitwise_matches_plain(self, stream_engine):
        """The warm-start executable fed zeros must reproduce the plain
        serving executable BITWISE at a serving iteration count — the
        property that lets cold frames share the stream executables and
        anchors the serve<->stream parity chain (satellite of the
        single-iter shift test at tests/test_model.py)."""
        eng = stream_engine
        a, b = _img(seed=1), _img(seed=2)
        plain = eng.infer_batch([(a, b)], 12)[0]
        disp, low, _ = eng.infer_stream_batch([(a, b)], 12, [None])[0]
        np.testing.assert_array_equal(disp, plain)
        assert low.shape == eng.low_hw((64, 96)) == (16, 24)
        # Mixed plain/stream compile keys coexist (and stay sortable for
        # /healthz).
        keys = eng.compiled_keys
        assert (64, 96, 12, "batch", "r1", "passive", "fp32") in keys
        assert (64, 96, 12, "stream", "passive", "fp32") in keys
        sorted(keys)

    def test_flow_init_shape_validated(self, stream_engine):
        a = _img()
        with pytest.raises(AssertionError, match="flow_init"):
            stream_engine.infer_stream_batch(
                [(a, a)], 12, [np.zeros((4, 4), np.float32)])

    def test_nonzero_flow_init_changes_result(self, stream_engine):
        """flow_init actually reaches the scan (guards against the zeros
        substitution silently swallowing real warm starts)."""
        eng = stream_engine
        a, b = _img(seed=1), _img(seed=2)
        zero, _, _ = eng.infer_stream_batch([(a, b)], 12, [None])[0]
        init = np.full(eng.low_hw((64, 96)), -3.0, np.float32)
        warm, _, _ = eng.infer_stream_batch([(a, b)], 12, [init])[0]
        assert np.abs(zero - warm).max() > 1e-3


# -------------------------------------------------- warm-start acceptance

class TestWarmStartAcceptance:
    def test_half_iters_within_5pct_of_cold_baseline(self, stream_engine):
        """THE acceptance gate: on a temporally coherent synthetic
        sequence, warm-started frames at HALF the iterations reach a
        final-frame EPE within 5% of the cold full-iteration baseline
        (same engine, same executables)."""
        seq = _sequence(n=6)
        report = compare_warm_cold(stream_engine, seq.frames, STREAM_CFG)
        s = report["summary"]
        wr = report["warm"]
        # Every frame after the first warm-started, at half the iterations.
        assert [r["warm"] for r in wr] == [False] + [True] * 5
        assert all(r["iters"] == 6 for r in wr[1:])
        assert s["warm_mean_iters_after_first"] == 6 <= 12 / 2
        assert s["iters_saved_frac"] == pytest.approx(0.5)
        # Accuracy: within 5% of the cold baseline at the final frame.
        assert s["final_epe_ratio"] is not None
        assert s["final_epe_ratio"] <= 1.05, s
        # Temporal-consistency EPE is computed for both passes.
        assert s["warm_tc_epe"] is not None and s["cold_tc_epe"] is not None
        # The cold baseline reuses the ladder[0] executable: no compile
        # beyond the ladder, so compile-free latencies exist for both.
        assert s["cold_mean_latency_ms"] and s["warm_mean_latency_ms"]

    def test_cold_pass_frames_are_independent(self, stream_engine):
        """The baseline really is cold: frame t of the cold pass equals a
        fresh single-frame session on the same pair."""
        seq = _sequence(n=3)
        cold = run_sequence(stream_engine, seq.frames, STREAM_CFG,
                            warm=False)
        runner = StreamRunner(stream_engine, STREAM_CFG)
        res = runner.step("solo", 0, seq.frames[2][0], seq.frames[2][1])
        assert not res.warm and res.iters == 12
        np.testing.assert_array_equal(cold["preds"][2], res.disparity)


# ----------------------------------------------------------------- end2end

class TestEndToEnd:
    def test_http_session_parity_eviction_expiry_metrics(self, stream_model,
                                                         stream_engine,
                                                         retrace_guard):
        """One server, four acceptance checks: (1) a session over real
        HTTP is bitwise-identical to the offline runner on the same
        frames; (2) exceeding session_limit LRU-evicts and the evicted
        session's next frame is COLD, not an error; (3) an expired session
        falls back to a cold frame; (4) sequence-replay load-gen works and
        everything is visible in /metrics + /healthz.  The PR 3 invariant
        — streaming adds zero compiles beyond the ladder — runs under the
        shared retrace guard: budget 2 covers exactly the two warmed
        ladder levels, so ALL session traffic must be compile-free."""
        model, variables = stream_model
        scfg = StreamConfig(ladder=(12, 6), promote_threshold=2.0,
                            demote_threshold=0.1,
                            cold_reset_threshold=50.0,
                            session_limit=2, session_ttl_s=300.0)
        cfg = ServeConfig(
            port=0, divis_by=32, bucket_multiple=32, buckets=((60, 90),),
            warmup=False, max_batch_size=1, max_wait_ms=5.0,
            queue_limit=16, request_timeout_ms=120000.0, iters=12,
            degraded_iters=6, max_body_mb=1.0, max_image_dim=128,
            stream=scfg, stream_warmup=True)
        metrics = ServeMetrics()
        seq = _sequence(n=3)
        # The offline parity baseline runs FIRST, on the module-shared
        # engine, so its (possible) compiles stay outside the server's
        # guarded budget when this test runs alone.
        offline = run_sequence(stream_engine, seq.frames, scfg, warm=True)
        with retrace_guard(2, what="stream warmup compiles the ladder",
                           min_duration_s=0.5) as warmup_report:
            server = build_server(model, variables, cfg, metrics)
        # EXACTLY the two ladder levels — also proves the 0.5 s floor is
        # below the real compile time, so the budget-0 traffic guard
        # below cannot pass vacuously.
        assert warmup_report.compiles == 2, warmup_report.durations
        port = server.port
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            client = ServeClient("127.0.0.1", port, timeout=120)

            # All session traffic below reuses the two warmed ladder
            # executables: zero further compiles allowed.
            with retrace_guard(0, what="session traffic is compile-free",
                               min_duration_s=0.5):
                # (1) parity: session over HTTP == offline runner, bitwise.
                # seq_no omitted on the wire: in-order clients are implicit.
                http_disps, metas = [], []
                for left, right, _ in seq.frames:
                    disp, meta = client.predict(left, right,
                                                session_id="cam0")
                    http_disps.append(disp)
                    metas.append(meta)
                assert [m["warm"] for m in metas] == [False, True, True]
                assert [m["iters"] for m in metas] == [12, 6, 6]
                assert [m["seq_no"] for m in metas] == [0, 1, 2]
                for got, want in zip(http_disps, offline["preds"]):
                    np.testing.assert_array_equal(got, want)

                # Explicit iters cannot ride a session (controller owns it).
                from raftstereo_tpu.serve import ServeError
                with pytest.raises(ServeError) as ei:
                    client.predict(*seq.frames[0][:2], iters=12,
                                   session_id="cam0")
                assert ei.value.status == 400

                # Out-of-sequence frame: cold restart, never an error.
                disp, meta = client.predict(*seq.frames[0][:2],
                                            session_id="cam0", seq_no=99)
                assert not meta["warm"] and meta["iters"] == 12

                # (2) LRU eviction at session_limit=2: cam0 + s1 live; s2
                # evicts cam0; cam0's next frame is cold.
                client.predict(*seq.frames[0][:2], session_id="s1")
                client.predict(*seq.frames[0][:2], session_id="s2")
                _, meta = client.predict(*seq.frames[1][:2],
                                         session_id="cam0")
                assert not meta["warm"]        # state was evicted -> cold
                assert metrics.stream_evicted.value >= 1

                # (3) TTL expiry: zero the TTL so the next touch of a live
                # session expires it server-side — cold frame, 200 OK.
                _, meta = client.predict(*seq.frames[0][:2], session_id="s3")
                assert not meta["warm"]
                _, meta = client.predict(*seq.frames[1][:2], session_id="s3")
                assert meta["warm"]            # still live
                server.stream.store.ttl_s = 0.0
                _, meta = client.predict(*seq.frames[2][:2], session_id="s3")
                assert not meta["warm"]        # expired -> cold, no error
                server.stream.store.ttl_s = 300.0
                assert metrics.stream_expired.value >= 1

                # Admission control covers the session path too: with the
                # in-flight count saturated, a frame sheds with 503 instead
                # of queueing unboundedly on the engine lock.
                server.stream_inflight = cfg.queue_limit
                with pytest.raises(ServeError) as ei:
                    client.predict(*seq.frames[0][:2], session_id="cam0")
                assert ei.value.status == 503
                server.stream_inflight = 0

                # (4) sequence-replay load-gen: 2 sessions x 2 frames.
                stats = run_load("127.0.0.1", port,
                                 lambda i: seq.frames[i % 2][:2],
                                 requests=4, concurrency=2, sequence_len=2,
                                 timeout=120)
                assert stats["ok"] == 4 and stats["error"] == 0
                assert stats["warm_frames"] == 2 and stats["cold_frames"] == 2

                # Observability: counters/gauges in /metrics, ladder+sessions
                # in /healthz, stream compile keys in compiled_buckets.
                text = client.metrics_text()

                def sample(name):
                    # Labeled families render one series per label set; the
                    # label-blind total is their sum.
                    vals = [float(l.split()[-1]) for l in text.splitlines()
                            if l.startswith(name + " ")
                            or l.startswith(name + "{")]
                    assert vals, f"no samples for {name}"
                    return sum(vals)

                assert sample("stream_warm_frames_total") >= 4
                assert sample("stream_cold_frames_total") >= 6
                assert sample("stream_sessions_evicted_total") >= 1
                assert sample("stream_sessions_expired_total") >= 1
                assert sample("stream_sessions_active") >= 1
                assert sample("stream_frame_iters_count") >= 10
                health = client.healthz()
                assert health["stream"]["ladder"] == [12, 6]
                assert health["stream"]["session_limit"] == 2
                assert sorted({k[2] for k in map(
                    tuple, health["compiled_buckets"])
                    if k[3] == "stream"}) == [6, 12]
                # Stream warmup compiled the two ladder levels; the session
                # traffic above added none — the engine-level view of the
                # budget the retrace guard just enforced for real.
                assert metrics.compile_misses.value == 2
            client.close()
        finally:
            server.close()
            thread.join(10)

    def test_streaming_disabled_rejects_sessions(self, stream_model):
        """A server built without a stream config answers session frames
        with a clear 400, and plain requests still work."""
        model, variables = stream_model
        cfg = ServeConfig(port=0, bucket_multiple=32, buckets=((60, 90),),
                          warmup=False, max_batch_size=1, max_wait_ms=5.0,
                          queue_limit=16, request_timeout_ms=120000.0,
                          iters=12, degraded_iters=6)
        server = build_server(model, variables, cfg)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            from raftstereo_tpu.serve import ServeError

            client = ServeClient("127.0.0.1", server.port, timeout=120)
            with pytest.raises(ServeError) as ei:
                client.predict(_img(), _img(), session_id="nope")
            assert ei.value.status == 400
            assert "streaming disabled" in ei.value.payload["error"]
            client.close()
        finally:
            server.close()
            thread.join(10)


# --------------------------------------------------------------------- cli

def test_cli_stream_runner_smoke(capsys):
    """The offline sequence runner end to end through argparse: warm
    session + cold baseline, JSON report with the acceptance numbers."""
    from raftstereo_tpu.cli.stream import main

    rc = main(["--frames", "3", "--image_size", "48x64",
               "--stream_ladder", "4", "2", "--promote_threshold", "2.0",
               "--demote_threshold", "0.1",
               "--cold_reset_threshold", "50.0", "--bucket_multiple", "32",
               "--n_gru_layers", "2", "--hidden_dims", "32", "32",
               "--corr_levels", "2", "--corr_radius", "2"])
    assert rc == 0
    out = [l for l in capsys.readouterr().out.strip().splitlines()
           if l.startswith("{")][-1]
    rep = json.loads(out)
    assert rep["summary"]["frames"] == 3
    assert [r["warm"] for r in rep["warm"]] == [False, True, True]
    assert all(not r["warm"] for r in rep["cold"])
    assert rep["summary"]["warm_mean_iters_after_first"] == 2.0
    assert rep["summary"]["final_epe_ratio"] is not None
