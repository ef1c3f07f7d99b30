"""Durable session tier (raftstereo_tpu/stream/tier.py,
docs/streaming.md "Durable sessions").

Unit + service-level coverage for the PR 18 robustness layers:

* snapshot wire compression — int8 exact-dequant path with a
  per-snapshot exactness manifest, bitwise f32 fallback when the bound
  would be violated, unknown codecs refused cleanly (``cold_schema``
  at importers, never garbage);
* byte-accurate session accounting — in-replica ``SessionStore`` and
  tier-side ``_TierStore`` both bound their footprint with
  budget-driven LRU eviction surfaced on gauges;
* the write-behind ``TierPublisher`` — coalescing, bounded queue,
  degrade-to-local-pin on outage, re-probe + resync on recovery (all
  against a fake client with an injected clock: no real sleeps);
* a REAL ``cli.sessiontier`` process — snapshot roundtrip bitwise
  through the wire, monotonic stale refusal, schema-mismatch imports
  falling back ``cold_schema``, and the model-free import contract;
* the autoscaler's memory-pressure signal;
* a slow-marked 10k-session soak proving the tier holds its byte
  budget under eviction pressure while the gauges stay truthful and
  int8 keeps its >= 3x byte reduction.

The router-level chaos certification (SIGKILL a session's home backend
=> warm resume from the tier, ``tier_outage`` mid-replay => degraded
but zero errors) lives in tests/test_cluster.py where the real-model
router harness is.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from raftstereo_tpu.config import TierConfig
from raftstereo_tpu.obs import validate_prometheus
from raftstereo_tpu.ops.autoscale import (AutoscalePolicy, Autoscaler,
                                          recommend)
from raftstereo_tpu.serve.metrics import MetricsRegistry, ServeMetrics
from raftstereo_tpu.serve.server import (UnsupportedSnapshotCodec,
                                         snapshot_to_wire,
                                         wire_to_snapshot)
from raftstereo_tpu.stream.session import STATE_VERSION, SessionStore
from raftstereo_tpu.stream.tier import (SessionTier, TierClient,
                                        TierMetrics, TierPublisher,
                                        _TierStore, build_session_tier)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ----------------------------------------------------------------- helpers

_SCHEMA = {"factor": 4, "input_mode": "passive"}


def _snapshot(sid="s0", next_seq=3, hw=(15, 23), seed=0, schema=None,
              smooth=False):
    """A fabricated-but-valid SessionStore snapshot.  ``smooth`` draws a
    low-dynamic-range plane (int8-quantizable within the default bound);
    the default draw has ~16 px of range so the int8 step stays
    measurable."""
    rng = np.random.default_rng(seed)
    disp = (rng.normal(size=hw) * (0.5 if smooth else 8.0)
            ).astype(np.float32)
    return {
        "version": STATE_VERSION,
        "schema": dict(schema if schema is not None else _SCHEMA),
        "session_id": sid,
        "next_seq": int(next_seq),
        "frame_idx": int(next_seq),
        "prev_disp_low": disp,
        "bucket_hw": (60, 90),
        "ema": 0.5,
        "level": 1,
        "force_cold": False,
        "warm_frames": max(0, int(next_seq) - 1),
        "cold_frames": 1,
    }


def _wire_json(snap, **kw):
    """Serialized wire bytes — what actually crosses HTTP and what the
    tier accounts, so byte-reduction claims measure THIS."""
    return json.dumps(snapshot_to_wire(snap, **kw)).encode()


def _tier(port=0, **kw):
    cfg = TierConfig(port=port, **kw)
    tier = build_session_tier(cfg)
    th = threading.Thread(target=tier.serve_forever, daemon=True)
    th.start()
    return tier, th


# ---------------------------------------------------- snapshot compression

class TestSnapshotWire:
    def test_off_roundtrip_is_bitwise(self):
        snap = _snapshot()
        wire = json.loads(json.dumps(snapshot_to_wire(snap)))
        back = wire_to_snapshot(wire)
        np.testing.assert_array_equal(back["prev_disp_low"],
                                      snap["prev_disp_low"])
        assert back["prev_disp_low"].dtype == np.float32
        assert "snapshot_codec" not in wire["schema"]
        assert back["bucket_hw"] == (60, 90)
        assert back["next_seq"] == 3

    def test_int8_manifest_is_decoder_truth(self):
        """The encoder-measured max_abs_err IS the decode error: both
        ends run the same single dequant multiply, so the exactness
        manifest certifies what the importer actually installs."""
        snap = _snapshot(hw=(64, 96), smooth=True)
        wire = json.loads(json.dumps(
            snapshot_to_wire(snap, compress="int8", compress_bound=0.05)))
        plane = wire["prev_disp_low"]
        assert plane["codec"] == "int8"
        manifest = plane["manifest"]
        assert manifest["bound"] == 0.05
        assert 0 < manifest["max_abs_err"] <= 0.05
        # The mixed-fleet refusal handle: int8 stamps the schema.
        assert wire["schema"]["snapshot_codec"] == "int8-v1"
        back = wire_to_snapshot(wire)
        err = float(np.max(np.abs(back["prev_disp_low"]
                                  - snap["prev_disp_low"])))
        assert err == pytest.approx(manifest["max_abs_err"], abs=1e-9)

    def test_int8_cuts_wire_bytes_3x(self):
        """The acceptance number: >= 3x fewer snapshot wire bytes than
        the bitwise f32 form for a real-sized low-res plane."""
        snap = _snapshot(hw=(64, 96), smooth=True)
        raw = _wire_json(snap)
        packed = _wire_json(snap, compress="int8", compress_bound=0.05)
        assert len(packed) * 3 <= len(raw), (len(packed), len(raw))

    def test_violated_bound_falls_back_bitwise(self):
        """A plane the bound cannot certify ships as raw f32 — the
        compressed path never costs more warmth than its manifest, and
        the schema carries no codec so ANY peer imports it."""
        snap = _snapshot(hw=(16, 24))
        wire = json.loads(json.dumps(
            snapshot_to_wire(snap, compress="int8", compress_bound=1e-7)))
        assert not isinstance(wire["prev_disp_low"], dict) or \
            "codec" not in wire["prev_disp_low"]
        assert "snapshot_codec" not in wire["schema"]
        back = wire_to_snapshot(wire)
        np.testing.assert_array_equal(back["prev_disp_low"],
                                      snap["prev_disp_low"])

    def test_unknown_codec_refused_never_garbage(self):
        wire = snapshot_to_wire(_snapshot(), compress="int8",
                                compress_bound=10.0)
        assert wire["prev_disp_low"]["codec"] == "int8"
        wire["prev_disp_low"]["codec"] = "fp4-exotic"
        with pytest.raises(UnsupportedSnapshotCodec):
            wire_to_snapshot(wire)

    def test_unknown_codec_import_is_cold_schema(self):
        """End of the refusal chain: an importer seeing a codec it
        cannot decode answers the documented cold_schema fallback."""
        wire = snapshot_to_wire(_snapshot(), compress="int8",
                                compress_bound=10.0)
        wire["prev_disp_low"]["codec"] = "fp4-exotic"
        store = SessionStore(limit=4, ttl_s=100.0)
        try:
            snap = wire_to_snapshot(wire)
        except UnsupportedSnapshotCodec:
            snap = None
        assert snap is None
        # A peer that decodes but schema-compares also refuses: the
        # int8 stamp itself makes fingerprints differ vs a codec-naive
        # exporter comparing its own extra field... the canonical path
        # is version/schema, exercised here with the raw dict.
        assert store.import_state(wire, schema=_SCHEMA) == "cold_schema"


# ------------------------------------------------------------ _TierStore

class TestTierStore:
    def test_put_get_stale_and_lru(self):
        m = TierMetrics()
        st = _TierStore(limit=8, budget_mb=1.0, metrics=m)
        assert st.put("a", b'{"x":1}', 3) == "stored"
        assert st.get("a") == b'{"x":1}'
        # Monotonic guard: equal-or-older next_seq never overwrites.
        assert st.put("a", b'{"x":0}', 3) == "stale"
        assert st.put("a", b'{"x":0}', 2) == "stale"
        assert st.get("a") == b'{"x":1}'
        assert st.put("a", b'{"x":2}', 4) == "stored"
        assert st.total_bytes() == len(b'{"x":2}')
        assert st.get("missing") is None

    def test_count_cap_evicts_lru(self):
        m = TierMetrics()
        st = _TierStore(limit=2, budget_mb=0.0, metrics=m)
        st.put("a", b"a" * 10, 1)
        st.put("b", b"b" * 10, 1)
        st.get("a")  # touch: b is now LRU
        st.put("c", b"c" * 10, 1)
        assert len(st) == 2
        assert st.get("b") is None and st.get("a") is not None
        text = m.render()
        assert "tier_evictions_total 1" in text
        assert "tier_sessions_active 2" in text

    def test_byte_budget_evicts_but_never_last(self):
        m = TierMetrics()
        budget_mb = 100 / 2 ** 20  # 100 bytes
        st = _TierStore(limit=1000, budget_mb=budget_mb, metrics=m)
        st.put("a", b"a" * 60, 1)
        st.put("b", b"b" * 60, 1)  # 120 > 100: evicts a
        assert len(st) == 1 and st.get("a") is None
        assert st.total_bytes() == 60
        # One over-budget session is kept (served + surfaced), not
        # dropped: the bound never evicts the last stored session.
        st.put("c", b"c" * 300, 1)
        st.put("c", b"c" * 400, 2)
        assert len(st) == 1 and len(st.get("c")) == 400
        assert st.total_bytes() == 400
        assert "tier_session_bytes 400" in m.render()


# -------------------------------------------- SessionStore byte accounting

class TestSessionStoreBytes:
    def _store(self, **kw):
        m = ServeMetrics(MetricsRegistry())
        return SessionStore(limit=kw.pop("limit", 16), ttl_s=100.0,
                            metrics=m, **kw), m

    def test_accounting_tracks_plane_bytes_and_gauge(self):
        store, m = self._store()
        assert store.total_bytes() == 0
        snap = _snapshot("cam0", hw=(15, 23))
        assert store.import_state(snap, schema=_SCHEMA) == "warm"
        total = store.total_bytes()
        assert total >= snap["prev_disp_low"].nbytes  # plane + overhead
        assert f"stream_session_bytes {total}" in m.registry.render()
        # Re-importing fresher state for the SAME session re-accounts,
        # not double-counts.
        bigger = _snapshot("cam0", next_seq=9, hw=(30, 23))
        assert store.import_state(bigger, schema=_SCHEMA) == "warm"
        total2 = store.total_bytes()
        assert total2 - total == (bigger["prev_disp_low"].nbytes
                                  - snap["prev_disp_low"].nbytes)
        store.drop("cam0")
        assert store.total_bytes() == 0

    def test_byte_budget_evicts_lru_session(self):
        plane_bytes = 15 * 23 * 4
        budget_mb = (3 * plane_bytes) / 2 ** 20  # fits ~2 sessions
        store, m = self._store(limit=100, budget_mb=budget_mb)
        for i in range(4):
            snap = _snapshot(f"cam{i}", hw=(15, 23))
            assert store.import_state(snap, schema=_SCHEMA) == "warm"
        sids = store.session_ids()
        assert "cam0" not in sids and "cam3" in sids
        assert store.total_bytes() <= int(budget_mb * 2 ** 20)
        text = m.registry.render()
        assert "stream_sessions_evicted_total" in text


# --------------------------------------------------- TierPublisher (fake)

class FakeTier:
    """Scripted TierClient stand-in: togglable health/failure, recorded
    puts — the publisher's degradation policy asserts deterministically."""

    host, port = "fake-tier", 0

    def __init__(self):
        self.puts = []
        self.failing = False
        self.healthy = True

    def healthz(self):
        return self.healthy and not self.failing

    def put_wire(self, wire_obj):
        if self.failing:
            raise OSError("tier down")
        self.puts.append(wire_obj)
        return {"session_id": wire_obj["session_id"], "outcome": "stored"}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestTierPublisher:
    def _publisher(self, tier, snapshots, clock=None, **kw):
        m = ServeMetrics(MetricsRegistry())
        pub = TierPublisher(
            tier, export_fn=snapshots.get, to_wire=lambda s: dict(s),
            metrics=m, clock=clock or time.monotonic,
            sleep=lambda s: None, **kw)
        return pub, m

    def _count(self, m, needle):
        for line in m.registry.render().splitlines():
            if line.startswith(needle + " "):
                return float(line.split()[-1])
        return 0.0

    def test_burst_coalesces_to_one_push(self):
        tier = FakeTier()
        snaps = {"s0": {"session_id": "s0", "next_seq": 9}}
        pub, m = self._publisher(tier, snaps)
        for _ in range(5):  # 5 completed frames before the worker runs
            pub.enqueue("s0")
        assert pub.pending() == 1  # the queue holds SIDs, not snapshots
        pub.start()
        assert pub.flush(timeout_s=5.0)
        pub.close()
        assert len(tier.puts) == 1  # freshest-at-send-time, one POST
        assert tier.puts[0]["next_seq"] == 9
        assert self._count(
            m, 'stream_tier_pushes_total{outcome="ok"}') == 1

    def test_missing_session_push_is_skipped(self):
        tier = FakeTier()
        pub, m = self._publisher(tier, {})
        pub.start()
        pub.enqueue("gone")  # dropped between frame and push
        assert pub.flush(timeout_s=5.0)
        pub.close()
        assert tier.puts == []
        assert self._count(
            m, 'stream_tier_pushes_total{outcome="skipped"}') == 1

    def test_queue_limit_drops_oldest_counted(self):
        tier = FakeTier()
        pub, m = self._publisher(tier, {}, queue_limit=2)
        for sid in ("a", "b", "c"):
            pub.enqueue(sid)
        assert pub.pending() == 2  # a dropped; push deferred, not lost
        pub.close()
        assert self._count(
            m, 'stream_tier_pushes_total{outcome="dropped"}') == 1

    def test_outage_degrades_then_reattaches_and_resyncs(self):
        """The full robustness cycle with an injected clock: push fails
        => detach + degraded counter (request path untouched); while
        detached pushes are suppressed; once the re-probe is due and
        the tier answers, the publisher re-attaches and resyncs every
        live session so the tier catches up."""
        tier = FakeTier()
        clock = FakeClock()
        snaps = {"s0": {"session_id": "s0", "next_seq": 2},
                 "s1": {"session_id": "s1", "next_seq": 5}}
        pub, m = self._publisher(
            tier, snaps, clock=clock, retries=1, reprobe_s=1.0,
            resync_fn=lambda: ["s0", "s1"])
        pub.start()
        try:
            tier.failing = True
            pub.enqueue("s0")
            assert pub.flush(timeout_s=5.0)
            assert pub.attached() is False
            assert self._count(
                m, 'stream_tier_pushes_total{outcome="error"}') == 1
            assert self._count(m, "stream_tier_degraded_total") >= 1
            assert self._count(m, "stream_tier_attached") == 0.0

            # Re-probe not due yet: the push is suppressed (local-pin).
            pub.enqueue("s0")
            assert pub.flush(timeout_s=5.0)
            assert tier.puts == [] and pub.attached() is False
            degraded = self._count(m, "stream_tier_degraded_total")
            assert degraded >= 2

            # Outage ends; the due probe re-attaches and resyncs BOTH
            # live sessions — the tier catches up on what it missed.
            tier.failing = False
            clock.t += 2.0
            pub.enqueue("s1")
            assert pub.flush(timeout_s=5.0)
            assert pub.attached() is True
            assert self._count(m, "stream_tier_attached") == 1.0
            assert {p["session_id"] for p in tier.puts} == {"s0", "s1"}
            assert pub.state()["attached"] is True
        finally:
            pub.close()


# ------------------------------------------------ the real tier service

class TestSessionTierService:
    def test_roundtrip_healthz_metrics_and_stale(self):
        tier, th = _tier(budget_mb=8.0)
        client = TierClient("127.0.0.1", tier.port, timeout_s=5.0)
        try:
            assert client.healthz() is True
            snap = _snapshot("cam/0", next_seq=4)  # sid needs quoting
            wire = snapshot_to_wire(snap)
            assert client.put_wire(wire)["outcome"] == "stored"
            # Verbatim storage: what comes back IS what went in.
            got = client.get_session("cam/0")
            assert got == json.loads(json.dumps(wire))
            back = wire_to_snapshot(got)
            np.testing.assert_array_equal(back["prev_disp_low"],
                                          snap["prev_disp_low"])
            # Stale write refused by the shared monotonic guard.
            older = snapshot_to_wire(_snapshot("cam/0", next_seq=2,
                                               seed=9))
            assert client.put_wire(older)["outcome"] == "stale"
            assert wire_to_snapshot(
                client.get_session("cam/0"))["next_seq"] == 4
            assert client.get_session("never-seen") is None
            # A body without the seam's keys is a clean 400.
            with pytest.raises(OSError):
                client.put_wire({"not": "a snapshot"})
            status, body = client._request("GET", "/healthz")
            h = json.loads(body)
            assert h["ready"] and h["sessions"] == 1
            assert h["session_bytes"] == tier.store.total_bytes() > 0
            status, text = client._request("GET", "/metrics")
            assert status == 200
            assert validate_prometheus(text.decode()) == []
            assert "tier_session_bytes" in text.decode()
            assert 'tier_requests_total{op="put",outcome="stale"} 1' \
                in text.decode()
        finally:
            tier.close()
            th.join(5)

    def test_a_parent_builds_snapshot_resumes_warm_from_the_tier(self):
        """The tier stores a snapshot verbatim, whatever build wrote it:
        one whose schema still carries an older build's ``gru_backend``
        comes back as it went in, and a backend of this build (which
        compares only ``factor`` / ``input_mode``) resumes it warm."""
        tier, th = _tier()
        client = TierClient("127.0.0.1", tier.port, timeout_s=5.0)
        try:
            snap = _snapshot("cam0", next_seq=4,
                             schema=dict(_SCHEMA, gru_backend="xla"))
            assert client.put_wire(snapshot_to_wire(snap))["outcome"] \
                == "stored"
            got = client.get_session("cam0")
            assert got["schema"]["gru_backend"] == "xla"
            store = SessionStore(limit=4, ttl_s=100.0)
            assert store.import_state(wire_to_snapshot(got),
                                      schema=_SCHEMA) == "warm"
            out = store.export_state("cam0", schema=_SCHEMA)
            assert out["next_seq"] == 4
            np.testing.assert_array_equal(out["prev_disp_low"],
                                          snap["prev_disp_low"])
        finally:
            tier.close()
            th.join(5)

    def test_chaos_grammar_tier_slow_and_outage(self):
        """The armable chaos seams: tier_slow delays the next N replies,
        tier_outage holds EVERY reply until the window ends — clients
        time out against their own budgets, the tier itself never
        errors."""
        tier, th = _tier()
        client = TierClient("127.0.0.1", tier.port, timeout_s=5.0)
        try:
            status, body = client._request(
                "POST", "/debug/faults",
                json.dumps({"faults": "tier_slow@request=1:0.3"}).encode())
            assert status == 200
            assert json.loads(body)["armed"] == \
                ["tier_slow@request=1:0.3s"]
            t0 = time.perf_counter()
            assert client.healthz() is True  # delayed, then answered
            assert time.perf_counter() - t0 >= 0.25
            t0 = time.perf_counter()
            assert client.healthz() is True  # budget spent: fast again
            assert time.perf_counter() - t0 < 0.25

            status, body = client._request(
                "POST", "/debug/faults",
                json.dumps({"faults": "tier_outage@t_ms=0:0.5"}).encode())
            assert status == 200
            fast = TierClient("127.0.0.1", tier.port, timeout_s=0.15)
            assert fast.healthz() is False  # held past the budget
            deadline = time.perf_counter() + 5
            while time.perf_counter() < deadline:
                if fast.healthz():
                    break
            assert fast.healthz() is True  # window over: back to normal
        finally:
            tier.close()
            th.join(5)


class TestSessionTierProcess:
    def _spawn(self, *extra):
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "raftstereo_tpu.cli.sessiontier",
             "--port", "0", *extra],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env, cwd=REPO)
        line = proc.stdout.readline()
        info = json.loads(line)
        port = int(info["tier"].rsplit(":", 1)[1])
        return proc, port, info

    def test_process_roundtrip_warm_stale_and_schema(self):
        """The PR 18 acceptance seam through a REAL tier process: a
        snapshot exported from one SessionStore crosses the tier and
        installs WARM + bitwise in another; a rewound import stays
        refused by the importer's monotonic guard; a schema-mismatched
        fleet falls back cold_schema, never garbage."""
        proc, port, info = self._spawn("--budget_mb", "32")
        client = TierClient("127.0.0.1", port, timeout_s=10.0)
        try:
            assert info["session_limit"] >= 1
            assert "/debug/sessions" in info["endpoints"]
            src = SessionStore(limit=4, ttl_s=100.0)
            assert src.import_state(_snapshot("cam0", next_seq=5),
                                    schema=_SCHEMA) == "warm"
            snap = src.export_state("cam0", schema=_SCHEMA)
            assert client.put_wire(snapshot_to_wire(snap))["outcome"] \
                == "stored"

            dst = SessionStore(limit=4, ttl_s=100.0)
            got = wire_to_snapshot(client.get_session("cam0"))
            assert dst.import_state(got, schema=_SCHEMA) == "warm"
            out = dst.export_state("cam0", schema=_SCHEMA)
            np.testing.assert_array_equal(out["prev_disp_low"],
                                          snap["prev_disp_low"])
            assert out["next_seq"] == snap["next_seq"]

            # Monotonic refusal end-to-end: a STALE tier copy imported
            # into a store that moved on reports warm WITHOUT rewinding.
            assert dst.import_state(_snapshot("cam0", next_seq=9),
                                    schema=_SCHEMA) == "warm"
            again = wire_to_snapshot(client.get_session("cam0"))
            assert dst.import_state(again, schema=_SCHEMA) == "warm"
            assert dst.export_state("cam0",
                                    schema=_SCHEMA)["next_seq"] == 9

            # Mixed fleet: an importer whose engine fingerprint differs
            # refuses the tier copy with the documented cold fallback.
            other = SessionStore(limit=4, ttl_s=100.0)
            mismatched = dict(_SCHEMA, input_mode="sl")
            assert other.import_state(again, schema=mismatched) \
                == "cold_schema"
        finally:
            proc.terminate()
            proc.wait(timeout=10)

    def test_sessiontier_import_is_model_free(self):
        """Like the router (PR 8): the tier must start in milliseconds,
        so its import path must never drag in the engine/model stack."""
        script = textwrap.dedent("""
            import sys
            from raftstereo_tpu.stream.tier import build_session_tier
            import raftstereo_tpu.cli.sessiontier  # the CLI itself
            assert callable(build_session_tier)
            heavy = sorted(m for m in sys.modules if m.startswith((
                "raftstereo_tpu.serve.engine",
                "raftstereo_tpu.serve.server",
                "raftstereo_tpu.serve.sched",
                "raftstereo_tpu.models", "flax")))
            assert not heavy, heavy
            print("MODEL_FREE_OK")
        """)
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, env=env,
                              cwd=REPO, timeout=120)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "MODEL_FREE_OK" in proc.stdout


# ------------------------------------------------- autoscaler integration

class TestAutoscaleMemoryPressure:
    def test_memory_pressure_recommends_scale_out(self):
        policy = AutoscalePolicy()
        direction, reason = recommend(policy, ready=2, utilization=0.3,
                                      memory_pressure=0.95)
        assert direction == 1 and "memory pressure" in reason
        # Below the threshold the signal is inert (utilization rules).
        direction, _ = recommend(policy, ready=2, utilization=0.3,
                                 memory_pressure=0.5)
        assert direction == 0

    def test_observe_surfaces_signal_with_hysteresis(self):
        scaler = Autoscaler(AutoscalePolicy(hysteresis=2))
        advice = scaler.observe(ready=2, utilization=0.3,
                                memory_pressure=0.93)
        assert advice["action"] == "hold"  # first observation: damped
        advice = scaler.observe(ready=2, utilization=0.3,
                                memory_pressure=0.93)
        assert advice["action"] == "scale_up"
        assert advice["signals"]["memory_pressure"] == 0.93
        assert "memory pressure" in advice["reason"]


# ---------------------------------------------------------- 10k soak (slow)

@pytest.mark.slow
class TestTierSoak:
    def test_10k_sessions_hold_the_byte_budget(self):
        """Budget certification at fleet scale: 10k+ distinct sessions
        pushed through the REAL tier service with a budget sized for
        ~1/4 of them.  The tier must stay within its byte budget the
        whole way (evicting LRU, counting each one), the gauges must
        equal the accounted truth at the end, int8 must keep its >= 3x
        wire-byte reduction, and the fleet's memory-pressure signal
        must be driving scale-out advice."""
        n_sessions, hw = 10_000, (64, 96)  # a real low-res plane: the
        # >= 3x claim is about plane bytes, not fixed JSON overhead
        sample = _wire_json(_snapshot("probe", hw=hw, smooth=True),
                            compress="int8")
        budget_mb = len(sample) * (n_sessions / 4) / 2 ** 20
        tier, th = _tier(budget_mb=budget_mb, session_limit=n_sessions * 2)
        client = TierClient("127.0.0.1", tier.port, timeout_s=10.0)
        try:
            raw_bytes = packed_bytes = 0
            base = _snapshot("template", hw=hw, smooth=True)
            for i in range(n_sessions):
                snap = dict(base, session_id=f"cam{i}", next_seq=3)
                body = snapshot_to_wire(snap, compress="int8")
                assert client.put_wire(body)["outcome"] == "stored"
                if i % 1000 == 0:
                    raw_bytes += len(json.dumps(snapshot_to_wire(snap)))
                    packed_bytes += len(json.dumps(body))
                    # Never over budget mid-soak, not only at the end.
                    assert tier.store.total_bytes() \
                        <= int(budget_mb * 2 ** 20)
            assert packed_bytes * 3 <= raw_bytes
            assert tier.store.total_bytes() <= int(budget_mb * 2 ** 20)
            assert 1 < len(tier.store) < n_sessions  # evictions fired
            text = tier.metrics.render()
            assert validate_prometheus(text) == []
            evicted = sessions = total = None
            for line in text.splitlines():
                if line.startswith("tier_evictions_total "):
                    evicted = float(line.split()[-1])
                if line.startswith("tier_sessions_active "):
                    sessions = float(line.split()[-1])
                if line.startswith("tier_session_bytes "):
                    total = float(line.split()[-1])
            assert evicted and evicted >= n_sessions / 2
            assert sessions == len(tier.store)  # gauge == truth
            assert total == tier.store.total_bytes()
            # The freshest sessions survived; the oldest paid eviction.
            assert client.get_session(f"cam{n_sessions - 1}") is not None
            assert client.get_session("cam0") is None

            # The same accounting feeds the fleet autoscaler: a fleet
            # at 95% of its session budget draws scale-out advice.
            scaler = Autoscaler(AutoscalePolicy(hysteresis=1))
            pressure = tier.store.total_bytes() / (budget_mb * 2 ** 20)
            advice = scaler.observe(ready=2, utilization=0.3,
                                    memory_pressure=pressure)
            assert advice["action"] == "scale_up"
        finally:
            tier.close()
            th.join(5)
