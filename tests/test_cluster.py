"""Replicated multi-chip serving (raftstereo_tpu/serve/cluster,
docs/serving.md "Cluster").

Placement/stickiness policy tests run the ClusterDispatcher against stub
replicas (no device); the acceptance gates use a tiny real model on the
suite's virtual CPU devices (conftest forces 8):

* ``test_two_replica_cluster_mixed_traffic`` — a 2-replica cluster
  behind one HTTP server serves mixed cold + stream-session + scheduled
  traffic bitwise-identical to a single-engine baseline, sessions pin to
  one replica, a failed replica degrades (traffic continues on the
  survivor), steady state stays under a ZERO-compile retrace budget, and
  /metrics passes the Prometheus validator with the ``cluster_*``
  families populated;
* ``test_router_...`` — the front-end router over two backend servers:
  readiness gating (live vs ready), session stickiness over the wire,
  killing a backend mid-load loses ZERO accepted cold requests
  (failover) and session frames degrade to cold re-pins, exhausted
  backends give clean 503s (never hangs), and per-backend drain
  completes with in-flight work finished;
* ``test_zero_downtime_restart_and_kill`` — warm session migration
  (PR 13): ``POST /debug/restart`` drains a backend and hands its
  sessions over WARM (bitwise-identical to an unmigrated twin, zero
  compiles), sequence-replay load through the router loses zero
  accepted requests and zero mid-sequence warm frames, the restarted
  process rejoins through the readiness probe at a zero-compile steady
  state, and an unplanned kill costs at most the documented
  ``cold_lost`` fallback.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax

from raftstereo_tpu import wire
from raftstereo_tpu.config import (ClusterConfig, RAFTStereoConfig,
                                   RouterConfig, SchedConfig, ServeConfig,
                                   StreamConfig, TierConfig)
from raftstereo_tpu.ops.autoscale import (AutoscalePolicy, Autoscaler,
                                          recommend)
from raftstereo_tpu.serve import (BatchEngine, ClusterDispatcher,
                                  DynamicBatcher, IterationScheduler,
                                  Overloaded, RequestTimedOut, ServeClient,
                                  ServeError, ServeMetrics, ShuttingDown,
                                  build_router, build_server)
from raftstereo_tpu.serve.batcher import Future, ServeResult
from raftstereo_tpu.serve.client import run_load
from raftstereo_tpu.serve.cluster.pins import PinTable
from raftstereo_tpu.serve.cluster.replica import Replica
from raftstereo_tpu.serve.cluster.router import (Backend, CircuitBreaker,
                                                 _ProbeSchedule)
from raftstereo_tpu.serve.server import snapshot_to_wire, wire_to_snapshot
from raftstereo_tpu.stream.session import STATE_VERSION, SessionStore
from raftstereo_tpu.utils.faults import FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ----------------------------------------------------------------- fixtures

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)


@pytest.fixture(scope="module")
def cluster_model():
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), (64, 96))
    return model, variables


def _img(h=60, w=90, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


def _cfg(**kw):
    base = dict(port=0, bucket_multiple=32, buckets=((60, 90),),
                warmup=False, max_batch_size=2, max_wait_ms=5.0,
                queue_limit=16, request_timeout_ms=60000.0, iters=4,
                degraded_iters=2, degrade_queue_depth=10 ** 6,
                cluster=ClusterConfig(replicas=2))
    base.update(kw)
    return ServeConfig(**base)


# ------------------------------------------------- dispatcher policy (stubs)

class StubReplica:
    """Replica-surface stand-in: scripted outstanding work, overload and
    stream behaviour so placement decisions assert deterministically."""

    def __init__(self, rid, outstanding=0, overloaded=False,
                 state="ready"):
        from raftstereo_tpu.serve.cluster.replica import \
            _ReplicaMetricsView

        self.rid = rid
        self.name = f"r{rid}"
        self.scheduler = None
        self.batcher = self
        self.stream = self
        # the real Replica's per-replica gauge view (the dispatcher
        # aggregates these onto the shared registry in _refresh_gauges)
        self.metrics = _ReplicaMetricsView(ServeMetrics())
        self._outstanding = outstanding
        self._inflight = 0
        self.overloaded = overloaded
        self._state = state
        self.submitted = []
        self.stepped = []
        self.futures = []

    # batcher contract
    def submit(self, image1, image2, iters=None, trace_id=None, mode=None):
        if self.overloaded:
            raise Overloaded("full")
        self.submitted.append(iters)
        fut = Future()
        self.futures.append(fut)
        return fut

    # stream contract
    def step(self, session_id, seq_no, left, right, trace_id=None,
             mode=None):
        from raftstereo_tpu.stream.runner import StreamResult

        self.stepped.append((session_id, seq_no))
        return StreamResult(
            disparity=np.zeros((4, 4), np.float32), iters=1, warm=False,
            frame_idx=0, seq_no=seq_no or 0, session_id=session_id,
            update_ema=0.0, latency_s=0.0, included_compile=False)

    # replica surface the dispatcher uses
    def routable(self):
        return self._state == "ready"

    @property
    def state(self):
        return self._state

    def outstanding(self):
        return self._outstanding + self._inflight

    def begin_dispatch(self):
        self._inflight += 1

    def end_dispatch(self, ok):
        self._inflight -= 1

    def drain(self):
        self._state = "draining"

    def stats(self):
        return {"state": self._state}


class StubRSet:
    def __init__(self, replicas, **cluster_kw):
        self.replicas = replicas
        self.cluster_cfg = ClusterConfig(replicas=len(replicas),
                                         **cluster_kw)
        self.metrics = ServeMetrics()

    def ready_replicas(self):
        return [r for r in self.replicas if r.routable()]

    def states(self):
        counts = {}
        for r in self.replicas:
            counts[r.state] = counts.get(r.state, 0) + 1
        return counts

    def stats(self):
        return {"replicas": {r.name: r.stats() for r in self.replicas},
                "states": self.states()}

    def stop(self, drain=True):
        pass


def _dispatcher(replicas, **cluster_kw):
    rset = StubRSet(replicas, **cluster_kw)
    return ClusterDispatcher(rset, _cfg()), rset


class TestDispatcherPolicy:
    def test_least_outstanding_placement(self):
        r0, r1 = StubReplica(0, outstanding=3), StubReplica(1)
        d, _ = _dispatcher([r0, r1])
        d.submit(_img(), _img(), 4)
        assert r1.submitted == [4] and r0.submitted == []
        # The tracked dispatch counts as outstanding until resolved, so
        # the next two spread: r0 (3) vs r1 (0+1) -> r1 again, then both
        # resolve and r1 keeps winning on ties only via rid order.
        r1._outstanding = 5
        d.submit(_img(), _img(), 2)
        assert r0.submitted == [2]

    def test_overload_spills_then_raises(self):
        r0, r1 = StubReplica(0, overloaded=True), StubReplica(1)
        d, _ = _dispatcher([r0, r1])
        d.submit(_img(), _img())  # spilled to r1
        assert r1.submitted == [None]
        r1.overloaded = True
        with pytest.raises(Overloaded):
            d.submit(_img(), _img())
        fam = {lv: c.value
               for lv, c in d.cluster_metrics.dispatch.series()}
        assert fam[("r0", "shed")] >= 2 and fam[("r1", "shed")] >= 1

    def test_no_ready_replica_raises_clean(self):
        d, _ = _dispatcher([StubReplica(0, state="starting"),
                            StubReplica(1, state="failed")])
        with pytest.raises(ShuttingDown):
            d.submit(_img(), _img())

    def test_result_annotated_with_replica_before_visible(self):
        r0 = StubReplica(0)
        d, _ = _dispatcher([r0])
        fut = d.submit(_img(), _img(), 4)
        res = ServeResult(disparity=np.zeros((2, 2), np.float32), iters=4,
                          degraded=False, batch_size=1, latency_s=0.0)
        r0.futures[0]._resolve(value=res)
        out = fut.result(timeout=5)
        assert out.replica == "r0"
        assert r0.outstanding() == 0  # settled
        fam = {lv: c.value
               for lv, c in d.cluster_metrics.dispatch.series()}
        assert fam[("r0", "ok")] == 1

    def test_sticky_sessions_pin_and_repin(self):
        r0, r1 = StubReplica(0), StubReplica(1, outstanding=9)
        d, _ = _dispatcher([r0, r1])
        for seq in range(3):
            res = d.step("cam0", seq, _img(), _img())
            assert res.replica == "r0"  # least-loaded at pin time, sticky
        assert len(r0.stepped) == 3 and not r1.stepped
        assert d.cluster_metrics.session_repins.value == 0
        # Pinned replica lost -> re-pin to the survivor; the frame is
        # served (cold on the new replica), never an error.
        r0._state = "failed"
        res = d.step("cam0", 3, _img(), _img())
        assert res.replica == "r1" and r1.stepped == [("cam0", 3)]
        assert d.cluster_metrics.session_repins.value == 1
        reasons = {lv: c.value
                   for lv, c in d.cluster_metrics.session_repins.series()}
        assert reasons == {("failed",): 1}
        # The stub exposes no session store behind its stream seam, so
        # the re-pin's handoff attempt lands on the documented fallback
        # (counted, never raised — the frame above was still served).
        outs = {lv: c.value
                for lv, c in d.cluster_metrics.session_handoffs.series()}
        assert outs == {("cold_lost",): 1}

    def test_autoscale_advice_surfaces_in_stats_and_gauge(self):
        d, _ = _dispatcher([StubReplica(0)])
        d.step("s", 0, _img(), _img())  # any traffic refreshes gauges
        advice = d.stats()["autoscale"]
        assert advice["action"] in ("hold", "scale_up", "scale_down")
        assert d.cluster_metrics.autoscale_recommendation.value \
            == advice["delta"]

    def test_session_pin_table_is_bounded(self):
        d, _ = _dispatcher([StubReplica(0)], session_pin_limit=4)
        for i in range(10):
            d.step(f"s{i}", 0, _img(), _img())
        with d._lock:
            assert len(d._pins) <= 4


# ------------------------------------------- warm session migration (PR 13)

# Engine-level state-schema fingerprint used by the store-level tests
# (shape of BatchEngine.session_schema()).
SCHEMA = {"factor": 4, "input_mode": "passive"}


def _warm_store(sid="cam0", next_seq=3):
    """A SessionStore holding one session with completed-frame state."""
    store = SessionStore(limit=4, ttl_s=60.0)
    sess, _ = store.get_or_create(sid)
    with sess.lock:
        sess.prev_disp_low = (np.arange(15, dtype=np.float32)
                              .reshape(3, 5) / 7.0)
        sess.bucket_hw = (60, 90)
        sess.next_seq = next_seq
        sess.frame_idx = next_seq
        sess.ema = 0.25
        sess.level = 2
        sess.warm_frames = next_seq - 1
        sess.cold_frames = 1
    return store


class StoreStubReplica(StubReplica):
    """Stub replica with a REAL SessionStore behind the migration seam
    (the scripted ``step`` never touches it — tests seed state directly),
    and an injectable schema to model engine-fingerprint mismatches."""

    def __init__(self, rid, schema=None, **kw):
        super().__init__(rid, **kw)
        self.store = SessionStore(limit=8, ttl_s=600.0)
        self.schema = dict(schema if schema is not None else SCHEMA)

    def export_session(self, session_id):
        return self.store.export_state(session_id, schema=self.schema)

    def import_session(self, snapshot):
        return self.store.import_state(snapshot, schema=self.schema)


def _seed_state(replica, sid, next_seq=1, salt=0.0):
    """Install warm state for ``sid`` in a StoreStubReplica's store;
    returns the disparity array (the bitwise reference)."""
    sess, _ = replica.store.get_or_create(sid)
    with sess.lock:
        sess.prev_disp_low = (np.arange(15, dtype=np.float32)
                              .reshape(3, 5) / 7.0) + salt
        sess.bucket_hw = (60, 90)
        sess.next_seq = next_seq
        sess.frame_idx = next_seq
        sess.ema = 0.5
        sess.level = 2
        return sess.prev_disp_low


class TestPinTable:
    def test_pin_triple_and_peek(self):
        pt = PinTable(4)
        assert pt.pin("s", still_ok=lambda t: True,
                      choose=lambda: 0) == (0, False, None)
        # Sticky: a live pin wins, choose() is not consulted.
        assert pt.pin("s", still_ok=lambda t: True,
                      choose=lambda: 1) == (0, False, 0)
        # Stale pin replaced: repinned=True carries the old home so the
        # caller can attempt the warm handoff from it.
        assert pt.pin("s", still_ok=lambda t: False,
                      choose=lambda: 1) == (1, True, 0)
        assert pt.peek("s") == 1 and pt.peek("nope") is None

    def test_no_candidate_leaves_pin_untouched(self):
        pt = PinTable(4)
        pt.pin("s", still_ok=lambda t: True, choose=lambda: 0)
        assert pt.pin("s", still_ok=lambda t: False,
                      choose=lambda: None) == (None, False, 0)
        # The stale pin survives: the session's state is still at its
        # old home, and the next pin() may find a ready target.
        assert pt.peek("s") == 0

    def test_pinned_to_and_reassign_cas(self):
        pt = PinTable(8)
        for i, sid in enumerate(("a", "b", "c")):
            pt.pin(sid, still_ok=lambda t: True, choose=lambda i=i: i % 2)
        assert pt.pinned_to(0) == ["a", "c"]
        assert pt.pinned_to(7) == []
        assert pt.reassign("a", 0, 1)  # expectation holds -> moved
        assert pt.peek("a") == 1
        assert not pt.reassign("c", 1, 0)  # stale expectation -> no-op
        assert pt.peek("c") == 0
        assert not pt.reassign("new", 0, 1)  # absent but 0 expected
        assert pt.reassign("new", None, 1)  # absent CAS (import path)
        assert pt.peek("new") == 1


class TestSessionStateSnapshot:
    """SessionStore.export_state / import_state — the host-side seam
    every migration path (dispatcher, router, HTTP endpoints) rides."""

    def test_nothing_warm_exports_none(self):
        store = _warm_store()
        assert store.export_state("nope", schema=SCHEMA) is None
        store.get_or_create("stateless")  # session exists, no frame yet
        assert store.export_state("stateless", schema=SCHEMA) is None

    def test_roundtrip_is_bitwise_and_copies(self):
        store = _warm_store("cam0", next_seq=3)
        snap = store.export_state("cam0", schema=SCHEMA)
        assert snap["version"] == STATE_VERSION
        assert snap["schema"]["bucket"] == [60, 90]
        dst = SessionStore(limit=4, ttl_s=60.0)
        assert dst.import_state(snap, schema=SCHEMA) == "warm"
        sess, created = dst.get_or_create("cam0")
        assert not created
        with sess.lock:
            np.testing.assert_array_equal(sess.prev_disp_low,
                                          snap["prev_disp_low"])
            assert sess.prev_disp_low.dtype == np.float32
            assert (sess.next_seq, sess.frame_idx) == (3, 3)
            assert sess.bucket_hw == (60, 90)
            assert (sess.ema, sess.level) == (0.25, 2)
            assert (sess.warm_frames, sess.cold_frames) == (2, 1)

    def test_mismatch_is_cold_schema_never_error(self):
        store = _warm_store()
        snap = store.export_state("cam0", schema=SCHEMA)
        dst = SessionStore(limit=4, ttl_s=60.0)
        mismatched = dict(SCHEMA, factor=8)
        assert dst.import_state(snap, schema=mismatched) == "cold_schema"
        assert len(dst) == 0  # nothing installed
        assert dst.import_state(dict(snap, version=99),
                                schema=SCHEMA) == "cold_schema"
        assert dst.import_state({}, schema=SCHEMA) == "cold_schema"
        assert dst.import_state(dict(snap, prev_disp_low="junk"),
                                schema=SCHEMA) == "cold_schema"
        # A differing BUCKET rides along informationally, not as a gate:
        # the engine keys agree, so the import is warm (a bucket change
        # re-buckets cold at the next frame anyway — runner policy).
        rebucketed = dict(snap, schema=dict(snap["schema"],
                                            bucket=[120, 180]))
        assert dst.import_state(rebucketed, schema=SCHEMA) == "warm"

    def test_monotonic_guard_keeps_fresher_state(self):
        store = _warm_store("s", next_seq=5)
        snap = store.export_state("s", schema=SCHEMA)
        sess, _ = store.get_or_create("s")
        with sess.lock:
            sess.next_seq = 7  # frames kept landing after the export
            sess.ema = 0.9
        # Re-importing the stale snapshot (drain sweep racing a per-frame
        # handoff) must not rewind: a rewound next_seq would turn the
        # client's next in-order frame into an out_of_order cold frame.
        assert store.import_state(snap, schema=SCHEMA) == "warm"
        with sess.lock:
            assert (sess.next_seq, sess.ema) == (7, 0.9)

    def test_wire_form_roundtrip_is_bitwise(self):
        store = _warm_store()
        snap = store.export_state("cam0", schema=SCHEMA)
        wire = json.loads(json.dumps(snapshot_to_wire(snap)))
        back = wire_to_snapshot(wire)
        np.testing.assert_array_equal(back["prev_disp_low"],
                                      snap["prev_disp_low"])
        assert back["prev_disp_low"].dtype == np.float32
        assert back["bucket_hw"] == (60, 90)
        dst = SessionStore(limit=4, ttl_s=60.0)
        assert dst.import_state(back, schema=SCHEMA) == "warm"


class TestDispatcherMigration:
    def test_drain_window_race_repins_warm(self):
        """Satellite fix: a frame arriving AFTER drain() but BEFORE the
        proactive sweep re-pins with a warm handoff — the drain window
        costs zero cold frames, not just the planned sweep."""
        r0, r1 = StoreStubReplica(0), StoreStubReplica(1)
        d, _ = _dispatcher([r0, r1])
        assert d.step("cam0", 0, _img(), _img()).replica == "r0"
        ref = _seed_state(r0, "cam0", next_seq=1)
        r0.drain()  # drain marked; the sweep has NOT run yet
        res = d.step("cam0", 1, _img(), _img())
        assert res.replica == "r1"
        reasons = {lv: c.value
                   for lv, c in d.cluster_metrics.session_repins.series()}
        assert reasons == {("draining",): 1}
        outs = {lv: c.value
                for lv, c in d.cluster_metrics.session_handoffs.series()}
        assert outs == {("warm",): 1}
        sess, created = r1.store.get_or_create("cam0")
        assert not created
        with sess.lock:
            np.testing.assert_array_equal(sess.prev_disp_low, ref)
            assert (sess.next_seq, sess.ema) == (1, 0.5)

    def test_drain_replica_sweep_migrates_before_frames(self):
        """drain_replica (the rolling-restart verb): every session on
        the draining replica — pinned or state-only straggler — moves
        warm, pins follow the state, and the next frames run on the new
        home WITHOUT counting a repin."""
        r0, r1 = StoreStubReplica(0), StoreStubReplica(1, outstanding=9)
        d, _ = _dispatcher([r0, r1])
        assert d.step("camA", 0, _img(), _img()).replica == "r0"
        assert d.step("camB", 0, _img(), _img()).replica == "r0"
        refs = {"camA": _seed_state(r0, "camA", salt=1.0),
                "camB": _seed_state(r0, "camB", salt=2.0)}
        _seed_state(r0, "ghost", salt=3.0)  # state survives, pin gone
        report = d.drain_replica(0)
        assert report["migrated"] == {"camA": "warm", "camB": "warm",
                                      "ghost": "warm"}
        outs = {lv: c.value
                for lv, c in d.cluster_metrics.session_handoffs.series()}
        assert outs == {("warm",): 3}
        for sid, ref in refs.items():
            assert d._pins.peek(sid) == 1
            sess, created = r1.store.get_or_create(sid)
            assert not created
            with sess.lock:
                np.testing.assert_array_equal(sess.prev_disp_low, ref)
        assert d.step("camA", 1, _img(), _img()).replica == "r1"
        assert d.cluster_metrics.session_repins.value == 0

    def test_schema_mismatch_handoff_is_cold_schema(self):
        r0 = StoreStubReplica(0)
        r1 = StoreStubReplica(1, schema=dict(SCHEMA, input_mode="sl"))
        d, _ = _dispatcher([r0, r1])
        assert d.step("cam0", 0, _img(), _img()).replica == "r0"
        _seed_state(r0, "cam0")
        r0._state = "failed"
        assert d.step("cam0", 1, _img(), _img()).replica == "r1"
        reasons = {lv: c.value
                   for lv, c in d.cluster_metrics.session_repins.series()}
        assert reasons == {("failed",): 1}
        outs = {lv: c.value
                for lv, c in d.cluster_metrics.session_handoffs.series()}
        assert outs == {("cold_schema",): 1}
        # Nothing installed on the new home: the next frame runs cold
        # and re-establishes state there (documented fallback).
        _, created = r1.store.get_or_create("cam0")
        assert created

    def test_export_import_seam_through_wire_form(self):
        """The dispatcher half of the HTTP endpoints: export resolves
        the pinned replica, import installs on a ready one and re-pins
        so the next frame is sticky without counting a repin."""
        r0, r1 = StoreStubReplica(0), StoreStubReplica(1, outstanding=9)
        d, _ = _dispatcher([r0, r1])
        assert d.step("cam0", 0, _img(), _img()).replica == "r0"
        ref = _seed_state(r0, "cam0")
        assert d.export_session("nope") is None
        snap = d.export_session("cam0")
        assert snap is not None and snap["session_id"] == "cam0"
        wire = json.loads(json.dumps(snapshot_to_wire(snap)))
        r0._state = "failed"
        assert d.import_session(wire_to_snapshot(wire)) == "warm"
        assert d._pins.peek("cam0") == 1  # re-pinned to the importer
        sess, created = r1.store.get_or_create("cam0")
        assert not created
        with sess.lock:
            np.testing.assert_array_equal(sess.prev_disp_low, ref)
        assert d.step("cam0", 1, _img(), _img()).replica == "r1"
        assert d.cluster_metrics.session_repins.value == 0


class TestAutoscale:
    def test_recommend_directions(self):
        p = AutoscalePolicy()
        assert recommend(p, ready=0, utilization=1.0)[0] == 0
        assert recommend(p, ready=2, utilization=0.9)[0] == 1
        assert recommend(p, ready=2, utilization=0.5)[0] == 0
        assert recommend(p, ready=2, utilization=0.5, occupancy=0.9)[0] \
            == 1
        assert recommend(p, ready=2, utilization=0.1)[0] == -1
        # min_replicas floor: never advise scaling in the last replica.
        assert recommend(p, ready=1, utilization=0.0)[0] == 0
        # Sheds dominate: refused traffic means scale out even when the
        # utilization gauge looks idle.
        assert recommend(p, ready=2, utilization=0.1, shed_delta=3)[0] \
            == 1

    def test_hysteresis_damps_and_sheds_fire_immediately(self):
        a = Autoscaler()
        assert a.observe(ready=2, utilization=0.9)["action"] == "hold"
        second = a.observe(ready=2, utilization=0.9)
        assert (second["action"], second["delta"]) == ("scale_up", 1)
        b = Autoscaler()
        adv = b.observe(ready=2, utilization=0.1, shed_total=5)
        assert adv["action"] == "scale_up"  # no streak needed
        assert adv["signals"]["shed_delta"] == 5.0
        # The shed signal is a counter DELTA: an unchanged total is not
        # a new shed.
        adv = b.observe(ready=2, utilization=0.5, shed_total=5)
        assert adv["action"] == "hold"
        assert adv["signals"]["shed_delta"] == 0.0

    def test_scale_down_clamped_at_min_replicas(self):
        a = Autoscaler()
        for _ in range(2):
            adv = a.observe(ready=2, utilization=0.0)
        assert (adv["action"], adv["delta"]) == ("scale_down", -1)
        b = Autoscaler()
        for _ in range(5):
            adv = b.observe(ready=1, utilization=0.0)
        assert (adv["action"], adv["delta"]) == ("hold", 0)


class TestKillBackendFault:
    def test_fires_exactly_once_at_n(self):
        plan = FaultPlan.parse("kill_backend@request=3")
        fired = [n for n in range(1, 6) if plan.on_request(n)]
        assert fired == [3]
        assert not plan.on_request(3)  # consumed: deterministic, once

    def test_wrong_dim_rejected(self):
        with pytest.raises(ValueError):
            FaultPlan.parse("kill_backend@step=3")


class TestReplicaLifecycle:
    """Real Replica state machine — no device work (warmup never runs,
    the engine compiles nothing)."""

    def _replica(self):
        return Replica(0, None, None, {}, _cfg(), ServeMetrics(),
                       fail_threshold=3)

    def test_consecutive_errors_mark_failed(self):
        r = self._replica()
        try:
            r.mark_ready()
            for _ in range(2):
                r.begin_dispatch()
                r.end_dispatch(ok=False)
            assert r.state == "ready"  # below threshold
            r.begin_dispatch()
            r.end_dispatch(ok=True)  # success resets the streak
            for _ in range(3):
                r.begin_dispatch()
                r.end_dispatch(ok=False)
            assert r.state == "failed"
        finally:
            r.stop()

    def test_drain_resolves_to_drained_when_idle(self):
        r = self._replica()
        try:
            r.mark_ready()
            r.begin_dispatch()
            r.drain()
            assert r.state == "draining" and not r.routable()
            r.end_dispatch(ok=True)
            assert r.state == "drained"
        finally:
            r.stop()


# ------------------------------------------- future-resolution lock safety

class TestResolveOutsideLocks:
    """The dispatcher's settle callback reads queue depths across ALL
    replicas (_refresh_gauges), so the batcher/scheduler must never
    resolve a future while holding their own ``_cv`` — two replica
    workers doing so concurrently is an ABBA deadlock (see
    batcher.Future._resolve).  Each test registers a done-callback that
    proves the lock is released and the depth readable at callback
    time."""

    class _Eng:
        def bucket_of(self, shape):
            return (64, 96)

    def test_batcher_stop_fails_queued_outside_its_lock(self):
        b = DynamicBatcher(self._Eng(), _cfg(cluster=None))
        fut = b.submit(_img(), _img())
        held = []
        fut.add_done_callback(
            lambda f: held.append((b._cv._is_owned(), b.queue_depth)))
        b.stop(drain=False)  # worker never started: stop resolves here
        assert held == [(False, 0)]
        with pytest.raises(ShuttingDown):
            fut.result(0)

    def test_scheduler_stop_fails_queued_outside_its_lock(self):
        cfg = _cfg(cluster=None,
                   sched=SchedConfig(iters_per_step=2, max_iters=8))
        s = IterationScheduler(self._Eng(), cfg, ServeMetrics())
        fut = s.submit(_img(), _img(), iters=4)
        held = []
        fut.add_done_callback(
            lambda f: held.append((s._cv._is_owned(), s.queue_depth)))
        s.stop(drain=False)
        assert held == [(False, 0)]
        with pytest.raises(ShuttingDown):
            fut.result(0)

    def test_scheduler_queue_timeout_resolves_outside_its_lock(self):
        t = [0.0]
        cfg = _cfg(cluster=None, request_timeout_ms=10.0,
                   sched=SchedConfig(iters_per_step=2, max_iters=8))
        s = IterationScheduler(self._Eng(), cfg, ServeMetrics(),
                               now_fn=lambda: t[0])
        fut = s.submit(_img(), _img(), iters=4)
        held = []
        fut.add_done_callback(
            lambda f: held.append((s._cv._is_owned(), s.queue_depth)))
        t[0] += 1.0  # way past the 10 ms queue timeout
        s.run_once()  # worker not started; drive one round directly
        assert held == [(False, 0)]
        with pytest.raises(RequestTimedOut):
            fut.result(0)


# ---------------------------------------------------- cluster e2e (devices)

class TestClusterEndToEnd:
    def test_two_replica_cluster_mixed_traffic(self, cluster_model,
                                               retrace_guard):
        """THE acceptance gate (ISSUE 8): mixed cold + session + sched
        traffic on a 2-replica CPU cluster, bitwise vs single-engine,
        sticky sessions, zero-compile steady state, degraded (not dead)
        on replica failure, drain to completion, validator-clean
        /metrics."""
        from raftstereo_tpu.obs import validate_prometheus

        model, variables = cluster_model
        cfg = _cfg(warmup=True, queue_limit=32,
                   sched=SchedConfig(iters_per_step=2, max_iters=8),
                   stream=StreamConfig(ladder=(4, 2)))
        metrics = ServeMetrics()
        # Warmup compiles the 4 phase executables on EACH replica's
        # device: 8 total.  The monolithic single-engine reference (the
        # bitwise baseline) is hoisted here too, so the traffic below
        # runs under a ZERO-compile budget.
        with retrace_guard(9, what="4 sched phases x 2 replicas + 1 "
                                   "monolithic reference",
                           min_duration_s=0.5):
            server = build_server(model, variables, cfg, metrics)
            ref_engine = BatchEngine(model, variables,
                                     _cfg(max_batch_size=2))
            a, b = _img(60, 90, 1), _img(60, 90, 2)
            # both rows filled: the program shape of the replicas'
            # two-slot batches
            ref_cold = ref_engine.infer_batch([(a, b)] * 2, 4)[0]
        assert server.is_ready
        port = server.port
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            with retrace_guard(0, what="cluster steady state is "
                                       "compile-free on every replica",
                               min_duration_s=0.5):
                results, errors = [], []

                def send_cold(i):
                    try:
                        client = ServeClient("127.0.0.1", port,
                                             timeout=120)
                        disp, meta = client.predict(a, b)
                        results.append((disp, meta))
                        client.close()
                    except Exception as e:  # pragma: no cover
                        errors.append(e)

                def send_sched(i):
                    try:
                        client = ServeClient("127.0.0.1", port,
                                             timeout=120)
                        disp, meta = client.predict(a, b, iters=8,
                                                    priority="high")
                        assert meta["iters"] == 8
                        assert meta["replica"] in ("r0", "r1")
                        client.close()
                    except Exception as e:  # pragma: no cover
                        errors.append(e)

                session_meta = {s: [] for s in ("camA", "camB")}

                def send_session(sid):
                    try:
                        client = ServeClient("127.0.0.1", port,
                                             timeout=120)
                        for seq in range(3):
                            disp, meta = client.predict(
                                a, b, session_id=sid, seq_no=seq)
                            session_meta[sid].append(meta)
                        client.close()
                    except Exception as e:  # pragma: no cover
                        errors.append(e)

                threads = [threading.Thread(target=send_cold, args=(i,))
                           for i in range(4)]
                threads += [threading.Thread(target=send_sched, args=(i,))
                            for i in range(2)]
                threads += [threading.Thread(target=send_session,
                                             args=(sid,))
                            for sid in ("camA", "camB")]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(120)
                assert not errors, errors

                # Bitwise: every cold answer equals the single-engine
                # monolithic baseline, whichever replica computed it
                # (PR 7 established sched == monolithic; this extends it
                # across devices).
                assert len(results) == 4
                replicas_used = set()
                for disp, meta in results:
                    np.testing.assert_array_equal(disp, ref_cold)
                    replicas_used.add(meta["replica"])
                assert replicas_used <= {"r0", "r1"}

                # Session stickiness: all frames of one session answered
                # by ONE replica, warm from frame 1.
                for sid, metas in session_meta.items():
                    assert len(metas) == 3
                    assert len({m["replica"] for m in metas}) == 1, metas
                    assert [m["warm"] for m in metas] == [False, True,
                                                          True]
                # First frames are cold == the monolithic baseline too
                # (cold session frames run the same program).
                # (Disparity equality is covered by the cold results
                # above; here the scheduling route is what differs.)

            # Replica failure degrades, never hangs: fail r0, traffic
            # continues on r1 (still compile-free — r1 is warm).
            server.cluster.rset.replicas[0].mark_failed("test kill")
            with retrace_guard(0, what="failover traffic reuses the "
                                       "survivor's warm executables",
                               min_duration_s=0.5):
                client = ServeClient("127.0.0.1", port, timeout=120)
                for _ in range(2):
                    disp, meta = client.predict(a, b)
                    assert meta["replica"] == "r1"
                    np.testing.assert_array_equal(disp, ref_cold)
                health = client.healthz()
                assert health["cluster"]["states"]["failed"] == 1
                assert health["cluster"]["states"]["ready"] == 1
                assert health["ready"] is True

                # /metrics: validator-clean with the cluster_* families
                # populated per replica.
                text = client.metrics_text()
                assert validate_prometheus(text) == []
                assert 'cluster_replicas{state="failed"} 1' in text
                assert 'cluster_dispatch_total{replica="r0",outcome="ok"}' \
                    in text
                assert 'cluster_dispatch_total{replica="r1",outcome="ok"}' \
                    in text
                assert any(l.startswith("cluster_queue_depth{")
                           for l in text.splitlines())
                assert any(l.startswith("cluster_utilization ")
                           for l in text.splitlines())

                # Drain: stop admitting, finish everything, report
                # drained; new work gets a clean 503.
                status, raw, _ = client._request("POST", "/debug/drain")
                assert status == 200 and json.loads(raw)["draining"]
                deadline = time.perf_counter() + 10
                while time.perf_counter() < deadline:
                    if client.healthz()["drained"]:
                        break
                    time.sleep(0.05)
                health = client.healthz()
                assert health["drained"] and not health["ready"]
                with pytest.raises(ServeError) as ei:
                    client.predict(a, b)
                assert ei.value.status == 503
                assert "draining" in ei.value.payload["detail"]
                client.close()
        finally:
            server.close()
            thread.join(10)


# ------------------------------------------------------------ router e2e

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TestRouter:
    def _backend(self, cluster_model, warmup_async=False, port=0,
                 stream=None):
        model, variables = cluster_model
        cfg = _cfg(warmup=True, iters=2, degraded_iters=2, port=port,
                   stream=stream or StreamConfig(ladder=(2, 1)),
                   stream_warmup=True, cluster=None)
        srv = build_server(model, variables, cfg,
                           warmup_async=warmup_async)
        th = threading.Thread(target=srv.serve_forever, daemon=True)
        th.start()
        return srv, th

    def test_router_readiness_stickiness_failover_drain(self,
                                                        cluster_model):
        """One sequenced scenario over two real backends (compiles are
        the expensive part; pay each backend's warmup once)."""
        from raftstereo_tpu.obs import validate_prometheus

        b0, t0 = self._backend(cluster_model)  # blocking warmup: ready
        b1, t1 = self._backend(cluster_model, warmup_async=True)
        # Satellite: live vs ready on the single server.  b1 is LIVE
        # immediately (healthz answers) but NOT READY until its warmup
        # compiles finish — and /predict says so with a 503 instead of
        # silently paying the cold compile.
        c1 = ServeClient("127.0.0.1", b1.port)
        h = c1.healthz()
        if not h["ready"]:  # warmup takes seconds; guard a fast machine
            assert h["live"] is True and h["status"] == "ok"
            with pytest.raises(ServeError) as ei:
                c1.predict(_img(), _img())
            assert ei.value.status == 503
            assert "not ready" in ei.value.payload["detail"]
        router = build_router(RouterConfig(
            port=0, backends=(("127.0.0.1", b0.port),
                              ("127.0.0.1", b1.port)),
            probe_interval_s=0.15, fail_after=1, retries=2,
            retry_backoff_ms=20.0, request_timeout_s=60.0))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        client = ServeClient("127.0.0.1", router.port, timeout=120,
                             retries=2)
        try:
            # Router is ready as soon as ONE backend is (b0 warmed
            # synchronously); b1 joins rotation when its probe flips.
            assert client.healthz()["ready"] is True
            a = _img(60, 90, 3)
            disp, meta = client.predict(a, a)
            assert meta["backend"] == "b0" or b1.is_ready
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                h = client.healthz()
                if h["backends"]["b1"]["state"] == "ready":
                    break
                time.sleep(0.1)
            assert h["backends"]["b1"]["state"] == "ready"

            # Session stickiness over the wire: one backend serves every
            # frame, warm from frame 1.
            backends_seen, warm = set(), []
            for seq in range(4):
                disp, meta = client.predict(a, a, session_id="cam0",
                                            seq_no=seq)
                backends_seen.add(meta["backend"])
                warm.append(meta["warm"])
            assert len(backends_seen) == 1
            assert warm == [False, True, True, True]
            victim_name = backends_seen.pop()
            victim = b0 if victim_name == "b0" else b1
            survivor_name = "b1" if victim_name == "b0" else "b0"

            # Kill the session's backend MID-LOAD: cold requests keep
            # succeeding (failover; zero accepted-request loss) ...
            results, errors = [], []

            def send(i):
                try:
                    c = ServeClient("127.0.0.1", router.port, timeout=120)
                    d, m = c.predict(a, a)
                    results.append(m["backend"])
                    c.close()
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            threads = [threading.Thread(target=send, args=(i,))
                       for i in range(6)]
            for i, t in enumerate(threads):
                t.start()
                if i == 1:
                    victim.close()  # die with 4 requests still to come
            for t in threads:
                t.join(120)
            assert not errors, errors
            assert len(results) == 6  # zero lost cold requests
            # ... and the NEXT session frame re-pins: answered (200) by
            # the survivor as a cold frame — degraded, never an error.
            disp, meta = client.predict(a, a, session_id="cam0", seq_no=4)
            assert meta["backend"] == survivor_name
            assert meta["warm"] is False
            # The prober notices the corpse and /metrics stays valid.
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                h = client.healthz()
                if h["backends"][victim_name]["state"] == "unreachable":
                    break
                time.sleep(0.1)
            assert h["backends"][victim_name]["state"] == "unreachable"
            text = client.metrics_text()
            assert validate_prometheus(text) == []
            assert 'cluster_replicas{state="unreachable"} 1' in text
            assert f'cluster_dispatch_total{{replica="{survivor_name}"' \
                   f',outcome="ok"}}' in text

            # Drain the survivor through the router: the backend reports
            # drained (everything admitted finished), and with no ready
            # backend left the router answers a clean 503 — it never
            # hangs.
            status, raw, _ = client._request(
                "POST", "/debug/drain",
                json.dumps({"backend": survivor_name}).encode())
            assert status == 200
            reply = json.loads(raw)
            assert reply["drain"]["draining"] is True
            deadline = time.perf_counter() + 10
            survivor = b1 if victim_name == "b0" else b0
            while time.perf_counter() < deadline:
                if survivor.drained:
                    break
                time.sleep(0.05)
            assert survivor.drained
            t_start = time.perf_counter()
            c2 = ServeClient("127.0.0.1", router.port, timeout=30)
            with pytest.raises(ServeError) as ei:
                c2.predict(a, a)
            assert ei.value.status == 503
            assert time.perf_counter() - t_start < 10  # clean, not a hang
            c2.close()
        finally:
            client.close()
            c1.close()
            router.close()
            rt.join(10)
            for srv, th in ((b0, t0), (b1, t1)):
                try:
                    srv.close()
                except Exception:
                    pass
                th.join(5)

    def test_router_streams_binary_without_buffering(self, cluster_model):
        """Tentpole assertion (docs/wire_format.md "Router forwarding"):
        a binary /predict larger than the 64 KiB pump window crosses the
        router bitwise-correct while the router's peak per-request
        buffer stays AT OR UNDER one WIRE_CHUNK — instrumented via
        ``stream_stats()``, so "never buffers the full body" is a
        measured number, not a code-reading claim.  Also pins the
        session route off the streamed frame's meta and keeps the legacy
        JSON dialect working through the same router."""
        from raftstereo_tpu.serve.httpbase import WIRE_CHUNK

        b0, t0 = self._backend(cluster_model)
        router = build_router(RouterConfig(
            port=0, backends=(("127.0.0.1", b0.port),),
            probe_interval_s=0.15, fail_after=1, retries=1,
            retry_backoff_ms=20.0, request_timeout_s=60.0))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        # Non-integer pixels defeat the codec's uint8-exact demotion and
        # compress=False keeps the planes raw: two 60x90x3 f32 planes
        # ≈ 127 KiB of body — comfortably more than one chunk, so a
        # buffering regression would show up in peak_chunk_bytes
        # immediately.
        a = _img(60, 90, 3) + 0.5
        client = ServeClient("127.0.0.1", router.port, timeout=120,
                             compress=False)
        direct = ServeClient("127.0.0.1", b0.port, timeout=120)
        json_client = ServeClient("127.0.0.1", router.port, timeout=120,
                                  wire_format="json")
        try:
            disp, meta = client.predict(a, a)
            assert meta["backend"] == "b0"
            ref, _ = direct.predict(a, a)
            np.testing.assert_array_equal(disp, ref)
            assert client.bytes_sent > WIRE_CHUNK  # body spans chunks
            stats = router.stream_stats()
            assert stats["requests"] >= 1
            assert 0 < stats["peak_chunk_bytes"] <= WIRE_CHUNK, stats
            # Session pinning reads session_id out of the streamed
            # frame's meta block (never the decoded planes).
            for seq in range(2):
                _, m = client.predict(a, a, session_id="scam0", seq_no=seq)
                assert m["backend"] == "b0"
            assert router.pin_count() >= 1
            # JSON dialect through the same router: the relay must hand
            # back the backend's Content-Type, not assume one.
            dj, mj = json_client.predict(a, a)
            np.testing.assert_array_equal(dj, ref)
            # The stream counters are scrapeable and label by direction.
            text = router.cluster_metrics.render()
            assert 'cluster_wire_stream_bytes_total{direction="in"}' \
                in text
            assert "cluster_wire_stream_peak_chunk_bytes" in text
        finally:
            client.close()
            direct.close()
            json_client.close()
            router.close()
            rt.join(10)
            b0.close()
            t0.join(5)

    def test_zero_downtime_restart_and_kill(self, cluster_model,
                                            retrace_guard):
        """THE acceptance gate (ISSUE 13): zero-downtime cluster ops
        under sequence-replay load through the router over two real
        backends.

        (a) ``POST /debug/restart`` drains a backend, migrates its
        pinned sessions WARM — bitwise-identical to a twin session that
        never moved — loses zero accepted requests, and the whole
        drain -> handoff -> serve-on-the-survivor path compiles NOTHING
        (migration is pure host numpy).  The operator's half (rebuild at
        the same address with ``warmup_async``) rejoins through the
        readiness probe, and post-rejoin steady state also holds a
        zero-compile budget.

        (b) an unplanned kill (fault-hook-scheduled, so the kill point
        is deterministic) costs at most the documented ``cold_lost``
        fallback: the orphaned session's next frame runs cold on the
        survivor — never an error, never a hang.
        """
        from raftstereo_tpu.obs import validate_prometheus

        b0, t0 = self._backend(cluster_model)
        b1, t1 = self._backend(cluster_model)
        ports = {"b0": b0.port, "b1": b1.port}
        servers = {"b0": (b0, t0), "b1": (b1, t1)}
        router = build_router(RouterConfig(
            port=0, backends=(("127.0.0.1", b0.port),
                              ("127.0.0.1", b1.port)),
            probe_interval_s=0.15, fail_after=1, retries=2,
            retry_backoff_ms=20.0, request_timeout_s=60.0))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        client = ServeClient("127.0.0.1", router.port, timeout=120,
                             retries=2)
        frames = [_img(60, 90, 100 + i) for i in range(6)]
        try:
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                h = client.healthz()
                if all(h["backends"][n]["state"] == "ready"
                       for n in ("b0", "b1")):
                    break
                time.sleep(0.1)
            assert h["backends"]["b0"]["state"] == "ready"
            assert h["backends"]["b1"]["state"] == "ready"

            # Pre-pay both backends' cold + warm stream paths OUTSIDE
            # the guards (direct, bypassing the router) so the budgets
            # below measure migration, not leftover warmup gaps.
            for name, (srv, _th) in servers.items():
                direct = ServeClient("127.0.0.1", srv.port, timeout=120)
                direct.predict(frames[0], frames[0])
                for seq in range(2):
                    direct.predict(frames[seq], frames[seq],
                                   session_id=f"prewarm-{name}",
                                   seq_no=seq)
                direct.close()

            # The session that will migrate: 3 frames via the router.
            mig_meta = []
            for seq in range(3):
                _, meta = client.predict(frames[seq], frames[seq],
                                         session_id="mig", seq_no=seq)
                mig_meta.append(meta)
            assert [m["warm"] for m in mig_meta] == [False, True, True]
            assert len({m["backend"] for m in mig_meta}) == 1
            victim_name = mig_meta[0]["backend"]
            survivor_name = "b1" if victim_name == "b0" else "b0"
            victim, victim_thread = servers[victim_name]
            survivor, _st = servers[survivor_name]

            # The unmigrated TWIN: the same 6 frames as one
            # uninterrupted session DIRECTLY on the survivor — the
            # bitwise reference for "a warm handoff is indistinguishable
            # from having stayed".
            twin = ServeClient("127.0.0.1", survivor.port, timeout=120)
            twin_disp = []
            for seq in range(6):
                dsp, meta = twin.predict(frames[seq], frames[seq],
                                         session_id="twin", seq_no=seq)
                twin_disp.append(dsp)
            assert meta["warm"] is True
            twin.close()

            # ---- (a) drain-and-restart under sequence-replay load:
            # zero compiles, zero lost accepted requests, zero cold
            # frames beyond each sequence's head.
            with retrace_guard(0, what="restart = drain + warm handoff "
                                       "+ serve on the survivor; "
                                       "migration is host-side numpy",
                               min_duration_s=0.5):
                load = {}

                def _load():
                    load.update(run_load(
                        "127.0.0.1", router.port,
                        lambda i: (frames[i % 4], frames[i % 4]),
                        requests=32, concurrency=3, sequence_len=4,
                        timeout=120, retries=2))

                lt = threading.Thread(target=_load)
                lt.start()
                time.sleep(0.2)  # let sequences land on both backends
                status, raw, _ = client._request(
                    "POST", "/debug/restart",
                    json.dumps({"backend": victim_name}).encode())
                assert status == 200, raw
                reply = json.loads(raw)
                assert reply["drained"] is True
                assert reply["migrated"].get("mig") == "warm", reply
                lt.join(120)
                # Zero lost accepted requests: every load frame answered
                # 200 (client retries ride out the drain window); cold
                # only at each sequence head, so migrated mid-sequence
                # sessions stayed warm.
                assert load["ok"] == 32, load
                assert load["cold_frames"] == 32 // 4, load
                assert load["warm_frames"] == 32 - 32 // 4, load

                # The migrated session: warm on the survivor and
                # bitwise-identical to the twin that never moved.
                for seq in range(3, 6):
                    dsp, meta = client.predict(frames[seq], frames[seq],
                                               session_id="mig",
                                               seq_no=seq)
                    assert meta["backend"] == survivor_name, meta
                    assert meta["warm"] is True, meta
                    np.testing.assert_array_equal(dsp, twin_disp[seq])

            text = client.metrics_text()
            assert validate_prometheus(text) == []
            assert 'cluster_session_handoffs_total{outcome="warm"}' \
                in text

            # ---- operator's half: rebuild the victim at the SAME
            # address with warmup_async (compiles paid OUTSIDE the
            # steady-state guard), readiness probe gates the rejoin.
            victim.close()
            victim_thread.join(10)
            servers[victim_name] = self._backend(
                cluster_model, warmup_async=True,
                port=ports[victim_name])
            deadline = time.perf_counter() + 120
            while time.perf_counter() < deadline:
                h = client.healthz()
                if h["backends"][victim_name]["state"] == "ready":
                    break
                time.sleep(0.1)
            assert h["backends"][victim_name]["state"] == "ready"

            # Steady state after the rejoin: still zero compiles.
            with retrace_guard(0, what="post-rejoin steady state reuses "
                                       "warm executables on both "
                                       "backends",
                               min_duration_s=0.5):
                for _ in range(4):
                    _, meta = client.predict(frames[0], frames[0])
                    assert meta["backend"] in ("b0", "b1")
                _, meta = client.predict(frames[0], frames[0],
                                         session_id="mig", seq_no=6)
                assert meta["warm"] is True

            # ---- (b) kill, no drain: the fault hook picks the moment;
            # the orphaned session's next frame is the documented
            # cold_lost fallback, served by the survivor.
            plan = FaultPlan.parse("kill_backend@request=2")
            warm_seen, chaos_home = [], None
            for seq in range(5):
                _, meta = client.predict(frames[seq % 4], frames[seq % 4],
                                         session_id="chaos", seq_no=seq)
                warm_seen.append(meta["warm"])
                if seq == 0:
                    chaos_home = meta["backend"]
                if plan.on_request(seq + 1):
                    srv, th = servers[chaos_home]
                    srv.close()  # SIGKILL stand-in: no drain, no sweep
                    th.join(10)
            assert warm_seen == [False, True, False, True, True]
            text = client.metrics_text()
            assert validate_prometheus(text) == []
            assert 'cluster_session_handoffs_total{outcome="cold_lost"}' \
                in text
            assert 'cluster_session_repins_total{reason="failed"}' \
                in text
        finally:
            client.close()
            router.close()
            rt.join(10)
            for srv, th in servers.values():
                try:
                    srv.close()
                except Exception:
                    pass
                th.join(5)

    def test_durable_tier_warm_resume_and_outage(self, cluster_model,
                                                 retrace_guard):
        """THE acceptance gate (ISSUE 18): chaos-certified durable
        sessions over a shared external session tier
        (docs/streaming.md "Durable sessions").

        (a) the home backend is SIGKILLed (``close()`` — no drain, no
        handoff sweep) and the orphaned session's next frame resumes
        WARM on the survivor from the tier's write-behind snapshot —
        bitwise-identical to a twin that never moved, zero cold frames
        for the migrated session, ``session_handoffs{outcome="warm"}``,
        zero compiles (the resume is pure host numpy);

        (b) a ``tier_outage`` armed mid-replay costs ZERO request
        errors: frames keep answering warm (the tier is never on the
        request path), the survivor's publisher detaches and counts
        ``stream_tier_degraded_total``, and once the outage window ends
        it re-attaches and the tier catches back up to the session's
        latest state — nothing is lost.
        """
        from raftstereo_tpu.obs import validate_prometheus
        from raftstereo_tpu.stream.tier import (TierClient,
                                                build_session_tier)

        tier = build_session_tier(TierConfig(port=0))
        tt = threading.Thread(target=tier.serve_forever, daemon=True)
        tt.start()
        tier_addr = ("127.0.0.1", tier.port)
        # Tight client budgets so the outage window below actually
        # defeats the push (timeout 0.5s x 2 attempts < 2s outage) and
        # the re-probe lands fast after it lifts.
        stream_cfg = StreamConfig(ladder=(2, 1), tier=tier_addr,
                                  tier_timeout_s=0.5, tier_retries=1,
                                  tier_backoff_ms=10.0,
                                  tier_reprobe_s=0.2)
        b0, t0 = self._backend(cluster_model, stream=stream_cfg)
        b1, t1 = self._backend(cluster_model, stream=stream_cfg)
        servers = {"b0": (b0, t0), "b1": (b1, t1)}
        router = build_router(RouterConfig(
            port=0, backends=(("127.0.0.1", b0.port),
                              ("127.0.0.1", b1.port)),
            probe_interval_s=0.15, fail_after=1, retries=2,
            retry_backoff_ms=20.0, request_timeout_s=60.0,
            session_tier=tier_addr))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        client = ServeClient("127.0.0.1", router.port, timeout=120,
                             retries=2)
        frames = [_img(60, 90, 200 + i) for i in range(6)]
        try:
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                h = client.healthz()
                if all(h["backends"][n]["state"] == "ready"
                       for n in ("b0", "b1")):
                    break
                time.sleep(0.1)
            assert h["backends"]["b0"]["state"] == "ready"
            assert h["backends"]["b1"]["state"] == "ready"

            # Pre-pay both backends' cold + warm stream paths outside
            # the retrace guards (same idiom as the PR 13 gate).
            for name, (srv, _th) in servers.items():
                direct = ServeClient("127.0.0.1", srv.port, timeout=120)
                direct.predict(frames[0], frames[0])
                for seq in range(2):
                    direct.predict(frames[seq], frames[seq],
                                   session_id=f"prewarm-{name}",
                                   seq_no=seq)
                direct.close()

            # The session that will lose its home: 3 frames via the
            # router, then make sure the write-behind push landed.
            mig_meta = []
            for seq in range(3):
                _, meta = client.predict(frames[seq], frames[seq],
                                         session_id="mig", seq_no=seq)
                mig_meta.append(meta)
            assert [m["warm"] for m in mig_meta] == [False, True, True]
            victim_name = mig_meta[0]["backend"]
            survivor_name = "b1" if victim_name == "b0" else "b0"
            victim, victim_thread = servers[victim_name]
            survivor, _st = servers[survivor_name]
            assert victim.tier_publisher is not None
            assert victim.tier_publisher.flush(timeout_s=30)
            assert tier.store.get("mig") is not None
            vc = ServeClient("127.0.0.1", victim.port, timeout=30)
            assert vc.healthz()["stream"]["tier"]["attached"] is True
            vc.close()

            # The unkilled TWIN on the survivor: the bitwise reference.
            twin = ServeClient("127.0.0.1", survivor.port, timeout=120)
            twin_disp = []
            for seq in range(6):
                dsp, _m = twin.predict(frames[seq], frames[seq],
                                       session_id="twin", seq_no=seq)
                twin_disp.append(dsp)
            twin.close()

            # ---- (a) SIGKILL the home backend: the next frames resume
            # WARM from the tier on the survivor — zero cold frames for
            # the migrated session, bitwise == the unkilled twin, zero
            # compiles.
            victim.close()  # SIGKILL stand-in: no drain, no sweep
            victim_thread.join(10)
            with retrace_guard(0, what="warm resume from the session "
                                       "tier is pure host numpy",
                               min_duration_s=0.5):
                for seq in range(3, 6):
                    dsp, meta = client.predict(frames[seq], frames[seq],
                                               session_id="mig",
                                               seq_no=seq)
                    assert meta["backend"] == survivor_name, meta
                    assert meta["warm"] is True, meta
                    np.testing.assert_array_equal(dsp, twin_disp[seq])
            text = client.metrics_text()
            assert validate_prometheus(text) == []
            assert 'cluster_session_handoffs_total{outcome="warm"}' \
                in text

            # ---- (b) tier outage mid-replay: zero request errors,
            # counted degradation, warm re-attach + catch-up.
            tc = TierClient("127.0.0.1", tier.port, timeout_s=5.0)
            status, _ = tc._request(
                "POST", "/debug/faults",
                json.dumps({"faults": "tier_outage@t_ms=0:2"}).encode())
            assert status == 200
            for seq in range(6, 10):
                dsp, meta = client.predict(frames[seq % 4],
                                           frames[seq % 4],
                                           session_id="mig", seq_no=seq)
                assert meta["warm"] is True, meta  # never an error
            # The publisher detached at some point during the window
            # (and may have legitimately re-attached already — the
            # window is short by design); the MONOTONIC evidence of the
            # degradation is the counter, not the transient gauge.
            def _degraded_count():
                for line in survivor.metrics.render().splitlines():
                    if line.startswith("stream_tier_degraded_total "):
                        return float(line.split()[-1])
                return 0.0

            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                if _degraded_count() > 0:
                    break
                time.sleep(0.05)
            assert _degraded_count() > 0

            # Outage window over: the next completed frame's enqueue
            # drives the re-probe; the publisher re-attaches and
            # resyncs, so the tier holds the session's LATEST state.
            deadline = time.perf_counter() + 30
            seq = 10
            while time.perf_counter() < deadline:
                _, meta = client.predict(frames[seq % 4], frames[seq % 4],
                                         session_id="mig", seq_no=seq)
                assert meta["warm"] is True, meta
                seq += 1
                if survivor.tier_publisher.attached():
                    break
                time.sleep(0.2)
            assert survivor.tier_publisher.attached() is True
            assert survivor.tier_publisher.flush(timeout_s=30)
            durable = json.loads(tier.store.get("mig"))
            assert durable["next_seq"] == seq  # caught back up
            text = survivor.metrics.render()
            assert validate_prometheus(text) == []
            assert "stream_tier_attached 1" in text
        finally:
            client.close()
            router.close()
            rt.join(10)
            tier.close()
            tt.join(10)
            for srv, th in servers.values():
                try:
                    srv.close()
                except Exception:
                    pass
                th.join(5)

    def test_fleet_observatory_e2e(self, cluster_model, retrace_guard):
        """THE acceptance gate (ISSUE 20): the fleet observatory over a
        REAL router + 2-backend + session-tier cluster under a
        zero-compile retrace budget, with a chaos-grammar fault window
        (utils/faults.py) declared mid-replay.

        (a) one ``GET /debug/trace?trace_id=`` returns ONE stitched
        tree in which the router's hop span is an ancestor of the
        backend's admission -> queue_wait -> dispatch -> host_fetch
        spans;
        (b) the tail sampler provably retains the fault window's
        slow/error traces while dropping the fast-path bulk;
        (c) ONE ``GET /metrics/fleet`` scrape passes the exposition
        validator and its per-backend-labeled counter sums equal the
        individual backends' own scrapes;
        (d) the burn-rate alert fires during the declared fault window,
        clears in recovery, and the autoscaler's advice reflects it.
        """
        from raftstereo_tpu.obs import validate_prometheus
        from raftstereo_tpu.obs.prom import parse_text
        from raftstereo_tpu.serve.httpbase import (TRACE_HEADER,
                                                   format_trace_context)
        from raftstereo_tpu.serve.server import encode_array
        from raftstereo_tpu.stream.tier import build_session_tier

        model, variables = cluster_model
        tier = build_session_tier(TierConfig(port=0))
        tt = threading.Thread(target=tier.serve_forever, daemon=True)
        tt.start()
        tier_addr = ("127.0.0.1", tier.port)
        stream_cfg = StreamConfig(ladder=(2, 1), tier=tier_addr)
        # b0 is the fault-window victim: a tiny queue so an overload
        # storm sheds (outcome="shed" burns the shed budget fleet-wide).
        cfg0 = _cfg(warmup=True, iters=2, degraded_iters=2,
                    stream=stream_cfg, stream_warmup=True, cluster=None,
                    max_batch_size=1, queue_limit=2)
        b0 = build_server(model, variables, cfg0)
        t0 = threading.Thread(target=b0.serve_forever, daemon=True)
        t0.start()
        b1, t1 = self._backend(cluster_model, stream=stream_cfg)
        servers = {"b0": (b0, t0), "b1": (b1, t1)}
        # Tight alert windows (fast 1s / slow 5s) so fire-and-clear
        # fits a test: page at burn >= 2 on a 25% shed budget.
        router = build_router(RouterConfig(
            port=0, backends=(("127.0.0.1", b0.port),
                              ("127.0.0.1", b1.port)),
            probe_interval_s=0.15, fail_after=1, retries=2,
            retry_backoff_ms=20.0, request_timeout_s=60.0,
            session_tier=tier_addr, alert_window_s=1.0,
            alert_shed_budget=0.25, alert_page_burn=2.0,
            fleet_timeout_s=10.0))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        client = ServeClient("127.0.0.1", router.port, timeout=120,
                             retries=2)
        frames = [_img(60, 90, 300 + i) for i in range(4)]
        body = json.dumps({"left": encode_array(frames[0]),
                           "right": encode_array(frames[0])}).encode()

        def alerts_eval():
            status, raw, _ = client._request("GET", "/debug/alerts")
            assert status == 200, raw
            return json.loads(raw)["classes"][0]

        try:
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                h = client.healthz()
                if all(h["backends"][n]["state"] == "ready"
                       for n in ("b0", "b1")):
                    break
                time.sleep(0.1)
            assert h["backends"]["b0"]["state"] == "ready"
            assert h["backends"]["b1"]["state"] == "ready"
            for name, (srv, _th) in servers.items():
                direct = ServeClient("127.0.0.1", srv.port, timeout=120)
                direct.predict(frames[0], frames[0])
                direct.close()

            with retrace_guard(0, what="observatory reads run beside "
                                       "steady-state traffic; the fault "
                                       "window sheds and sleeps, it "
                                       "never compiles",
                               min_duration_s=0.5):
                # Steady state: 100 fast JSON requests through the
                # router — they seed the live forward p99 the tail
                # sampler thresholds against.
                load = run_load(
                    "127.0.0.1", router.port,
                    lambda i: (frames[i % 4], frames[i % 4]),
                    requests=100, concurrency=4, timeout=120,
                    retries=2, wire_format="json")
                assert load["ok"] == 100, load
                base = alerts_eval()
                assert base["state_name"] == "ok"

                # ---- (a) the traced request: a client-minted trace
                # context continued router -> backend over HTTP.
                status, raw, _ = client._request(
                    "POST", "/predict", body,
                    headers={"Content-Type": "application/json",
                             "X-Request-Id": "rid-e2e",
                             TRACE_HEADER: format_trace_context(
                                 "tr-e2e", "client-span")})
                assert status == 200, raw
                status, raw, _ = client._request(
                    "GET", "/debug/trace?trace_id=tr-e2e")
                assert status == 200
                doc = json.loads(raw)
                assert doc["stitch"]["gaps"] == []
                assert set(doc["stitch"]["sources"]) >= \
                    {"router", "b0", "b1", "session_tier"}
                root = doc["tree"][0]["span"]
                assert (root["source"], root["name"]) == ("router",
                                                          "route")
                assert root["parent_id"] == "client-span"
                hop = doc["tree"][0]["children"][0]
                assert hop["span"]["name"] == "router_hop"

                def descend(node, out):
                    for ch in node["children"]:
                        out.append((ch["span"]["source"],
                                    ch["span"]["name"]))
                        descend(ch, out)
                below_hop = []
                descend(hop, below_hop)
                backend_src = below_hop[0][0]
                assert backend_src in ("b0", "b1")
                names = {n for s, n in below_hop if s == backend_src}
                assert {"request", "admission", "queue_wait",
                        "dispatch", "host_fetch"} <= names, below_hop

                # ---- (b)+(d) the declared fault window:
                # slow_replica makes b0's next dispatch sleep, and an
                # overload storm against its 2-deep queue sheds.
                vc = ServeClient("127.0.0.1", b0.port, timeout=30)
                status, raw, _ = vc._request(
                    "POST", "/debug/faults",
                    json.dumps({"faults":
                                "slow_replica@request=1:1.5"}).encode())
                assert status == 200, raw
                vc.close()
                # Barrier-released storm: all 12 requests hit b0 while
                # the 1.5s fault holds its single-dispatch engine, so
                # the 2-deep queue sheds >= 7 even on a loaded host —
                # enough that shed_rate >= 0.5 over the alert window
                # (>= 2x the 25% budget, the page threshold below).
                outcomes = {"ok": [], "shed": []}
                gate = threading.Barrier(12)

                def storm():
                    c = ServeClient("127.0.0.1", b0.port, timeout=30)
                    try:
                        gate.wait(30)
                        c.predict(frames[0], frames[0])
                        outcomes["ok"].append(1)
                    except ServeError as e:
                        assert e.status == 503, e
                        assert e.payload["error"] == "overloaded"
                        outcomes["shed"].append(1)
                    finally:
                        c.close()

                threads = [threading.Thread(target=storm)
                           for _ in range(12)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join(60)
                assert len(outcomes["shed"]) >= 7, outcomes
                # A slow trace through the router inside the window:
                # both backends armed so the cold pick lands slow
                # either way.
                for name, (srv, _th) in servers.items():
                    c = ServeClient("127.0.0.1", srv.port, timeout=30)
                    c._request("POST", "/debug/faults", json.dumps(
                        {"faults": "slow_replica@request=1:8.0"}
                    ).encode())
                    c.close()
                status, _, _, _ = router.route_predict(
                    body, None, "rid-slow", trace=("tr-slow", None))
                assert status == 200
                # An error trace: the client budget dies at the router
                # hop — 504 without touching a backend.
                status, _, _, _ = router.route_predict(
                    body, None, "rid-dead", deadline_ms=1e-6,
                    trace=("tr-dead", None))
                assert status == 504

                # The alert FIRES inside the window: the storm's sheds
                # burn the 25% shed budget at >= page rate in both
                # windows, and the autoscaler sees it.
                fired = alerts_eval()
                assert fired["state_name"] == "page", fired
                assert fired["burn"] >= 2.0
                router.refresh_gauges()
                adv = router.autoscale_advice
                assert adv["signals"]["alert_burn"] >= 2.0, adv
                assert "burn" in adv["reason"], adv

                # Tail retention: the fault window's error + slow
                # traces are kept, the 100-request fast bulk dropped.
                assert "tr-dead" in router.tail
                assert "tr-slow" in router.tail
                kept = {r["trace_id"]: r["why"]
                        for r in router.tail.retained()}
                assert kept["tr-dead"] == "error"
                assert kept["tr-slow"] == "slow"
                stats = router.tail.stats()
                assert stats["dropped"] >= 50, stats
                # The fast-path bulk is provably NOT retained: at most
                # the fault-window traces plus a borderline keep sit in
                # the ring while 100+ steady-state routes were offered.
                assert stats["kept"] <= 4, router.tail.retained()

                # Spend the leftover armed fault outside any timing
                # assertion (count-valued faults persist until fired):
                # tr-slow fired on one backend only, so hit BOTH
                # directly — the recovery loop below must never absorb
                # a surprise 8s dispatch.
                for name, (srv, _th) in servers.items():
                    direct = ServeClient("127.0.0.1", srv.port,
                                         timeout=60)
                    direct.predict(frames[1], frames[1])
                    direct.close()

                # ---- (c) ONE federated scrape: validator-clean, and
                # per-backend sums equal the backends' own scrapes
                # (no traffic between the two reads).
                status, raw, _ = client._request("GET", "/metrics/fleet")
                assert status == 200
                fleet_text = raw.decode()
                assert validate_prometheus(fleet_text) == []
                assert 'fleet_scrape_failures_total{backend=' \
                    not in fleet_text
                fleet = parse_text(fleet_text)
                m = fleet.get("serve_requests_total")
                sums = {}
                for litems, value in m.series("serve_requests_total"):
                    b = dict(litems)["backend"]
                    sums[b] = sums.get(b, 0.0) + value
                for name, (srv, _th) in servers.items():
                    own = parse_text(srv.metrics.render())
                    own_total = own.total("serve_requests_total")
                    assert sums[name] == own_total, (name, sums)
                # the tier is federated too, under its own label
                assert 'fleet_scrapes_total{backend="session_tier"}' \
                    in fleet_text

                # ---- (d) recovery: sheds age out of the 5s slow
                # window while ok traffic keeps flowing; the alert
                # clears and the advice drops the burn signal.
                deadline = time.perf_counter() + 60
                cleared = None
                while time.perf_counter() < deadline:
                    client.predict(frames[2], frames[2])
                    cleared = alerts_eval()
                    if cleared["state_name"] == "ok":
                        break
                    time.sleep(0.5)
                assert cleared["state_name"] == "ok", cleared
                router.refresh_gauges()
                adv = router.autoscale_advice
                assert adv["signals"]["alert_burn"] < 2.0, adv
                assert "burn" not in adv["reason"], adv
        finally:
            client.close()
            router.close()
            rt.join(10)
            tier.close()
            tt.join(10)
            for srv, th in servers.values():
                try:
                    srv.close()
                except Exception:
                    pass
                th.join(5)

    def test_drained_backend_restart_rejoins_rotation(self):
        """Scale-in undo: a backend drained through the router and then
        RESTARTED at the same host:port reports draining=false on its
        fresh /healthz and must rejoin rotation — the router-side drain
        mark must not outlive the process it was aimed at."""
        b = Backend(0, "127.0.0.1", 1)
        b.on_probe({"live": True, "ready": True, "draining": False,
                    "drained": False, "queue_depth": 0}, fail_after=3)
        assert b.routable()
        b.mark_draining()  # router-side decision, ahead of the forward
        assert not b.routable()
        b.on_probe({"live": True, "ready": False, "draining": True,
                    "drained": True, "queue_depth": 0}, fail_after=3)
        assert b.state() == "drained"
        # Fresh process at the same address: healthz clears draining.
        b.on_probe({"live": True, "ready": True, "draining": False,
                    "drained": False, "queue_depth": 0}, fail_after=3)
        assert b.routable() and b.state() == "ready"

    def test_backend_without_draining_flag_keeps_router_mark(self):
        """A backend predating the live/ready split reports no draining
        key at all: the router's local drain decision stays sticky."""
        b = Backend(0, "127.0.0.1", 1)
        b.mark_draining()
        b.on_probe({"live": True, "ready": True}, fail_after=3)
        assert not b.routable() and b.state() == "draining"

    def test_router_import_is_model_free(self):
        """The cli.router / build_router import path must not drag in
        the engine/model stack (serve exports lazily to keep it that
        way): a proxy process carrying flax + the model would pay
        startup latency and memory for nothing."""
        script = textwrap.dedent("""
            import sys
            from raftstereo_tpu.serve.cluster import build_router
            import raftstereo_tpu.cli.router  # the CLI module itself
            assert callable(build_router)
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

    def test_router_migrates_a_parent_builds_snapshot_warm(self):
        """A rolling restart across builds: the source backend's snapshot
        still carries the schema key an older build wrote
        (``gru_backend``); the router relays it verbatim and the
        destination, which compares only the keys it knows, installs it
        warm and bitwise — not ``cold_schema``."""
        import http.server

        src_store, dst_store = _warm_store("cam0"), SessionStore(
            limit=4, ttl_s=60.0)

        class Stub(http.server.BaseHTTPRequestHandler):
            def _json(self, obj):
                body = json.dumps(obj).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path.startswith("/debug/sessions/"):
                    self._json(snapshot_to_wire(src_store.export_state(
                        "cam0", schema=dict(SCHEMA, gru_backend="xla"))))
                else:
                    self._json({"live": True, "ready": True,
                                "queue_depth": 0})

            def do_POST(self):
                raw = self.rfile.read(int(self.headers["Content-Length"]))
                self._json({"outcome": dst_store.import_state(
                    wire_to_snapshot(json.loads(raw)), schema=SCHEMA)})

            def log_message(self, *a):
                pass

        stubs = [http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stub)
                 for _ in range(2)]
        threads = [threading.Thread(target=s.serve_forever, daemon=True)
                   for s in stubs]
        for t in threads:
            t.start()
        router = build_router(RouterConfig(
            port=0, backends=tuple(("127.0.0.1", s.server_address[1])
                                   for s in stubs),
            probe_interval_s=30.0))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        try:
            assert router._handoff("cam0", *router.backends) == "warm"
            outs = {lv: c.value for lv, c in
                    router.cluster_metrics.session_handoffs.series()}
            assert outs == {("warm",): 1}
            sess, created = dst_store.get_or_create("cam0")
            src, _ = src_store.get_or_create("cam0")
            assert not created and sess.next_seq == 3
            np.testing.assert_array_equal(sess.prev_disp_low,
                                          src.prev_disp_low)
        finally:
            router.close()
            rt.join(5)
            for s, t in zip(stubs, threads):
                _stop_stub(s, t)

    def test_router_failover_unit_no_model(self):
        """Deterministic failover path: a backend that died between
        probes (router still believes it ready) fails at connect time
        and the request lands on the live backend — counted as a
        connect_error + an ok.  With EVERY backend dead the router
        answers 503 within the bounded retry budget."""
        import http.server

        class Tiny(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length",
                                                     0) or 0))
                body = json.dumps({"ok": True}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                body = json.dumps({"live": True, "ready": True,
                                   "queue_depth": 0}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        live = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Tiny)
        lt = threading.Thread(target=live.serve_forever, daemon=True)
        lt.start()
        dead_port = _free_port()
        router = build_router(RouterConfig(
            port=0, backends=(("127.0.0.1", dead_port),
                              ("127.0.0.1", live.server_address[1])),
            probe_interval_s=30.0, retries=2, retry_backoff_ms=5.0,
            request_timeout_s=5.0))
        # serve_forever must run for close() to complete (socketserver
        # shutdown handshake), even though we call route_predict directly.
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        try:
            # Simulate "died since the last probe": force b0 routable.
            b0 = router.backends[0]
            with b0._lock:
                b0.live = b0.ready = True
            status, body, ctype, headers = router.route_predict(
                json.dumps({"left": [], "right": []}).encode(), None,
                "rid-1")
            assert status == 200 and headers["X-Backend"] == "b1"
            fam = {lv: c.value
                   for lv, c in router.cluster_metrics.dispatch.series()}
            assert fam[("b0", "connect_error")] == 1
            assert fam[("b1", "ok")] == 1
            assert not router.backends[0].routable()  # marked on failure

            # All backends dead -> bounded clean 503, no hang.
            live.shutdown()
            live.server_close()
            for b in router.backends:
                with b._lock:
                    b.live = b.ready = True
            t0 = time.perf_counter()
            status, body, _, _ = router.route_predict(b"{}", None,
                                                      "rid-2")
            assert status == 503
            assert json.loads(body)["error"] == "unavailable"
            assert time.perf_counter() - t0 < 5.0
        finally:
            router.close()
            rt.join(5)
            lt.join(5)


# ------------------------------------------------- chaos: breaker policy

class TestCircuitBreaker:
    """Pure breaker policy — injected clock, no sockets
    (docs/fault_tolerance.md "Per-backend circuit breaker")."""

    def _breaker(self, threshold=2, reset_s=5.0):
        clock = [0.0]
        seen = []
        br = CircuitBreaker(threshold, reset_s, clock=lambda: clock[0],
                            listener=seen.append)
        return br, clock, seen

    def test_full_cycle_closed_open_half_open_closed(self):
        br, clock, seen = self._breaker()
        assert br.current() == "closed" and br.allow_request()
        br.record_failure()
        assert br.current() == "closed"  # below threshold
        br.record_failure()
        assert br.current() == "open"
        assert not br.allow_request()  # reset window not elapsed
        clock[0] = 5.0
        assert br.allow_request()  # admits THE trial
        assert br.current() == "half_open"
        br.record_success()
        assert br.current() == "closed"
        assert seen == ["open", "half_open", "closed"]

    def test_half_open_admits_exactly_one_trial(self):
        br, clock, _ = self._breaker()
        br.record_failure()
        br.record_failure()
        clock[0] = 5.0
        assert br.allow_request()
        # exclusivity: no second trial until the verdict lands
        assert not br.allow_request()
        br.record_failure()  # trial failed -> open, FRESH window
        assert br.current() == "open"
        assert not br.allow_request()  # window restarted at t=5
        clock[0] = 10.0
        assert br.allow_request()

    def test_open_window_keeps_aging_under_repeated_failures(self):
        # Failures while already open must NOT refresh _opened_at —
        # a steady trickle of failed picks would otherwise push the
        # recovery trial out forever.
        br, clock, _ = self._breaker()
        br.record_failure()
        br.record_failure()  # open at t=0
        clock[0] = 2.0
        br.record_failure()
        clock[0] = 4.0
        br.record_failure()
        clock[0] = 5.0
        assert br.allow_request()  # reset_s measured from t=0

    def test_probe_recovery_is_two_step(self):
        # One lucky probe mid-flap never slams the breaker shut: the
        # first healthy probe after the window only reaches half_open.
        br, clock, seen = self._breaker()
        br.record_failure()
        br.record_failure()  # open at t=0
        clock[0] = 1.0
        br.on_probe(True)
        assert br.current() == "open"  # window not elapsed yet
        clock[0] = 5.0
        br.on_probe(True)
        assert br.current() == "half_open"  # step one
        br.on_probe(True)
        assert br.current() == "closed"  # step two
        assert seen == ["open", "half_open", "closed"]
        br.on_probe(False)  # a failed probe counts like a failure
        assert br.current() == "closed"
        br.on_probe(False)
        assert br.current() == "open"

    def test_success_resets_consecutive_count(self):
        br, _, seen = self._breaker(threshold=2)
        br.record_failure()
        br.record_success()  # any HTTP reply = responsive
        br.record_failure()
        assert br.current() == "closed" and seen == []


class TestProbeSchedule:
    """Thundering-herd jitter policy — explicit ``now``, no sleeps."""

    def test_phase_and_period_decorrelate(self):
        names = [f"b{i}" for i in range(4)]
        sched = _ProbeSchedule(names, 10.0, now=0.0)
        periods = [sched.period_s(n) for n in names]
        assert all(10.0 <= p <= 15.0 for p in periods)
        assert len({round(p, 6) for p in periods}) == len(names)
        phases = list(sched._next.values())
        assert all(0.0 <= t < 10.0 for t in phases)
        assert len({round(t, 6) for t in phases}) == len(names)

    def test_schedule_is_identical_across_restarts(self):
        a = _ProbeSchedule(["b0", "b1"], 3.0, now=0.0)
        b = _ProbeSchedule(["b0", "b1"], 3.0, now=0.0)
        assert a._next == b._next and a._period == b._period

    def test_due_advances_past_now_without_catch_up_burst(self):
        sched = _ProbeSchedule(["b0", "b1"], 1.0, now=0.0)
        assert sorted(sched.due(2.0)) == ["b0", "b1"]
        assert sched.due(2.0) == []  # advanced PAST now
        # a very late round (stalled host) still probes each backend
        # at most once — missed periods are skipped, not replayed
        assert sorted(sched.due(100.0)) == ["b0", "b1"]
        assert sched.due(100.0) == []

    def test_next_wake_is_nonnegative_and_bounded(self):
        sched = _ProbeSchedule(["b0"], 1.0, now=0.0)
        assert sched.next_wake(50.0) == 0.0  # overdue -> wake now
        sched.due(50.0)
        assert 0.0 < sched.next_wake(50.0) <= 1.5  # one period max


# ------------------------------------------ chaos: router fault handling

def _stub_backend(delay_s=0.0, capture=None, decode_wire=False):
    """Model-free backend stub for router policy tests: /healthz says
    ready; /predict replies canned JSON after ``delay_s`` (request
    headers appended to ``capture``).  With ``decode_wire`` the body
    must frame-decode as a binary request and a ``WireError`` is
    answered as the backend's documented clean 400."""
    import http.server

    class Stub(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            raw = self.rfile.read(int(self.headers.get("Content-Length",
                                                       0) or 0))
            if capture is not None:
                capture.append(dict(self.headers))
            if delay_s:
                time.sleep(delay_s)
            status, payload = 200, {"ok": True}
            if decode_wire:
                try:
                    wire.decode_request(raw)
                except wire.WireError as e:
                    status, payload = 400, {"error": str(e)}
            body = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.send_header("X-Request-Id",
                             self.headers.get("X-Request-Id", ""))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            body = json.dumps({"live": True, "ready": True,
                               "queue_depth": 0}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, t


def _stop_stub(srv, t):
    srv.shutdown()
    srv.server_close()
    t.join(5)


class TestBreakerRouting:
    def _router(self, stubs, **kw):
        cfg = dict(port=0,
                   backends=tuple(("127.0.0.1", s.server_address[1])
                                  for s in stubs),
                   probe_interval_s=30.0, retries=2, retry_backoff_ms=5.0,
                   request_timeout_s=5.0)
        cfg.update(kw)
        router = build_router(RouterConfig(**cfg))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        return router, rt

    def test_breaker_open_spills_cold_and_sessions_bypass(self):
        s0, t0 = _stub_backend()
        s1, t1 = _stub_backend()
        router, rt = self._router([s0, s1], fail_after=2,
                                  breaker_reset_s=60.0)
        try:
            assert router._hedge_delay_s() is None  # hedging is opt-in
            b0 = router.backends[0]
            b0.breaker.record_failure()
            b0.breaker.record_failure()
            assert b0.breaker.current() == "open"
            # Cold request: b0 is still routable (probes pass — the
            # breaker opened on forward failures) but its breaker
            # refuses, so the pick SPILLS to b1.
            status, _, _, headers = router.route_predict(b"{}", None,
                                                         "rid-s1")
            assert status == 200 and headers["X-Backend"] == "b1"
            fam = {lv: c.value
                   for lv, c in router.cluster_metrics.dispatch.series()}
            assert fam[("b0", "breaker_open")] == 1
            assert fam[("b1", "ok")] == 1
            # Session frames bypass the breaker: stickiness beats
            # breaker pessimism (docs/fault_tolerance.md).
            raw = json.dumps({"session_id": "sess-bypass"}).encode()
            status, _, _, headers = router.route_predict(
                raw, "sess-bypass", "rid-s2")
            assert status == 200 and headers["X-Backend"] == "b0"
            # Exported gauge + transition counter saw the open.
            router.refresh_gauges()
            gauge = {lv: g.value for lv, g in
                     router.cluster_metrics.breaker_state.series()}
            assert gauge[("b0",)] == 1 and gauge[("b1",)] == 0
            trans = {lv: c.value for lv, c in
                     router.cluster_metrics.breaker_transitions.series()}
            assert trans[("b0", "open")] == 1
        finally:
            router.close()
            rt.join(5)
            _stop_stub(s0, t0)
            _stop_stub(s1, t1)

    def test_deadline_exhausted_at_router_hop(self):
        caps = []
        s0, t0 = _stub_backend(capture=caps)
        router, rt = self._router([s0])
        try:
            status, body, ctype, headers = router.route_predict(
                b"{}", None, "rid-d0", deadline_ms=0.0)
            assert status == 504 and ctype == "application/json"
            obj = json.loads(body)
            assert obj["error"] == "timeout"
            assert "router hop" in obj["detail"]
            assert headers["X-Request-Id"] == "rid-d0"
            assert caps == []  # no backend slot burned
            # A live budget forwards decremented, never grown.
            status, _, _, _ = router.route_predict(
                b"{}", None, "rid-d1", deadline_ms=10000.0)
            assert status == 200
            fwd = float(caps[0]["X-Deadline-Ms"])
            assert 0.0 < fwd <= 10000.0
        finally:
            router.close()
            rt.join(5)
            _stop_stub(s0, t0)

    def test_debug_faults_arms_and_rejects_over_http(self):
        import http.client

        s0, t0 = _stub_backend()
        router, rt = self._router([s0])
        try:
            conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                              timeout=10)
            conn.request("POST", "/debug/faults", body=json.dumps(
                {"faults": "flap_probe@backend=2"}).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            armed = json.loads(resp.read())["armed"]
            assert resp.status == 200
            assert len(armed) == 1
            assert armed[0].startswith("flap_probe@backend=2")
            # training-only dims are rejected on the serving plane
            conn.request("POST", "/debug/faults", body=json.dumps(
                {"faults": "slow_replica@step=2:0.5"}).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            err = json.loads(resp.read())
            assert resp.status == 400
            assert "bad fault spec" in err["error"]
            conn.close()
        finally:
            router.close()
            rt.join(5)
            _stop_stub(s0, t0)


class TestHedgedRequests:
    def test_hedge_fires_and_wins_on_slow_primary(self):
        s0, t0 = _stub_backend(delay_s=1.0)  # tail-slow primary
        s1, t1 = _stub_backend()
        router, rt = TestBreakerRouting()._router(
            [s0, s1], hedge_floor_ms=150.0, hedge_min_samples=10 ** 6,
            retries=0)
        try:
            t_start = time.perf_counter()
            status, _, _, headers = router.route_predict(b"{}", None,
                                                         "rid-h0")
            wall = time.perf_counter() - t_start
            # b0 (least bid) was primary; the hedge fired at the floor
            # and b1's reply won long before b0's 1s sleep ended.
            assert status == 200 and headers["X-Backend"] == "b1"
            assert wall < 0.8
            hedges = {lv: c.value for lv, c in
                      router.cluster_metrics.hedges.series()}
            assert hedges[("fired",)] == 1
            assert hedges[("won",)] == 1
            assert ("lost",) not in hedges
            # Session frames NEVER hedge (ordering): the pinned slow
            # backend is waited out and the counters stay put.
            raw = json.dumps({"session_id": "sess-h"}).encode()
            status, _, _, _ = router.route_predict(raw, "sess-h",
                                                   "rid-h1")
            assert status == 200
            hedges2 = {lv: c.value for lv, c in
                       router.cluster_metrics.hedges.series()}
            assert hedges2 == hedges
        finally:
            router.close()
            rt.join(5)
            _stop_stub(s0, t0)
            _stop_stub(s1, t1)


class TestCorruptFrameRelay:
    def _post_wire(self, port, body, rid):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        try:
            conn.request("POST", "/predict", body=body, headers={
                "Content-Type": wire.WIRE_CONTENT_TYPE,
                "X-Request-Id": rid})
            resp = conn.getresponse()
            return resp.status, resp.read(), dict(resp.headers)
        finally:
            conn.close()

    def test_corrupt_frame_budget_then_healthy_relay(self):
        import http.client

        s0, t0 = _stub_backend(decode_wire=True)
        router, rt = TestBreakerRouting()._router([s0])
        try:
            # Arm over the wire — the chaos controller's seam.
            conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                              timeout=10)
            conn.request("POST", "/debug/faults", body=json.dumps(
                {"faults": "corrupt_frame@request=1"}).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            conn.close()
            rng = np.random.default_rng(0)
            left = rng.standard_normal((16, 24, 3)).astype(np.float32)
            right = rng.standard_normal((16, 24, 3)).astype(np.float32)
            buf = wire.encode_request(left, right, {"iters": 2},
                                      compress=True)
            # The router bit-flips one relayed payload byte; the
            # backend's decoder must answer a clean 400 that relays
            # back with the request id — never a hung socket.
            status, body, headers = self._post_wire(router.port, buf,
                                                    "rid-c0")
            assert status == 400
            assert headers.get("X-Request-Id") == "rid-c0"
            assert json.loads(body)["error"]
            # Budget consumed: the identical frame now relays bitwise.
            status, body, headers = self._post_wire(router.port, buf,
                                                    "rid-c1")
            assert status == 200 and json.loads(body) == {"ok": True}
            assert headers.get("X-Backend") == "b0"
        finally:
            router.close()
            rt.join(5)
            _stop_stub(s0, t0)

    def test_truncated_and_garbage_wire_bodies_clean_400(self):
        s0, t0 = _stub_backend(decode_wire=True)
        router, rt = TestBreakerRouting()._router([s0])
        try:
            # Shorter than a frame header: rejected before any relay.
            status, body, headers = self._post_wire(router.port,
                                                    b"RSWF", "rid-t0")
            assert status == 400
            assert headers.get("X-Request-Id") == "rid-t0"
            assert "wire frame" in json.loads(body)["error"]
            # A full-size header of garbage: bad magic, same contract.
            status, body, headers = self._post_wire(
                router.port, b"\x00" * wire.HEADER_SIZE, "rid-t1")
            assert status == 400
            assert headers.get("X-Request-Id") == "rid-t1"
            json.loads(body)  # always JSON, never a hung socket
        finally:
            router.close()
            rt.join(5)
            _stop_stub(s0, t0)


# ----------------------------------------------------------- client retries

class TestClientRetries:
    def _flaky_server(self, failures, status=503):
        """HTTP stub: first ``failures`` /predict POSTs get ``status``,
        then 200s; counts attempts."""
        import http.server

        seen = {"n": 0}

        class Flaky(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length",
                                                     0) or 0))
                seen["n"] += 1
                if seen["n"] <= failures:
                    body = json.dumps({"error": "overloaded"}).encode()
                    self.send_response(status)
                else:
                    body = json.dumps({"ok": True}).encode()
                    self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Flaky)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, seen

    def test_retries_ride_out_transient_5xx(self, monkeypatch):
        srv, seen = self._flaky_server(failures=2)
        sleeps = []
        monkeypatch.setattr("raftstereo_tpu.serve.client.time.sleep",
                            sleeps.append)
        try:
            c = ServeClient("127.0.0.1", srv.server_address[1], retries=2,
                            retry_backoff_ms=10.0)
            status, raw, _ = c._request("POST", "/predict", b"{}")
            assert status == 200 and seen["n"] == 3
            assert len(sleeps) == 2  # backoff between each retry
            # Exponential base with +-50% jitter: 10ms*2^k scaled into
            # disjoint-by-construction windows is flaky, so assert each
            # attempt's window instead.
            assert 0.004 <= sleeps[0] <= 0.016, sleeps
            assert 0.009 <= sleeps[1] <= 0.031, sleeps
            c.close()
        finally:
            srv.shutdown()
            srv.server_close()

    def test_final_attempt_returns_the_5xx(self):
        srv, seen = self._flaky_server(failures=10)
        try:
            c = ServeClient("127.0.0.1", srv.server_address[1], retries=1,
                            retry_backoff_ms=1.0)
            status, raw, _ = c._request("POST", "/predict", b"{}")
            assert status == 503 and seen["n"] == 2
            c.close()
        finally:
            srv.shutdown()
            srv.server_close()

    def test_connection_refused_retries_then_raises(self, monkeypatch):
        sleeps = []
        monkeypatch.setattr("raftstereo_tpu.serve.client.time.sleep",
                            sleeps.append)
        c = ServeClient("127.0.0.1", _free_port(), retries=2,
                        retry_backoff_ms=5.0)
        with pytest.raises(OSError):
            c._request("GET", "/healthz")
        assert len(sleeps) == 2  # 3 attempts, bounded
        c.close()

    def test_default_is_fail_fast(self, monkeypatch):
        sleeps = []
        monkeypatch.setattr("raftstereo_tpu.serve.client.time.sleep",
                            sleeps.append)
        c = ServeClient("127.0.0.1", _free_port())
        with pytest.raises(OSError):
            c._request("GET", "/healthz")
        assert sleeps == []  # retries=0: the historical hard failure
        c.close()


# ------------------------------------------- load generator over a cluster

class TestClusterLoadgen:
    def test_run_load_over_two_replicas(self, cluster_model):
        """The load generator's own counts against a two-replica server:
        every request answered, none an error, and both replicas took
        traffic (a single hot replica means placement is broken)."""
        model, variables = cluster_model
        cfg = _cfg(warmup=True, degraded_iters=4)  # one program a replica
        server = build_server(model, variables, cfg, ServeMetrics())
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            pair = (_img(60, 90, 1), _img(60, 90, 2))
            stats = run_load("127.0.0.1", server.port, lambda i: pair,
                             requests=12, concurrency=4, retries=2)
            assert stats["ok"] == 12 and stats["error"] == 0, stats
            by_replica = {
                lv: c.value for lv, c in
                server.cluster.cluster_metrics.dispatch.series()}
            assert by_replica.get(("r0", "ok"), 0) > 0, by_replica
            assert by_replica.get(("r1", "ok"), 0) > 0, by_replica
            assert sum(by_replica.values()) == 12, by_replica
        finally:
            server.close()
            thread.join(10)
