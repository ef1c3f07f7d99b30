"""Speculative tier cascades (raftstereo_tpu/serve/cascade/,
docs/serving.md "Tier cascade").

Grammar, policy and vocabulary tests are pure (the schedule/policy
modules are deliberately jax-free; the vocab tests pin their local mode
tables to ops/quant so drift fails tier-1).  The acceptance gate is
``test_e2e_certified_rides_cascade``: on a warmed ``--sched`` server
offering certified cascades, ``/predict accuracy=certified`` rides the
cheapest certified schedule under a ZERO-compile retrace budget, the
served masked-EPE delta vs the monolithic fp32 path honors the
certified bound, the executed fp32-iteration fraction scraped from a
validator-clean ``/metrics`` is <= the schedule's K/total, uncertified
schedules are clean 400s naming the manifest — and default / explicit-
iters / single-tier traffic stays BITWISE identical to a cascade-free
engine's executables.  ``test_e2e_divergence_promotes_early`` proves
the EMA trigger hands a seeded adversarial pair off before its
scheduled boundary.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax

from raftstereo_tpu.config import (RAFTStereoConfig, SchedConfig,
                                   ServeConfig)
from raftstereo_tpu.serve.cascade.policy import (DIVERGENCE_DECAY,
                                                 promotion_kind,
                                                 should_promote,
                                                 update_ema)
from raftstereo_tpu.serve.cascade.schedule import (CERT_MODE, MODE_COST,
                                                   _MODES, _TIER_MODES,
                                                   CascadeSchedule,
                                                   cheapest,
                                                   parse_schedule,
                                                   validate_schedule)

# ----------------------------------------------------------------- fixtures

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)
HW = (64, 96)
SCHEDULE = "int8:2+fp32:2"    # certified below (generous bound)
OVERBOUND = "int8:4+fp32:2"   # impossible bound -> refused at startup
CERT_SEED, CERT_PAIRS = 7, 2


@pytest.fixture(scope="module")
def cascade_model():
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), HW)
    return model, variables


@pytest.fixture(scope="module")
def cascade_manifest(cascade_model):
    """One manifest carrying BOTH tables: 'fast' as a certified single
    tier (the bitwise single-tier leg below) and the two cascade
    schedules — SCHEDULE certified under a generous bound, OVERBOUND
    refused under an impossible one (the clean-400 leg)."""
    from raftstereo_tpu.eval.certify import certify_cascades, certify_tiers

    model, variables = cascade_model
    base = certify_tiers(model.config, variables, ("fast",), hw=HW,
                         n_pairs=CERT_PAIRS, iters=4, seed=CERT_SEED,
                         bounds={"fast": 5.0})
    return certify_cascades(model.config, variables,
                            (SCHEDULE, OVERBOUND), hw=HW,
                            n_pairs=CERT_PAIRS, seed=CERT_SEED,
                            bounds={SCHEDULE: 5.0, OVERBOUND: -1e9},
                            base=base)


def _img(h=64, w=96, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


def _cfg(manifest_path, **kw):
    base = dict(port=0, buckets=(HW,), bucket_multiple=32, divis_by=32,
                max_batch_size=2, max_wait_ms=1.0, queue_limit=16,
                request_timeout_ms=60000.0, iters=4, degraded_iters=4,
                sched=SchedConfig(iters_per_step=1, max_iters=16),
                cascades=(SCHEDULE, OVERBOUND),
                tiers=("certified", "fast"),
                cert_manifest=manifest_path)
    base.update(kw)
    return ServeConfig(**base)


def _metric(text, needle):
    for line in text.splitlines():
        if line.startswith(needle + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{needle!r} not found in /metrics")


# ------------------------------------------------------------ pure grammar


class TestScheduleGrammar:

    def test_parse_canonical(self):
        s = parse_schedule("int8:24+fp32:8")
        assert s.legs == (("int8", 24), ("fp32", 8))
        assert s.cheap_mode == "int8" and s.cert_mode == "fp32"
        assert s.cheap_iters == 24 and s.cert_iters == 8
        assert s.total_iters == 32
        assert s.fp32_fraction == pytest.approx(0.25)
        assert s.schedule == "int8:24+fp32:8" == str(s)
        # The canonical string round-trips through the parser.
        assert parse_schedule(s.schedule) == s

    def test_tier_names_normalize_to_one_schedule(self):
        # "turbo:24+certified:8" and "int8:24+fp32:8" are ONE schedule
        # (one manifest key, one metric label, one /healthz row).
        assert parse_schedule("turbo:24+certified:8").schedule \
            == "int8:24+fp32:8"
        assert parse_schedule("fast:4+certified:2").schedule \
            == "bf16:4+fp32:2"

    @pytest.mark.parametrize("text,msg", [
        ("", "non-empty"),
        ("int8:24", "exactly 2"),
        ("int8:8+bf16:8+fp32:8", "exactly 2"),      # version-2 grammar
        ("int8:24+fp32", "MODE:ITERS"),
        ("int4:24+fp32:8", "unknown mode"),
        ("int8:x+fp32:8", "non-integer"),
        ("int8:0+fp32:8", ">= 1"),
        ("int8:24+bf16:8", "END on the certified mode"),
        ("fp32:24+fp32:8", "monolithic certified path"),
    ])
    def test_rejections_carry_the_defect(self, text, msg):
        with pytest.raises(ValueError, match=msg):
            parse_schedule(text)

    def test_validate_granularity_and_budget(self):
        s = parse_schedule("int8:24+fp32:8")
        assert validate_schedule(s, iters_per_step=4, max_iters=32) is s
        with pytest.raises(ValueError, match="step boundary"):
            validate_schedule(s, iters_per_step=3)
        with pytest.raises(ValueError, match="max_iters"):
            validate_schedule(s, max_iters=16)

    def test_cheapest_is_cost_ordered_and_deterministic(self):
        assert cheapest([]) is None
        a = parse_schedule("int8:24+fp32:8")    # cost 14
        b = parse_schedule("bf16:24+fp32:8")    # cost 20
        c = parse_schedule("int8:16+fp32:16")   # cost 20, ties with b
        assert cheapest([b, a, c]) is a
        # Cost tie breaks on the canonical string: deterministic across
        # processes, so every replica resolves "certified" identically.
        assert cheapest([c, b]).schedule == min(b.schedule, c.schedule)

    def test_vocabulary_matches_ops_quant(self):
        # schedule.py spells the mode tables locally so parsing never
        # imports jax (config validation, loadgen trace grammar); this
        # is the drift tripwire the module's comment promises.
        from raftstereo_tpu.ops.quant import MODES, TIER_MODES, TIERS

        assert tuple(_MODES) == tuple(MODES)
        assert dict(_TIER_MODES) == dict(TIER_MODES)
        assert set(_TIER_MODES) == set(TIERS)
        assert CERT_MODE == TIER_MODES["certified"]
        assert set(MODE_COST) == set(MODES)
        assert MODE_COST["fp32"] > MODE_COST["bf16"] \
            > MODE_COST["int8"] > 0


# ------------------------------------------------------------- pure policy


class TestPromotionPolicy:

    def test_update_ema_seeds_with_first_observation(self):
        # None seeds with the raw delta — a zero seed would mask an
        # immediately-divergent pair for several boundaries.
        assert update_ema(None, 3.5) == 3.5
        assert update_ema(2.0, 4.0) == pytest.approx(
            DIVERGENCE_DECAY * 2.0 + (1 - DIVERGENCE_DECAY) * 4.0)
        assert update_ema(2.0, 4.0, decay=0.5) == pytest.approx(3.0)

    def test_scheduled_promotion_at_cheap_boundary(self):
        assert should_promote(24, 24, None, None) == (True, False)
        assert should_promote(25, 24, 0.0, 0.5) == (True, False)
        assert should_promote(23, 24, None, None) == (False, False)

    def test_early_promotion_needs_armed_trigger_and_seeded_ema(self):
        assert should_promote(4, 24, 1.0, 0.5) == (True, True)
        assert should_promote(4, 24, 0.4, 0.5) == (False, False)
        # threshold None / <= 0 disables; an unseeded EMA never fires.
        assert should_promote(4, 24, 1.0, None) == (False, False)
        assert should_promote(4, 24, 1.0, 0.0) == (False, False)
        assert should_promote(4, 24, None, 0.5) == (False, False)

    def test_promotion_kind_labels(self):
        assert promotion_kind(True) == "early"
        assert promotion_kind(False) == "scheduled"


# ------------------------------------------------------- config validation


class TestConfigValidation:

    def test_cascades_require_sched(self):
        with pytest.raises(AssertionError, match="require --sched"):
            ServeConfig(port=0, cascades=(SCHEDULE,))

    def test_divergence_without_cascades_refused(self):
        with pytest.raises(AssertionError, match="nothing can fire"):
            ServeConfig(port=0, sched=SchedConfig(),
                        cascade_divergence=0.1)

    def test_schedules_canonicalize_and_validate_at_config_time(self):
        cfg = ServeConfig(port=0, sched=SchedConfig(iters_per_step=2),
                          cascades=("turbo:4+certified:2",))
        assert cfg.cascades == ("int8:4+fp32:2",)
        with pytest.raises(ValueError, match="step boundary"):
            ServeConfig(port=0, sched=SchedConfig(iters_per_step=2),
                        cascades=("int8:4+fp32:3",))
        with pytest.raises(ValueError, match="max_iters"):
            ServeConfig(port=0,
                        sched=SchedConfig(iters_per_step=2, max_iters=4),
                        cascades=("int8:4+fp32:2",))
        with pytest.raises(AssertionError, match="duplicate"):
            ServeConfig(port=0, sched=SchedConfig(),
                        cascades=("int8:4+fp32:2", "turbo:4+certified:2"))

    def test_scheduler_submit_rejects_iters_and_mode_with_cascade(self):
        from test_sched import StubSchedEngine

        from raftstereo_tpu.serve import IterationScheduler

        cfg = _cfg(None, cascades=(), tiers=(), cert_manifest=None)
        s = IterationScheduler(StubSchedEngine(), cfg)  # never started:
        # submit validates synchronously before any worker runs
        sched = parse_schedule(SCHEDULE)
        a = _img()
        with pytest.raises(ValueError, match="iters is fixed"):
            s.submit(a, a, iters=4, cascade=sched)
        with pytest.raises(ValueError, match="carried by the cascade"):
            s.submit(a, a, mode="int8", cascade=sched)
        with pytest.raises(ValueError, match="outside"):
            s.submit(a, a, cascade=parse_schedule("int8:12+fp32:8"))


# ------------------------------------------------------------ certification


class TestCertification:

    def test_manifest_entries_measure_the_schedule(self, cascade_manifest):
        entry = cascade_manifest["cascades"][SCHEDULE]
        assert entry["certified"] is True
        assert entry["cheap_mode"] == "int8"
        assert entry["cert_mode"] == "fp32"
        assert entry["total_iters"] == 4
        assert entry["fp32_fraction"] == pytest.approx(0.5)
        assert entry["epe_delta"] == pytest.approx(
            entry["epe"] - entry["epe_ref"], abs=1e-5)
        assert entry["epe_delta"] <= entry["bound"]
        # The impossible bound refuses: the manifest genuinely carries a
        # refusable entry for the 400 leg below.
        bad = cascade_manifest["cascades"][OVERBOUND]
        assert bad["certified"] is False
        # The merged manifest keeps the tier table it was based on.
        assert cascade_manifest["tiers"]["fast"]["certified"] is True

    def test_cascade_ok_gates(self, cascade_manifest, cascade_model,
                              tmp_path):
        from raftstereo_tpu.eval.certify import (cascade_ok,
                                                 load_manifest,
                                                 write_manifest)

        model, _ = cascade_model
        path = str(tmp_path / "cert.json")
        write_manifest(cascade_manifest, path)
        loaded = load_manifest(path)
        ok, reason = cascade_ok(loaded, SCHEDULE, model.config)
        assert ok and reason == "certified"
        ok, reason = cascade_ok(loaded, OVERBOUND, model.config)
        assert not ok and "over bound" in reason
        ok, reason = cascade_ok(loaded, "bf16:2+fp32:2")
        assert not ok and "not present" in reason
        ok, reason = cascade_ok(None, SCHEDULE)
        assert not ok and "no certification manifest" in reason
        # Platform and architecture fingerprints gate like tier_ok's.
        ok, reason = cascade_ok(dict(loaded, platform="tpu"), SCHEDULE)
        assert not ok and "platform" in reason
        from raftstereo_tpu.config import RAFTStereoConfig as RC
        other = RC(**dict(TINY, corr_levels=4))
        ok, reason = cascade_ok(loaded, SCHEDULE, other)
        assert not ok and "different model" in reason

    def test_a_parent_builds_manifest_still_certifies(
            self, cascade_manifest, cascade_model):
        """The cascade twin of the tier gate: a manifest whose model
        block still carries an older build's ``gru_backend`` certifies
        its schedules, and its mismatches are still named."""
        from raftstereo_tpu.config import RAFTStereoConfig as RC
        from raftstereo_tpu.eval.certify import cascade_ok

        model, _ = cascade_model
        old = dict(cascade_manifest,
                   model=dict(cascade_manifest["model"],
                              gru_backend="auto"))
        assert cascade_ok(old, SCHEDULE, model.config) \
            == (True, "certified")
        ok, reason = cascade_ok(old, SCHEDULE,
                                RC(**dict(TINY, corr_levels=4)))
        assert not ok and "['corr_levels']" in reason

    def test_resolve_cascades_without_manifest_refuses_all(self):
        from raftstereo_tpu.eval.certify import resolve_cascades

        cfg = _cfg(None, cert_manifest=None)
        advertised, refused = resolve_cascades(cfg)
        assert advertised == {}
        assert set(refused) == {SCHEDULE, OVERBOUND}
        assert all("no certification manifest" in r
                   for r in refused.values())


# ------------------------------------------------------------------- e2e


class TestCascadeE2E:

    def test_e2e_certified_rides_cascade(self, cascade_model,
                                         cascade_manifest, tmp_path,
                                         retrace_guard):
        """The acceptance gate (ISSUE 19): certified requests ride the
        cheapest certified cascade compile-free, the served EPE delta
        honors the certified bound, the executed fp32-iteration
        fraction from validator-clean /metrics is <= K/total,
        uncertified schedules 400 naming the manifest, /healthz reports
        both sides — and default / explicit-iters / single-tier
        traffic is BITWISE identical to a cascade-free engine."""
        from raftstereo_tpu.eval.certify import _cert_data, write_manifest
        from raftstereo_tpu.obs.prom import validate_prometheus
        from raftstereo_tpu.serve import (BatchEngine, IterationScheduler,
                                          ServeClient, ServeError,
                                          ServeMetrics)
        from raftstereo_tpu.serve.server import build_server

        model, variables = cascade_model
        path = str(tmp_path / "cert.json")
        write_manifest(cascade_manifest, path)
        cfg = _cfg(path)
        server = build_server(model, variables, cfg)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        client = None
        try:
            # Startup gate: only the certified schedule is advertised.
            assert set(server.cascades) == {SCHEDULE}
            assert "over bound" in server.cascade_reasons[OVERBOUND]
            client = ServeClient("127.0.0.1", server.port,
                                 timeout=120.0)
            # The SAME pairs the manifest was measured on (exact-GT
            # synthetic), so the served delta is the certified quantity.
            lefts, rights, gts, valid, n_valid, _ = _cert_data(
                model.config, HW, CERT_PAIRS, CERT_SEED)

            # "certified" rides the cheapest certified cascade, with a
            # zero-compile retrace budget (warmup covered both legs'
            # phases, the cascade executables AND the transition pair).
            with retrace_guard(0, what="cascade traffic after warmup "
                                       "is compile-free",
                               min_duration_s=0.5):
                served = [client.predict(lefts[i], rights[i],
                                         accuracy="certified")
                          for i in range(CERT_PAIRS)]
            for _, meta in served:
                assert meta["cascade"] == SCHEDULE
                assert meta["promoted_early"] is False
                assert meta["accuracy"] == "certified"
                assert meta["iters"] == 4 and meta["degraded"] is False
            # Explicit cascade:<schedule> requests resolve too, tier
            # spelling normalizing to the same canonical schedule — and
            # replaying the same pair is deterministic.
            d_exp, meta_exp = client.predict(
                lefts[0], rights[0], accuracy="cascade:turbo:2+certified:2")
            assert meta_exp["cascade"] == SCHEDULE
            np.testing.assert_array_equal(d_exp, served[0][0])

            # The served masked-EPE delta vs the monolithic fp32 path
            # at EQUAL total iters honors the certified bound.
            mono = [client.predict(lefts[i], rights[i])[0]
                    for i in range(CERT_PAIRS)]

            def epe(preds):
                stack = np.stack(preds)[..., None]
                return float((np.abs(stack - gts) * valid).sum() / n_valid)

            delta = epe([d for d, _ in served]) - epe(mono)
            entry = cascade_manifest["cascades"][SCHEDULE]
            assert delta <= entry["bound"] + 1e-6, (
                f"served EPE delta {delta} over certified bound "
                f"{entry['bound']}")

            # Executed fp32-iteration fraction <= scheduled K/total,
            # scraped from a validator-clean /metrics (3 completed
            # cascades so far: 2 certified + 1 explicit).
            text = client.metrics_text()
            assert validate_prometheus(text) == []
            cheap = _metric(text, 'cascade_iterations_total'
                                  '{phase="cheap"}')
            cert = _metric(text, 'cascade_iterations_total'
                                 '{phase="certified"}')
            sched = parse_schedule(SCHEDULE)
            assert cheap == 6.0 and cert == 6.0
            assert cert / (cheap + cert) <= sched.fp32_fraction + 1e-9
            assert _metric(
                text, f'cascade_schedules_total{{schedule="{SCHEDULE}"}}'
            ) == 3.0
            assert _metric(
                text, 'cascade_promotions_total{kind="scheduled"}') == 3.0
            assert _metric(text, 'cascade_fp32_fraction') \
                == pytest.approx(0.5)
            assert _metric(
                text, 'serve_tier_requests_total{tier="certified"}') == 2.0

            # Uncertified / unoffered / malformed schedules are clean
            # 400s carrying the reason AND the manifest path.
            a, b = lefts[0], rights[0]
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy=f"cascade:{OVERBOUND}")
            assert ei.value.status == 400
            err = ei.value.payload["error"]
            assert "not advertised" in err and "over bound" in err
            assert path in err
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy="cascade:bf16:2+fp32:2")
            assert ei.value.status == 400
            assert "not offered by this server" \
                in ei.value.payload["error"]
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy="cascade:int8:4")
            assert ei.value.status == 400
            assert "bad cascade schedule" in ei.value.payload["error"]
            # The schedule owns the iteration budget; sessions are
            # single-tier (v1).
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy="certified", iters=4)
            assert ei.value.status == 400
            assert "iters is fixed by the cascade schedule" \
                in ei.value.payload["error"]
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy="certified",
                               session_id="s0", seq_no=0)
            assert ei.value.status == 400
            assert "cannot run as cascades" in ei.value.payload["error"]

            # /healthz reports both sides of the startup decision.
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz").read())
            assert health["cascade"]["advertised"] == [SCHEDULE]
            assert OVERBOUND in health["cascade"]["refused"]
            assert health["cascade"]["divergence"] == 0.0

            # Bitwise-unchanged defaults: a cascade-free engine (the
            # pre-PR program set) serves byte-identical disparities for
            # default, explicit-iters and single-tier requests — the
            # cascade is new executables NEXT TO the old ones, never a
            # modification of them.
            d_iters = client.predict(a, b, iters=4)[0]
            d_fast, meta_fast = client.predict(a, b, accuracy="fast")
            assert meta_fast["accuracy"] == "fast"
            assert "cascade" not in meta_fast
            ref_cfg = _cfg(None, cascades=(), tiers=(),
                           cert_manifest=None)
            ref_metrics = ServeMetrics()
            ref_engine = BatchEngine(model, variables, ref_cfg,
                                     ref_metrics)
            ref_engine.warmup_sched(iters_per_step=1,
                                    modes=["fp32", "bf16"])
            ref_sched = IterationScheduler(ref_engine, ref_cfg,
                                           ref_metrics).start()
            try:
                r_def = ref_sched.submit(a, b).result(timeout=120)
                r_it = ref_sched.submit(a, b, iters=4).result(timeout=120)
                r_fast = ref_sched.submit(a, b, mode="bf16").result(
                    timeout=120)
            finally:
                ref_sched.stop(drain=False)
            np.testing.assert_array_equal(mono[0], r_def.disparity)
            np.testing.assert_array_equal(d_iters, r_it.disparity)
            np.testing.assert_array_equal(d_fast, r_fast.disparity)
        finally:
            if client is not None:
                client.close()
            server.close()
            thread.join(10)

    def test_e2e_divergence_promotes_early(self, cascade_model,
                                           cascade_manifest, tmp_path,
                                           retrace_guard):
        """The EMA trigger provably promotes a seeded adversarial pair
        before its scheduled boundary: with a near-zero threshold the
        first boundary's delta fires, the slot hands off after ONE
        cheap iteration, every remaining iteration runs certified — so
        the EXECUTED fp32 fraction (3/4) exceeds the SCHEDULED one
        (2/4), all still compile-free."""
        from raftstereo_tpu.eval.certify import write_manifest
        from raftstereo_tpu.serve import ServeClient
        from raftstereo_tpu.serve.server import build_server

        model, variables = cascade_model
        path = str(tmp_path / "cert.json")
        write_manifest(cascade_manifest, path)
        cfg = _cfg(path, cascades=(SCHEDULE,), tiers=(),
                   cascade_divergence=1e-9)
        server = build_server(model, variables, cfg)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        client = None
        try:
            client = ServeClient("127.0.0.1", server.port, timeout=120.0)
            # Seeded noise pair: random-texture int8 drafting produces a
            # nonzero boundary delta, which IS the adversarial signal a
            # near-zero threshold converts into an early promotion.
            a, b = _img(seed=11), _img(seed=12)
            with retrace_guard(0, what="early promotion is compile-free "
                                       "(handoff pair warmed)",
                               min_duration_s=0.5):
                _, meta = client.predict(a, b,
                                         accuracy=f"cascade:{SCHEDULE}")
            assert meta["cascade"] == SCHEDULE
            assert meta["promoted_early"] is True
            assert meta["iters"] == 4 and meta["degraded"] is False
            text = client.metrics_text()
            assert _metric(
                text, 'cascade_promotions_total{kind="early"}') == 1.0
            cheap = _metric(text, 'cascade_iterations_total'
                                  '{phase="cheap"}')
            cert = _metric(text, 'cascade_iterations_total'
                                 '{phase="certified"}')
            assert cheap == 1.0 and cert == 3.0
            sched = parse_schedule(SCHEDULE)
            assert cert / (cheap + cert) > sched.fp32_fraction
            assert _metric(text, 'cascade_fp32_fraction') \
                == pytest.approx(0.75)
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz").read())
            assert health["cascade"]["divergence"] == pytest.approx(1e-9)
        finally:
            if client is not None:
                client.close()
            server.close()
            thread.join(10)
