"""Quantized serving fast path (ops/quant.py + accuracy tiers).

Four layers, mirroring how the feature is built:

* **ops** — symmetric per-row int8 quantization units, dequant-scale
  EXACTNESS (the epilogue algebra is exact: on exactly-representable
  inputs the int8 volume equals the fp32 volume bit-for-bit), a
  quantization-theory error bound on random inputs, and the Pallas int8
  kernel verified BITWISE against the XLA integer-einsum path in
  interpret mode on CPU;
* **corr wiring** — quant resolution forces a volume backend, the
  convc1 epilogue disengages, and the phase-split state path
  (build_corr_state / corr_fn_from_state) matches the monolithic
  closure bitwise under quant;
* **engine tiers** — the precision mode joins every executable cache
  key, the DEFAULT path is bitwise-unchanged (no ``accuracy`` field ==
  explicit fp32 == the pre-tier executable), and steady-state traffic
  across all warmed tiers runs under a retrace-guard budget of 0;
* **certification** — the ``fast`` (bf16) tier's measured EPE delta
  stays within its bound on synthetic data, the manifest round-trips,
  and a server refuses to advertise an uncertified/over-bound tier
  (clean 400 on /predict requesting it) while certified tiers serve.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raftstereo_tpu.config import RAFTStereoConfig, ServeConfig
from raftstereo_tpu.ops.corr import (build_corr_state, build_corr_volume,
                                     corr_epilogue_active,
                                     corr_fn_from_state, make_corr_fn,
                                     resolve_implementation)
from raftstereo_tpu.ops.quant import (MODES, TIER_MODES, config_for_mode,
                                      default_mode, mode_for_accuracy,
                                      pallas_int8_corr_volume,
                                      quant_corr_volume, quantize_rows)

# ----------------------------------------------------------------- fixtures


def _tiny_cfg(**kw):
    base = dict(corr_implementation="reg", n_gru_layers=2,
                hidden_dims=(32, 32), corr_levels=2, corr_radius=2)
    base.update(kw)
    return RAFTStereoConfig(**base)


@pytest.fixture(scope="module")
def quant_model():
    """Tiny reg-backend model shared by the engine/cert tests (module
    scope: every executable here is a real XLA compile)."""
    from raftstereo_tpu.models import RAFTStereo

    cfg = _tiny_cfg()
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(7), (64, 96))
    return model, variables


def _img(h=64, w=96, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, (h, w, 3)).astype(np.float32)


def _fmaps(rng, b=2, h=5, w1=7, w2=9, c=16):
    f1 = jnp.asarray(rng.normal(size=(b, h, w1, c)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(b, h, w2, c)), jnp.float32)
    return f1, f2


# ---------------------------------------------------------------------- ops


class TestQuantOps:
    def test_quantize_rows_basics(self, rng):
        x = jnp.asarray(rng.normal(size=(2, 3, 4, 8)) * 10, jnp.float32)
        q, s = quantize_rows(x)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float32
        assert q.shape == x.shape and s.shape == x.shape[:-1]
        qn = np.asarray(q, np.int64)
        assert qn.min() >= -127 and qn.max() <= 127
        # Every row's max-magnitude element hits full scale.
        assert np.all(np.abs(qn).max(axis=-1) == 127)
        # Dequantized values are within half a quantization step.
        deq = qn * np.asarray(s)[..., None]
        assert np.all(np.abs(deq - np.asarray(x))
                      <= np.asarray(s)[..., None] * 0.5 + 1e-7)

    def test_quantize_rows_zero_row(self):
        x = jnp.zeros((1, 1, 2, 4), jnp.float32)
        q, s = quantize_rows(x)
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.asarray(s) == 1.0)  # never a 0 scale

    def test_dequant_scale_exactness(self, rng):
        """On exactly-representable inputs (power-of-two row scales, the
        row max at full int8 range) the quantization recovers the rows
        exactly AND the dequant epilogue reproduces ``build_corr_volume``
        bit-for-bit: products/sums stay exact integers scaled by powers
        of two in fp32, and with C = 16 (sqrt a power of two, like the
        real feature dim 256) the 1/sqrt(C) normalization is exact in
        both its divide and multiply forms."""
        def exact(shape_q, shape_s):
            q = rng.integers(-127, 128, shape_q).astype(np.float32)
            q[..., 0] = 127  # full-scale element pins the row amax
            s = 2.0 ** rng.integers(-6, 3, shape_s).astype(np.float32)
            return jnp.asarray(q * s, jnp.float32)

        f1 = exact((1, 3, 6, 16), (1, 3, 6, 1))
        f2 = exact((1, 3, 5, 16), (1, 3, 5, 1))
        vq = quant_corr_volume(f1, f2, kernel=False)
        vr = build_corr_volume(f1, f2)
        np.testing.assert_array_equal(np.asarray(vq), np.asarray(vr))

    def test_int8_volume_error_bounded(self, rng):
        """Random inputs: the only error is the int8 rounding of the two
        operands, so |quant - fp32| is bounded by the first-order
        quantization bound (rows' scales x operand magnitudes)."""
        f1, f2 = _fmaps(rng)
        c = f1.shape[-1]
        vq = np.asarray(quant_corr_volume(f1, f2, kernel=False))
        vr = np.asarray(build_corr_volume(f1, f2))
        _, s1 = quantize_rows(f1)
        _, s2 = quantize_rows(f2)
        a1 = np.abs(np.asarray(f1)).max(axis=-1)   # == 127 * s1
        a2 = np.abs(np.asarray(f2)).max(axis=-1)
        s1, s2 = np.asarray(s1), np.asarray(s2)
        # Per (row, col) pair: |f1.df2| + |f2.df1| + |df1.df2| with
        # |df| <= scale/2 per element, c elements, 1/sqrt(c) overall.
        bound = (a1[..., :, None] * s2[..., None, :] / 2
                 + a2[..., None, :] * s1[..., :, None] / 2
                 + s1[..., :, None] * s2[..., None, :] / 4
                 ) * c / np.sqrt(c) + 1e-5
        assert np.all(np.abs(vq - vr) <= bound)
        # And it is genuinely quantized (not silently fp32).
        assert np.abs(vq - vr).max() > 0

    def test_pallas_kernel_bitwise_vs_xla(self, rng):
        """The Pallas int8 kernel (interpret mode on CPU, the PR 9
        protocol) is bitwise-equal to the XLA integer-einsum path: both
        run exact int32 accumulation and the SAME dequant epilogue
        expression.  Odd shapes make the lane/row padding do real work."""
        for shape in ((2, 5, 7, 9, 16), (1, 3, 17, 13, 12)):
            b, h, w1, w2, c = shape
            f1 = jnp.asarray(rng.normal(size=(b, h, w1, c)), jnp.float32)
            f2 = jnp.asarray(rng.normal(size=(b, h, w2, c)), jnp.float32)
            q1, s1 = quantize_rows(f1)
            q2, s2 = quantize_rows(f2)
            vk = pallas_int8_corr_volume(q1, s1, q2, s2)
            vx = quant_corr_volume(f1, f2, kernel=False)
            np.testing.assert_array_equal(np.asarray(vk), np.asarray(vx))

    def test_quant_volume_dtype(self, rng):
        f1, f2 = _fmaps(rng, b=1, h=2)
        assert quant_corr_volume(f1, f2, dtype=jnp.bfloat16,
                                 kernel=True).dtype == jnp.bfloat16


# -------------------------------------------------------------- corr wiring


class TestQuantCorrWiring:
    def test_quant_forces_volume_backend(self):
        # CPU: every configured backend resolves to the precomputed-
        # volume gather path under quant (on-demand backends would
        # re-quantize per lookup), and the pallas_alt-only convc1
        # epilogue disengages.
        for impl in ("auto", "reg", "alt", "pallas", "pallas_alt"):
            assert resolve_implementation(impl, quant=True) == "reg"
            assert corr_epilogue_active(impl, quant=True) is False

    def test_state_split_matches_monolithic_quant(self, rng):
        """build_corr_state + corr_fn_from_state under quant is bitwise
        the monolithic make_corr_fn closure — the property that makes
        monolithic, stream and sched phase-split paths share one
        quantized numeric story."""
        f1, f2 = _fmaps(rng, b=1, h=4, w1=8, w2=8, c=8)
        coords = jnp.asarray(
            rng.uniform(0, 7, (1, 4, 8, 1)), jnp.float32)
        mono = make_corr_fn("reg", f1, f2, 2, 2, quant=True)(coords)
        state = build_corr_state("reg", f1, f2, 2, quant=True)
        split = corr_fn_from_state("reg", state, 2, 2, quant=True)(coords)
        np.testing.assert_array_equal(np.asarray(mono), np.asarray(split))
        # And quant actually changed the state vs the unquantized build.
        ref_state = build_corr_state("reg", f1, f2, 2, quant=False)
        assert not np.array_equal(np.asarray(state[0]),
                                  np.asarray(ref_state[0]))


# ------------------------------------------------------------ tiers (pure)


class TestTierVocabulary:
    def test_tier_modes_and_resolution(self):
        assert mode_for_accuracy("certified") == "fp32"
        assert mode_for_accuracy("fast") == "bf16"
        assert mode_for_accuracy("turbo") == "int8"
        with pytest.raises(ValueError, match="unknown accuracy tier"):
            mode_for_accuracy("bogus")

    def test_config_for_mode_swaps_only_numeric_policy(self):
        base = _tiny_cfg(corr_implementation="pallas_alt")
        for mode, (cd, qd) in {"fp32": ("float32", False),
                               "bf16": ("bfloat16", False),
                               "int8": ("bfloat16", True)}.items():
            c = config_for_mode(base, mode)
            assert c.compute_dtype == cd and c.corr_quant == qd
            assert c.corr_implementation == base.corr_implementation
            assert c.hidden_dims == base.hidden_dims
            assert default_mode(c) == mode
        with pytest.raises(ValueError, match="unknown precision mode"):
            config_for_mode(base, "fp16")

    def test_default_mode_aliases_only_canonical_configs(self):
        """A base config keys onto a tier mode ONLY when it is exactly
        that mode's canonical config — a lossy alias (e.g. fp32 compute
        with a bf16 corr volume) would let `accuracy="certified"` serve
        the base program's numerics instead of the certified fp32 one."""
        assert default_mode(_tiny_cfg()) == "fp32"
        for mode in MODES:
            assert default_mode(config_for_mode(_tiny_cfg(), mode)) == mode
        # Non-canonical numeric mixes get the distinct "base" token.
        assert default_mode(_tiny_cfg(corr_dtype="bfloat16")) == "base"
        assert default_mode(_tiny_cfg(compute_dtype="bfloat16")) == "base"
        assert default_mode(
            _tiny_cfg(compute_dtype="bfloat16", corr_dtype="bfloat16",
                      corr_quant=True)) == "int8"

    def test_serve_config_validates_tiers(self):
        with pytest.raises(AssertionError, match="unknown accuracy tier"):
            ServeConfig(port=0, tiers=("fast", "ultra"))


# ------------------------------------------------------------ engine tiers


class TestEngineTiers:
    def test_tier_keys_default_bitwise_and_budget0(self, quant_model,
                                                   retrace_guard):
        """One engine through the whole tier lifecycle (one test: the
        compiles are the expensive part).  (1) every executable key ends
        in the precision mode and the DEFAULT path == explicit fp32
        bitwise (same executable — the pre-tier behaviour); (2) int8
        produces a different (quantized) result; (3) after per-tier
        warmup, steady-state traffic across ALL warmed tiers — plain,
        stream and sched phases — compiles NOTHING (budget 0)."""
        from raftstereo_tpu.serve.engine import BatchEngine

        model, variables = quant_model
        cfg = ServeConfig(port=0, buckets=((64, 96),), max_batch_size=2,
                          iters=2, degraded_iters=2, divis_by=32,
                          bucket_multiple=32, warmup=False)
        eng = BatchEngine(model, variables, cfg)
        assert eng.default_mode == "fp32"
        a, b = _img(seed=1), _img(seed=2)

        warmed = eng.warmup(iters_list=[2], modes=["fp32", "bf16", "int8"])
        # every row count (max_batch_size 2 -> 1, 2) of every tier
        assert sorted(warmed) == [
            (64, 96, 2, "batch", f"r{rows}", "passive", mode)
            for rows in (1, 2) for mode in ("bf16", "fp32", "int8")]
        # Stream + sched tier executables (bf16 exercises a non-default
        # mode through BOTH split paths).
        eng.warmup_stream(ladder=[2], modes=["bf16"])
        eng.warmup_sched(iters_per_step=1, modes=["bf16"])
        assert (64, 96, 2, "stream", "passive", "bf16") in eng.compiled_keys
        assert eng.is_stream_warm((64, 96), 2, mode="bf16")
        assert not eng.is_stream_warm((64, 96), 2)  # default not warmed
        assert eng.is_sched_warm((64, 96), 1, mode="bf16")
        sorted(eng.compiled_keys)  # mixed-arity keys stay sortable

        with retrace_guard(0, what="steady-state traffic across warmed "
                                   "tiers is compile-free",
                           min_duration_s=0.5):
            d_default = eng.infer_batch([(a, b)], 2)[0]
            d_fp32 = eng.infer_batch([(a, b)], 2, mode="fp32")[0]
            d_bf16 = eng.infer_batch([(a, b)], 2, mode="bf16")[0]
            d_int8 = eng.infer_batch([(a, b)], 2, mode="int8")[0]
            _, low, miss = eng.infer_stream_batch([(a, b)], 2, [None],
                                                  mode="bf16")[0]
            assert not miss
            hw, st, miss = eng.infer_sched_prologue([(a, b)], [None], [0],
                                                    mode="bf16")
            assert not miss
            st, miss = eng.infer_sched_step(hw, st, 1, mode="bf16")
            assert not miss
            _, _, miss = eng.infer_sched_epilogue(hw, st, mode="bf16")
            assert not miss
        # The default path IS the fp32 path, bitwise (tier system off ==
        # tier system on with no accuracy field).
        np.testing.assert_array_equal(d_default, d_fp32)
        # The tiers genuinely change numerics (not silently fp32).
        assert not np.array_equal(d_default, d_bf16)
        assert not np.array_equal(d_default, d_int8)

    def test_non_canonical_base_never_aliases_a_tier(self, quant_model):
        """An engine whose base config matches no canonical tier config
        keys its default path as "base": an explicit fp32 tier request
        resolves to a DIFFERENT key and a freshly-built canonical fp32
        model, never the base program's numerics (no compiles here —
        key/model wiring only)."""
        from raftstereo_tpu.models import RAFTStereo
        from raftstereo_tpu.serve.engine import BatchEngine

        model, variables = quant_model
        mixed = RAFTStereo(_tiny_cfg(corr_dtype="bfloat16"))
        cfg = ServeConfig(port=0, buckets=((64, 96),), max_batch_size=2,
                          iters=2, degraded_iters=2, warmup=False)
        eng = BatchEngine(mixed, variables, cfg)
        assert eng.default_mode == "base"
        assert eng._mode(None) == "base" != eng._mode("fp32")
        assert eng._model_for("fp32").config == \
            config_for_mode(mixed.config, "fp32")
        assert eng._model_for("base") is mixed

    def test_batcher_groups_by_mode(self, quant_model):
        """Two same-bucket requests in different tiers never share a
        batch: the mode is part of the batcher's grouping key."""
        from raftstereo_tpu.serve.batcher import DynamicBatcher

        class SpyEngine:
            def __init__(self):
                self.calls = []

            def bucket_of(self, shape):
                return (64, 96)

            def infer_batch(self, pairs, iters, mode=None):
                self.calls.append((len(pairs), iters, mode))
                return [np.zeros((64, 96), np.float32)] * len(pairs)

        eng = SpyEngine()
        cfg = ServeConfig(port=0, max_batch_size=4, iters=2,
                          degraded_iters=2, max_wait_ms=40.0)
        with DynamicBatcher(eng, cfg) as batcher:
            futs = [batcher.submit(_img(), _img(), mode=None),
                    batcher.submit(_img(), _img(), mode="bf16"),
                    batcher.submit(_img(), _img(), mode=None)]
            for f in futs:
                f.result(timeout=30)
        modes = sorted((n, m) for n, _, m in eng.calls)
        assert modes == [(1, "bf16"), (2, None)]


# ------------------------------------------------------------ certification


@pytest.fixture(scope="module")
def fast_manifest(quant_model):
    """Certification manifest for the tiny model: 'fast' measured and
    certified; 'turbo' measured with an impossible bound so it is
    PRESENT but uncertified (the over-bound refusal case)."""
    from raftstereo_tpu.eval.certify import certify_tiers

    model, variables = quant_model
    return certify_tiers(model.config, variables, ("fast", "turbo"),
                         hw=(64, 96), n_pairs=2, iters=3,
                         bounds={"fast": 0.75, "turbo": -1.0})


class TestCertification:
    def test_fast_tier_certified_within_bound(self, fast_manifest):
        """THE satellite assertion: the fast (bf16) tier's measured EPE
        delta vs the fp32 reference stays within its certification bound
        on synthetic data."""
        entry = fast_manifest["tiers"]["fast"]
        assert entry["mode"] == "bf16"
        assert entry["epe_delta"] <= entry["bound"] == 0.75
        assert entry["certified"] is True
        # The impossible bound flags turbo as over-bound, so the
        # manifest carries a genuinely refusable entry.
        assert fast_manifest["tiers"]["turbo"]["certified"] is False

    def test_a_parent_builds_manifest_still_certifies(self, fast_manifest,
                                                      quant_model):
        """A manifest written when the architecture fingerprint had one
        more field (``gru_backend``, always the XLA step on a chip) keeps
        its certificate: only the fields this build fingerprints are
        compared, and a mismatch among those still names itself."""
        from raftstereo_tpu.eval.certify import tier_ok

        model, _ = quant_model
        assert "gru_backend" not in fast_manifest["model"]
        old = dict(fast_manifest,
                   model=dict(fast_manifest["model"], gru_backend="auto"))
        assert tier_ok(old, "fast", model.config) == (True, "certified")
        ok, reason = tier_ok(old, "fast", _tiny_cfg(corr_levels=3))
        assert not ok and "['corr_levels']" in reason

    def test_manifest_roundtrip_and_validation(self, fast_manifest,
                                               quant_model, tmp_path):
        from raftstereo_tpu.eval.certify import (load_manifest, tier_ok,
                                                 write_manifest)

        model, _ = quant_model
        path = str(tmp_path / "cert.json")
        write_manifest(fast_manifest, path)
        loaded = load_manifest(path)
        assert loaded["tiers"] == fast_manifest["tiers"]
        ok, _ = tier_ok(loaded, "fast", model.config)
        assert ok
        # Over-bound, absent, and architecture-mismatched all refuse.
        assert tier_ok(loaded, "turbo", model.config)[0] is False
        assert tier_ok(None, "fast")[0] is False
        other = _tiny_cfg(n_gru_layers=1, hidden_dims=(32,))
        ok, reason = tier_ok(loaded, "fast", other)
        assert not ok and "architecture" in reason
        # Numeric-relevant non-tier fields are fingerprinted too (a
        # manifest must certify the kernels actually served) ...
        ok, reason = tier_ok(loaded, "fast",
                             _tiny_cfg(corr_implementation="alt"))
        assert not ok and "corr_implementation" in reason
        # ... and so is the platform: "auto" backends resolve per
        # platform, so CPU-measured deltas cannot certify TPU kernels.
        assert loaded["platform"] == "cpu"
        ok, reason = tier_ok(dict(loaded, platform="tpu"), "fast",
                             model.config)
        assert not ok and "platform" in reason
        # Corrupt manifests refuse loudly.
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        with pytest.raises(ValueError, match="not valid JSON"):
            load_manifest(str(bad))

    def test_server_advertises_only_certified_tiers(self, quant_model,
                                                    fast_manifest,
                                                    tmp_path,
                                                    retrace_guard):
        """HTTP e2e: certified+fast advertised (fast serves, 200, meta
        tier label), turbo requested-but-over-bound is refused at
        startup and /predict requesting it is a clean 400 carrying the
        reason; default requests stay bitwise == explicit certified; a
        second round of tier traffic is compile-free."""
        from raftstereo_tpu.eval.certify import write_manifest
        from raftstereo_tpu.serve.client import ServeClient, ServeError
        from raftstereo_tpu.serve.server import build_server

        model, variables = quant_model
        path = str(tmp_path / "cert.json")
        write_manifest(fast_manifest, path)
        cfg = ServeConfig(port=0, buckets=((64, 96),), max_batch_size=2,
                          iters=2, degraded_iters=2, divis_by=32,
                          bucket_multiple=32, max_wait_ms=1.0,
                          tiers=("certified", "fast", "turbo"),
                          cert_manifest=path)
        server = build_server(model, variables, cfg)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            assert server.tiers == {"certified": "fp32", "fast": "bf16"}
            assert "over bound" in server.tier_reasons["turbo"] \
                or "bound" in server.tier_reasons["turbo"]
            client = ServeClient("127.0.0.1", server.port)
            a, b = _img(seed=3), _img(seed=4)
            d_default, _ = client.predict(a, b)
            d_cert, meta_c = client.predict(a, b, accuracy="certified")
            d_fast, meta_f = client.predict(a, b, accuracy="fast")
            np.testing.assert_array_equal(d_default, d_cert)
            assert meta_c["accuracy"] == "certified"
            assert meta_f["accuracy"] == "fast"
            assert not np.array_equal(d_default, d_fast)
            # The uncertified tier is a clean 400 with the reason.
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy="turbo")
            assert ei.value.status == 400
            assert "not advertised" in ei.value.payload["error"]
            # Unknown tiers too (never a 500, never a silent default).
            with pytest.raises(ServeError) as ei:
                client.predict(a, b, accuracy="extreme")
            assert ei.value.status == 400
            # /healthz reports both sides of the decision.
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz").read())
            assert health["tiers"]["advertised"] == {
                "certified": "fp32", "fast": "bf16"}
            assert "turbo" in health["tiers"]["refused"]
            # Tier-labeled metrics made it to /metrics, lint-clean.
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics").read().decode()
            assert 'serve_tier_requests_total{tier="fast"} 1' in text
            assert 'serve_tier_requests_total{tier="default"} 1' in text
            from raftstereo_tpu.obs.prom import validate_prometheus
            assert validate_prometheus(text) == []
            # Warmed tiers stay warm under traffic: budget 0.
            with retrace_guard(0, what="tier traffic after warmup is "
                                       "compile-free",
                               min_duration_s=0.5):
                client.predict(a, b, accuracy="fast")
                client.predict(a, b, accuracy="certified")
                client.predict(a, b)
            client.close()
        finally:
            server.close()
            thread.join(10)
