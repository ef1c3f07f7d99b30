"""Spatial sharding (ISSUE 14: parallel/spatial.py + serve/spatial/,
docs/serving.md "Spatial sharding").

The acceptance gate for the subsystem: on a real (1, 4) mesh of virtual
CPU devices the sharded forward is BITWISE-identical to the single-device
reference — cold, warm, and on a session-style ``flow_init`` frame — and
the serving stack routes, admits and refuses spatial requests over real
HTTP without ever compiling under traffic (retrace budget 0 once warm).

The mesh-level test uses the shared ``tiny_model`` (alt corr); the engine
and HTTP tests use the smaller serve-model so each layer's executables
stay cheap.  conftest forces 8 virtual CPU devices; ``spatial_mesh(4)``
takes the first 4.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raftstereo_tpu.config import RAFTStereoConfig, ServeConfig
from raftstereo_tpu.ops.image import BucketPadder
from raftstereo_tpu.parallel.spatial import (SpatialShardingUnsupported,
                                             check_spatial_shape,
                                             jitted_spatial_infer_init,
                                             spatial_mesh,
                                             spatial_row_multiple,
                                             validate_spatial_config)
from raftstereo_tpu.serve import (BatchEngine, ServeClient, ServeError,
                                  ServeMetrics, build_server)


# ----------------------------------------------------------------- fixtures

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)


@pytest.fixture(scope="module")
def serve_model():
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), (64, 96))
    return model, variables


def _img(h, w, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


def _cfg(**kw):
    base = dict(port=0, bucket_multiple=32, buckets=((60, 90),),
                warmup=False, max_batch_size=2, max_wait_ms=40.0,
                queue_limit=32, request_timeout_ms=5000.0, iters=2,
                degraded_iters=2, degrade_queue_depth=16,
                spatial_shards=4, spatial_buckets=((128, 96),))
    base.update(kw)
    return ServeConfig(**base)


# ----------------------------------------------------------------- config

class TestSpatialValidation:
    def test_row_multiple_and_shape_admission(self):
        cfg = RAFTStereoConfig(**TINY)
        # factor 4, two GRU levels -> one stride-2 context stage: 8 rows.
        assert spatial_row_multiple(cfg) == 8
        check_spatial_shape(cfg, 4, 64, 96)  # 64 = 4 shards x 2 multiples
        with pytest.raises(SpatialShardingUnsupported, match="H % 32"):
            check_spatial_shape(cfg, 4, 60, 96)
        with pytest.raises(SpatialShardingUnsupported, match="factor"):
            check_spatial_shape(cfg, 4, 64, 90)
        with pytest.raises(SpatialShardingUnsupported):
            check_spatial_shape(cfg, 0, 64, 96)

    def test_unsupported_configs_refused_eagerly(self):
        validate_spatial_config(RAFTStereoConfig(**TINY))
        for bad in (dict(shared_backbone=True), dict(context_norm="group"),
                    dict(corr_quant=True)):
            with pytest.raises(SpatialShardingUnsupported):
                validate_spatial_config(RAFTStereoConfig(**TINY, **bad))

    def test_body_cap_auto_raises_for_spatial_buckets(self):
        # Satellite: the httpbase body cap becomes a policy knob — a
        # server offering 4K spatial buckets must not 413 its own
        # advertised resolution.
        assert _cfg(max_body_mb=0.1).max_body_mb > 0.1
        big = _cfg(max_body_mb=160.0,
                   spatial_buckets=((2160, 3840),)).max_body_mb
        assert big > 300.0  # 253.1 MiB 4K pair -> cap ~316 MiB with headroom
        # No spatial buckets -> the operator's cap stands untouched.
        assert ServeConfig(port=0, max_body_mb=0.1).max_body_mb == 0.1


# ------------------------------------------------------------- mesh level

class TestSpatialBitwise:
    def test_sharded_forward_bitwise_vs_single_device(self, tiny_model,
                                                      rng):
        """The tentpole numeric contract on a real (1, 4) mesh: zeros
        ``flow_init`` (the cold frame — same executable) and a nonzero
        warm-start frame both reproduce the single-device jit
        bit-for-bit, low-res field and upsampled output alike."""
        model, variables = tiny_model
        iters, h, w = 3, 64, 96
        check_spatial_shape(model.config, 4, h, w)
        i1 = jnp.asarray(rng.standard_normal((1, h, w, 3)) * 50 + 120,
                         jnp.float32)
        i2 = jnp.asarray(rng.standard_normal((1, h, w, 3)) * 50 + 120,
                         jnp.float32)
        f = model.config.factor
        zeros = jnp.zeros((1, h // f, w // f, 1), jnp.float32)

        sp = jitted_spatial_infer_init(model, spatial_mesh(4), iters=iters)
        low_s, up_s = sp(variables, i1, i2, zeros)
        low_r, up_r = model.jitted_infer(iters=iters)(variables, i1, i2)
        np.testing.assert_array_equal(np.asarray(low_s), np.asarray(low_r))
        np.testing.assert_array_equal(np.asarray(up_s), np.asarray(up_r))

        # Session-style warm start: seed the next frame with the low-res
        # field the cold frame produced — same executable, still bitwise.
        low_r2, up_r2 = model.jitted_infer_init(iters=iters)(
            variables, i1, i2, low_r)
        low_s2, up_s2 = sp(variables, i1, i2, low_s)
        np.testing.assert_array_equal(np.asarray(low_s2),
                                      np.asarray(low_r2))
        np.testing.assert_array_equal(np.asarray(up_s2), np.asarray(up_r2))


# ----------------------------------------------------------------- engine

class TestSpatialEngine:
    def test_warmup_infer_bitwise_and_budget_zero(self, serve_model,
                                                  retrace_guard):
        model, variables = serve_model
        eng = BatchEngine(model, variables, _cfg())
        assert eng.spatial_shards == 4
        # Shape policy: the spatial padder raises alignment to 32 rows
        # (4 shards x row multiple 8) on top of the plain bucket grid.
        assert eng.spatial_bucket_of((60, 90, 3)) == (64, 96)
        assert eng.spatial_bucket_of((128, 96, 3)) == (128, 96)

        with retrace_guard(1, what="one spatial bucket, one compile",
                           min_duration_s=0.5):
            warmed = eng.warmup_spatial()
        assert warmed == [(128, 96, 2, "spatial", "s4", "passive", "fp32")]
        assert eng.is_spatial_warm((128, 96), 2)
        assert eng.warmup_spatial() == []  # idempotent: already warm

        left, right = _img(128, 96, seed=1), _img(128, 96, seed=2)
        ref_low, ref_up = model.jitted_infer(iters=2)(
            variables, jnp.asarray(left)[None], jnp.asarray(right)[None])

        # Cold frame AND flow_init session frame share the ONE warmed
        # executable: budget 0 covers the whole steady state.
        with retrace_guard(0, what="warm spatial steady state",
                           min_duration_s=0.5):
            disp, low, miss = eng.infer_spatial(left, right, 2)
            assert miss is False
            disp2, low2, miss2 = eng.infer_spatial(left, right, 2,
                                                   flow_init=low)
            assert miss2 is False
        np.testing.assert_array_equal(disp, np.asarray(ref_up)[0, ..., 0])
        np.testing.assert_array_equal(low, np.asarray(ref_low)[0, :, :, 0])

        ref_low2, ref_up2 = model.jitted_infer_init(iters=2)(
            variables, jnp.asarray(left)[None], jnp.asarray(right)[None],
            ref_low)
        np.testing.assert_array_equal(disp2,
                                      np.asarray(ref_up2)[0, ..., 0])
        np.testing.assert_array_equal(low2,
                                      np.asarray(ref_low2)[0, :, :, 0])

    def test_shard_count_is_engine_fixed(self, serve_model):
        model, variables = serve_model
        eng = BatchEngine(model, variables, _cfg())
        with pytest.raises(AssertionError, match="mesh has 4"):
            eng.infer_spatial(_img(64, 96), _img(64, 96), 2, shards=2)
        off = BatchEngine(model, variables,
                          _cfg(spatial_shards=0, spatial_buckets=()))
        assert off.spatial_shards == 1
        with pytest.raises(AssertionError, match="disabled"):
            off.infer_spatial(_img(64, 96), _img(64, 96), 2)


# ------------------------------------------------------------------- HTTP

class TestSpatialHTTP:
    def test_oversized_pair_served_spatially_end_to_end(self, serve_model,
                                                        retrace_guard):
        """Acceptance gate: a pair the single-chip path refuses
        (max_image_dim 90) is served via the ``spatial`` capability over
        real HTTP — bitwise-equal to the single-device reference — while
        every v1 limitation is a 400 and the warm steady state holds
        retrace budget 0."""
        model, variables = serve_model
        cfg = _cfg(warmup=True, max_image_dim=90, max_body_mb=0.1,
                   cold_buckets=False, spatial_buckets=((64, 96),),
                   request_timeout_ms=120000.0)
        assert cfg.max_body_mb == pytest.approx(0.2)  # auto-raised
        metrics = ServeMetrics()
        server = build_server(model, variables, cfg, metrics)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            client = ServeClient("127.0.0.1", server.port, timeout=120)
            deadline = time.time() + 120
            while time.time() < deadline:
                if client.healthz().get("status") == "ok":
                    break
                time.sleep(0.2)
            health = client.healthz()
            assert health["status"] == "ok"
            # Capability negotiation: /healthz advertises the mesh.
            assert health["spatial"] == {
                "shards": 4, "buckets": [[64, 96]], "row_multiple": 32,
                "iters": [2], "max_body_mb": cfg.max_body_mb}

            big = (_img(64, 96, seed=3), _img(64, 96, seed=4))
            fit = (_img(60, 90, seed=5), _img(60, 90, seed=6))
            ref_low, ref_up = model.jitted_infer(iters=2)(
                variables, jnp.asarray(big[0])[None],
                jnp.asarray(big[1])[None])
            padder = BucketPadder(fit[0].shape, divis_by=cfg.divis_by,
                                  bucket_multiple=cfg.bucket_multiple)
            assert padder.bucket_hw == (64, 96)
            _, ref_fit_up = model.jitted_infer(iters=2)(
                variables, *padder.pad(jnp.asarray(fit[0])[None],
                                       jnp.asarray(fit[1])[None]))
            ref_fit = np.asarray(padder.unpad(ref_fit_up))[0, ..., 0]

            with retrace_guard(0, what="warm spatial HTTP steady state",
                               min_duration_s=0.5):
                # (1) oversized -> auto-routed spatial, bitwise.
                disp, meta = client.predict(*big)
                assert meta["spatial"] == 4 and meta["warm"] is True
                assert meta["iters"] == 2
                np.testing.assert_array_equal(
                    disp, np.asarray(ref_up)[0, ..., 0])
                # (2) spatial=False restores the plain refusal verbatim.
                with pytest.raises(ServeError) as ei:
                    client.predict(*big, spatial=False)
                assert ei.value.status == 400
                assert "max_image_dim" in str(ei.value)
                # (3) explicit spatial=True on a fitting pair: padded to
                # the same bucket, still bitwise through pad/unpad.
                disp_f, meta_f = client.predict(*fit, spatial=True)
                assert meta_f["spatial"] == 4
                np.testing.assert_array_equal(disp_f, ref_fit)
                # (4) the plain path is untouched beside it.
                disp_p, meta_p = client.predict(*fit)
                assert "spatial" not in meta_p
                # (5) v1 limitations are 400s, never silent, never a
                # compile: tiers, sessions, scheduler fields, off-menu
                # iters, unwarmed buckets.
                for kw, frag in [(dict(accuracy="bf16"), "accuracy tier"),
                                 (dict(session_id="s1"), "session"),
                                 (dict(deadline_ms=50.0), "scheduler"),
                                 (dict(priority="interactive"),
                                  "scheduler"),
                                 (dict(iters=7), "not served spatially")]:
                    with pytest.raises(ServeError) as ei:
                        client.predict(*big, **kw)
                    assert ei.value.status == 400, kw
                    assert frag in str(ei.value), kw
                # (96, 64) routes spatially (side 96 > 90) and fits the
                # body cap, but its (96, 64) bucket was never warmed.
                with pytest.raises(ServeError) as ei:
                    client.predict(_img(96, 64, seed=7),
                                   _img(96, 64, seed=8))
                assert ei.value.status == 400
                assert "spatial_buckets" in str(ei.value)

            # Body cap: a pair beyond every configured bucket hits the
            # 413 (possibly as a mid-upload reset — both are the refusal,
            # httpbase module docstring).  The cap is a bytes policy
            # sized to the base64 dialect — the same pair as a wire
            # frame fits under it (that is the wire format's point,
            # docs/wire_format.md), so exercise the refusal over JSON.
            try:
                client2 = ServeClient("127.0.0.1", server.port, timeout=30,
                                      wire_format="json")
                with pytest.raises(ServeError) as ei:
                    client2.predict(_img(128, 192, seed=9),
                                    _img(128, 192, seed=10))
                assert ei.value.status == 413
                assert "spatial_buckets" in str(ei.value)
                client2.close()
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass

            # Observability: the gauge reports the mesh width, spatial
            # requests are counted by outcome, warm latency is observed.
            text = client.metrics_text()

            def sample(prefix):
                vals = [float(l.split()[-1]) for l in text.splitlines()
                        if l.startswith(prefix)]
                assert vals, prefix
                return sum(vals)

            assert sample("spatial_shards ") == 4
            assert sample('spatial_requests_total{outcome="ok"}') >= 2
            assert sample("spatial_request_latency_seconds_count") >= 2
            client.close()
        finally:
            server.shutdown()

    def test_413_message_points_at_spatial_buckets(self):
        # Satellite: the client surfaces the body cap as an actionable
        # configuration hint, not a bare status code.
        err = ServeError(413, {"error": "request body 1.0 MB over limit",
                               "limit_mb": 0.2})
        assert "0.2 MB" in str(err)
        assert "spatial_buckets" in str(err)

    def test_spatial_and_cluster_are_mutually_exclusive(self, serve_model):
        from raftstereo_tpu.config import ClusterConfig

        model, variables = serve_model
        with pytest.raises(ValueError, match="mutually exclusive"):
            build_server(model, variables,
                         _cfg(cluster=ClusterConfig(replicas=2)),
                         ServeMetrics())
