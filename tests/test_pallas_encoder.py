"""Fused Pallas encoder stem (ops/pallas_encoder.py): equivalence with the
plain flax path it replaces, in interpret mode on the CPU suite."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raftstereo_tpu.ops import pallas_encoder as pe



@pytest.fixture
def stage(rng):
    B, H, W, C = 2, 16, 24, 8
    y1 = jnp.asarray(rng.normal(size=(B, H, W, C)).astype(np.float32)) * 2 + 0.3
    params = {k: {"kernel": jnp.asarray(
                      rng.normal(size=(3, 3, C, C)).astype(np.float32)) * 0.2,
                  "bias": jnp.asarray(
                      rng.normal(size=(C,)).astype(np.float32)) * 0.1}
              for k in ("c10", "c11", "c20", "c21")}
    return y1, params


class TestPackedConv:
    def test_matches_lax_conv(self, rng):
        B, H, W, C = 1, 8, 12, 8
        x = jnp.asarray(np.abs(rng.normal(size=(B, H, W, C))).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(3, 3, C, C)).astype(np.float32)) * 0.2
        # Identity prep affine: relu(x*1 + 0) (inputs are nonnegative).
        ident = (jnp.ones((B, 1, 2 * C), jnp.float32),
                 jnp.zeros((B, 1, 2 * C), jnp.float32))
        y, _ = pe._enc_conv(pe.pack_view(x), ident, pe.pack_weights(w),
                            pe.pack_vec(jnp.zeros((C,), jnp.float32)))
        want = jax.lax.conv_general_dilated(
            x, w, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        np.testing.assert_allclose(np.asarray(pe.unpack_view(y)),
                                   np.asarray(want), rtol=1e-4, atol=1e-5)


class TestFusedStage:
    def test_matches_reference(self, stage):
        y1, params = stage
        got = pe.fused_stem_layer1(y1, params)
        want = pe._xla_reference(y1, params)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_multi_block_halo(self, rng):
        """H spanning several row blocks exercises the prepped-halo edge
        masking (zero padding must stay zero AFTER normalization)."""
        B, H, W, C = 1, 24, 16, 8   # _row_block(24) = 8 -> 3 blocks
        y1 = jnp.asarray(rng.normal(size=(B, H, W, C)).astype(np.float32)) - 0.7
        params = {k: {"kernel": jnp.asarray(
                          rng.normal(size=(3, 3, C, C)).astype(np.float32)) * 0.2,
                      "bias": jnp.zeros((C,), jnp.float32)}
                  for k in ("c10", "c11", "c20", "c21")}
        got = pe.fused_stem_layer1(y1, params)
        want = pe._xla_reference(y1, params)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_gradients_match_reference(self, stage):
        y1, params = stage
        g1 = jax.grad(lambda a: (pe.stem_layer1(a, params) ** 2).sum())(y1)
        g2 = jax.grad(lambda a: (pe._xla_reference(a, params) ** 2).sum())(y1)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-3, atol=1e-4)


class TestEncoderIntegration:
    def test_encoder_fused_equals_plain(self, rng):
        """BasicEncoder end-to-end: the fused fast path must match the
        plain flax path (which the CPU suite, torch parity, and all
        sharded paths keep using) at stat-precision tolerance."""
        from raftstereo_tpu.models.encoders import BasicEncoder

        enc = BasicEncoder(output_dim=32, norm_fn="instance", downsample=2,
                           dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 32, 48, 3)).astype(np.float32))
        v = enc.init(jax.random.key(0), x)
        plain = enc.apply(v, x)
        with pe.override_fused_stem(True):
            fused = enc.apply(v, x)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                                   rtol=2e-3, atol=2e-3)

    def test_gate(self):
        from raftstereo_tpu.parallel import make_mesh
        from raftstereo_tpu.parallel.context import use_corr_mesh

        shape = (8, 32, 64, 64)
        # batch norm qualifies structurally (frozen BN folds to an
        # affine), but 8 images trip the <=4-per-shard auto gate...
        assert not pe.use_fused_stem("batch", shape)
        assert not pe.use_fused_stem("instance", shape)
        # ...small batches pass it (on TPU; forced here via override).
        assert pe.use_fused_stem("batch", (2, 32, 64, 64), override=True)
        assert not pe.use_fused_stem("instance", (8, 32, 63, 64))
        assert not pe.use_fused_stem("group", shape, override=True)
        # Explicit override (config.fused_encoder) wins over backend auto.
        assert pe.use_fused_stem("instance", shape, override=True)
        assert not pe.use_fused_stem("instance", shape, override=False)
        n = jax.device_count()
        if n > 1:
            with use_corr_mesh(make_mesh(data=n)):
                # Partitionable under the mesh: override may force it on...
                assert pe.use_fused_stem("instance", shape, override=True)
                # ...but a non-divisible batch falls back, loudly.
                with pytest.warns(RuntimeWarning, match="cannot partition"):
                    assert not pe.use_fused_stem(
                        "instance", (3, 32, 64, 64), override=True)

    @pytest.mark.skipif(jax.device_count() < 2,
                        reason="needs a multi-device mesh")
    def test_sharded_equals_unsharded(self, stage):
        """shard_map'd fused stage (data x space mesh: stats psum +
        ppermute'd halo rows) must match the single-device fused stage."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from raftstereo_tpu.parallel import (DATA_AXIS, SPACE_AXIS,
                                             make_mesh)
        from raftstereo_tpu.parallel.context import use_corr_mesh

        y1, params = stage  # B=2, H=16: shards over data=2 x space=2
        want = pe._xla_reference(y1, params)
        space = 2 if jax.device_count() >= 4 else 1
        data = 2
        mesh = make_mesh(data=data, space=space)
        y1s = jax.device_put(
            y1, NamedSharding(mesh, P(DATA_AXIS, SPACE_AXIS, None, None)))
        with use_corr_mesh(mesh):
            got = jax.jit(pe.stem_layer1)(y1s, params)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.skipif(jax.device_count() < 4,
                        reason="needs a data x space mesh")
    def test_sharded_gradients(self, stage):
        """Backward under the mesh: the XLA-reference VJP runs on global
        arrays (GSPMD partitions it), so grads match the unsharded ones."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from raftstereo_tpu.parallel import (DATA_AXIS, SPACE_AXIS,
                                             make_mesh)
        from raftstereo_tpu.parallel.context import use_corr_mesh

        y1, params = stage
        f = lambda a: (pe.stem_layer1(a, params) ** 2).sum()
        want = jax.grad(lambda a: (pe._xla_reference(a, params) ** 2).sum())(y1)
        mesh = make_mesh(data=2, space=2)
        y1s = jax.device_put(
            y1, NamedSharding(mesh, P(DATA_AXIS, SPACE_AXIS, None, None)))
        with use_corr_mesh(mesh):
            got = jax.jit(jax.grad(f))(y1s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


class TestFusedConv1:
    def make(self, rng, B=1, H=16, W=24):
        img = jnp.asarray(rng.normal(size=(B, H, W, 3)).astype(np.float32))
        c1 = {"kernel": jnp.asarray(
                  rng.normal(size=(7, 7, 3, 8)).astype(np.float32)) * 0.2,
              "bias": jnp.asarray(
                  rng.normal(size=(8,)).astype(np.float32)) * 0.1}
        return img, c1

    def test_stem_conv1_matches_lax(self, rng):
        img, c1 = self.make(rng, H=16, W=24)   # 2 row blocks: halo paths
        y, (s1, s2) = pe._stem_conv1(img, c1, jnp.float32)
        want = pe._xla_conv1(img, c1, jnp.float32)
        got = pe.unpack_view(y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        # fused stats must equal the raw output's sums (packed halves)
        c = s1.shape[-1] // 2
        t1 = np.asarray(s1[..., :c] + s1[..., c:]).ravel()
        np.testing.assert_allclose(
            t1, np.asarray(want.sum(axis=(1, 2))).ravel(), rtol=1e-4)

    def test_conv1_stage_matches_reference(self, rng):
        img, c1 = self.make(rng)
        params = {k: {"kernel": jnp.asarray(
                          rng.normal(size=(3, 3, 8, 8)).astype(np.float32)) * 0.2,
                      "bias": jnp.asarray(
                          rng.normal(size=(8,)).astype(np.float32)) * 0.1}
                  for k in ("c10", "c11", "c20", "c21")}
        got = pe.conv1_stem_layer1(img, c1, params, jnp.float32)
        want = pe._xla_reference(pe._xla_conv1(img, c1, jnp.float32), params)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_conv1_stage_gradients(self, rng):
        img, c1 = self.make(rng)
        params = {k: {"kernel": jnp.asarray(
                          rng.normal(size=(3, 3, 8, 8)).astype(np.float32)) * 0.2,
                      "bias": jnp.zeros((8,), jnp.float32)}
                  for k in ("c10", "c11", "c20", "c21")}
        f = lambda im: (pe.conv1_stem_layer1(im, c1, params) ** 2).sum()
        r = lambda im: (pe._xla_reference(
            pe._xla_conv1(im, c1, jnp.float32), params) ** 2).sum()
        np.testing.assert_allclose(np.asarray(jax.grad(f)(img)),
                                   np.asarray(jax.grad(r)(img)),
                                   rtol=1e-3, atol=1e-4)

    @pytest.mark.skipif(jax.device_count() < 4,
                        reason="needs a data x space mesh")
    def test_conv1_stage_sharded(self, rng):
        """Space sharding exchanges 3 image halo rows per boundary; the
        result must match the single-device pipeline."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from raftstereo_tpu.parallel import DATA_AXIS, SPACE_AXIS, make_mesh
        from raftstereo_tpu.parallel.context import use_corr_mesh

        img, c1 = self.make(rng, B=2, H=16, W=24)
        params = {k: {"kernel": jnp.asarray(
                          rng.normal(size=(3, 3, 8, 8)).astype(np.float32)) * 0.2,
                      "bias": jnp.zeros((8,), jnp.float32)}
                  for k in ("c10", "c11", "c20", "c21")}
        want = pe._xla_reference(pe._xla_conv1(img, c1, jnp.float32), params)
        mesh = make_mesh(data=2, space=2)
        imgs = jax.device_put(
            img, NamedSharding(mesh, P(DATA_AXIS, SPACE_AXIS, None, None)))
        with use_corr_mesh(mesh):
            got = jax.jit(
                lambda a: pe.conv1_stem_layer1(a, c1, params))(imgs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestStatsPrecisionEnvelope:
    def test_variance_formulation_error_bound(self, rng):
        """The E[x^2] - mean^2 formulation (pallas_norm / stats_from_packed)
        loses precision when |mean| >> std (fp32 cancellation).  Pin the
        measured envelope so the regime where it holds is explicit:
        at |mean|/std = 100 — far beyond encoder activations, whose
        conv outputs keep |mean|/std < ~10 — rstd error stays < 1%."""
        h, w, c = 32, 48, 8
        for ratio, tol in ((10.0, 1e-4), (100.0, 1e-2)):
            x = (ratio + rng.normal(size=(1, h, w, c))).astype(np.float32)
            xp = pe.pack_view(jnp.asarray(x))
            s1, s2 = pe._packed_stats(xp)
            mean, rstd = pe.stats_from_packed(s1, s2, float(h * w))
            x64 = np.asarray(x, np.float64)
            want_rstd = 1.0 / np.sqrt(x64.var(axis=(1, 2)) + 1e-5)
            rel = np.abs(np.asarray(rstd)[:, 0] - want_rstd) / want_rstd
            assert rel.max() < tol, (ratio, rel.max())


class TestBNAffineStage:
    """Frozen-BatchNorm encoders through the fused pipeline: the norms
    fold to constant prep affines (bn_affine) — no stats, no psum."""

    def make(self, rng, C=8):
        params = {k: {"kernel": jnp.asarray(
                          rng.normal(size=(3, 3, C, C)).astype(np.float32)) * 0.2,
                      "bias": jnp.asarray(
                          rng.normal(size=(C,)).astype(np.float32)) * 0.1}
                  for k in ("c10", "c11", "c20", "c21")}
        affines = [(jnp.asarray(np.abs(rng.normal(size=(C,)) * 0.5 + 1)
                                .astype(np.float32)),
                    jnp.asarray(rng.normal(size=(C,)).astype(np.float32) * 0.3))
                   for _ in range(5)]
        # One dead-gamma channel: the affine form must represent s=0
        # exactly (output = relu(t)).
        s0, t0 = affines[1]
        affines[1] = (s0.at[0].set(0.0), t0.at[0].set(0.7))
        return params, affines

    def test_matches_affine_reference(self, rng):
        B, H, W, C = 2, 16, 24, 8
        y1 = jnp.asarray(rng.normal(size=(B, H, W, C)).astype(np.float32))
        params, affines = self.make(rng)
        got = pe.bn_stem_layer1(y1, params, affines)
        want = pe._xla_reference_affine(y1, params, affines)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_conv1_variant_and_gradients(self, rng):
        B, H, W = 1, 16, 24
        img = jnp.asarray(rng.normal(size=(B, H, W, 3)).astype(np.float32))
        c1 = {"kernel": jnp.asarray(
                  rng.normal(size=(7, 7, 3, 8)).astype(np.float32)) * 0.2,
              "bias": jnp.zeros((8,), jnp.float32)}
        params, affines = self.make(rng)
        got = pe.bn_conv1_stem_layer1(img, c1, params, affines)
        want = pe._xla_reference_affine(pe._xla_conv1(img, c1, jnp.float32),
                                        params, affines)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        # Gradients flow into the affines (BatchNorm scale/bias train).
        f = lambda aff: (pe.bn_conv1_stem_layer1(img, c1, params, aff)
                         ** 2).sum()
        r = lambda aff: (pe._xla_reference_affine(
            pe._xla_conv1(img, c1, jnp.float32), params, aff) ** 2).sum()
        ga, gr = jax.grad(f)(affines), jax.grad(r)(affines)
        for (a1, b1), (a2, b2) in zip(ga, gr):
            np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                                       rtol=1e-3, atol=1e-4)
            np.testing.assert_allclose(np.asarray(b1), np.asarray(b2),
                                       rtol=1e-3, atol=1e-4)

    def test_encoder_bn_fused_equals_plain(self, rng):
        """MultiBasicEncoder-style BN trunk end-to-end: fused == plain,
        with realistic (nonzero-mean) running statistics."""
        from raftstereo_tpu.models.encoders import BasicEncoder

        enc = BasicEncoder(output_dim=32, norm_fn="batch", downsample=2,
                           dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 32, 48, 3)).astype(np.float32))
        v = enc.init(jax.random.key(0), x)
        # Perturb running stats away from init (mean 0 / var 1) so the
        # affine fold is exercised nontrivially.
        import jax as _jax
        bs = _jax.tree.map(lambda a: a + 0.3 * jnp.arange(a.size,
                                                          dtype=a.dtype)
                           .reshape(a.shape) / a.size, v["batch_stats"])
        v = {"params": v["params"], "batch_stats": bs}
        plain = enc.apply(v, x)
        with pe.override_fused_stem(True):
            fused = enc.apply(v, x)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                                   rtol=2e-3, atol=2e-3)


class TestFusedConv1Stride2:
    def test_stem_conv1_s2_matches_lax(self, rng):
        B, H, W = 1, 24, 32   # H/2=12 output rows -> row block 4: halos
        img = jnp.asarray(rng.normal(size=(B, H, W, 3)).astype(np.float32))
        c1 = {"kernel": jnp.asarray(
                  rng.normal(size=(7, 7, 3, 8)).astype(np.float32)) * 0.2,
              "bias": jnp.asarray(
                  rng.normal(size=(8,)).astype(np.float32)) * 0.1}
        y, (s1, s2) = pe._stem_conv1_s2(img, c1, jnp.float32)
        want = pe._xla_conv1(img, c1, jnp.float32, stride=2)
        got = pe.unpack_view(y)
        assert got.shape == want.shape, (got.shape, want.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        c = s1.shape[-1] // 2
        t1 = np.asarray(s1[..., :c] + s1[..., c:]).ravel()
        np.testing.assert_allclose(
            t1, np.asarray(want.sum(axis=(1, 2))).ravel(), rtol=1e-4)

    def test_conv1_s2_stage_and_gradients(self, rng):
        img = jnp.asarray(rng.normal(size=(1, 24, 32, 3)).astype(np.float32))
        c1 = {"kernel": jnp.asarray(
                  rng.normal(size=(7, 7, 3, 8)).astype(np.float32)) * 0.2,
              "bias": jnp.zeros((8,), jnp.float32)}
        params = {k: {"kernel": jnp.asarray(
                          rng.normal(size=(3, 3, 8, 8)).astype(np.float32)) * 0.2,
                      "bias": jnp.zeros((8,), jnp.float32)}
                  for k in ("c10", "c11", "c20", "c21")}
        got = pe.conv1_stem_layer1(img, c1, params, jnp.float32, 2)
        want = pe._xla_reference(pe._xla_conv1(img, c1, jnp.float32, 2),
                                 params)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        f = lambda im: (pe.conv1_stem_layer1(im, c1, params,
                                             jnp.float32, 2) ** 2).sum()
        r = lambda im: (pe._xla_reference(
            pe._xla_conv1(im, c1, jnp.float32, 2), params) ** 2).sum()
        np.testing.assert_allclose(np.asarray(jax.grad(f)(img)),
                                   np.asarray(jax.grad(r)(img)),
                                   rtol=1e-3, atol=1e-4)

    def test_realtime_encoder_shape_bn(self, rng):
        """MultiBasicEncoder trunk path (BN, downsample 3) end-to-end."""
        from raftstereo_tpu.models.encoders import BasicEncoder

        enc = BasicEncoder(output_dim=32, norm_fn="batch", downsample=3,
                           dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 32, 48, 3)).astype(np.float32))
        v = enc.init(jax.random.key(0), x)
        plain = enc.apply(v, x)
        with pe.override_fused_stem(True):
            fused = enc.apply(v, x)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                                   rtol=2e-3, atol=2e-3)


class TestParamGradients:
    """Parameter gradients (kernel AND nonzero bias) of the hand-written
    saved-residual backward (_stage_bwd_xla / _stage_bwd_xla_affine /
    _conv1_bwd) vs the reference formulation's autodiff — the input-grad
    tests above cannot catch a swapped dkernel, a dropped _drelu on a
    param branch, or a mistransposed weight-grad conv."""

    def params(self, rng, C=8):
        return {k: {"kernel": jnp.asarray(
                        rng.normal(size=(3, 3, C, C)).astype(np.float32)) * 0.2,
                    "bias": jnp.asarray(
                        rng.normal(size=(C,)).astype(np.float32)) * 0.1}
                for k in ("c10", "c11", "c20", "c21")}

    def assert_tree_close(self, got, want, rtol=1e-3):
        # atol keyed to the gradient tree's scale: the instance-norm stage
        # is shift-invariant, so conv BIAS grads are analytically zero and
        # their computed values are fp cancellation noise (~1e-9 of the
        # kernel-grad scale) that differs between formulations; a bug this
        # suite exists to catch (swapped dkernels, dropped relu mask,
        # mistransposed conv) shifts leaves at the tree's own magnitude.
        leaves_w = jax.tree.leaves(want)
        scale = max(float(np.abs(np.asarray(w)).max()) for w in leaves_w)
        atol = 1e-4 * (1.0 + scale)
        for g, w in zip(jax.tree.leaves(got), leaves_w):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=rtol, atol=atol)

    def test_stage_param_grads(self, rng):
        y1 = jnp.asarray(rng.normal(size=(2, 16, 24, 8))
                         .astype(np.float32)) * 2 + 0.3
        params = self.params(rng)
        f = lambda p: (pe.stem_layer1(y1, p) ** 2).sum()
        r = lambda p: (pe._xla_reference(y1, p) ** 2).sum()
        self.assert_tree_close(jax.grad(f)(params), jax.grad(r)(params))

    @pytest.mark.parametrize("stride", [1, 2])
    def test_conv1_stage_param_grads(self, rng, stride):
        img = jnp.asarray(rng.normal(size=(1, 16, 32, 3)).astype(np.float32))
        c1 = {"kernel": jnp.asarray(
                  rng.normal(size=(7, 7, 3, 8)).astype(np.float32)) * 0.2,
              "bias": jnp.asarray(
                  rng.normal(size=(8,)).astype(np.float32)) * 0.1}
        params = self.params(rng)
        f = lambda c, p: (pe.conv1_stem_layer1(img, c, p, jnp.float32,
                                               stride) ** 2).sum()
        r = lambda c, p: (pe._xla_reference(
            pe._xla_conv1(img, c, jnp.float32, stride), p) ** 2).sum()
        got = jax.grad(f, argnums=(0, 1))(c1, params)
        want = jax.grad(r, argnums=(0, 1))(c1, params)
        self.assert_tree_close(got, want)

    def test_bn_stage_param_grads(self, rng):
        y1 = jnp.asarray(rng.normal(size=(2, 16, 24, 8)).astype(np.float32))
        params = self.params(rng)
        affines = [(jnp.asarray(np.abs(rng.normal(size=(8,)) * 0.5 + 1)
                                .astype(np.float32)),
                    jnp.asarray(rng.normal(size=(8,)).astype(np.float32)
                                * 0.3))
                   for _ in range(5)]
        f = lambda p: (pe.bn_stem_layer1(y1, p, affines) ** 2).sum()
        r = lambda p: (pe._xla_reference_affine(y1, p, affines) ** 2).sum()
        self.assert_tree_close(jax.grad(f)(params), jax.grad(r)(params))

    def test_bn_conv1_param_grads(self, rng):
        img = jnp.asarray(rng.normal(size=(1, 16, 24, 3)).astype(np.float32))
        c1 = {"kernel": jnp.asarray(
                  rng.normal(size=(7, 7, 3, 8)).astype(np.float32)) * 0.2,
              "bias": jnp.asarray(
                  rng.normal(size=(8,)).astype(np.float32)) * 0.1}
        params = self.params(rng)
        affines = [(jnp.asarray(np.abs(rng.normal(size=(8,)) * 0.5 + 1)
                                .astype(np.float32)),
                    jnp.asarray(rng.normal(size=(8,)).astype(np.float32)
                                * 0.3))
                   for _ in range(5)]
        f = lambda c, p: (pe.bn_conv1_stem_layer1(img, c, p, affines,
                                                  jnp.float32) ** 2).sum()
        r = lambda c, p: (pe._xla_reference_affine(
            pe._xla_conv1(img, c, jnp.float32), p, affines) ** 2).sum()
        got = jax.grad(f, argnums=(0, 1))(c1, params)
        want = jax.grad(r, argnums=(0, 1))(c1, params)
        self.assert_tree_close(got, want)

    def test_packed_sum_backward_matches_xla(self, rng):
        """The Pallas dual-sum path of the IN backward (single-device TPU
        form, forced here in interpret mode) == the XLA mean form."""
        y1 = jnp.asarray(rng.normal(size=(2, 16, 24, 8))
                         .astype(np.float32)) * 2 + 0.3
        params = self.params(rng)
        f = lambda p: (pe.stem_layer1(y1, p) ** 2).sum()
        prev = pe._bwd_packed_sums
        try:
            pe._bwd_packed_sums = True
            got = jax.grad(f)(params)
        finally:
            pe._bwd_packed_sums = prev
        r = lambda p: (pe._xla_reference(y1, p) ** 2).sum()
        self.assert_tree_close(got, jax.grad(r)(params))
