"""On-demand Pallas correlation backend (ops/pallas_alt.py) vs the alt/reg
oracles (interpret mode on CPU).

The kernel recomputes correlation rows per W1-block instead of reading a
precomputed volume; since pooling fmap2 commutes with correlating, its output
must match both ``alt`` (same pyramid) and ``reg`` (pooled volume) exactly
(SURVEY.md §4.3: redundant implementations as oracles)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raftstereo_tpu.ops import coords_grid_x, make_corr_fn
from raftstereo_tpu.ops.pallas_alt import pallas_alt_lookup


@pytest.fixture
def fmaps(rng):
    f1 = rng.standard_normal((2, 3, 40, 32)).astype(np.float32)
    f2 = rng.standard_normal((2, 3, 40, 32)).astype(np.float32)
    return jnp.asarray(f1), jnp.asarray(f2)


@pytest.fixture
def coords(rng):
    x = coords_grid_x(2, 3, 40)
    return x - jnp.asarray(rng.uniform(0, 12, (2, 3, 40, 1)).astype(np.float32))


class TestForward:
    def test_matches_alt_and_reg(self, fmaps, coords):
        f1, f2 = fmaps
        outs = {impl: np.asarray(make_corr_fn(impl, f1, f2, 4, 4)(coords))
                for impl in ("reg", "alt", "pallas_alt")}
        np.testing.assert_allclose(outs["pallas_alt"], outs["alt"],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(outs["pallas_alt"], outs["reg"],
                                   rtol=1e-5, atol=1e-5)

    def test_under_jit(self, fmaps, coords):
        f1, f2 = fmaps
        fn = jax.jit(lambda c: make_corr_fn("pallas_alt", f1, f2, 2, 3)(c))
        want = make_corr_fn("alt", f1, f2, 2, 3)(coords)
        np.testing.assert_allclose(np.asarray(fn(coords)), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_oob_taps_zero(self, fmaps):
        f1, f2 = fmaps
        taps = jnp.full((2, 3, 40, 9), 1e6, jnp.float32)
        out = np.asarray(pallas_alt_lookup(f1, f2, taps))
        np.testing.assert_allclose(out, 0.0)

    def test_bf16_fmaps(self, fmaps, coords):
        f1, f2 = fmaps
        taps = jnp.broadcast_to(coords[..., 0:1], (2, 3, 40, 5))
        got = pallas_alt_lookup(f1.astype(jnp.bfloat16),
                                f2.astype(jnp.bfloat16), taps)
        want = pallas_alt_lookup(f1.astype(jnp.bfloat16).astype(jnp.float32),
                                 f2.astype(jnp.bfloat16).astype(jnp.float32),
                                 taps)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-2, atol=1e-2)

    def test_bf16_dtype_option(self, fmaps, coords):
        """make_corr_fn(dtype=bf16) stores the pyramid in bf16 (the CUDA
        kernel's fp16 dispatch analogue); results match fp32 at bf16
        input-quantization tolerance."""
        f1, f2 = fmaps
        got = make_corr_fn("pallas_alt", f1, f2, 3, 3,
                           dtype=jnp.bfloat16)(coords)
        want = make_corr_fn("pallas_alt", f1, f2, 3, 3)(coords)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_level_edge_taps(self, fmaps):
        """Taps within 1 of a level's right edge: the hat support crosses
        into the fused kernel's zero-padded columns, which must contribute
        exactly zero (same zero-outside semantics as the reg oracle)."""
        f1, f2 = fmaps
        b, h, w1, _ = 2, 3, 40, None
        # Per-level widths 40,20,10: park every tap at w2_l - 0.5.
        x = jnp.full((b, h, w1, 1), 39.0, jnp.float32)
        got = make_corr_fn("pallas_alt", f1, f2, 3, 0)(x)   # radius 0: 1 tap/level
        want = make_corr_fn("reg", f1, f2, 3, 0)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_non_block_aligned_w1(self, rng):
        f1 = jnp.asarray(rng.standard_normal((1, 2, 10, 16)).astype(np.float32))
        f2 = jnp.asarray(rng.standard_normal((1, 2, 13, 16)).astype(np.float32))
        taps = jnp.asarray(rng.uniform(-2, 15, (1, 2, 10, 7)).astype(np.float32))
        got = np.asarray(pallas_alt_lookup(f1, f2, taps))
        assert got.shape == (1, 2, 10, 7)
        # Oracle: explicit volume + linear sampling.
        from raftstereo_tpu.ops import build_corr_volume, linear_sample_1d
        vol = build_corr_volume(f1, f2)
        want = np.asarray(linear_sample_1d(vol, taps))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestBackward:
    def test_fmap_grads_match_alt_backend(self, fmaps, coords):
        """d/dfmap of the summed correlation must match the XLA alt path."""
        f1, f2 = fmaps

        def loss(impl, a, b):
            return jnp.sum(make_corr_fn(impl, a, b, 3, 3)(coords) ** 2)

        g_alt = jax.grad(lambda a, b: loss("alt", a, b), argnums=(0, 1))(f1, f2)
        g_pal = jax.grad(lambda a, b: loss("pallas_alt", a, b),
                         argnums=(0, 1))(f1, f2)
        for ga, gp in zip(g_alt, g_pal):
            np.testing.assert_allclose(np.asarray(gp), np.asarray(ga),
                                       rtol=1e-4, atol=1e-4)

    def test_taps_grad_is_zero(self, fmaps):
        f1, f2 = fmaps
        taps = jnp.full((2, 3, 40, 5), 7.3, jnp.float32)
        g = jax.grad(lambda t: jnp.sum(pallas_alt_lookup(f1, f2, t)))(taps)
        np.testing.assert_allclose(np.asarray(g), 0.0)

    def test_grad_accumulation_across_blocks(self, rng):
        """W1 spans multiple blocks: the df2 accumulation over the innermost
        grid dimension must sum every block's contribution exactly once."""
        from raftstereo_tpu.ops import pallas_corr as pc
        old = pc._BLOCK_W1
        f1 = jnp.asarray(rng.standard_normal((1, 1, 40, 16)).astype(np.float32))
        f2 = jnp.asarray(rng.standard_normal((1, 1, 24, 16)).astype(np.float32))
        taps = jnp.asarray(rng.uniform(0, 23, (1, 1, 40, 3)).astype(np.float32))

        def loss(b):
            return jnp.sum(pallas_alt_lookup(f1, b, taps) ** 2)

        try:
            pc._BLOCK_W1 = 8   # force 5 blocks over W1=40
            from raftstereo_tpu.ops.pallas_alt import _make_alt_pyr
            _make_alt_pyr.cache_clear()
            got = jax.grad(loss)(f2)
        finally:
            pc._BLOCK_W1 = old
            _make_alt_pyr.cache_clear()
        want = jax.grad(loss)(f2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_multi_block_multi_level_grads(self, rng):
        """The fused pyramid path with W1 spanning several blocks AND several
        levels: df2 accumulation and per-level slicing together, checked
        against the XLA alt backend."""
        from raftstereo_tpu.ops import pallas_corr as pc
        from raftstereo_tpu.ops.pallas_alt import _make_alt_pyr
        f1 = jnp.asarray(rng.standard_normal((1, 2, 40, 16)).astype(np.float32))
        f2 = jnp.asarray(rng.standard_normal((1, 2, 40, 16)).astype(np.float32))
        x = coords_grid_x(1, 2, 40) - 5.0

        def loss(impl, a, b):
            return jnp.sum(make_corr_fn(impl, a, b, 3, 2)(x) ** 2)

        old = pc._BLOCK_W1
        try:
            pc._BLOCK_W1 = 16  # 3 blocks over W1=40
            _make_alt_pyr.cache_clear()
            got = jax.grad(lambda a, b: loss("pallas_alt", a, b),
                           argnums=(0, 1))(f1, f2)
        finally:
            pc._BLOCK_W1 = old
            _make_alt_pyr.cache_clear()
        want = jax.grad(lambda a, b: loss("alt", a, b), argnums=(0, 1))(f1, f2)
        for gp, ga in zip(got, want):
            np.testing.assert_allclose(np.asarray(gp), np.asarray(ga),
                                       rtol=1e-4, atol=1e-4)


class TestModelIntegration:
    def test_forward_matches_alt_model(self, rng):
        from raftstereo_tpu import RAFTStereoConfig
        from raftstereo_tpu.models import RAFTStereo

        kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                  corr_radius=3)
        m_alt = RAFTStereo(RAFTStereoConfig(corr_implementation="alt", **kw))
        m_pal = RAFTStereo(
            RAFTStereoConfig(corr_implementation="pallas_alt", **kw))
        variables = m_alt.init(jax.random.key(0))
        i1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)).astype(np.float32))
        i2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)).astype(np.float32))
        out_alt = m_alt.forward(variables, i1, i2, iters=2)
        out_pal = m_pal.forward(variables, i1, i2, iters=2)
        np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_alt),
                                   rtol=1e-4, atol=1e-4)


def _flats(f1, f2, levels):
    """The radial kernels' operands as ops/corr.py's construct() lays them
    out: (f1flat, f2cat, per-level lane-padded widths)."""
    from raftstereo_tpu.ops.corr import build_fmap2_pyramid
    from raftstereo_tpu.ops.pallas_alt import (pad_w2_lane, preflatten_fmap1,
                                               preflatten_fmap2)
    f1flat = preflatten_fmap1(jnp.asarray(f1))
    pyr = [pad_w2_lane(preflatten_fmap2(x))
           for x in build_fmap2_pyramid(jnp.asarray(f2), levels)]
    w2s = tuple(p.shape[1] for p in pyr)
    return f1flat, jnp.concatenate(pyr, axis=1), w2s


class TestRadialKernel:
    """The model-pattern radial entry (shared-fraction windows) must be
    numerically interchangeable with the general-taps kernel — it is the
    same lookup, resolved with ~1.7x fewer VPU ops."""

    _flats = staticmethod(lambda f1, f2, levels=3: _flats(f1, f2, levels))

    def test_matches_general_taps(self, fmaps, coords):
        from raftstereo_tpu.ops.pallas_alt import (
            pallas_alt_pyramid_flat, pallas_alt_pyramid_radial_flat)
        f1, f2 = fmaps
        radius, levels = 4, 3
        f1flat, f2cat, w2s = self._flats(f1, f2, levels)
        x = jnp.asarray(coords)[..., 0]
        xl = jnp.stack([x / 2.0 ** i for i in range(levels)], axis=-1)
        offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
        taps = jnp.concatenate([xl[..., i:i + 1] + offsets
                                for i in range(levels)], axis=-1)
        want = pallas_alt_pyramid_flat(f1flat, f2cat, taps, w2s)
        got = pallas_alt_pyramid_radial_flat(f1flat, f2cat, xl, w2s, radius)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_integer_and_oob_centers(self, fmaps):
        from raftstereo_tpu.ops.pallas_alt import (
            pallas_alt_pyramid_flat, pallas_alt_pyramid_radial_flat)
        f1, f2 = fmaps
        radius, levels = 3, 2
        f1flat, f2cat, w2s = self._flats(f1, f2, levels)
        # exact integers (f == 0) and far out-of-range values
        x = jnp.asarray(np.tile(np.array([0.0, 7.0, -50.0, 200.0, 39.0],
                                         np.float32), (2, 3, 8))[..., :40])
        xl = jnp.stack([x / 2.0 ** i for i in range(levels)], axis=-1)
        offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
        taps = jnp.concatenate([xl[..., i:i + 1] + offsets
                                for i in range(levels)], axis=-1)
        want = pallas_alt_pyramid_flat(f1flat, f2cat, taps, w2s)
        got = pallas_alt_pyramid_radial_flat(f1flat, f2cat, xl, w2s, radius)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_general(self, fmaps, coords):
        from raftstereo_tpu.ops.pallas_alt import (
            pallas_alt_pyramid_flat, pallas_alt_pyramid_radial_flat)
        f1, f2 = fmaps
        radius, levels = 2, 2
        f1flat, f2cat, w2s = self._flats(f1, f2, levels)
        x = jnp.asarray(coords)[..., 0]
        xl = jnp.stack([x / 2.0 ** i for i in range(levels)], axis=-1)
        offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
        taps = jnp.concatenate([xl[..., i:i + 1] + offsets
                                for i in range(levels)], axis=-1)

        def loss_radial(a, b):
            return (pallas_alt_pyramid_radial_flat(a, b, xl, w2s, radius)
                    ** 2).sum()

        def loss_general(a, b):
            return (pallas_alt_pyramid_flat(a, b, taps, w2s) ** 2).sum()

        gr = jax.grad(loss_radial, argnums=(0, 1))(f1flat, f2cat)
        gg = jax.grad(loss_general, argnums=(0, 1))(f1flat, f2cat)
        for a, b in zip(gr, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_bf16_out_dtype(self, fmaps, coords):
        from raftstereo_tpu.ops.pallas_alt import (
            pallas_alt_pyramid_radial_flat)
        f1, f2 = fmaps
        f1flat, f2cat, w2s = self._flats(f1, f2, 2)
        x = jnp.asarray(coords)[..., 0]
        xl = jnp.stack([x / 2.0 ** i for i in range(2)], axis=-1)
        ref = pallas_alt_pyramid_radial_flat(f1flat, f2cat, xl, w2s, 3)
        got = pallas_alt_pyramid_radial_flat(f1flat, f2cat, xl, w2s, 3,
                                             out_dtype=jnp.bfloat16)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=1e-2, atol=1e-2)

    def test_level_scales_matches_explicit_centers(self, fmaps, coords):
        """The static level_scales path (single-channel level-0 center,
        per-level locals derived in-kernel) must equal the explicit
        per-level-centers path, gradients included."""
        from raftstereo_tpu.ops.pallas_alt import (
            pallas_alt_pyramid_radial_flat)
        f1, f2 = fmaps
        radius, levels = 4, 3
        f1flat, f2cat, w2s = self._flats(f1, f2, levels)
        x = jnp.asarray(coords)[..., 0]
        scales = tuple(1.0 / 2.0 ** i for i in range(levels))
        xl = jnp.stack([x * s for s in scales], axis=-1)
        want = pallas_alt_pyramid_radial_flat(f1flat, f2cat, xl, w2s, radius)
        got = pallas_alt_pyramid_radial_flat(f1flat, f2cat, x[..., None],
                                             w2s, radius,
                                             level_scales=scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

        # Gradients in the PRODUCTION configuration: level_scales is
        # always combined with out_channels padding in the model
        # (raft_stereo passes a lane-friendly width), so the bwd's
        # lk-derivation + cotangent slice must be exercised with padded
        # channels.
        oc = 64

        def loss_s(a, b):
            return (pallas_alt_pyramid_radial_flat(
                a, b, x[..., None], w2s, radius, level_scales=scales,
                out_channels=oc) ** 2).sum()

        def loss_e(a, b):
            return (pallas_alt_pyramid_radial_flat(
                a, b, xl, w2s, radius, out_channels=oc) ** 2).sum()

        gs = jax.grad(loss_s, argnums=(0, 1))(f1flat, f2cat)
        ge = jax.grad(loss_e, argnums=(0, 1))(f1flat, f2cat)
        for a, b in zip(gs, ge):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestEpilogue:
    """The fused convc1 epilogue (relu(corr @ W + b) in-kernel) must match
    the module path: lookup -> 1x1 conv -> relu."""

    def test_matches_module_path(self, fmaps, coords):
        from raftstereo_tpu.ops.corr import make_pallas_alt_corr_fn

        f1, f2 = fmaps
        rng = np.random.default_rng(7)
        lk = 4 * 9
        co = 64
        epi = {"kernel": jnp.asarray(
                   rng.normal(size=(1, 1, lk, co)).astype(np.float32)) * 0.2,
               "bias": jnp.asarray(
                   rng.normal(size=(co,)).astype(np.float32)) * 0.1}
        plain = make_pallas_alt_corr_fn(f1, f2, 4, 4)(coords)
        fused = make_pallas_alt_corr_fn(f1, f2, 4, 4, epilogue=epi)(coords)
        want = jax.nn.relu(
            jnp.tensordot(plain[..., :lk], epi["kernel"][0, 0], 1)
            + epi["bias"])
        assert fused.shape == want.shape
        np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_model_forward_epilogue_matches(self, rng):
        """Whole-model test-mode forward with the epilogue gate on vs off
        (explicit pallas_alt on CPU exercises the interpret kernels)."""
        from raftstereo_tpu.config import RAFTStereoConfig
        from raftstereo_tpu.models.raft_stereo import RAFTStereo
        from raftstereo_tpu.ops import corr as corr_mod

        # bf16 compute: the epilogue gate requires it (fp32 keeps the
        # certified module-conv numerics; models/raft_stereo.py).
        cfg = RAFTStereoConfig(corr_implementation="pallas_alt",
                               compute_dtype="bfloat16")
        model = RAFTStereo(cfg)
        v = model.init(jax.random.key(0), (64, 96))
        img1 = jnp.asarray(rng.integers(0, 255, (1, 64, 96, 3))
                           .astype(np.float32))
        img2 = jnp.asarray(rng.integers(0, 255, (1, 64, 96, 3))
                           .astype(np.float32))
        prev = corr_mod.corr_epilogue_enabled
        try:
            corr_mod.corr_epilogue_enabled = False
            _, up_off = model.forward(v, img1, img2, iters=3, test_mode=True)
            corr_mod.corr_epilogue_enabled = True
            _, up_on = model.forward(v, img1, img2, iters=3, test_mode=True)
        finally:
            corr_mod.corr_epilogue_enabled = prev
        np.testing.assert_allclose(np.asarray(up_on), np.asarray(up_off),
                                   rtol=1e-4, atol=1e-4)


def _kernel_dots(fn, *args):
    """(lhs dtype, rhs dtype, precision) of every dot inside the
    ``alt_lookup_fwd`` kernels that ``fn(*args)`` traces."""
    dots = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside and eqn.primitive.name == "dot_general":
                prec = eqn.params["precision"]
                prec = prec[0] if isinstance(prec, tuple) else prec
                dots.append((eqn.invars[0].aval.dtype.name,
                             eqn.invars[1].aval.dtype.name,
                             None if prec is None else prec.name))
            here = inside or (
                eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "alt_lookup_fwd")
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, here)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return dots


def _window_sum_body(f1flat, f2cat, x, w2s, radius, scales):
    """The radial lookup as it stood before PR 27, in plain jnp: the
    float32 ``highest`` product, K+1 masked window SUMS a level, the lerp.
    The oracle for 'bit-equal to the parent's'."""
    c = f1flat.shape[-1]
    m = jax.lax.dot_general(
        f1flat, f2cat, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) * (1.0 / float(c) ** 0.5)
    x0 = x.reshape(f1flat.shape[0], -1)
    kk, cols, off = 2 * radius + 1, [], 0
    for li, w2p in enumerate(w2s):
        ml = m[:, :, off:off + w2p]
        off += w2p
        xl = x0 * scales[li]
        b0 = jnp.floor(xl)
        f = xl - b0
        z = (jnp.arange(w2p, dtype=jnp.int32)
             - b0.astype(jnp.int32)[..., None] + radius)
        wins = [jnp.sum(jnp.where(z == d, ml, 0.0), axis=-1)
                for d in range(kk + 1)]
        cols += [wins[k] * (1.0 - f) + wins[k + 1] * f for k in range(kk)]
    return jnp.stack(cols, axis=-1).reshape(x.shape[:3] + (len(cols),))


class TestMatmulForm:
    """PR 27: the radial body reads its windows (one lane gather per
    128-column chunk) and, for bf16-BORN features, runs only the bf16
    passes of the float32 product that can be non-zero — one for level 0,
    three for the pooled levels.  What decides is the dtype the encoder
    handed the features over in, nothing else."""

    LEVELS, RADIUS = 3, 3
    SCALES = (1.0, 0.5, 0.25)

    def _operands(self, rng, born, c=64):
        """Float32 operands as ops/corr.py's construct() builds them from
        features born in ``born`` (C = 64: 1/sqrt(C) is a power of two)."""
        f1, f2 = (jnp.asarray(rng.standard_normal((2, 4, 40, c))
                              .astype(np.float32)).astype(born)
                  for _ in range(2))
        return _flats(f1.astype(jnp.float32), f2.astype(jnp.float32),
                      self.LEVELS)

    def _centres(self, rng, kind):
        grid = coords_grid_x(2, 4, 40)
        if kind == "frac":
            return grid - jnp.asarray(
                rng.uniform(0, 12, (2, 4, 40, 1)).astype(np.float32))
        if kind == "halves":   # f in {0, .5} at every level: an exact lerp
            x = grid - 2.0 * jnp.asarray(
                rng.integers(0, 8, (2, 4, 40, 1)).astype(np.float32))
            return x - x % 2.0
        # integers, the level edges, far out of range, NaN
        vals = np.array([0.0, 7.0, -50.0, 200.0, 39.0, 39.5, -0.5, np.nan,
                         1e6, 19.75], np.float32)
        return jnp.asarray(np.tile(vals, (2, 4, 4))[..., None])

    def _lookup(self, ops, x, feature_dtype, epi=None, **kw):
        from raftstereo_tpu.ops.pallas_alt import (
            pallas_alt_pyramid_radial_epi_flat,
            pallas_alt_pyramid_radial_flat)
        f1flat, f2cat, w2s = ops
        if epi is not None:
            return pallas_alt_pyramid_radial_epi_flat(
                f1flat, f2cat, x, w2s, self.RADIUS, *epi,
                level_scales=self.SCALES, feature_dtype=feature_dtype, **kw)
        return pallas_alt_pyramid_radial_flat(
            f1flat, f2cat, x, w2s, self.RADIUS, level_scales=self.SCALES,
            feature_dtype=feature_dtype, **kw)

    def _epi(self):
        r = np.random.default_rng(7)
        lk = self.LEVELS * (2 * self.RADIUS + 1)
        return (jnp.asarray(r.normal(size=(lk, 64)).astype(np.float32)) * 0.2,
                jnp.asarray(r.normal(size=(1, 1, 64)).astype(np.float32))
                * 0.1)

    @pytest.mark.parametrize("kind", ["normal", "tiny", "huge", "special"])
    def test_split3_reconstructs_float32_exactly(self, rng, kind):
        from raftstereo_tpu.ops.pallas_alt import _split3
        x = rng.standard_normal(4096).astype(np.float32)
        if kind == "tiny":     # down to where the low piece leaves bf16
            x = np.copysign(1 + np.abs(x), x) * np.float32(2.0 ** -100)
        elif kind == "huge":
            x = x * np.float32(2.0 ** 120)
        elif kind == "special":
            x = np.array([0.0, -0.0, 1.0, -1.0, 1.0 + 2.0 ** -23,
                          1.0 - 2.0 ** -24, 2.0 - 2.0 ** -23, 255.5,
                          1.00390625, 0.99609375, 3.0e38,
                          2.0 ** -102, 16777215.0, 1 / 3], np.float32)
        hi, mid, lo = _split3(jnp.asarray(x))
        assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
        back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)) \
            + lo.astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(back), x)

    @pytest.mark.parametrize("feature,operand,precision,want", [
        ("bfloat16", "float32", "highest", "bf16_exact_1+3"),
        ("float32", "float32", "highest", "f32_highest"),
        (None, "float32", "highest", "f32_highest"),
        ("bfloat16", "float32", "high", "f32_high"),
        ("bfloat16", "float32", "default", "f32_default"),
        ("bfloat16", "bfloat16", "highest", "bf16_native"),
        ("float32", "bfloat16", "highest", "bf16_native"),
    ])
    def test_resolver(self, feature, operand, precision, want):
        from raftstereo_tpu.ops.pallas_alt import resolve_corr_matmul
        assert resolve_corr_matmul(feature, operand, precision) == want

    @pytest.mark.parametrize("born,corr_dtype,want", [
        # one native pass over level 0, three over the pooled levels
        (jnp.bfloat16, jnp.float32,
         [("bfloat16", "bfloat16", "DEFAULT")] * 4),
        (jnp.float32, jnp.float32, [("float32", "float32", "HIGHEST")]),
        (jnp.bfloat16, jnp.bfloat16, [("bfloat16", "bfloat16", "DEFAULT")]),
    ])
    def test_path_follows_the_feature_dtype_and_no_flag(self, fmaps, coords,
                                                        born, corr_dtype,
                                                        want):
        """make_corr_fn is called the same way every time; only the dtype
        of the features it is handed differs."""
        f1, f2 = (f.astype(born) for f in fmaps)

        def lookup(a, b, c):
            return make_corr_fn("pallas_alt", a, b, 4, 4, dtype=corr_dtype,
                                out_channels=64)(c)

        assert _kernel_dots(lookup, f1, f2, coords) == want

    def test_phase_split_state_takes_the_dtype_it_is_told(self, fmaps,
                                                          coords):
        """The state arrays are float32 whatever the features were born
        as, so corr_fn_from_state is told (the model passes self.dtype)."""
        from raftstereo_tpu.ops.corr import (build_corr_state,
                                             corr_fn_from_state)
        f1, f2 = (f.astype(jnp.bfloat16) for f in fmaps)
        state = build_corr_state("pallas_alt", f1, f2, 4)
        assert all(s.dtype == jnp.float32 for s in state)

        def lookup(fd):
            return lambda s, c: corr_fn_from_state(
                "pallas_alt", s, 4, 4, feature_dtype=fd)(c)

        assert len(_kernel_dots(lookup(jnp.bfloat16), state, coords)) == 4
        assert _kernel_dots(lookup(None), state, coords) == [
            ("float32", "float32", "HIGHEST")]
        want = make_corr_fn("pallas_alt", f1, f2, 4, 4)(coords)
        got = lookup(jnp.bfloat16)(state, coords)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("epilogue", [False, True],
                             ids=["raw", "epilogue"])
    @pytest.mark.parametrize("kind", ["frac", "edges"])
    def test_bf16_born_equals_highest_body(self, rng, kind, epilogue):
        """Same operands, both bodies: the exact passes against the six-pass
        ``highest`` product.  Level 0 (one pass) and the pooled levels
        (three passes) are asserted separately; float32 rounding only."""
        ops = self._operands(rng, jnp.bfloat16)
        x = self._centres(rng, kind)
        epi = self._epi() if epilogue else None
        assert len(_kernel_dots(
            lambda c: self._lookup(ops, c, jnp.bfloat16, epi), x)) == 4 + epilogue
        got = np.asarray(self._lookup(ops, x, jnp.bfloat16, epi))
        want = np.asarray(self._lookup(ops, x, None, epi))
        assert np.array_equal(np.isnan(got), np.isnan(want))
        if kind == "edges":
            assert np.isnan(want).any() and (want == 0).any()
        if epilogue:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            return
        kk = 2 * self.RADIUS + 1
        np.testing.assert_allclose(got[..., :kk], want[..., :kk],
                                   rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(got[..., kk:], want[..., kk:],
                                   rtol=2e-6, atol=2e-6)
        # and the general-taps kernel (hat form), an independent body
        from raftstereo_tpu.ops.pallas_alt import pallas_alt_pyramid_flat
        xl = x[..., 0:1] * jnp.asarray(self.SCALES)
        taps = (xl[..., None] + jnp.arange(-self.RADIUS, self.RADIUS + 1.0)
                ).reshape(*xl.shape[:-1], -1)
        hat = np.asarray(pallas_alt_pyramid_flat(ops[0], ops[1], taps,
                                                 ops[2]))
        np.testing.assert_allclose(got, hat, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("kw", [{}, {"out_dtype": jnp.bfloat16,
                                         "out_channels": 64}],
                             ids=["f32_out", "bf16_out_padded"])
    @pytest.mark.parametrize("kind", ["halves", "frac", "edges"])
    def test_f32_born_is_the_parents_result(self, rng, kind, kw):
        """Float32-born features keep the ``highest`` dot, and a window
        that is read is the window that was summed: bit-equal to the
        parent's body wherever the lerp is exact (the CPU compiler is free
        to fuse a*b + c*d either way, so fractional centres are held to an
        ulp instead)."""
        ops = self._operands(rng, jnp.float32)
        x = self._centres(rng, kind)
        assert _kernel_dots(lambda c: self._lookup(ops, c, jnp.float32, **kw),
                            x) == [("float32", "float32", "HIGHEST")]
        got = self._lookup(ops, x, jnp.float32, **kw)
        lk = self.LEVELS * (2 * self.RADIUS + 1)
        want = _window_sum_body(ops[0], ops[1], x, ops[2], self.RADIUS,
                                self.SCALES).astype(got.dtype)
        assert got.dtype == kw.get("out_dtype", jnp.float32)
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert not got[..., lk:].any()           # the channel padding
        if kind == "halves":
            np.testing.assert_array_equal(got[..., :lk], want)
        else:
            tol = 1e-2 if kw else 1e-6
            np.testing.assert_allclose(got[..., :lk], want, rtol=tol,
                                       atol=tol)

    def test_gradients_unchanged_for_bf16_born(self, rng):
        """The backward kernel is not this PR's: same cotangents whatever
        the forward's matmul form (custom_vjp; residuals are the operands)."""
        ops = self._operands(rng, jnp.bfloat16)
        x = self._centres(rng, "frac")

        def loss(fd):
            return lambda a, b: (self._lookup((a, b, ops[2]), x, fd)
                                 ** 2).sum()

        g_exact = jax.grad(loss(jnp.bfloat16), argnums=(0, 1))(*ops[:2])
        g_high = jax.grad(loss(None), argnums=(0, 1))(*ops[:2])
        for a, b in zip(g_exact, g_high):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_runtime_line_names_the_form(self):
        from raftstereo_tpu.config import RAFTStereoConfig
        from raftstereo_tpu.utils.platform import describe_runtime
        want = {("bfloat16", "float32"): "bf16_exact_1+3",
                ("float32", "float32"): "f32_highest",
                ("bfloat16", "bfloat16"): "bf16_native"}
        for (compute, corr), form in want.items():
            cfg = RAFTStereoConfig(corr_implementation="pallas_alt",
                                   compute_dtype=compute, corr_dtype=corr)
            assert describe_runtime(cfg, 1, (64, 96))["corr_matmul"] == form
        # off the TPU ``auto`` is the XLA gather path: no such matmul
        assert describe_runtime(RAFTStereoConfig(), 1,
                                (64, 96))["corr_matmul"] is None
