"""Correlation-engine tests: numpy oracle + backend equivalence (SURVEY.md §4.3:
redundant implementations as oracles, made into actual automated tests)."""

import numpy as np
import jax.numpy as jnp
import pytest

from raftstereo_tpu.ops import (build_corr_pyramid, build_corr_volume,
                                make_alt_corr_fn, make_corr_fn, make_reg_corr_fn)


def numpy_corr_volume(f1, f2):
    c = f1.shape[-1]
    return np.einsum("bhwc,bhvc->bhwv", f1, f2) / np.sqrt(c)


def numpy_lookup(pyramid, x, radius):
    """Straight-line oracle for the pyramid lookup."""
    outs = []
    for i, vol in enumerate(pyramid):
        w2 = vol.shape[-1]
        for k in range(-radius, radius + 1):
            pos = (x.astype(np.float32) / np.float32(2 ** i)
                   + np.float32(k)).astype(np.float32)
            x0 = np.floor(pos).astype(np.int64)
            dx = pos - x0
            v0 = np.where((x0 >= 0) & (x0 < w2),
                          np.take_along_axis(vol, np.clip(x0, 0, w2 - 1)[..., None],
                                             axis=-1)[..., 0], 0.0)
            x1 = x0 + 1
            v1 = np.where((x1 >= 0) & (x1 < w2),
                          np.take_along_axis(vol, np.clip(x1, 0, w2 - 1)[..., None],
                                             axis=-1)[..., 0], 0.0)
            outs.append(v0 * (1 - dx) + v1 * dx)
    return np.stack(outs, axis=-1).reshape(*x.shape, -1)


@pytest.fixture
def fmaps(rng):
    f1 = rng.standard_normal((2, 6, 20, 32)).astype(np.float32)
    f2 = rng.standard_normal((2, 6, 20, 32)).astype(np.float32)
    return f1, f2


def test_volume_against_numpy(fmaps):
    f1, f2 = fmaps
    vol = build_corr_volume(jnp.asarray(f1), jnp.asarray(f2))
    np.testing.assert_allclose(vol, numpy_corr_volume(f1, f2), rtol=1e-4, atol=1e-5)


def test_pyramid_shapes_floor_halving(fmaps):
    f1, f2 = fmaps
    vol = build_corr_volume(jnp.asarray(f1), jnp.asarray(f2))
    pyr = build_corr_pyramid(vol, 4)
    assert [p.shape[-1] for p in pyr] == [20, 10, 5, 2]


def test_reg_lookup_against_numpy(fmaps, rng):
    f1, f2 = fmaps
    radius, levels = 3, 3
    x = rng.uniform(-2, 22, (2, 6, 20)).astype(np.float32)
    corr_fn = make_reg_corr_fn(jnp.asarray(f1), jnp.asarray(f2), levels, radius)
    got = corr_fn(jnp.asarray(x)[..., None])
    vol = numpy_corr_volume(f1, f2)
    pyr = [vol]
    for _ in range(levels - 1):
        v = pyr[-1]
        w2 = v.shape[-1]
        pyr.append(v[..., : (w2 // 2) * 2].reshape(*v.shape[:-1], w2 // 2, 2).mean(-1))
    want = numpy_lookup(pyr, x, radius)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_alt_equals_reg(fmaps, rng):
    """The on-demand backend must be numerically interchangeable with reg
    (reference capability: core/corr.py:64-107 vs :110-156)."""
    f1, f2 = fmaps
    x = rng.uniform(0, 20, (2, 6, 20)).astype(np.float32)[..., None]
    reg = make_reg_corr_fn(jnp.asarray(f1), jnp.asarray(f2), 4, 4)
    alt = make_alt_corr_fn(jnp.asarray(f1), jnp.asarray(f2), 4, 4)
    np.testing.assert_allclose(reg(jnp.asarray(x)), alt(jnp.asarray(x)),
                               rtol=1e-4, atol=1e-4)


def test_dispatch_and_output_shape(fmaps):
    f1, f2 = fmaps
    for impl in ("reg", "alt"):
        fn = make_corr_fn(impl, jnp.asarray(f1), jnp.asarray(f2), 4, 4)
        out = fn(jnp.zeros((2, 6, 20, 1)))
        assert out.shape == (2, 6, 20, 4 * 9)
        assert out.dtype == jnp.float32


def test_gradients_flow_through_lookup(fmaps):
    import jax
    f1, f2 = fmaps
    x = jnp.full((2, 6, 20, 1), 5.25)

    def loss(f1j, f2j):
        return make_reg_corr_fn(f1j, f2j, 2, 2)(x).sum()

    g1, g2 = jax.grad(loss, argnums=(0, 1))(jnp.asarray(f1), jnp.asarray(f2))
    assert np.isfinite(np.asarray(g1)).all() and np.isfinite(np.asarray(g2)).all()
    assert np.abs(np.asarray(g1)).sum() > 0


def test_precision_policies_agree(fmaps, rng):
    """corr_precision plumbing: "high" (3-pass bf16) and "default" (1-pass)
    stay within their documented error of the exact "highest" path on every
    backend.  On CPU the XLA einsum ignores precision (native fp32), but the
    pallas_alt kernel's manual hi/lo decomposition (ops/pallas_alt._dot) is
    real arithmetic in interpret mode, so the 3-pass construction itself is
    exercised.  Perf decision (measured on v5e):
    neither is faster on the default path, so "highest" stays the default."""
    f1, f2 = fmaps
    x = rng.uniform(0, 20, (2, 6, 20)).astype(np.float32)[..., None]
    for impl in ("reg", "pallas_alt"):
        ref = make_corr_fn(impl, jnp.asarray(f1), jnp.asarray(f2), 3, 3,
                           precision="highest")(jnp.asarray(x))
        for precision, rtol in (("high", 2e-4), ("default", 2e-2)):
            got = make_corr_fn(impl, jnp.asarray(f1), jnp.asarray(f2), 3, 3,
                               precision=precision)(jnp.asarray(x))
            np.testing.assert_allclose(got, ref, rtol=rtol, atol=rtol)
