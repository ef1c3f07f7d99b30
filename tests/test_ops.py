"""Golden-value unit tests for the primitive ops (SURVEY.md §7 stage 1)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raftstereo_tpu.ops import (InputPadder, avg_pool2x, avg_pool_w2,
                                coords_grid_x, convex_upsample,
                                extract_3x3_patches, linear_sample_1d,
                                linear_sample_1d_dense,
                                resize_bilinear_align_corners, upsample_interp)


class TestLinearSample1D:
    def test_integer_positions_identity(self, rng):
        vol = rng.standard_normal((2, 3, 4, 16)).astype(np.float32)
        x = np.broadcast_to(np.arange(16.0, dtype=np.float32)[:5], (2, 3, 4, 5))
        out = linear_sample_1d(jnp.asarray(vol), jnp.asarray(x))
        np.testing.assert_allclose(out, vol[..., :5], rtol=1e-6)

    def test_midpoint_average(self):
        vol = jnp.asarray([[0.0, 2.0, 4.0, 6.0]])
        x = jnp.asarray([[0.5, 1.5, 2.5]])
        out = linear_sample_1d(vol, x)
        np.testing.assert_allclose(out, [[1.0, 3.0, 5.0]], rtol=1e-6)

    def test_zero_padding_outside(self):
        """Out-of-range taps contribute zero, like grid_sample zero padding
        (reference: core/utils/utils.py:67)."""
        vol = jnp.asarray([[1.0, 2.0, 3.0]])
        x = jnp.asarray([[-1.0, -0.5, 2.5, 3.0, 10.0]])
        out = linear_sample_1d(vol, x)
        np.testing.assert_allclose(out, [[0.0, 0.5, 1.5, 0.0, 0.0]], rtol=1e-6)

    def test_dense_equals_gather(self, rng):
        vol = rng.standard_normal((3, 5, 7, 24)).astype(np.float32)
        x = (rng.uniform(-3, 27, (3, 5, 7, 9))).astype(np.float32)
        a = linear_sample_1d(jnp.asarray(vol), jnp.asarray(x))
        b = linear_sample_1d_dense(jnp.asarray(vol), jnp.asarray(x))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    def test_gradient_flows(self, rng):
        vol = jnp.asarray(rng.standard_normal((2, 8)).astype(np.float32))
        x = jnp.asarray([[1.25, 3.5], [0.0, 6.75]])
        g = jax.grad(lambda v: linear_sample_1d(v, x).sum())(vol)
        assert np.isfinite(np.asarray(g)).all()
        # Scatter-add structure: weights per sample sum to 1 for interior taps.
        assert np.asarray(g).sum() == pytest.approx(4.0, rel=1e-5)


class TestResize:
    def test_align_corners_endpoints(self):
        x = jnp.arange(4.0).reshape(1, 1, 4, 1)
        out = resize_bilinear_align_corners(x, (1, 7))
        np.testing.assert_allclose(
            np.asarray(out)[0, 0, :, 0], [0, 0.5, 1, 1.5, 2, 2.5, 3], rtol=1e-6)

    def test_2d(self):
        x = jnp.asarray([[0.0, 1.0], [2.0, 3.0]]).reshape(1, 2, 2, 1)
        out = resize_bilinear_align_corners(x, (3, 3))
        expected = np.array([[0, 0.5, 1], [1, 1.5, 2], [2, 2.5, 3]])
        np.testing.assert_allclose(np.asarray(out)[0, :, :, 0], expected, rtol=1e-6)

    def test_identity(self, rng):
        x = jnp.asarray(rng.standard_normal((2, 5, 6, 3)).astype(np.float32))
        out = resize_bilinear_align_corners(x, (5, 6))
        np.testing.assert_array_equal(out, x)


class TestPooling:
    def test_avg_pool2x_counts_padding(self):
        """count_include_pad=True: corner window sums 4 values but divides by 9,
        matching torch avg_pool2d defaults (reference: core/update.py:87-88)."""
        x = jnp.ones((1, 4, 4, 1))
        out = avg_pool2x(x)
        assert out.shape == (1, 2, 2, 1)
        np.testing.assert_allclose(np.asarray(out)[0, 0, 0, 0], 4.0 / 9.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out)[0, 1, 1, 0], 1.0, rtol=1e-6)

    def test_avg_pool_w2_floor_halving(self):
        x = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 5.0]])
        out = avg_pool_w2(x)
        np.testing.assert_allclose(out, [[1.5, 3.5]], rtol=1e-6)


class TestInputPadder:
    def test_pad_to_divisible(self, rng):
        x = jnp.asarray(rng.standard_normal((1, 37, 50, 3)).astype(np.float32))
        padder = InputPadder(x.shape, divis_by=32)
        y = padder.pad(x)
        assert y.shape[1] % 32 == 0 and y.shape[2] % 32 == 0
        z = padder.unpad(y)
        np.testing.assert_array_equal(z, x)

    def test_already_divisible_is_noop(self, rng):
        x = jnp.asarray(rng.standard_normal((1, 64, 96, 3)).astype(np.float32))
        padder = InputPadder(x.shape, divis_by=32)
        assert padder.pad(x).shape == x.shape

    def test_kitti_mode_pads_bottom_only(self):
        x = jnp.ones((1, 37, 64, 3))
        padder = InputPadder(x.shape, mode="kitti", divis_by=32)
        y = padder.pad(x)
        assert y.shape == (1, 64, 64, 3)
        np.testing.assert_array_equal(np.asarray(y)[:, 37:], 1.0)


class TestBucketPadder:
    """Shared pad+bucket policy (eval runner + serve engine)."""

    def test_bucket_round_up_and_roundtrip(self, rng):
        from raftstereo_tpu.ops.image import BucketPadder

        x = jnp.asarray(rng.standard_normal((1, 70, 100, 3))
                        .astype(np.float32))
        p = BucketPadder(x.shape, divis_by=32, bucket_multiple=64)
        assert p.bucket_hw == (128, 128)  # 70->96->128, 100->128
        y = p.pad(x)
        assert y.shape == (1, 128, 128, 3)
        np.testing.assert_array_equal(p.unpad(np.asarray(y)), x)

    def test_without_bucket_equals_input_padder(self, rng):
        from raftstereo_tpu.ops.image import BucketPadder

        x = jnp.asarray(rng.standard_normal((1, 37, 50, 3))
                        .astype(np.float32))
        a = BucketPadder(x.shape, divis_by=32).pad(x)
        b = InputPadder(x.shape, divis_by=32).pad(x)
        np.testing.assert_array_equal(a, b)

    def test_accepts_3d_and_2d_dims(self):
        from raftstereo_tpu.ops.image import BucketPadder

        assert BucketPadder((60, 90, 3), divis_by=32).bucket_hw == (64, 96)
        assert BucketPadder((60, 90), divis_by=32).bucket_hw == (64, 96)
        assert BucketPadder((1, 60, 90, 3), divis_by=32,
                            bucket_multiple=128).bucket_hw == (128, 128)

    def test_pad_pair(self, rng):
        from raftstereo_tpu.ops.image import BucketPadder

        x = jnp.asarray(rng.standard_normal((1, 60, 90, 3))
                        .astype(np.float32))
        p = BucketPadder(x.shape, divis_by=32, bucket_multiple=64)
        a, b = p.pad(x, x * 2)
        assert a.shape == b.shape == (1, 64, 128, 3)
        np.testing.assert_array_equal(p.unpad(np.asarray(b)),
                                      np.asarray(x * 2))


    @pytest.mark.parametrize("hw,divis_by,multiple,mode", [
        ((60, 90), 32, 64, "sintel"),     # both stages, split both ways
        ((70, 100), 32, 64, "sintel"),
        ((37, 50), 32, None, "sintel"),   # alignment only
        ((64, 128), 32, 64, "sintel"),    # bucket-sized: nothing to add
        ((61, 95), 8, 32, "kitti"),       # all height padding at the bottom
        ((1, 1), 32, 64, "sintel"),       # one pixel, replicated everywhere
    ])
    def test_pad_into_is_pad_bit_for_bit_on_the_host(self, rng, hw,
                                                     divis_by, multiple,
                                                     mode):
        """The serving engine's staging: one image into its row of a
        NumPy batch, equal to the device's two-stage ``pad``."""
        from raftstereo_tpu.ops.image import BucketPadder

        x = rng.standard_normal((*hw, 3)).astype(np.float32)
        p = BucketPadder(x.shape, divis_by=divis_by, bucket_multiple=multiple,
                         mode=mode)
        out = np.full((*p.bucket_hw, 3), np.nan, np.float32)
        p.pad_into(out, x)
        np.testing.assert_array_equal(out, np.asarray(p.pad(x[None]))[0])
        # any dtype in, the batch's dtype out (an exact cast here)
        p.pad_into(out, (x * 0 + 7).astype(np.uint8))
        assert out.dtype == np.float32 and (out == 7).all()


class TestConvexUpsample:
    def test_patches_order(self):
        x = jnp.arange(9.0).reshape(1, 3, 3, 1)
        p = extract_3x3_patches(x)
        # centre pixel (1,1): patches are the full 3x3 block row-major
        np.testing.assert_allclose(np.asarray(p)[0, 1, 1, :, 0], np.arange(9.0))
        # corner (0,0): top/left neighbours zero-padded
        np.testing.assert_allclose(np.asarray(p)[0, 0, 0, :, 0],
                                   [0, 0, 0, 0, 0, 1, 0, 3, 4])

    def test_uniform_mask_center_equals_scaled_flow(self, rng):
        """With a mask fully peaked on the centre tap, output = nearest
        upsampling of factor*flow."""
        b, h, w, f = 1, 3, 4, 4
        flow = jnp.asarray(rng.standard_normal((b, h, w, 1)).astype(np.float32))
        mask = np.full((b, h, w, 9, f, f), -1e9, np.float32)
        mask[:, :, :, 4] = 0.0  # centre tap
        out = convex_upsample(flow, jnp.asarray(mask.reshape(b, h, w, -1)), f)
        assert out.shape == (b, h * f, w * f, 1)
        expected = np.repeat(np.repeat(np.asarray(flow) * f, f, 1), f, 2)
        np.testing.assert_allclose(out, expected, rtol=1e-5)

    def test_softmax_convexity_bounds(self, rng):
        b, h, w, f = 2, 4, 5, 2
        flow = jnp.asarray(rng.standard_normal((b, h, w, 1)).astype(np.float32))
        mask = jnp.asarray(rng.standard_normal((b, h, w, 9 * f * f)).astype(np.float32))
        out = np.asarray(convex_upsample(flow, mask, f))
        assert out.min() >= np.asarray(flow).min() * f - 1e-5
        assert out.max() <= np.asarray(flow).max() * f + 1e-5

    def test_upsample_interp_scales(self):
        flow = jnp.ones((1, 2, 2, 1))
        out = upsample_interp(flow, 4)
        assert out.shape == (1, 8, 8, 1)
        np.testing.assert_allclose(np.asarray(out), 4.0, rtol=1e-6)


def test_coords_grid_x():
    g = coords_grid_x(2, 3, 5)
    assert g.shape == (2, 3, 5, 1)
    np.testing.assert_allclose(np.asarray(g)[1, 2, :, 0], np.arange(5.0))


class TestForwardInterpolate:
    """Warm-start forward splat (reference: core/utils/utils.py:28-56)."""

    def test_zero_flow_fixed_point(self):
        from raftstereo_tpu.ops import forward_interpolate
        flow = np.zeros((2, 6, 8), np.float32)
        # All splat targets are on the open border -> reference drops them and
        # nearest-fills from nothing; interior-shifted variant below is the
        # meaningful check.  Here: constant small flow maps to itself.
        flow += 0.25
        out = forward_interpolate(flow)
        assert out.shape == (2, 6, 8)
        np.testing.assert_allclose(out, 0.25, atol=1e-6)

    def test_stereo_single_channel(self):
        from raftstereo_tpu.ops import forward_interpolate
        d = np.full((5, 7), -1.5, np.float32)
        out = forward_interpolate(d)
        assert out.shape == (5, 7)
        np.testing.assert_allclose(out, -1.5, atol=1e-6)

    def test_all_out_of_frame_gives_zeros(self):
        from raftstereo_tpu.ops import forward_interpolate
        d = np.full((4, 4), -100.0, np.float32)
        out = forward_interpolate(d)
        np.testing.assert_allclose(out, 0.0)

    def test_matches_reference_semantics(self):
        """Property: output at a splat target equals the splatted value."""
        from raftstereo_tpu.ops import forward_interpolate
        d = np.zeros((6, 10), np.float32)
        d[3, 5] = -2.0          # pixel (3,5) maps to x=3 -> nearest fill
        out = forward_interpolate(d)
        assert out[3, 3] == -2.0
