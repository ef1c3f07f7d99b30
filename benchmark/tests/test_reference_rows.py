"""``raft_stereo_rows`` (the plain reference for pairs too large to run it
whole) against ``raft_stereo``, on seeded weights at a size where both fit."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HW = (60, 200)          # bucket 64x256: a 16x64 field


@pytest.fixture(scope="module")
def setup():
    import jax.numpy as jnp

    from benchmark.loadgen.pairs import make_pair
    from benchmark.weights import make_weights

    with open(os.path.join(HERE, "configs",
                           "raftstereo_middlebury_f.json")) as f:
        cfg = json.load(f)["model"]
    p = {k: jnp.asarray(v) for k, v in make_weights(cfg, 7).items()}
    return cfg, p, make_pair(7, 0, HW)


def test_the_lookup_in_blocks_of_rows_is_bit_equal(setup):
    """A row's sums are the same sums in the same order, whichever block
    of rows it is computed in."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import raft_stereo as R
    from benchmark.reference import raft_stereo_rows as RR

    rng = np.random.default_rng(5)
    f1, f2 = (jnp.asarray(rng.standard_normal((1, 16, 64, 256)), jnp.float32)
              for _ in range(2))
    pyr = R.corr_pyramid(R._Ops(None), f1, f2, 4)
    x = jnp.asarray(rng.uniform(-5, 70, (1, 16, 64)), jnp.float32)
    whole = jax.jit(lambda pyr, x: R.lookup(pyr, x, 4))(pyr, x)
    for rows in (1, 4, 16):
        blocked = jax.jit(lambda pyr, x: RR.lookup_rows(
            RR.pyramid_rows(pyr, rows), x, 4))(pyr, x)
        assert np.array_equal(np.asarray(whole), np.asarray(blocked)), rows
    assert RR.rows_per_block(512, 752, 4) == 8       # 163 MB of hat weights
    assert RR.rows_per_block(16, 64, 4) == 16


@pytest.mark.parametrize("operand_dtype", [None, "float8_e4m3fn"],
                         ids=["reference", "control"])
def test_forward_equals_the_plain_reference(setup, operand_dtype,
                                            monkeypatch):
    """Through the server's pad policy, four rows a block.  Bit-equal but
    for ``fnet`` an image at a time: XLA's CPU convolution orders its
    float32 sums by the batch it is given, and the features move by 2e-5
    (the context encoder, the pyramid's pooling and the lookup do not
    move).  Four iterations carry that to 5e-6 of a field whose mean is 2:
    1e-5 of the mean is the tolerance, four orders under the control's
    distance from the reference.  float8 operands round it away."""
    import jax

    from benchmark.reference import raft_stereo as R
    from benchmark.reference import raft_stereo_rows as RR

    cfg, p, (left, right) = setup
    monkeypatch.setattr(RR, "ROW_BLOCK_BYTES", 4 * 64 * 9 * 64 * 4)
    whole, blocked = (np.asarray(jax.jit(lambda p, l, r: M.serve_reference(
        p, cfg, l, r, 4, 32, 64, operand_dtype))(p, left, right))
        for M in (R, RR))
    assert whole.shape == blocked.shape == HW
    scale = float(np.abs(whole).mean())
    assert scale > 0.01
    assert float(np.abs(whole - blocked).max()) < 1e-5 * scale
