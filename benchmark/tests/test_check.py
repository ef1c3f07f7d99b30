"""The comparison that decides ``correct``, driven off the chip at a size a
test can hold (96x128, the realtime configuration: 7 iterations).

The harness's look for a chip is skipped and the rest of the check runs as in
a measured run (``serving.check`` -> ``reference/check.py`` in a child), with
replies made by the reference in the program's place:

* in the configuration's precision (bfloat16 operands): correct;
* the control, one precision step down (float8 operands): NOT correct;
* an answer altered where it is produced (a band of rows scaled): NOT correct;
* a reply that is another pair's (rows of a batch mixed up on the way back):
  NOT correct.

Of the faults a cell can have, a serve cell has these; a state left
unchanged, half a batch left out of a mean and a dropped exchange between
chips are a train cell's and a multi-chip cell's.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HW = (96, 128)
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def setting(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    from benchmark.loadgen.pairs import make_pair
    from benchmark.reference import raft_stereo as R
    from benchmark.weights import make_weights

    with open(os.path.join(HERE, "configs", "raftstereo_realtime.json")) as f:
        config = json.load(f)
    cfg = config["model"]
    p = {k: jnp.asarray(v) for k, v in make_weights(cfg, SEED).items()}
    run_dir = str(tmp_path_factory.mktemp("run"))

    def served(pair, dtype):
        left, right = make_pair(SEED, pair, HW)
        f = jax.jit(lambda p, l, r: R.serve_reference(
            p, cfg, l, r, config["iters"], 32, 64, dtype))
        return np.asarray(f(p, left, right))

    return config, run_dir, served


def _check(setting, replies):
    """replies: [(pair number, disparity)] -> the run's checks."""
    from benchmark import child, serving

    config, run_dir, _ = setting
    child.REHEARSE = True            # children on the CPU, no compile cache
    records = []
    for i, (pair, disp) in enumerate(replies):
        path = os.path.join(run_dir, f"reply_{i}.npy")
        np.save(path, disp.astype(np.float32))
        records.append({"i": i, "pair": pair, "ok": True, "kept": path})
    ctx = SimpleNamespace(
        cell={"image_hw": list(HW), "check_samples": len(replies)},
        config=config, seed=SEED, run_dir=run_dir, rehearse=True)
    checks = serving.check(ctx, {"records": records})
    c = checks["gap_over_control_max"]
    return c["value"], c["value"] <= c["limit"]


def test_sound_replies_are_correct_and_the_control_is_not(setting):
    _, _, served = setting
    sound = [(k, served(k, "bfloat16")) for k in (0, 1)]
    value, ok = _check(setting, sound)
    assert ok, value
    control = [(k, served(k, "float8_e4m3fn")) for k in (0, 1)]
    cvalue, cok = _check(setting, control)
    assert not cok, cvalue
    assert cvalue == pytest.approx(1.0, abs=0.05)   # the control against itself
    assert cvalue > 3 * value


def test_an_altered_answer_is_not_correct(setting):
    _, _, served = setting
    d = served(0, "bfloat16").copy()
    d[: HW[0] // 4] *= 1.5
    assert not _check(setting, [(0, d)])[1]


def test_another_pairs_reply_is_not_correct(setting):
    _, _, served = setting
    assert not _check(setting, [(0, served(1, "bfloat16"))])[1]
