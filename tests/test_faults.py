"""Chaos tests: run the real training loop against injected faults
(utils/faults.py) and assert each recovery mechanism actually recovers —
preemption-safe checkpoints, corrupt-checkpoint fallback, sample
quarantine, worker-pool recycle, nan_policy, progress-aware max_restarts.

Everything runs on synthetic data on CPU and is part of the tier-1
selection (marker ``chaos``).

NOTE: these tests deliberately do NOT use jax's persistent compilation
cache (utils/platform.setup_compile_cache sets none on the CPU).  When
PR 2 tried it, a cache-DESERIALIZED CPU executable was both
crash-prone (SIGSEGV/SIGABRT in ``_check_if_deleted`` when fed an
orbax-restored donated state) and numerically different from the
freshly-compiled one (bitwise train-state divergence after 4 steps), so
every train() invocation here pays its own compile on purpose.
"""

import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
from raftstereo_tpu.data.loader import DataLoader
from raftstereo_tpu.data.synthetic import ShiftStereoDataset, make_synthetic_kitti
from raftstereo_tpu.models import RAFTStereo
from raftstereo_tpu.train import (CheckpointManager, create_train_state,
                                  make_optimizer)
from raftstereo_tpu.utils import faults as fl
from raftstereo_tpu.utils.faults import (FaultPlan, InjectedCrash,
                                         InjectedSampleError)

pytestmark = pytest.mark.chaos

TINY = RAFTStereoConfig(corr_levels=2, corr_radius=2, n_gru_layers=2,
                        hidden_dims=(16, 16))
HW = (32, 48)


# ---------------------------------------------------------------------------
# FaultPlan grammar
# ---------------------------------------------------------------------------

def test_plan_parse_roundtrip():
    plan = FaultPlan.parse("crash@step=7, corrupt@sample=3,"
                           "hang@worker=1:10s,nan@step=5,slow@step=2:250ms")
    assert [f.spec() for f in plan.faults] == [
        "crash@step=7", "corrupt@sample=3", "hang@worker=1:10s",
        "nan@step=5", "slow@step=2:0.25s"]
    assert FaultPlan.parse(None).faults == [] and not FaultPlan.parse("")


def test_plan_parse_rejects_malformed():
    for bad in ("crash@sample=1",       # wrong dimension
                "hang@worker=1",        # missing required duration
                "bogus@step=1",         # unknown kind
                "crash@step",           # no value
                "crash@step=x"):        # non-int value
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_plan_fire_once_vs_persistent():
    plan = FaultPlan.parse("nan@step=5,corrupt@sample=3")
    assert plan.at_step(5) == {"nan"}
    assert plan.at_step(5) == set()                 # one-shot
    for _ in range(3):                              # persistent
        with pytest.raises(InjectedSampleError):
            plan.on_sample(3)
    plan.on_sample(2)                               # other indices untouched


def test_plan_from_env(monkeypatch):
    monkeypatch.setenv(fl.ENV_VAR, "crash@step=9")
    assert FaultPlan.from_env().peek("crash", "step", 9) is not None
    monkeypatch.delenv(fl.ENV_VAR)
    assert not FaultPlan.from_env()


# ---------------------------------------------------------------------------
# Serving-plane grammar (PR 17)
# ---------------------------------------------------------------------------

def test_serving_count_kinds_are_budgets():
    """``slow_replica@request=N`` fires on the next N consults — a
    budget, not an N-th-request trigger; same for flap/corrupt."""
    plan = FaultPlan.parse("slow_replica@request=2:0.25,"
                           "flap_probe@backend=1,"
                           "corrupt_frame@request=1").arm(now=0.0)
    assert plan.dispatch_delay() == 0.25
    assert plan.dispatch_delay() == 0.25
    assert plan.dispatch_delay() == 0.0       # budget of 2 exhausted
    assert plan.healthz_lie() is True
    assert plan.healthz_lie() is False
    assert plan.corrupt_stream() is True
    assert plan.corrupt_stream() is False


def test_serving_grammar_rejects_malformed():
    for bad in ("slow_replica@request=2",     # missing required duration
                "slow_replica@step=2:1s",     # wrong dimension
                "blackhole_backend@t_ms=100",  # missing window length
                "corrupt_frame@request=0",    # count must be >= 1
                "evict_sessions@t_ms=-5"):    # offset must be >= 0
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_blackhole_window_measures_from_arming():
    plan = FaultPlan.parse("blackhole_backend@t_ms=100:0.5").arm(now=10.0)
    assert plan.blackhole_until(now=10.05) is None     # before the window
    assert plan.blackhole_until(now=10.1) == pytest.approx(10.6)
    assert plan.blackhole_until(now=10.59) == pytest.approx(10.6)
    assert plan.blackhole_until(now=10.6) is None      # window closed


def test_blackhole_hold_sleeps_to_window_end():
    plan = FaultPlan.parse("blackhole_backend@t_ms=0:0.5").arm(now=0.0)
    clock = [0.1]
    slept = []

    def fake_sleep(s):
        slept.append(s)
        clock[0] += s

    held = plan.blackhole_hold(clock=lambda: clock[0], sleep=fake_sleep)
    assert held == pytest.approx(0.4) and slept == [pytest.approx(0.4)]
    # Outside the window the hook is free.
    assert plan.blackhole_hold(clock=lambda: clock[0],
                               sleep=fake_sleep) == 0.0


def test_evict_due_fires_once_after_offset():
    plan = FaultPlan.parse("evict_sessions@t_ms=200").arm(now=0.0)
    assert plan.evict_due(now=0.1) is False
    assert plan.evict_due(now=0.25) is True
    assert plan.evict_due(now=0.3) is False            # one-shot


def test_extend_arms_at_extend_time_not_parse_time():
    """Runtime arming (the /debug/faults seam): a spec extended at t=5
    measures its offsets from t=5, and a bad spec changes nothing."""
    plan = FaultPlan.parse("").arm(now=0.0)
    armed = plan.extend("blackhole_backend@t_ms=0:1.0", now=5.0)
    assert [f.kind for f in armed] == ["blackhole_backend"]
    assert plan.blackhole_until(now=4.5) is None
    assert plan.blackhole_until(now=5.5) == pytest.approx(6.0)
    with pytest.raises(ValueError):
        plan.extend("bogus@request=1", now=6.0)
    assert len(plan.faults) == 1


# ---------------------------------------------------------------------------
# Session-tier grammar (PR 18)
# ---------------------------------------------------------------------------

def test_tier_grammar_rejects_malformed():
    for bad in ("tier_outage@t_ms=100",      # missing window length
                "tier_outage@request=1:1s",  # wrong dimension
                "tier_slow@request=2",       # missing required duration
                "tier_slow@t_ms=100:1s",     # wrong dimension
                "tier_slow@request=0:1s",    # count must be >= 1
                "tier_outage@t_ms=-5:1s"):   # offset must be >= 0
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_tier_outage_window_measures_from_arming():
    plan = FaultPlan.parse("tier_outage@t_ms=100:0.5").arm(now=10.0)
    assert plan.tier_outage_until(now=10.05) is None   # before the window
    assert plan.tier_outage_until(now=10.1) == pytest.approx(10.6)
    assert plan.tier_outage_until(now=10.59) == pytest.approx(10.6)
    assert plan.tier_outage_until(now=10.6) is None    # window closed


def test_tier_outage_hold_sleeps_to_window_end():
    plan = FaultPlan.parse("tier_outage@t_ms=0:0.5").arm(now=0.0)
    clock = [0.1]
    slept = []

    def fake_sleep(s):
        slept.append(s)
        clock[0] += s

    held = plan.tier_outage_hold(clock=lambda: clock[0], sleep=fake_sleep)
    assert held == pytest.approx(0.4) and slept == [pytest.approx(0.4)]
    assert plan.tier_outage_hold(clock=lambda: clock[0],
                                 sleep=fake_sleep) == 0.0


def test_tier_outage_does_not_hold_blackhole_and_vice_versa():
    """The two window kinds are independent hooks: a tier outage must
    not stall backend replies, and a backend blackhole must not stall
    the tier."""
    plan = FaultPlan.parse("tier_outage@t_ms=0:1.0").arm(now=0.0)
    assert plan.blackhole_until(now=0.5) is None
    plan2 = FaultPlan.parse("blackhole_backend@t_ms=0:1.0").arm(now=0.0)
    assert plan2.tier_outage_until(now=0.5) is None


def test_tier_slow_is_a_count_budget():
    plan = FaultPlan.parse("tier_slow@request=2:0.25").arm(now=0.0)
    assert plan.tier_slow_delay() == 0.25
    assert plan.tier_slow_delay() == 0.25
    assert plan.tier_slow_delay() == 0.0              # budget exhausted


# ---------------------------------------------------------------------------
# Self-healing data loader
# ---------------------------------------------------------------------------

def _shift_ds(n=8):
    return ShiftStereoDataset(n=n, hw=(16, 24))


def test_poisoned_sample_quarantined_exactly_once():
    """1 of N samples always raises: the loop completes with the correct
    batch count, the bad index is quarantined exactly once (later epochs
    replace it at dispatch) and the counters report it."""
    dl = DataLoader(_shift_ds(), 2, num_workers=0, seed=1,
                    retry_backoff=0.001,
                    fault_plan=FaultPlan.parse("corrupt@sample=3"))
    for _ in range(2):
        assert sum(1 for _ in dl) == 4
    assert dl.quarantined == {3}
    assert dl.stats["samples_quarantined"] == 1
    assert dl.stats["samples_replaced"] >= 2        # once live, once dispatch
    assert dl.health_metrics()["data_samples_quarantined"] == 1.0


class _TransientDataset:
    """First access raises IOError, then behaves (flaky NFS read)."""

    def __init__(self, inner):
        self.inner = inner
        self.tripped = False

    def __len__(self):
        return len(self.inner)

    def __getitem__(self, i):
        if not self.tripped:
            self.tripped = True
            raise IOError("injected transient failure")
        return self.inner[i]

    def reseed(self, seed):
        pass


def test_transient_failure_retried_not_quarantined():
    dl = DataLoader(_TransientDataset(_shift_ds()), 2, num_workers=0,
                    seed=1, retry_backoff=0.001)
    assert sum(1 for _ in dl) == 4
    assert dl.stats["samples_retried"] == 1
    assert dl.stats["samples_quarantined"] == 0 and not dl.quarantined


def test_quarantine_is_bounded():
    plan = FaultPlan.parse(",".join(f"corrupt@sample={i}" for i in range(4)))
    dl = DataLoader(_shift_ds(), 2, num_workers=0, seed=1,
                    retry_backoff=0.001, quarantine_limit=2, fault_plan=plan)
    with pytest.raises(RuntimeError, match="quarantine limit"):
        for _ in dl:
            pass


def test_hung_worker_recovers_via_pool_recycle():
    """A hang injected into worker 0 exceeds the batch timeout; the loader
    recycles the pool (fresh worker ids) and the epoch completes instead of
    deadlocking."""
    dl = DataLoader(_shift_ds(), 2, num_workers=1, seed=1, batch_timeout=3.0,
                    fault_plan=FaultPlan.parse("hang@worker=0:60s"))
    assert sum(1 for _ in dl) == 4
    assert dl.stats["pool_recycles"] == 1
    assert dl.stats["load_timeouts"] == 1


def test_worker_pool_quarantines_corrupt_sample():
    dl = DataLoader(_shift_ds(), 2, num_workers=1, seed=1, batch_timeout=60.0,
                    retry_backoff=0.001,
                    fault_plan=FaultPlan.parse("corrupt@sample=5"))
    assert sum(1 for _ in dl) == 4
    assert dl.quarantined == {5}
    assert dl.stats["samples_quarantined"] == 1


def test_persistent_hang_gives_up_after_two_timeouts():
    """If the replacement pool hangs too, the loader raises instead of
    recycling forever."""
    plan = FaultPlan.parse("hang@worker=0:60s,hang@worker=1:60s")
    dl = DataLoader(_shift_ds(), 2, num_workers=1, seed=1, batch_timeout=2.0,
                    fault_plan=plan)
    with pytest.raises(RuntimeError, match="timed out twice"):
        for _ in dl:
            pass
    assert dl.stats["pool_recycles"] == 1


# ---------------------------------------------------------------------------
# Checkpoint integrity + fallback
# ---------------------------------------------------------------------------

def _tiny_state(step=0):
    model = RAFTStereo(TINY)
    tx, _ = make_optimizer(TrainConfig(num_steps=6))
    state = create_train_state(model, jax.random.key(0), tx, HW)
    return state.replace(step=jnp.asarray(step, jnp.int32))


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_restore_falls_back_when_latest_corrupt(tmp_path):
    mngr = CheckpointManager(str(tmp_path / "ck"), keep=3,
                             fault_plan=FaultPlan.parse(""))
    mngr.save(1, _tiny_state(1), wait=True)
    mngr.save(2, _tiny_state(2), wait=True)
    fl.corrupt_tree(os.path.join(mngr.directory, "2"))
    # latest_step still points at the corrupt step — the trap init_state()
    # used to re-walk into forever.
    assert mngr.latest_step() == 2
    with pytest.raises(Exception):
        mngr.restore(_tiny_state())                 # explicit latest: raises
    state, step = mngr.restore_latest_valid(_tiny_state())
    assert step == 1 and int(state.step) == 1
    mngr.close()


def test_corrupt_ckpt_fault_hook_and_total_loss(tmp_path):
    plan = FaultPlan.parse("corrupt_ckpt@step=1,corrupt_ckpt@step=2")
    mngr = CheckpointManager(str(tmp_path / "ck"), keep=3, fault_plan=plan)
    mngr.save(1, _tiny_state(1))                    # corrupted by the hook
    mngr.save(2, _tiny_state(2))
    state, step = mngr.restore_latest_valid(_tiny_state())
    assert state is None and step is None           # every step corrupt
    mngr.close()


# ---------------------------------------------------------------------------
# Train-loop chaos (in-process, real loop on synthetic data)
# ---------------------------------------------------------------------------

def _tcfg(tmp_path, name, **kw):
    base = dict(name=name, batch_size=2, num_steps=6, train_iters=2,
                image_size=HW, validation_frequency=100, seed=3,
                checkpoint_dir=str(tmp_path / "ckpt"), data_parallel=2,
                restart_backoff=0.0)
    base.update(kw)
    return TrainConfig(**base)


def _run_train(tmp_path, monkeypatch, plan, name, **kw):
    from raftstereo_tpu.cli.train import train
    monkeypatch.chdir(tmp_path)                     # runs/<name> under tmp
    return train(TINY, _tcfg(tmp_path, name, **kw),
                 dataset=ShiftStereoDataset(n=8, hw=HW), num_workers=0,
                 no_validation=True, fault_plan=plan)


def _last_metrics(tmp_path, name):
    lines = (tmp_path / "runs" / name / "metrics.jsonl").read_text()
    return json.loads(lines.strip().splitlines()[-1])


def test_crash_restart_progress_watchdog_quarantine_nanskip(
        tmp_path, monkeypatch, caplog):
    """One run, four mechanisms: two crashes survive a max_restarts=1
    budget because checkpoint progress resets it; an injected slow step
    trips the watchdog; a poisoned sample is quarantined and reported; an
    injected NaN batch is skipped under nan_policy=skip."""
    plan = FaultPlan.parse("crash@step=3,crash@step=5,nan@step=6,"
                           "slow@step=7:4s,corrupt@sample=3")
    state = _run_train(tmp_path, monkeypatch, plan, "combo",
                       validation_frequency=2, max_restarts=1,
                       nan_policy="skip", watchdog_factor=3.0)
    assert int(state.step) == 7                     # completed despite chaos
    assert "step watchdog" in caplog.text
    rec = _last_metrics(tmp_path, "combo")
    assert rec.get("data_samples_quarantined", 0.0) > 0
    assert rec.get("skipped", 0.0) > 0              # the NaN step, recorded
    assert (tmp_path / "ckpt" / "combo" / "combo-final").exists()


def test_crash_without_progress_exhausts_budget(tmp_path, monkeypatch):
    plan = FaultPlan.parse("crash@step=2,crash@step=2")
    with pytest.raises(InjectedCrash):
        # No checkpoint before step 2 => both restarts resume at step 0:
        # no progress, so the second one exceeds max_restarts=1.
        _run_train(tmp_path, monkeypatch, plan, "thrash", max_restarts=1,
                   nan_policy="skip")


def test_preemption_boundary_save_then_corrupt_fallback_resume(
        tmp_path, monkeypatch, caplog):
    """SIGTERM (self-delivered by the fault plan through the real signal
    handler) → checkpoint at the current step boundary → clean return.
    Then the chaos escalates: the boundary checkpoint (the latest) is
    corrupted, and the relaunch must fall back to the previous retained
    step instead of re-restoring the broken one forever, then complete."""
    import logging
    caplog.set_level(logging.INFO)
    plan = FaultPlan.parse("preempt@step=4")
    state = _run_train(tmp_path, monkeypatch, plan, "pre",
                       validation_frequency=2, nan_policy="skip")
    assert int(state.step) == 3                     # boundary before step 4
    ck = str(tmp_path / "ckpt" / "pre")
    mngr = CheckpointManager(ck)
    assert mngr.latest_step() == 3                  # the preemption save
    assert 2 in mngr.all_steps()                    # the periodic save
    mngr.close()
    assert not (tmp_path / "ckpt" / "pre" / "pre-final").exists()

    fl.corrupt_tree(os.path.join(ck, "3"))
    state = _run_train(tmp_path, monkeypatch, FaultPlan.parse(""), "pre",
                       validation_frequency=2, nan_policy="skip")
    assert "falling back to the previous retained step" in caplog.text
    assert "Resumed from step 2" in caplog.text
    assert int(state.step) == 7
    assert (tmp_path / "ckpt" / "pre" / "pre-final").exists()


def test_injected_nan_raises_under_abort_policy(tmp_path, monkeypatch):
    plan = FaultPlan.parse("nan@step=2")
    with pytest.raises(FloatingPointError):
        # max_restarts must NOT burn its budget replaying a deterministic
        # failure.
        _run_train(tmp_path, monkeypatch, plan, "nanabort",
                   nan_policy="abort", max_restarts=5)


# ---------------------------------------------------------------------------
# End-to-end over the CLI: SIGTERM → exit 0 → bitwise-exact resume
# ---------------------------------------------------------------------------

def _cli_cmd(data_root, ckpt_dir, name, num_steps, vf):
    return [sys.executable, "-m", "raftstereo_tpu.cli.train",
            "--train_datasets", "kitti", "--dataset_root", str(data_root),
            "--batch_size", "2", "--image_size", str(HW[0]), str(HW[1]),
            "--train_iters", "2", "--num_steps", str(num_steps),
            "--validation_frequency", str(vf), "--no_validation",
            "--num_workers", "0", "--checkpoint_dir", str(ckpt_dir),
            "--corr_levels", "2", "--corr_radius", "2", "--n_gru_layers", "2",
            "--hidden_dims", "16", "16", "--name", name, "--seed", "7",
            "--data_parallel", "2", "--restart_backoff", "0"]


def _run_cli(cmd, cwd, faults=None):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.pop(fl.ENV_VAR, None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # see module NOTE
    if faults:
        env[fl.ENV_VAR] = faults
    return subprocess.run(cmd, cwd=str(cwd), env=env, capture_output=True,
                          text=True, timeout=420)


def test_sigterm_preemption_exact_resume_cli(tmp_path):
    """The acceptance chaos path, through the real CLI in real processes:
    SIGTERM mid-run → checkpoint written at the step boundary → exit 0 →
    relaunch resumes at the exact step; the preemption-written checkpoint
    is bitwise-identical (params, optimizer moments, step) to the same
    step of an uninterrupted reference run."""
    data = tmp_path / "kitti"
    make_synthetic_kitti(data, n=4, rng=np.random.default_rng(0))

    # A: preempted before step 5 => boundary checkpoint at step 4, rc 0.
    a = _run_cli(_cli_cmd(data, tmp_path / "cka", "a", 6, 3), tmp_path,
                 faults="preempt@step=5")
    assert a.returncode == 0, a.stderr[-3000:]
    assert "checkpoint at step 4 written" in a.stderr

    # R: identical recipe, uninterrupted, checkpointing every step.
    r = _run_cli(_cli_cmd(data, tmp_path / "ckr", "r", 6, 1), tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]

    like = _tiny_state()
    ma = CheckpointManager(str(tmp_path / "cka" / "a"))
    mr = CheckpointManager(str(tmp_path / "ckr" / "r"))
    assert ma.latest_step() == 4
    sa, sr = ma.restore(like, step=4), mr.restore(like, step=4)
    ma.close(), mr.close()
    _assert_tree_equal(sa, sr)                      # bitwise-exact state

    # Relaunch A (same command): resumes at the exact preemption step,
    # completes, rc 0.
    b = _run_cli(_cli_cmd(data, tmp_path / "cka", "a", 6, 3), tmp_path)
    assert b.returncode == 0, b.stderr[-3000:]
    assert "Resumed from step 4" in b.stderr
    assert (tmp_path / "cka" / "a" / "a-final").exists()
