"""bench.py is the driver's benchmark entry point — guard its contract:
one JSON line with metric/value/unit/vs_baseline, on any backend."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, out.stdout[-2000:]
    return json.loads(lines[0])


def _bench_module():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import bench
    return bench


@pytest.mark.slow
def test_quick_inference_contract():
    r = _run(["--quick", "--reps", "1"])
    assert set(r) == {"metric", "value", "unit", "vs_baseline"}
    assert r["unit"] == "pairs/sec" and r["value"] > 0


@pytest.mark.slow
def test_quick_mfu_extras():
    r = _run(["--quick", "--reps", "1", "--mfu"])
    assert {"flops_per_pair", "model_tflops", "measured_peak_tflops",
            "mfu_vs_measured_peak"} <= set(r)
    assert r["flops_per_pair"] > 1e9  # the flagship forward is TFLOP-scale


@pytest.mark.slow
def test_data_mode_contract():
    r = _run(["--data", "--num_workers", "0", "--batch", "4"])
    assert r["unit"] == "samples/sec" and r["value"] > 0


@pytest.mark.slow
def test_gru_mode_contract():
    r = _run(["--gru", "--quick"])
    assert r["unit"] == "pairs/sec" and r["value"] > 0
    assert {"xla_ms_per_batch", "fused_ms_per_batch", "speedup",
            "max_abs_diff"} <= set(r)
    import math
    assert math.isfinite(r["max_abs_diff"])


@pytest.mark.slow
def test_sl_mode_contract():
    r = _run(["--sl", "--quick"])
    assert r["unit"] == "pairs/sec" and r["value"] > 0
    assert {"passive_ms_per_batch", "sl_ms_per_batch",
            "passive_pairs_per_sec", "sl_pairs_per_sec",
            "sl_slowdown_vs_passive"} <= set(r)
    assert r["sl_slowdown_vs_passive"] > 0


@pytest.mark.slow
def test_quant_mode_contract():
    r = _run(["--quant", "--quick"])
    assert r["unit"] == "pairs/sec" and r["value"] > 0
    assert {"fp32_ms_per_batch", "bf16_ms_per_batch", "int8_ms_per_batch",
            "bf16_speedup_vs_fp32", "int8_speedup_vs_fp32",
            "int8_max_abs_diff_vs_fp32"} <= set(r)
    import math
    assert math.isfinite(r["int8_max_abs_diff_vs_fp32"])
    # The tiers genuinely diverge numerically from fp32 (quant engaged).
    assert r["int8_max_abs_diff_vs_fp32"] > 0


@pytest.mark.slow
def test_spatial_mode_contract():
    r = _run(["--spatial", "--quick"])
    assert r["unit"] == "ms" and r["value"] > 0
    assert {"shards", "iters", "single_ms", "sharded_ms", "speedup",
            "max_abs_gap"} <= set(r)
    assert r["shards"] == 4
    # The A/B is the subsystem's numeric contract in miniature: the
    # sharded program is BITWISE-identical to the single-device jit at
    # fp32, so the gap is exactly zero — not merely small.
    assert r["max_abs_gap"] == 0.0


@pytest.mark.slow
def test_slo_mode_contract():
    """bench --slo: trace gen -> open-loop replay against a 2-replica
    CPU cluster -> SLO verdict -> capacity fit, one JSON line out."""
    r = _run(["--slo", "--quick"])
    assert r["unit"] == "pairs/sec" and r["value"] > 0
    assert {"replicas", "trace_events", "slo_pass", "checks", "groups",
            "metric_deltas", "per_chip_rps", "utilization", "whatif",
            "wall_s"} <= set(r)
    assert r["replicas"] == 2
    assert r["slo_pass"] is True
    assert all(c["pass"] for c in r["checks"])
    # The fit answers the headline question from the same run.
    assert r["per_chip_rps"] > 0
    assert r["whatif"]["users_served"] >= 1
    # Server-side cross-check of the client-observed request count.
    assert r["metric_deltas"]["cluster_dispatch_total"] == r["trace_events"]


@pytest.mark.slow
def test_chaos_mode_contract():
    """bench --chaos: trace replay against a 2-backend router cluster
    while a ChaosPlan blackholes one backend mid-replay; the degraded
    verdict plus breaker activity ride out on the one JSON line."""
    r = _run(["--chaos", "--quick"])
    assert r["unit"] == "pairs/sec" and r["value"] > 0
    assert {"trace_events", "slo_pass", "checks", "windows", "chaos",
            "breaker_transitions", "metric_deltas", "wall_s"} <= set(r)
    assert r["slo_pass"] is True
    assert all(c["pass"] for c in r["checks"])
    # The plan armed (and only) its declared action, cleanly.
    assert r["chaos"] == {"actions": 1, "armed": 1, "failed": 0}
    # The declared window saw traffic, and so did the recovery slice.
    labels = [k for k in r["windows"] if k.endswith("blackhole_b0")]
    assert labels and r["windows"][labels[0]]["count"] > 0
    # The fault was real enough to trip the breaker at least once.
    assert r["breaker_transitions"] >= 1
    assert r["metric_deltas"]["cluster_dispatch_total"] >= r["trace_events"]
