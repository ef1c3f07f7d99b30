"""Static-analysis suite (raftstereo_tpu/analysis, docs/static_analysis.md).

Two halves:

* the AST checkers — each of the four families (jit hygiene RSA1xx,
  donation RSA2xx, lock discipline RSA3xx, cache keys RSA4xx) must fire
  with exact codes and line numbers on its bad fixture and stay silent
  on the paired good fixture; suppressions and the baseline must
  round-trip; and — the tier-1 acceptance gate — the full runner
  (``python -m raftstereo_tpu.analysis``, AST + consolidated metric
  lint) must exit 0 on the shipped tree with the checked-in EMPTY
  baseline;
* the runtime retrace guard — a seeded Python-float jit closure (the
  classic silent-retrace hazard) must blow its declared compile budget,
  a cached jit must pass under budget, and the guard must refuse to run
  under a persistent JAX compile cache.
"""

import os

import numpy as np
import pytest

from raftstereo_tpu.analysis import (analyze, apply_baseline,
                                     default_baseline_path, load_baseline,
                                     save_baseline)
from raftstereo_tpu.analysis.__main__ import main as analysis_main
from raftstereo_tpu.analysis.retrace_guard import RetraceBudgetExceeded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_fixtures")


def _fx(name):
    return os.path.join(FIXTURES, name)


def _run(name):
    return analyze([_fx(name)], repo_root=REPO)


def _codes_lines(findings):
    return [(f.code, f.line) for f in findings]


# ------------------------------------------------------------ checker units

class TestJitHygiene:
    def test_bad_fixture_exact_codes_and_lines(self):
        assert _codes_lines(_run("jit_bad.py")) == [
            ("RSA101", 15), ("RSA101", 16), ("RSA101", 17),
            ("RSA102", 23), ("RSA102", 24), ("RSA102", 25),
            ("RSA103", 34), ("RSA104", 41), ("RSA105", 45),
            ("RSA106", 51)]

    def test_good_fixture_is_clean(self):
        assert _run("jit_good.py") == []


class TestDonation:
    def test_bad_fixture_exact_codes_and_lines(self):
        findings = _run("donation_bad.py")
        assert _codes_lines(findings) == [("RSA201", 14), ("RSA202", 19)]
        assert "donated (line 13)" in findings[0].message

    def test_good_fixture_is_clean(self):
        assert _run("donation_good.py") == []


class TestLockDiscipline:
    def test_bad_fixture_exact_codes_and_lines(self):
        findings = _run("locks_bad.py")
        assert _codes_lines(findings) == [
            ("RSA302", 12), ("RSA301", 19), ("RSA301", 22),
            ("RSA301", 27), ("RSA303", 31), ("RSA301", 42)]
        # The nested-def escape is attributed to the inner function.
        assert findings[3].context == "Box.deferred.later"
        # The unlocked export-in-flight marker (migration shape, PR 13).
        assert findings[5].context == "Migrator.begin"

    def test_good_fixture_is_clean(self):
        # Includes the caller-holds-lock def annotation, the inline
        # lambda transparency, the cross-object (srv.) base match, and
        # the migration shapes (export-in-flight markers + pin CAS).
        assert _run("locks_good.py") == []


class TestCacheKeys:
    def test_bad_fixture_exact_codes_and_lines(self):
        findings = _run("cache_keys_bad.py")
        assert _codes_lines(findings) == [
            ("RSA401", 16), ("RSA402", 19), ("RSA401", 23),
            ("RSA401", 30), ("RSA401", 35), ("RSA401", 44),
            ("RSA401", 50), ("RSA401", 57), ("RSA401", 62),
            ("RSA401", 71), ("RSA401", 77), ("RSA401", 86),
            ("RSA401", 92), ("RSA401", 101), ("RSA401", 107),
            ("RSA401", 117), ("RSA401", 122), ("RSA401", 131)]
        assert "precision" in findings[0].message
        assert "mode" in findings[2].message
        # Kernel-backend selectors are key-relevant too: an infer call
        # and a warmup membership test whose keys omit gru_backend.
        assert "gru_backend" in findings[7].message
        assert "gru_backend" in findings[8].message
        # Spatial mesh width (parallel/spatial.py): an infer call and a
        # warmup membership test whose keys omit the shard count.
        assert "shards" in findings[13].message
        assert "shards" in findings[14].message
        # Accuracy-tier executables (serve/engine.py + ops/quant.py): an
        # infer call dropping the tier and a warmup ladder dropping it.
        assert "accuracy" in findings[9].message
        assert "tier" in findings[10].message
        # The scheduler's phase-executable keys (serve/engine.py): a step
        # key missing iters_per_step, and a warmup membership test whose
        # key omits it.
        assert "iters_per_step" in findings[3].message
        assert "iters_per_step" in findings[4].message
        # The cluster-replica shapes (serve/cluster/): a per-replica key
        # that drops mode, and a replica ladder warmup that drops
        # precision.
        assert "mode" in findings[5].message
        assert "precision" in findings[6].message
        # Input-modality executables (sl/, serve/engine.py): an infer
        # call and a warmup ladder whose keys drop input_mode.
        assert "input_mode" in findings[11].message
        assert "input_mode" in findings[12].message
        # Dual-mode cascade executables (serve/cascade/): keys carrying
        # only cheap_mode must still be flagged for the missing
        # cert_mode — both modes are demanded independently — and a
        # schedule-string resolver must carry the schedule.
        assert "cert_mode" in findings[15].message
        assert "cert_mode" in findings[16].message
        assert "schedule" in findings[17].message

    def test_good_fixture_is_clean(self):
        # Includes the phase-executable shapes: prologue (no key-relevant
        # params, shape-derived key), step keyed by iters_per_step, and a
        # warmup loop whose membership test carries it — plus the
        # cluster-replica shapes (replica id in the key is fine; every
        # key-relevant param still reaches it).
        assert _run("cache_keys_good.py") == []


# ------------------------------------------------- suppression + baseline

class TestSuppressionAndBaseline:
    def test_noqa_suppresses_listed_codes_only(self):
        assert _run("suppressed.py") == []

    def test_baseline_round_trips(self, tmp_path):
        findings = _run("locks_bad.py")
        assert findings
        path = str(tmp_path / "baseline.txt")
        save_baseline(path, findings)
        baseline = load_baseline(path)
        assert sum(baseline.values()) == len(findings)
        new, stale = apply_baseline(findings, baseline)
        assert new == [] and stale == []

    def test_baseline_reports_new_and_stale(self, tmp_path):
        locks = _run("locks_bad.py")
        path = str(tmp_path / "baseline.txt")
        save_baseline(path, locks)
        baseline = load_baseline(path)
        jit = _run("jit_bad.py")
        new, stale = apply_baseline(jit, baseline)
        # None of the jit findings are covered; every locks entry is
        # stale (its finding is "fixed").
        assert len(new) == len(jit)
        assert len(stale) == len(locks)

    def test_malformed_baseline_rejected(self, tmp_path):
        p = tmp_path / "baseline.txt"
        p.write_text("not a baseline line\n")
        with pytest.raises(ValueError, match="malformed baseline"):
            load_baseline(str(p))


class TestRobustness:
    def test_syntax_error_is_a_finding_not_a_crash(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def oops(:\n")
        findings = analyze([str(bad)], repo_root=REPO)
        assert [f.code for f in findings] == ["RSA001"]
        assert "does not parse" in findings[0].message

    def test_missing_path_is_loud_not_green(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="does not exist"):
            analyze([str(tmp_path / "no_such_dir")], repo_root=REPO)
        assert analysis_main([str(tmp_path / "nope"),
                              "--no-metrics"]) == 2

    def test_guarded_comment_on_access_does_not_exempt(self, tmp_path):
        """A guarded_by comment on a mutation SITE (not the declaration)
        must not silently exempt that access from RSA301."""
        src = ("import threading\n\n\n"
               "class Box:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._depth = 0  # guarded_by: _lock\n\n"
               "    def bump(self):\n"
               "        self._depth += 1  # guarded_by: _lock\n")
        p = tmp_path / "sneaky.py"
        p.write_text(src)
        codes = [f.code for f in analyze([str(p)], repo_root=REPO)]
        assert "RSA301" in codes   # the unlocked mutation is flagged
        assert "RSA303" in codes   # and the rogue annotation declares
        # nothing (declarations live in the class body / constructor)

    def test_malformed_baseline_is_a_clean_diagnostic(self, tmp_path,
                                                      capsys):
        p = tmp_path / "baseline.txt"
        p.write_text("garbage line\n")
        rc = analysis_main([_fx("jit_good.py"), "--no-metrics",
                            "--baseline", str(p)])
        assert rc == 2
        assert "malformed baseline" in capsys.readouterr().err

    def test_vararg_callee_accepts_any_donate_position(self, tmp_path):
        src = ("import jax\n\n\n"
               "def f(a, *rest):\n    return a\n\n\n"
               "def run(x, y):\n"
               "    g = jax.jit(f, donate_argnums=(1,))\n"
               "    return g(x, y)\n")
        p = tmp_path / "vararg.py"
        p.write_text(src)
        assert analyze([str(p)], repo_root=REPO) == []


# ----------------------------------------------------------------- runner

class TestRunner:
    def test_exit_codes_and_update_baseline(self, tmp_path, capsys):
        bad = _fx("cache_keys_bad.py")
        base = str(tmp_path / "baseline.txt")
        assert analysis_main([bad, "--no-metrics", "--baseline",
                              base]) == 1
        assert analysis_main([bad, "--no-metrics", "--baseline", base,
                              "--update-baseline"]) == 0
        assert analysis_main([bad, "--no-metrics", "--baseline",
                              base]) == 0  # all baselined now
        assert analysis_main([_fx("cache_keys_good.py"), "--no-metrics",
                              "--baseline", base]) == 0
        out = capsys.readouterr()
        assert "stale baseline entry" in out.err  # fixed findings flagged

    def test_shipped_tree_clean_with_empty_baseline(self, monkeypatch):
        """THE acceptance gate (tier-1 wrapper for the whole suite):
        `python -m raftstereo_tpu.analysis raftstereo_tpu/` — all four
        AST families plus the consolidated metric lint (RSA5xx, formerly
        scripts/check_metrics.py) — exits 0 on the shipped tree, and the
        checked-in baseline is EMPTY (fixes landed, not suppressions)."""
        monkeypatch.delenv("RAFTSTEREO_ANALYSIS_BASELINE", raising=False)
        assert analysis_main([os.path.join(REPO, "raftstereo_tpu")]) == 0
        baseline = load_baseline(default_baseline_path())
        assert sum(baseline.values()) == 0

    def test_list_codes_covers_every_family(self, capsys):
        assert analysis_main(["--list-codes"]) == 0
        table = capsys.readouterr().out
        for code in ("RSA101", "RSA201", "RSA301", "RSA401", "RSA501"):
            assert code in table


# ---------------------------------------------------------- retrace guard

class TestRetraceGuard:
    def test_seeded_python_float_closure_blows_budget(self, retrace_guard):
        """THE runtime acceptance: the hazard RSA106 flags statically —
        a fresh jit over a Python-float closure per iteration — must be
        caught at runtime as compiles exceeding the declared budget."""
        import jax
        import jax.numpy as jnp

        xs = jnp.arange(8.0)  # any arange/asarray compile lands here,
        np.asarray(xs + 0.0)  # outside the guarded window
        with pytest.raises(RetraceBudgetExceeded,
                           match="retrace budget exceeded"):
            with retrace_guard(1, what="seeded python-float closure"):
                for i in range(3):
                    scale = float(i + 1)
                    step = jax.jit(lambda v: v * scale)  # noqa: RSA106
                    np.asarray(step(xs))

    def test_cached_jit_stays_within_budget(self, retrace_guard):
        import jax
        import jax.numpy as jnp

        xs = jnp.arange(8.0)
        np.asarray(xs + 0.0)
        cached = jax.jit(lambda v: v * 3.0)
        with retrace_guard(1, what="one compile, then cache hits") as rep:
            for _ in range(4):
                np.asarray(cached(xs))
        assert rep.compiles == 1       # first call compiled,
        assert rep.all_compiles == 1   # the other three hit the cache

    def test_min_duration_floor_filters_tiny_op_compiles(self,
                                                         retrace_guard):
        """The e2e adoption knob: with a floor, first-seen tiny host-op
        compiles don't count against a model-scale budget."""
        import jax
        import jax.numpy as jnp

        xs = jnp.arange(8.0)
        with retrace_guard(0, what="tiny compiles under the floor",
                           min_duration_s=30.0) as rep:
            fresh = jax.jit(lambda v: v * 7.0)  # noqa: RSA106
            np.asarray(fresh(xs))
        assert rep.all_compiles >= 1   # it DID compile...
        assert rep.compiles == 0       # ...but under the 30 s floor

    def test_refuses_persistent_compile_cache(self, retrace_guard,
                                              monkeypatch):
        """Deserialized executables skip the backend-compile event — the
        guard must refuse rather than silently under-count."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/never-used")
        with pytest.raises(RuntimeError, match="persistent"):
            with retrace_guard(0):
                pass
