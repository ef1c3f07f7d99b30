"""raftstereo_tpu.analysis — JAX/TPU hygiene + thread-safety lint, and a
runtime retrace guard (docs/static_analysis.md).

The system's headline guarantees — "one compile per bucket" (serve),
"streaming adds zero compiles beyond the ladder" (stream), "tracing adds
zero XLA compiles" (obs) — are invariants nothing used to enforce except
hand-written e2e assertions.  This package enforces them mechanically:

* **Static checkers** (AST, stdlib-only, nothing imported): jit/Pallas
  hygiene (RSA1xx), donation safety (RSA2xx), ``# guarded_by:`` lock
  discipline (RSA3xx), executable-cache key coverage (RSA4xx), plus the
  consolidated metric-name lint (RSA5xx, runtime).  Runner:
  ``python -m raftstereo_tpu.analysis [paths]``, wired into tier-1 via
  tests/test_analysis.py.  Per-line ``# noqa: RSA###`` suppressions and
  a checked-in baseline (``analysis_baseline.txt``, empty on the shipped
  tree) gate CI on NEW findings only.
* **Retrace guard** (``analysis/retrace_guard.py``): a context manager +
  pytest fixture that counts actual XLA backend compiles via
  ``jax.monitoring`` and fails any test whose compiles exceed its
  declared budget — the runtime complement the serve/stream/obs e2e
  tests run under.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

from .core import (Finding, SourceFile, apply_baseline, format_finding,
                   iter_python_files, load_baseline, save_baseline)

__all__ = ["Finding", "analyze", "apply_baseline",
           "default_baseline_path", "format_finding", "iter_python_files",
           "load_baseline", "save_baseline"]

# Env override so tests and tooling can point at a scratch baseline.
_BASELINE_ENV = "RAFTSTEREO_ANALYSIS_BASELINE"


def default_baseline_path() -> str:
    """``analysis_baseline.txt`` at the repo root (next to the package),
    overridable via ``RAFTSTEREO_ANALYSIS_BASELINE``."""
    env = os.environ.get(_BASELINE_ENV)
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "analysis_baseline.txt")


def _ast_checkers():
    from . import cache_keys, donation, jit_hygiene, locks
    return (jit_hygiene.check, donation.check, locks.check,
            cache_keys.check)


def analyze(paths: Sequence[str], repo_root: Optional[str] = None,
            metrics: bool = False) -> List[Finding]:
    """Run every checker over ``paths``; returns noqa-filtered findings
    (baseline application is the caller's job — see ``__main__``).

    ``metrics=True`` appends the runtime metric-lint pass (RSA5xx),
    which imports the package under analysis; leave it off for fixture
    runs."""
    findings: List[Finding] = []
    checkers = _ast_checkers()
    for abspath, relpath in iter_python_files(paths, repo_root):
        try:
            sf = SourceFile(abspath, relpath)
        except SyntaxError as e:
            # A finding, not a crash (flake8's E999 convention): one
            # broken scratch file must not take down the whole gate
            # with a traceback.
            findings.append(Finding(
                "RSA001", relpath, e.lineno or 1,
                f"file does not parse: {e.msg}", "<module>"))
            continue
        seen = set()
        for checker in checkers:
            for f in checker(sf):
                dedupe = (f.code, f.line, f.message)
                if dedupe in seen or sf.suppressed(f.code, f.line):
                    continue
                seen.add(dedupe)
                findings.append(f)
    if metrics:
        from .metrics_lint import run_metrics_lint
        findings.extend(run_metrics_lint())
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings
