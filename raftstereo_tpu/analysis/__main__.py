"""Runner: ``python -m raftstereo_tpu.analysis [paths...]``.

Exit 0 when every finding is suppressed (``# noqa: RSA###``) or
baselined; exit 1 on any NEW finding.  The default target is the
``raftstereo_tpu`` package and the default baseline is
``analysis_baseline.txt`` at the repo root (empty on the shipped tree).

Tier-1 runs this via tests/test_analysis.py.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from . import (analyze, apply_baseline, default_baseline_path,
               format_finding, load_baseline, save_baseline)

_CODE_TABLE = """\
RSA001 file does not parse (syntax error)
RSA101 impure call inside a traced (jit/Pallas) function
RSA102 host sync on a traced value (float()/np.asarray/.item())
RSA103 global/nonlocal mutation inside a traced function
RSA104 unhashable literal in a jit static_argnums position
RSA105 jax.jit(...)(...) built and invoked per call (silent retrace)
RSA106 jax.jit created inside a loop body (retrace per iteration)
RSA201 variable read after being passed at a donated position
RSA202 donate_argnums position out of the callee's signature
RSA301 guarded attribute accessed outside `with <base>.<lock>:`
RSA302 guarded_by names a lock the class never assigns
RSA303 guarded_by comment attached to nothing
RSA401 executable-cache key omits a key-relevant parameter
RSA402 constant executable-cache key
RSA501 metric-name lint violation (obs/prom.py)
RSA502 metrics render fails the Prometheus format validator
RSA503 serve/train metric bundles collide on one registry
"""


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m raftstereo_tpu.analysis",
        description="RSA static-analysis suite (docs/static_analysis.md)")
    p.add_argument("paths", nargs="*",
                   help="files/directories to analyze (default: the "
                        "raftstereo_tpu package)")
    p.add_argument("--baseline", default=None,
                   help="baseline file (default: analysis_baseline.txt "
                        "at the repo root, or $RAFTSTEREO_ANALYSIS_"
                        "BASELINE)")
    p.add_argument("--update-baseline", action="store_true",
                   help="write the current findings as the new baseline "
                        "and exit 0")
    p.add_argument("--no-metrics", action="store_true",
                   help="skip the runtime metric-lint pass (RSA5xx) — "
                        "for fixture/adhoc runs that don't import the "
                        "package")
    p.add_argument("--list-codes", action="store_true",
                   help="print the RSA code table and exit")
    args = p.parse_args(argv)
    if args.list_codes:
        print(_CODE_TABLE, end="")
        return 0

    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(os.path.dirname(pkg_dir))
    paths = args.paths or [os.path.dirname(pkg_dir)]
    try:
        findings = analyze(paths, repo_root=repo_root,
                           metrics=not args.no_metrics)
    except FileNotFoundError as e:
        print(f"analysis: {e}", file=sys.stderr)
        return 2

    baseline_path = args.baseline or default_baseline_path()
    if args.update_baseline:
        save_baseline(baseline_path, findings)
        print(f"analysis: baseline updated ({len(findings)} entr"
              f"{'y' if len(findings) == 1 else 'ies'}) -> "
              f"{baseline_path}")
        return 0
    try:
        baseline = load_baseline(baseline_path)
    except ValueError as e:
        print(f"analysis: {e}", file=sys.stderr)
        return 2
    new, stale = apply_baseline(findings, baseline)
    for f in new:
        print(format_finding(f))
    for key in stale:
        print(f"analysis: stale baseline entry {' '.join(key)} — the "
              "finding is gone; remove the line (or --update-baseline)",
              file=sys.stderr)
    n_base = len(findings) - len(new)
    print(f"analysis: {'FAIL' if new else 'OK'} ({len(new)} new finding"
          f"{'' if len(new) == 1 else 's'}, {n_base} baselined, "
          f"{len(stale)} stale baseline entr"
          f"{'y' if len(stale) == 1 else 'ies'})")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
