"""Unit coverage for the long-horizon harness's health-gate helpers
(scripts/longrun_tpu.py) — the gates that certify the committed chip
curve (docs/longrun_r05_curve.jsonl) must themselves be trustworthy: a parser
that silently drops records would turn a broken run into a PASS.
"""

import json

from scripts.longrun_tpu import jsonl_records, last_step


def _write(tmp_path, records, junk=()):
    p = tmp_path / "metrics.jsonl"
    with open(p, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
        for j in junk:
            f.write(j + "\n")
    return str(p)


def test_jsonl_records_roundtrip(tmp_path):
    recs = [{"step": 100, "loss": 2.0}, {"step": 200, "loss": 1.0}]
    p = _write(tmp_path, recs)
    assert jsonl_records(p) == recs


def test_jsonl_records_skips_torn_lines(tmp_path):
    """A SIGKILL mid-write leaves a torn last line — the parser must keep
    every intact record and drop only the torn one."""
    recs = [{"step": 100, "loss": 2.0}]
    p = _write(tmp_path, recs, junk=['{"step": 200, "lo'])
    assert jsonl_records(p) == recs


def test_jsonl_records_missing_file():
    assert jsonl_records("/nonexistent/metrics.jsonl") == []


def test_last_step_ignores_steplesss_records(tmp_path):
    p = _write(tmp_path, [{"note": "x"}, {"step": 300}, {"validation": 1}])
    assert last_step(p) == 300


def test_last_step_empty(tmp_path):
    p = _write(tmp_path, [])
    assert last_step(p) == 0
