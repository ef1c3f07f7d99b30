"""RSA4xx — executable-cache keys must cover every key-relevant input.

The serving engine promises "one compile per (bucket, iters, mode)"
(serve/engine.py): each executable-cache entry is keyed by everything
that changes the compiled program.  A key that *omits* one of those
inputs is the worst kind of bug — the cache HIT serves an executable
compiled for different parameters and silently returns wrong numerics
(e.g. an ``iters=32`` request answered by the ``iters=8`` program).

The checker cross-checks key construction against method signatures: in
every ``infer_*`` / ``warmup_*`` method, it finds the cache-key
expressions — the first argument of ``*dispatch*`` calls, operands of
``... in self._compiled``-style membership tests, and arguments of
``.add(...)`` on ``*compiled*``/``*cache*`` attributes — then computes
which names flow into them (transitively through simple assignments and
``for`` targets) and demands that every *key-relevant parameter* of the
method reaches the key:

* key-relevant = the parameter name contains ``iters``, ``mode``,
  ``precision``, ``dtype``, ``backend``, ``accuracy``, ``tier``,
  ``quant``, ``shards``, ``rows``, ``cascade`` or ``schedule`` — the
  inputs that select a distinct executable (shape inputs are carried by
  the bucket, which every key already starts from; ``rows`` is the
  compiled row count of a plain batch program — a dispatch holds the
  rows that came, at row count 1 or ``max_batch_size``, and the one-row
  and the eight-row program at the same bucket are different
  executables, serve/engine.py ``row_counts``; ``backend`` covers
  kernel-backend selectors,
  ``accuracy``/``tier``/``quant`` the per-request accuracy tiers whose
  precision mode joins every serving key, serve/engine.py +
  ops/quant.py, ``shards`` the spatial mesh width — a 2-shard and a
  4-shard program at the same bucket are different executables,
  parallel/spatial.py — and ``cascade``/``schedule`` the tier-cascade
  selectors, serve/cascade/: a cascade executable is keyed by BOTH its
  precision modes, and a resolver keyed by the canonical schedule
  string must carry it).

Note the dual-mode cascade shape (serve/engine.py ``infer_cascade_*``):
``cheap_mode`` and ``cert_mode`` are two *independent* key-relevant
parameters — a key carrying only one of them hits the wrong
(cheap, certified) pair's handoff program, which silently casts into
the wrong dtype tree.  The token match is per-parameter, so both are
demanded individually; no cascade-specific logic is needed.

Codes:

* RSA401 — a key-relevant parameter does not flow into the cache key.
* RSA402 — a cache key with no data flow from any name at all (a
  constant key: every call shares one executable slot).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Set

from .core import Finding, SourceFile, qualname_of

__all__ = ["check"]

_METHOD_RE = re.compile(r"^(infer|warmup)_")
_KEY_TOKENS = ("iters", "mode", "precision", "dtype", "backend",
               "accuracy", "tier", "quant", "input_mode", "shards", "rows",
               "cascade", "schedule")
_CACHE_ATTR_RE = re.compile(r"compiled|cache", re.IGNORECASE)
_DISPATCH_RE = re.compile(r"dispatch", re.IGNORECASE)


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _key_exprs(fn: ast.AST) -> List[ast.AST]:
    out: List[ast.AST] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            func = node.func
            if (isinstance(func, ast.Attribute)
                    and _DISPATCH_RE.search(func.attr) and node.args):
                out.append(node.args[0])
            elif (isinstance(func, ast.Attribute) and func.attr == "add"
                  and isinstance(func.value, ast.Attribute)
                  and _CACHE_ATTR_RE.search(func.value.attr)
                  and node.args):
                out.append(node.args[0])
        elif isinstance(node, ast.Compare):
            if (len(node.ops) == 1
                    and isinstance(node.ops[0], (ast.In, ast.NotIn))
                    and isinstance(node.comparators[0], ast.Attribute)
                    and _CACHE_ATTR_RE.search(node.comparators[0].attr)):
                out.append(node.left)
    return out


def _flow_closure(fn: ast.AST, seeds: Set[str]) -> Set[str]:
    """Names reachable backwards from ``seeds`` through assignments,
    tuple unpacking and ``for`` targets within ``fn`` (fixpoint)."""
    pairs: List[tuple] = []  # (target names, source names) per assignment
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            tgts: Set[str] = set()
            for t in node.targets:
                tgts |= _names_in(t)
            pairs.append((tgts, _names_in(node.value)))
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            if node.value is not None:
                pairs.append((_names_in(node.target),
                              _names_in(node.value)))
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            pairs.append((_names_in(node.target), _names_in(node.iter)))
        elif isinstance(node, ast.NamedExpr):
            pairs.append((_names_in(node.target),
                          _names_in(node.value)))
    closure = set(seeds)
    changed = True
    while changed:
        changed = False
        for tgts, srcs in pairs:
            if tgts & closure and not srcs <= closure:
                closure |= srcs
                changed = True
    return closure


def check(sf: SourceFile) -> Iterator[Finding]:
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _METHOD_RE.match(node.name):
            continue
        key_exprs = _key_exprs(node)
        if not key_exprs:
            continue
        params = [a.arg for a in (node.args.posonlyargs + node.args.args
                                  + node.args.kwonlyargs)
                  if a.arg not in ("self", "cls")]
        relevant = [p for p in params
                    if any(tok in p.lower() for tok in _KEY_TOKENS)]
        qual = qualname_of(node)
        reported: Set[str] = set()
        for expr in key_exprs:
            seeds = _names_in(expr)
            if not seeds:
                yield Finding(
                    "RSA402", sf.path, expr.lineno,
                    f"`{node.name}` uses a constant executable-cache "
                    "key: every call shares one cache slot regardless "
                    "of its inputs", qual)
                continue
            closure = _flow_closure(node, seeds)
            for p in relevant:
                if p in closure or p in reported:
                    continue
                reported.add(p)
                yield Finding(
                    "RSA401", sf.path, expr.lineno,
                    f"executable-cache key in `{node.name}` does not "
                    f"include key-relevant parameter `{p}`: a cache hit "
                    "would serve an executable compiled for a different "
                    f"{p}", qual)
