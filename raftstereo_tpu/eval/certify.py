"""Accuracy-tier certification: measured EPE deltas vs the fp32 reference.

The serving layer's accuracy tiers (ops/quant.py, docs/serving.md
"Accuracy tiers") trade numerics for throughput — ``fast`` runs bf16,
``turbo`` adds the int8-quantized correlation volume.  A tier is only
worth offering if its accuracy cost is KNOWN and BOUNDED, so this module
is the gate between "implemented" and "advertised":

* :func:`certify_tiers` runs synthetic stereo pairs with exact ground
  truth (data/synthetic.ShiftStereoDataset — matched textures, so the
  correlation volume is genuinely informative) through the fp32
  reference forward and through each tier's model (same weights, only
  the numeric-policy config fields swapped), and records each tier's
  mean-EPE delta against its bound;
* the resulting **certification manifest** (JSON, written by
  ``python -m raftstereo_tpu.cli.certify``) travels with the checkpoint;
* :func:`resolve_tiers` is what the server calls at startup
  (serve/server.build_server, serve/cluster/replica.py): a tier is
  advertised on ``/predict`` only when the manifest certifies it for
  this model — over-bound, missing, stale-architecture or unreadable
  manifests all refuse the tier with a recorded reason (a request for it
  is a clean 400, never a silently-degraded answer).

The deltas are measured on synthetic data — they certify the numeric
envelope of the tier's kernels, not benchmark leaderboard deltas; the
bounds are deliberately loose screens against implementation regressions
(a broken dequant shows up px-large), not sub-pixel accuracy claims.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ops.quant import (TIER_MODES, TIERS, config_for_mode,
                         mode_for_accuracy)

logger = logging.getLogger(__name__)

__all__ = ["DEFAULT_BOUNDS", "DEFAULT_CASCADE_BOUND", "cascade_ok",
           "certify_cascades", "certify_tiers", "load_manifest",
           "resolve_cascades", "resolve_tiers", "tier_ok",
           "write_manifest"]

MANIFEST_VERSION = 1

# Default mean-EPE-delta bounds (px) per tier on the synthetic
# certification set.  Loose by design: they catch implementation breakage
# (a wrong dequant scale or a mis-keyed executable is pixels-large), while
# the measured delta itself is recorded in the manifest for operators who
# want tighter SLOs.
DEFAULT_BOUNDS = {"fast": 0.5, "turbo": 1.0}

# Default mean-EPE-delta bound (px) for a CASCADE schedule vs the fp32
# monolithic reference at EQUAL TOTAL iteration count.  Tighter than the
# all-cheap tier bounds because the certifying fp32 leg pulls the
# estimate back toward the reference fixed point — a cascade that cannot
# beat its cheap tier's bound is pointless.
DEFAULT_CASCADE_BOUND = 0.5

# Model-config fields that must match between certification time and
# serving time for the certificate to transfer: everything that changes
# the traced program or its numerics APART from the three fields the tier
# itself swaps (compute_dtype/corr_dtype/corr_quant — config_for_mode
# overrides those identically on both sides, so base-config differences
# there are irrelevant to the tier programs).  Backend selectors with
# "auto" resolution (corr_implementation, fused_encoder)
# are fingerprinted as the RAW config strings; their platform-dependent
# resolution is covered by the separate platform check in tier_ok.
ARCH_FIELDS = ("corr_levels", "corr_radius", "n_downsample", "n_gru_layers",
               "hidden_dims", "slow_fast_gru", "shared_backbone",
               "context_norm", "corr_implementation", "corr_precision",
               "fused_encoder", "input_mode")


def _arch_of(config) -> Dict[str, object]:
    d = dataclasses.asdict(config)
    return {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in d.items() if k in ARCH_FIELDS}


def _arch_mismatch(manifest: Dict, config) -> List[str]:
    """The ARCH_FIELDS on which a manifest's model differs from
    ``config``.  Only the fields this build fingerprints are compared, so
    a manifest written when there were more of them still certifies."""
    want, have = _arch_of(config), manifest.get("model") or {}
    return sorted(k for k in want if have.get(k) != want[k])


def _cert_data(config, hw: Tuple[int, int], n_pairs: int, seed: int):
    """The certification set, stacked: ``(lefts, rights, gts, valid,
    n_valid, description)`` — shared by tier and cascade certification so
    both measure against the same pairs."""
    if config.input_mode == "sl":
        # SL models certify on SL data: the exact-GT synthetic twin with
        # 12-channel pattern-conditioned inputs (sl/synthetic.py).  A
        # passive certification set cannot even be fed to an SL model —
        # and the fingerprint (ARCH_FIELDS) keys the manifest to the
        # input mode, so certificates never transfer across modes.
        from ..sl import SLShiftStereoDataset
        ds = SLShiftStereoDataset(n=n_pairs, hw=hw, seed=seed)
        data_desc = "synthetic SLShiftStereoDataset (exact GT, masked)"
    else:
        from ..data.synthetic import ShiftStereoDataset
        ds = ShiftStereoDataset(n=n_pairs, hw=hw, seed=seed)
        data_desc = "synthetic ShiftStereoDataset (exact GT)"
    lefts = np.stack([ds[i][1] for i in range(n_pairs)])
    rights = np.stack([ds[i][2] for i in range(n_pairs)])
    gts = np.stack([ds[i][3] for i in range(n_pairs)])   # (N, H, W, 1)
    # Passive synthetic pairs are valid everywhere; SL pairs carry a
    # projector-shadow band that the EPE must skip (masked semantics).
    valid = np.stack([np.asarray(ds[i][4], np.float32)[..., None]
                      for i in range(n_pairs)])
    n_valid = max(float(valid.sum()), 1.0)
    return lefts, rights, gts, valid, n_valid, data_desc


def certify_tiers(config, variables, tiers: Sequence[str] = ("fast",
                                                             "turbo"), *,
                  hw: Tuple[int, int] = (64, 96), n_pairs: int = 4,
                  iters: int = 12, seed: int = 0,
                  bounds: Optional[Dict[str, float]] = None) -> Dict:
    """Measure per-tier EPE deltas vs the fp32 reference and build the
    certification manifest.

    One batched test-mode forward per tier (fp32 reference included), all
    at the same program shape so the comparison is apples-to-apples.
    ``bounds`` overrides :data:`DEFAULT_BOUNDS` per tier.  The returned
    manifest is self-contained: measured EPEs, deltas, bounds, the
    certified verdicts, and the model-architecture fingerprint
    :func:`tier_ok` later checks it against.
    """
    import jax
    import jax.numpy as jnp

    from ..models.raft_stereo import RAFTStereo

    bad = [t for t in tiers if t not in TIERS or t == "certified"]
    assert not bad, (f"cannot certify tiers {bad}: choose from "
                     f"{[t for t in TIERS if t != 'certified']}")
    bounds = {**DEFAULT_BOUNDS, **(bounds or {})}
    lefts, rights, gts, valid, n_valid, data_desc = _cert_data(
        config, hw, n_pairs, seed)

    def _epe(pred: np.ndarray) -> float:
        return float((np.abs(pred - gts) * valid).sum() / n_valid)

    def run(mode: str) -> np.ndarray:
        model = RAFTStereo(config_for_mode(config, mode))
        fn = jax.jit(lambda v, a, b, m=model: m.forward(
            v, a, b, iters=iters, test_mode=True)[1])
        up = fn(variables, jnp.asarray(lefts), jnp.asarray(rights))
        return np.asarray(up, np.float32)

    ref = run("fp32")
    epe_ref = _epe(ref)
    entries: Dict[str, Dict] = {}
    for tier in tiers:
        pred = run(TIER_MODES[tier])
        epe = _epe(pred)
        delta = epe - epe_ref
        bound = float(bounds[tier])
        entries[tier] = {
            "mode": TIER_MODES[tier],
            "epe": round(epe, 6),
            "epe_delta": round(delta, 6),
            "bound": bound,
            "max_abs_disp_diff": round(
                float((np.abs(pred - ref) * valid).max()), 6),
            "certified": bool(delta <= bound),
        }
        logger.info("certify %s: epe %.4f (ref %.4f, delta %+.4f, bound "
                    "%.3f) -> %s", tier, epe, epe_ref, delta, bound,
                    "CERTIFIED" if entries[tier]["certified"]
                    else "OVER BOUND")
    return {
        "version": MANIFEST_VERSION,
        "created": time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime()),
        # The platform the deltas were MEASURED on: "auto" backends and
        # kernel selection resolve per platform, so a CPU-measured
        # manifest must not certify the TPU kernels (tier_ok refuses).
        "platform": jax.default_backend(),
        "model": _arch_of(config),
        "eval": {"hw": list(hw), "n_pairs": n_pairs, "iters": iters,
                 "seed": seed, "epe_ref": round(epe_ref, 6),
                 "data": data_desc},
        "tiers": entries,
    }


def write_manifest(manifest: Dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.write("\n")


def load_manifest(path: str) -> Dict:
    """Parse + shape-check a manifest; raises ``ValueError`` on anything
    that should refuse certification loudly (bad JSON, wrong version,
    missing sections) rather than half-working."""
    with open(path) as f:
        try:
            manifest = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(f"certification manifest {path!r} is not "
                             f"valid JSON: {e}") from e
    if not isinstance(manifest, dict) \
            or manifest.get("version") != MANIFEST_VERSION \
            or not isinstance(manifest.get("tiers"), dict):
        raise ValueError(
            f"certification manifest {path!r} has unsupported layout/"
            f"version (want version {MANIFEST_VERSION} with a 'tiers' "
            f"table)")
    return manifest


def tier_ok(manifest: Optional[Dict], tier: str,
            model_config=None) -> Tuple[bool, str]:
    """Whether ``manifest`` certifies ``tier`` (optionally for
    ``model_config``'s architecture).  Returns ``(ok, reason)`` — the
    reason is what the server records and returns in the 400."""
    if tier not in TIER_MODES:
        return False, f"unknown tier {tier!r}"
    if manifest is None:
        return False, "no certification manifest"
    entry = manifest["tiers"].get(tier)
    if entry is None:
        return False, "tier not present in the certification manifest"
    if not entry.get("certified"):
        return False, (f"tier measured over bound (epe_delta "
                       f"{entry.get('epe_delta')} > bound "
                       f"{entry.get('bound')})")
    delta, bound = entry.get("epe_delta"), entry.get("bound")
    if not (isinstance(delta, (int, float)) and isinstance(bound,
                                                           (int, float))
            and delta <= bound):
        # Belt-and-braces: a hand-edited certified=true with an
        # over-bound delta must not advertise.
        return False, (f"manifest inconsistent: epe_delta {delta!r} vs "
                       f"bound {bound!r}")
    plat = manifest.get("platform")
    if plat is not None:
        import jax

        if plat != jax.default_backend():
            # "auto" backends resolve per platform: deltas measured on
            # CPU kernels say nothing about the TPU kernels /predict
            # would actually run.
            return False, (f"manifest measured on platform {plat!r}, "
                           f"serving on {jax.default_backend()!r} — "
                           f"re-certify on this platform")
    if model_config is not None:
        diff = _arch_mismatch(manifest, model_config)
        if diff:
            return False, (f"manifest certifies a different model "
                           f"architecture (mismatched: {diff})")
    return True, "certified"


def resolve_tiers(serve_cfg, model_config=None
                  ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """The server's startup gate: which requested tiers may be advertised.

    Returns ``(advertised, refused)``: ``advertised`` maps tier name ->
    precision mode (what /predict accepts and warmup compiles),
    ``refused`` maps tier name -> the human-readable reason (what the
    400 carries and /healthz reports).  ``certified`` needs no manifest —
    it IS the fp32 reference the others are certified against."""
    advertised: Dict[str, str] = {}
    refused: Dict[str, str] = {}
    if not serve_cfg.tiers:
        return advertised, refused
    manifest = None
    manifest_err = None
    if serve_cfg.cert_manifest:
        try:
            manifest = load_manifest(serve_cfg.cert_manifest)
        except (OSError, ValueError) as e:
            manifest_err = str(e)
    for tier in serve_cfg.tiers:
        if tier == "certified":
            advertised[tier] = mode_for_accuracy(tier)
            continue
        if manifest is None:
            refused[tier] = manifest_err or "no certification manifest " \
                "(--cert_manifest; python -m raftstereo_tpu.cli.certify)"
            continue
        ok, reason = tier_ok(manifest, tier, model_config)
        if ok:
            advertised[tier] = mode_for_accuracy(tier)
        else:
            refused[tier] = reason
    for tier, reason in refused.items():
        logger.warning("accuracy tier %r NOT advertised: %s", tier, reason)
    return advertised, refused


# --------------------------------------------------------------- cascades


def certify_cascades(config, variables, schedules: Sequence[str], *,
                     hw: Tuple[int, int] = (64, 96), n_pairs: int = 4,
                     seed: int = 0,
                     bounds: Optional[Dict[str, float]] = None,
                     base: Optional[Dict] = None) -> Dict:
    """Certify speculative tier-cascade schedules (serve/cascade/,
    docs/serving.md "Tier cascade") exactly like single tiers: masked
    mean-EPE delta vs the fp32 MONOLITHIC reference at EQUAL TOTAL
    iteration count, entries keyed by the canonical schedule string.

    What is measured is what serves: each schedule runs the model-level
    phase chain the engine's cascade executables trace — cheap-tier
    prologue + steps, the ``handoff_state`` cast/corr-swap, certified
    steps + epilogue — so the certificate covers the handoff itself, not
    just the tiers it connects.

    ``bounds`` maps canonical schedule string -> EPE-delta bound (px),
    defaulting to :data:`DEFAULT_CASCADE_BOUND`.  ``base`` merges the
    cascades table into an existing manifest (same architecture +
    platform required — a certificate never transfers); None builds a
    standalone manifest with an empty tiers table.
    """
    import jax
    import jax.numpy as jnp

    from ..models.raft_stereo import RAFTStereo
    from ..serve.cascade.handoff import handoff_state
    from ..serve.cascade.schedule import parse_schedule

    parsed = [parse_schedule(s) for s in schedules]
    assert parsed, "no cascade schedules to certify"
    bounds = dict(bounds or {})
    lefts, rights, gts, valid, n_valid, data_desc = _cert_data(
        config, hw, n_pairs, seed)

    def _epe(pred: np.ndarray) -> float:
        return float((np.abs(pred - gts) * valid).sum() / n_valid)

    def run_mono(mode: str, iters: int) -> np.ndarray:
        model = RAFTStereo(config_for_mode(config, mode))
        fn = jax.jit(lambda v, a, b, m=model: m.forward(
            v, a, b, iters=iters, test_mode=True)[1])
        return np.asarray(fn(variables, jnp.asarray(lefts),
                             jnp.asarray(rights)), np.float32)

    def run_cascade(s) -> np.ndarray:
        m_cheap = RAFTStereo(config_for_mode(config, s.cheap_mode))
        m_cert = RAFTStereo(config_for_mode(config, s.cert_mode))

        def fn(v, a, b):
            st = m_cheap.forward_prologue(v, a, b)
            st = m_cheap.forward_step(v, st, iters=s.cheap_iters)
            stage = m_cert.forward_prologue(v, a, b)
            st = handoff_state(st, stage)
            st = m_cert.forward_step(v, st, iters=s.cert_iters)
            return m_cert.forward_epilogue(v, st)[1]

        jitted = jax.jit(fn)
        return np.asarray(jitted(variables, jnp.asarray(lefts),
                                 jnp.asarray(rights)), np.float32)

    # One fp32 reference per distinct total iteration count (schedules
    # with different budgets certify against different references).
    refs = {total: run_mono("fp32", total)
            for total in sorted({s.total_iters for s in parsed})}
    entries: Dict[str, Dict] = {}
    for s in parsed:
        ref = refs[s.total_iters]
        epe_ref = _epe(ref)
        pred = run_cascade(s)
        epe = _epe(pred)
        delta = epe - epe_ref
        bound = float(bounds.get(s.schedule, DEFAULT_CASCADE_BOUND))
        entries[s.schedule] = {
            "cheap_mode": s.cheap_mode,
            "cert_mode": s.cert_mode,
            "total_iters": s.total_iters,
            "fp32_fraction": round(s.fp32_fraction, 6),
            "epe": round(epe, 6),
            "epe_ref": round(epe_ref, 6),
            "epe_delta": round(delta, 6),
            "bound": bound,
            "max_abs_disp_diff": round(
                float((np.abs(pred - ref) * valid).max()), 6),
            "certified": bool(delta <= bound),
        }
        logger.info(
            "certify cascade %s: epe %.4f (ref %.4f at %d iters, delta "
            "%+.4f, bound %.3f) -> %s", s, epe, epe_ref, s.total_iters,
            delta, bound,
            "CERTIFIED" if entries[s.schedule]["certified"]
            else "OVER BOUND")
    if base is not None:
        assert not _arch_mismatch(base, config), (
            "cannot merge cascade certificates into a manifest for a "
            "different model architecture")
        assert base.get("platform") == jax.default_backend(), (
            f"cannot merge cascade certificates measured on "
            f"{jax.default_backend()!r} into a manifest from "
            f"{base.get('platform')!r}")
        merged = dict(base)
        merged["cascades"] = {**base.get("cascades", {}), **entries}
        return merged
    return {
        "version": MANIFEST_VERSION,
        "created": time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime()),
        "platform": jax.default_backend(),
        "model": _arch_of(config),
        "eval": {"hw": list(hw), "n_pairs": n_pairs, "seed": seed,
                 "data": data_desc},
        "tiers": {},
        "cascades": entries,
    }


def cascade_ok(manifest: Optional[Dict], schedule: str,
               model_config=None) -> Tuple[bool, str]:
    """Whether ``manifest`` certifies cascade ``schedule`` (canonical
    string) — the cascade twin of :func:`tier_ok`, sharing its platform
    and architecture-fingerprint gates."""
    if manifest is None:
        return False, "no certification manifest"
    entry = manifest.get("cascades", {}).get(schedule)
    if entry is None:
        return False, ("cascade schedule not present in the "
                       "certification manifest (run 'python -m "
                       "raftstereo_tpu.cli.certify cascade')")
    if not entry.get("certified"):
        return False, (f"cascade measured over bound (epe_delta "
                       f"{entry.get('epe_delta')} > bound "
                       f"{entry.get('bound')})")
    delta, bound = entry.get("epe_delta"), entry.get("bound")
    if not (isinstance(delta, (int, float))
            and isinstance(bound, (int, float)) and delta <= bound):
        return False, (f"manifest inconsistent: epe_delta {delta!r} vs "
                       f"bound {bound!r}")
    plat = manifest.get("platform")
    if plat is not None:
        import jax

        if plat != jax.default_backend():
            return False, (f"manifest measured on platform {plat!r}, "
                           f"serving on {jax.default_backend()!r} — "
                           f"re-certify on this platform")
    if model_config is not None:
        diff = _arch_mismatch(manifest, model_config)
        if diff:
            return False, (f"manifest certifies a different model "
                           f"architecture (mismatched: {diff})")
    return True, "certified"


def resolve_cascades(serve_cfg, model_config=None
                     ) -> Tuple[Dict[str, object], Dict[str, str]]:
    """The startup gate for cascade schedules, mirroring
    :func:`resolve_tiers`: returns ``(advertised, refused)`` where
    ``advertised`` maps canonical schedule string -> parsed
    ``CascadeSchedule`` (what /predict accepts and warmup compiles) and
    ``refused`` maps schedule -> reason (the 400 payload and the
    /healthz report).  Unlike single tiers there is no manifest-free
    member: EVERY cascade must certify — its answer leaves fp32
    executables, but from a speculatively drafted state."""
    from ..serve.cascade.schedule import parse_schedule

    advertised: Dict[str, object] = {}
    refused: Dict[str, str] = {}
    if not getattr(serve_cfg, "cascades", ()):
        return advertised, refused
    manifest = None
    manifest_err = None
    if serve_cfg.cert_manifest:
        try:
            manifest = load_manifest(serve_cfg.cert_manifest)
        except (OSError, ValueError) as e:
            manifest_err = str(e)
    for text in serve_cfg.cascades:
        s = parse_schedule(text)  # canonical already (ServeConfig)
        if manifest is None:
            refused[s.schedule] = manifest_err or (
                "no certification manifest (--cert_manifest; python -m "
                "raftstereo_tpu.cli.certify cascade)")
            continue
        ok, reason = cascade_ok(manifest, s.schedule, model_config)
        if ok:
            advertised[s.schedule] = s
        else:
            refused[s.schedule] = reason
    for sched_str, reason in refused.items():
        logger.warning("cascade %r NOT advertised: %s", sched_str, reason)
    return advertised, refused
