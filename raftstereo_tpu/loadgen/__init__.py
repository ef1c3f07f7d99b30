"""Trace-driven SLO harness + capacity model (docs/slo_harness.md).

The bridge from "pairs/s on this box" to "N chips serve M users at
SLO", in four pieces:

* ``trace``    — versioned JSONL trace grammar (bursty arrivals,
                 session create/churn/close, tiers, priorities,
                 deadlines, iteration targets, resolution mix, spatial
                 pairs) + seeded deterministic generators
                 (poisson/burst/diurnal).
* ``replay``   — open-loop replay engine: drives a real server or the
                 ``cli.router`` cluster through ``ServeClient`` on the
                 trace's schedule (late sends counted, never silently
                 rescheduled), one ``records.RequestRow`` per request.
* ``slo``      — SLO spec + assertion report: per-(tier, priority)
                 p50/p99, shed/deadline-hit/cold-frame rates,
                 validator-clean ``/metrics`` deltas, retrace budget —
                 one machine-readable JSON verdict.
* ``capacity`` — requests/s/chip as f(tier, iters, resolution), fit
                 from a replay; feeds ``ops/autoscale.Autoscaler`` and
                 answers what-ifs via ``cli.loadgen``.

``records`` (the row store) and ``capacity`` are stdlib-only — they
are imported by client tooling and the model-free router's autoscaler.
"""

import importlib

# Lazy (PEP 562) exports, same contract as raftstereo_tpu.serve:
# importing the package must stay cheap — ``records``/``capacity`` are
# stdlib, but ``replay`` pulls ServeClient (numpy + the serve package)
# which the router-side capacity consumer has no use for.
_EXPORTS = {
    "Recorder": ".records",
    "RequestRow": ".records",
    "summarize": ".records",
    "TraceEvent": ".trace",
    "TraceSpec": ".trace",
    "generate": ".trace",
    "read_trace": ".trace",
    "write_trace": ".trace",
    # The replay() FUNCTION is deliberately NOT exported: it shares its
    # name with the submodule, and `from raftstereo_tpu.loadgen import
    # replay` would resolve to the function or the module depending on
    # import order.  Call sites import it from the submodule:
    # `from raftstereo_tpu.loadgen.replay import replay`.
    "ReplayConfig": ".replay",
    "pair_provider": ".replay",
    "SLOClass": ".slo",
    "SLOSpec": ".slo",
    "evaluate": ".slo",
    "fit": ".capacity",
    "load_model": ".capacity",
    "save_model": ".capacity",
    "sustainable_rps": ".capacity",
    "whatif": ".capacity",
    "LoadgenMetrics": ".metrics",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        rel = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    value = getattr(importlib.import_module(rel, __name__), name)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
