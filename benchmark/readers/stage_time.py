"""Device time of one ``jax.named_scope`` stage of the served step, in ms per
whole dispatch in the trace; or, with ``"stage": "unnamed_share"``, the share
of a dispatch's busy device time that lies under no stage (staging copies
count here).  params: stage, stages (every stage name the program uses: an
operation's stage is the innermost of them on its ``op_name`` path).

Every traced run prints the stages' milliseconds, their sum, ``unnamed``, and
beside them a dispatch's busy time and the model burst's length: the sum plus
``unnamed`` must come to the busy time within 2 %.  A trace whose operations
carry no stage (a program without the scopes) gives nothing."""

from benchmark import stages as st
from benchmark.child import say


def breakdown(run, stages):
    """One reduction a run, shared by the metrics that read it."""
    key = ("stage_breakdown", tuple(stages))
    if key not in run:
        run[key] = (st.stage_breakdown(run["xplane"], stages)
                    if run.get("xplane") else None)
        bd = run[key]
        if bd:
            total = sum(bd["stage_s"].values())
            say("[stages] per dispatch over %d whole of %d model bursts: "
                % (bd["dispatches"], bd["model_bursts"])
                + ", ".join(f"{s} {v * 1e3:.2f} ms"
                            for s, v in bd["stage_s"].items())
                + f"; sum {total * 1e3:.2f} + unnamed "
                f"{bd['unnamed_s'] * 1e3:.2f} = "
                f"{(total + bd['unnamed_s']) * 1e3:.2f} ms against busy "
                f"{bd['busy_s'] * 1e3:.2f} ms "
                f"({100 * (total + bd['unnamed_s']) / bd['busy_s'] - 100:+.2f}"
                f" %), model burst {bd['burst_s'] * 1e3:.2f} ms")
            say("[stages] unnamed, by operation (ms a dispatch): "
                + ", ".join(f"{k} {v * 1e3:.3f}" for k, v in bd["unnamed_ops"]))
    return run[key]


def read(ctx, run, params):
    bd = breakdown(run, params["stages"])
    if not bd:
        return None
    if params["stage"] == "unnamed_share":
        return 100.0 * bd["unnamed_s"] / bd["busy_s"]
    return bd["stage_s"][params["stage"]] * 1e3
