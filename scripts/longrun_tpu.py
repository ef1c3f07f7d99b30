"""Long-horizon training-health demonstration on the real chip (VERDICT r4 #6).

Runs ~1.2k steps of the REAL training CLI at the reference recipe shapes
(batch 8, 320x720 crops, bf16 + remat + pallas_alt + --device_photometric,
nan_policy=abort) on learnable KITTI-layout data, with a hard kill + resume
in the middle.  This scales toward the reference's de-facto 200k-step recipe
(reference: README.md:106-110, train_stereo.py:133-212) and exercises, on
real hardware, everything the short CPU tests cannot:

* a multi-hundred-step loss/EPE curve that actually DECREASES (the data is
  learnable: scripts use data/synthetic.py::make_learnable_kitti);
* checkpoint-resume mid-run: phase A is SIGKILLed after a target step, phase
  B restarts the SAME command and must resume from the latest periodic Orbax
  checkpoint and continue step-continuously (no LR-schedule restart — the
  reference would restart its schedule, train_stereo.py:143-148);
* nan_policy stays ``abort`` — the run completing proves the finiteness
  guard never fired over the whole horizon.

Outputs:
  runs/<name>/metrics.jsonl       raw curve (appended across the resume)
  docs/longrun_r05_curve.jsonl    committed copy
  runs/<name>/summary.md          summary: curve table, resume analysis
Exit code 0 only if every health gate passes.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def train_cmd(args, data_root):
    return [
        sys.executable, "-m", "raftstereo_tpu.cli.train",
        "--name", args.name,
        "--train_datasets", "kitti",
        "--dataset_root", data_root,
        "--batch_size", str(args.batch_size),
        "--image_size", str(args.image_size[0]), str(args.image_size[1]),
        "--train_iters", str(args.train_iters),
        "--num_steps", str(args.num_steps),
        "--validation_frequency", str(args.ckpt_every),
        "--checkpoint_dir", args.checkpoint_dir,
        "--no_validation",          # no FlyingThings tree in this env
        "--num_workers", str(args.num_workers),
        "--mixed_precision", "--remat",
        "--corr_implementation", args.corr,
        "--device_photometric",
        "--nan_policy", "abort",
        # Elastic recovery: a restart resumes from the latest checkpoint
        # (or step 0) instead of failing the whole horizon.
        "--max_restarts", "3",
        "--lr", str(args.lr),
    ]


def jsonl_records(path):
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


def last_step(path):
    recs = [r for r in jsonl_records(path) if "step" in r]
    return recs[-1]["step"] if recs else 0


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--name", default="longrun_r05")
    p.add_argument("--num_steps", type=int, default=1200)
    p.add_argument("--kill_after_step", type=int, default=600,
                   help="SIGKILL phase A once the metrics log reaches this "
                        "step; phase B must resume from the last checkpoint")
    p.add_argument("--ckpt_every", type=int, default=250)
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--image_size", type=int, nargs=2, default=[320, 720])
    p.add_argument("--corr", default="pallas_alt",
                   help="corr backend (use 'auto' for a CPU smoke run)")
    p.add_argument("--train_iters", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--num_workers", type=int, default=3)
    p.add_argument("--data_root", default="/tmp/longrun_kitti")
    p.add_argument("--checkpoint_dir", default="/tmp/longrun_ckpt")
    p.add_argument("--n_images", type=int, default=48)
    p.add_argument("--fresh", action="store_true",
                   help="wipe previous run state first")
    args = p.parse_args()

    run_dir = os.path.join("runs", args.name)
    metrics = os.path.join(run_dir, "metrics.jsonl")
    if args.fresh:
        for d in (run_dir, args.checkpoint_dir, args.data_root):
            shutil.rmtree(d, ignore_errors=True)

    if not os.path.exists(args.data_root):
        from raftstereo_tpu.data.synthetic import make_learnable_kitti
        make_learnable_kitti(args.data_root, n=args.n_images)
        print(f"built learnable KITTI tree: {args.n_images} pairs at "
              f"{args.data_root}", flush=True)

    cmd = train_cmd(args, args.data_root)
    print("cmd:", " ".join(cmd), flush=True)
    # The children place their own persistent compile cache
    # (utils/platform.setup_compile_cache), so phase B resumes without
    # re-paying the train step's compile.  This parent never initialises a
    # JAX backend: the children need the chip.
    env = dict(os.environ)

    # ---- phase A: run until the log shows kill_after_step, then SIGKILL ----
    t0 = time.time()
    proc = subprocess.Popen(cmd, cwd=REPO, env=env)
    killed_at = None
    try:
        while proc.poll() is None:
            time.sleep(10)
            s = last_step(metrics)
            if s >= args.kill_after_step:
                killed_at = s
                print(f"phase A: log reached step {s} -> SIGKILL "
                      f"(simulated crash)", flush=True)
                proc.send_signal(signal.SIGKILL)
                proc.wait()
                break
    finally:
        if proc.poll() is None:
            proc.kill()
    if killed_at is None:
        print(f"FAIL: phase A exited (rc={proc.returncode}) before "
              f"step {args.kill_after_step}", flush=True)
        return 1
    phase_a_wall = time.time() - t0

    # ---- phase B: same command; must resume and complete -------------------
    t1 = time.time()
    rc = subprocess.call(cmd, cwd=REPO, env=env)
    phase_b_wall = time.time() - t1
    if rc != 0:
        print(f"FAIL: phase B exited rc={rc} (nan_policy=abort fired, or "
              "the resume path broke)", flush=True)
        return 1

    # ---- health gates ------------------------------------------------------
    recs = [r for r in jsonl_records(metrics) if "loss" in r]
    steps = [r["step"] for r in recs]
    ok = True

    # 1. Step-continuity: every 100-step report from 100..num_steps present
    #    (the resume replays [ckpt, kill] — duplicates are expected and fine).
    expected = set(range(100, args.num_steps + 1, 100))
    missing = sorted(expected - set(steps))
    if missing:
        print(f"FAIL: missing step reports {missing}", flush=True)
        ok = False

    # 2. The resume actually resumed: some step <= killed_at appears twice
    #    (once from phase A, once replayed by phase B from the checkpoint),
    #    and the earliest replayed report sits just past the checkpoint
    #    boundary phase B restarted from — a replay starting beyond
    #    boundary+100 means the resume skipped ahead of the retained
    #    checkpoint (a step-discontinuity the duplicate check alone misses).
    dup = sorted({s for s in steps if steps.count(s) > 1})
    if not dup:
        print("FAIL: no replayed step reports — phase B did not resume "
              "from a mid-run checkpoint", flush=True)
        ok = False
    else:
        boundary = (killed_at // args.ckpt_every) * args.ckpt_every
        if min(dup) > boundary + 100:
            print(f"FAIL: first replayed report {min(dup)} is past the "
                  f"checkpoint boundary {boundary}+100 (killed at "
                  f"{killed_at}, ckpt_every {args.ckpt_every}) — phase B "
                  "resumed ahead of the retained checkpoint", flush=True)
            ok = False

    # 3. Learning: mean EPE of the last three reports < half the first report
    epes = [(r["step"], r["epe"]) for r in recs if "epe" in r]
    if not epes:
        print("FAIL: no epe records in the metrics log", flush=True)
        ok = False
        first_epe = tail_epe = float("nan")
    else:
        first_epe = epes[0][1]
        tail = [e for _, e in epes[-3:]]
        tail_epe = sum(tail) / len(tail)
        if not tail_epe < 0.5 * first_epe:
            print(f"FAIL: no learning: first epe {first_epe:.3f}, "
                  f"tail mean {tail_epe:.3f}", flush=True)
            ok = False

    # 4. nan_policy=abort never fired (phase B rc==0 already implies it;
    #    double-check no skipped steps were recorded).
    skipped = sum(r.get("skipped", 0.0) for r in recs)
    if skipped:
        print(f"FAIL: {skipped} skipped steps recorded", flush=True)
        ok = False

    # ---- artifacts ---------------------------------------------------------
    os.makedirs("docs", exist_ok=True)
    shutil.copy(metrics, "docs/longrun_r05_curve.jsonl")
    lines = [
        "# Long-horizon chip training run (round 5)\n",
        "Produced by `scripts/longrun_tpu.py` on the real TPU; "
        "VERDICT r4 item 6.\n",
        f"* recipe: batch {args.batch_size}, 320x720 crops, train_iters "
        f"{args.train_iters}, bf16 + remat + pallas_alt + "
        "--device_photometric, nan_policy=abort, AdamW + OneCycle "
        f"lr {args.lr}",
        f"* data: {args.n_images} learnable KITTI-layout pairs "
        "(make_learnable_kitti) through the full KITTI adapter + "
        "sparse-augmentor + multiprocess-loader path",
        f"* horizon: {args.num_steps} steps; phase A SIGKILLed at logged "
        f"step {killed_at} ({phase_a_wall:.0f}s); phase B resumed from the "
        f"latest {args.ckpt_every}-step Orbax checkpoint and completed "
        f"({phase_b_wall:.0f}s)",
        f"* replayed (duplicate) step reports after resume: {dup} — the "
        "curve is step-continuous across the crash",
        f"* EPE: first report {first_epe:.3f} px -> last-3 mean "
        f"{tail_epe:.3f} px; skipped steps: {int(skipped)}",
        "\n## Curve (running means every 100 steps)\n",
        "| step | loss | epe | 1px | steps/sec |",
        "|---|---|---|---|---|",
    ]
    seen = set()
    for r in recs:
        if r["step"] in seen:      # keep the PHASE-A row for replayed steps
            continue
        seen.add(r["step"])
        lines.append(f"| {r['step']} | {r.get('loss', float('nan')):.4f} | "
                     f"{r.get('epe', float('nan')):.3f} | "
                     f"{r.get('1px', float('nan')):.4f} | "
                     f"{r.get('steps_per_sec', float('nan')):.3f} |")
    summary = os.path.join(run_dir, "summary.md")
    with open(summary, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"\nwrote {summary}; health: {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
