"""Two-stack TRAINING parity (VERDICT r2 item 5).

Runs N identical optimization steps in both stacks — the reference torch
trainer (scripts/ref_train_probe.py: its model, sequence_loss,
AdamW+OneCycleLR+clip, train_stereo.py:162-200) and raftstereo_tpu's
train step — from the SAME random init (converted by utils/convert) on the
SAME fixed synthetic batches (no augmentation, fixed order), and compares
the loss trajectories.  This pins, end to end, the one pipeline
PARITY_CLI.md does not cover: gradients, the optimizer, the LR schedule,
and gradient clipping.

Both stacks run CPU fp32.  Divergence grows with step count — fp
reassociation amplified by the recurrent model AND the optimizer loop
(measured: by step 50 the loss trajectories decorrelate to tens of
percent while staying in the same loss regime).  To separate that
chaotic amplification from a real cross-stack bias, the harness also
runs a LYAPUNOV CONTROL: the reference against ITSELF with one weight
perturbed by 1e-6 (fp-noise scale).  The gate is then two-sided:
 * steps 1-10 (before amplification) must match tightly — this pins the
   gradients, AdamW moments, LR schedule, and clipping arithmetic;
 * the late-step cross-stack divergence must stay within a small factor
   of the control's SELF-divergence — i.e. the two stacks disagree no
   faster than the reference disagrees with a hair-flipped copy of
   itself, which is the system's intrinsic noise floor.

    python scripts/parity_train.py --workspace /tmp/ptrain --steps 50

Writes PARITY_TRAIN.md / .json at the repo root; non-zero exit on
mismatch.
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run_key(args, perturb=0.0):
    """Cache key: every parameter that changes the trajectories.  --reuse
    with a stale key re-runs instead of gating a bogus verdict."""
    key = {"steps": args.steps, "batch": args.batch,
           "height": args.height, "width": args.width,
           "train_iters": args.train_iters}
    if perturb:
        key["perturb"] = perturb
    return key


def _cache_valid(path, key):
    if not os.path.exists(path):
        return False
    with open(path) as f:
        d = json.load(f)
    cfg = d.get("run_key") or d.get("config", {})
    return all(cfg.get(k) == v for k, v in key.items())


def run_reference(args, ws, perturb=0.0):
    tag = "_pert" if perturb else ""
    ckpt = os.path.join(ws, f"init{tag}.pth")
    out = os.path.join(ws, f"ref{tag}_losses.json")
    if not (os.path.exists(ckpt) and args.reuse
            and _cache_valid(out, _run_key(args, perturb))):
        cmd = [sys.executable,
               os.path.join(REPO, "scripts", "ref_train_probe.py"),
               "--steps", str(args.steps), "--batch", str(args.batch),
               "--height", str(args.height), "--width", str(args.width),
               "--train_iters", str(args.train_iters),
               "--ckpt", ckpt, "--out", out]
        if perturb:
            cmd += ["--perturb", repr(perturb)]
        env = dict(os.environ, CUDA_VISIBLE_DEVICES="")
        subprocess.run(cmd, check=True, env=env)
    with open(out) as f:
        return ckpt, json.load(f)


def run_ours(args, ckpt, ws):
    cache = os.path.join(ws, "ours_losses.json")
    if args.reuse and _cache_valid(cache, _run_key(args)):
        with open(cache) as f:
            d = json.load(f)
        return d["losses"], d["epes"]
    losses, epes = _run_ours_impl(args, ckpt)
    with open(cache, "w") as f:
        json.dump({"losses": losses, "epes": epes,
                   "run_key": _run_key(args)}, f)
    return losses, epes


def _run_ours_impl(args, ckpt):
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.train import make_optimizer, make_train_step
    from raftstereo_tpu.train.state import state_from_variables
    from raftstereo_tpu.utils.convert import convert_checkpoint

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from ref_train_probe import synth_batches

    cfg = RAFTStereoConfig(corr_implementation="reg")  # fp32 everywhere
    tcfg = TrainConfig(batch_size=args.batch, train_iters=args.train_iters,
                       image_size=(args.height, args.width),
                       lr=2e-4, wdecay=1e-5, num_steps=1000)
    model = RAFTStereo(cfg)
    tx, sched = make_optimizer(tcfg)
    variables = convert_checkpoint(ckpt, cfg, (args.height, args.width))
    state = state_from_variables(variables, tx)
    step = jax.jit(make_train_step(model, tx, tcfg, lr_schedule=sched))

    losses, epes = [], []
    for img1, img2, disp, valid in synth_batches(
            args.steps, args.batch, args.height, args.width):
        batch = (jnp.asarray(img1), jnp.asarray(img2), jnp.asarray(disp),
                 jnp.asarray(valid))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        epes.append(float(metrics["epe"]))
        print(f"step {len(losses):3d}  loss {losses[-1]:.6f}  "
              f"epe {epes[-1]:.4f}", flush=True)
    return losses, epes


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workspace", default="/tmp/parity_train")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--height", type=int, default=96)
    p.add_argument("--width", type=int, default=160)
    p.add_argument("--train_iters", type=int, default=5)
    p.add_argument("--tol_rel_early", type=float, default=1e-3,
                   help="relative loss tolerance over the first 10 steps")
    p.add_argument("--perturb", type=float, default=1e-6,
                   help="Lyapunov-control perturbation (one weight, "
                        "fp-noise scale)")
    p.add_argument("--envelope_factor", type=float, default=5.0,
                   help="late-step gate: median cross-stack divergence of "
                        "the last 10 steps must stay within this factor "
                        "of the control's self-divergence (+1e-3 floor)")
    p.add_argument("--reuse", action="store_true",
                   help="reuse an existing reference run in the workspace")
    args = p.parse_args()
    if args.perturb <= 0:
        p.error("--perturb must be > 0: the Lyapunov control needs a "
                "nonzero perturbation (0 would collide with the reference "
                "run's cache files and degenerate the late-step gate)")

    os.makedirs(args.workspace, exist_ok=True)
    ckpt, ref = run_reference(args, args.workspace)
    _, ctl = run_reference(args, args.workspace, perturb=args.perturb)
    ours_losses, ours_epes = run_ours(args, ckpt, args.workspace)

    def rel_traj(a_seq, b_seq):
        assert len(a_seq) == len(b_seq) == args.steps, \
            (len(a_seq), len(b_seq), args.steps)
        return [abs(a - b) / max(abs(a), 1e-9)
                for a, b in zip(a_seq, b_seq)]

    d_ours = rel_traj(ref["losses"], ours_losses)
    d_ctl = rel_traj(ref["losses"], ctl["losses"])

    def median(xs):
        s = sorted(xs)
        return s[len(s) // 2]

    worst_early = max(d_ours[:10])
    med_ours = median(d_ours[-10:])
    med_ctl = median(d_ctl[-10:])
    late_bound = args.envelope_factor * med_ctl + 1e-3
    # Coarse ABSOLUTE loss-regime check alongside the relative envelope:
    # the Lyapunov control decorrelates by construction, so the envelope
    # alone could pass a grossly divergent trajectory; requiring the final
    # median losses to agree within a few x keeps that failure mode gated.
    fin_ours = median(ours_losses[-10:])
    fin_ref = median(ref["losses"][-10:])
    regime_ok = (fin_ours <= 4.0 * fin_ref + 1e-6
                 and fin_ref <= 4.0 * fin_ours + 1e-6)
    ok = (worst_early <= args.tol_rel_early and med_ours <= late_bound
          and regime_ok)

    md = ["# Two-stack training parity",
          "",
          f"{args.steps} identical AdamW+OneCycle+clip steps from the same "
          f"converted random init on the same synthetic batches "
          f"(batch {args.batch}, {args.width}x{args.height}, "
          f"{args.train_iters} GRU iters, CPU fp32 both stacks), plus a "
          f"LYAPUNOV CONTROL: the reference vs itself with one weight "
          f"perturbed by {args.perturb:g} (fp-noise scale).  The recurrent "
          f"model + optimizer loop amplify fp-reassociation noise "
          f"exponentially, so late-step trajectories decorrelate in ANY "
          f"two runs that differ by one ulp — the control measures that "
          f"intrinsic envelope, and the cross-stack gate is relative to "
          f"it.",
          "",
          "| step | reference loss | ours | rel diff | control rel diff |",
          "|---|---|---|---|---|"]
    rows = list(enumerate(zip(ref["losses"], ours_losses), 1))
    for i, (a, b) in rows[:10] + rows[10::10]:
        md.append(f"| {i} | {a:.6f} | {b:.6f} | {d_ours[i-1]:.2e} "
                  f"| {d_ctl[i-1]:.2e} |")
    md += ["",
           f"Max relative diff, steps 1-10 (pre-amplification — pins the "
           f"gradient, AdamW-moment, LR-schedule, and clipping "
           f"arithmetic): **{worst_early:.2e}** "
           f"(tolerance {args.tol_rel_early:.0e}).",
           "",
           f"Median relative diff over the last 10 steps: ours vs "
           f"reference **{med_ours:.2e}**; control (reference vs its own "
           f"{args.perturb:g}-perturbed copy) **{med_ctl:.2e}**; gate "
           f"<= {args.envelope_factor:g} x control + 1e-3 = "
           f"{late_bound:.2e}.  The two stacks diverge no faster than "
           f"the reference diverges from itself under a one-ulp-scale "
           f"change, i.e. the late-step difference is the system's "
           f"chaotic noise floor, not a cross-stack bias.",
           "",
           f"Loss-regime check (absolute backstop — the relative envelope "
           f"cannot pass a grossly divergent trajectory): median final-10 "
           f"losses ours **{fin_ours:.6f}** vs reference "
           f"**{fin_ref:.6f}**, required within 4x either way: "
           f"**{'OK' if regime_ok else 'VIOLATED'}**.",
           "",
           f"**{'PASS' if ok else 'FAIL'}** — pins gradients, optimizer "
           f"moments, LR schedule, and clipping across the two stacks "
           f"(reference loop: train_stereo.py:162-200)."]
    with open(os.path.join(REPO, "PARITY_TRAIN.md"), "w") as f:
        f.write("\n".join(md) + "\n")
    with open(os.path.join(REPO, "PARITY_TRAIN.json"), "w") as f:
        json.dump({"ref": ref["losses"], "ours": ours_losses,
                   "control": ctl["losses"], "ok": ok,
                   "worst_early": worst_early,
                   "med_last10_ours": med_ours,
                   "med_last10_control": med_ctl,
                   "late_bound": late_bound,
                   "final_loss_ours": fin_ours,
                   "final_loss_ref": fin_ref,
                   "regime_ok": regime_ok}, f, indent=1)
    print("\n".join(md))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
