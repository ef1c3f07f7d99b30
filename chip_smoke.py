#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Default run (one chip), two phases, one process on the chip at a time:

* serve — ``python -m raftstereo_tpu.cli.serve`` with random weights, the
  default three-level hidden-128 model, ``--buckets 540x960``, bf16 compute;
  waits for readiness on ``/healthz``, sends a few ``/predict`` requests at
  540x960 through the repo's own client, checks every reply, reads
  ``/metrics``, stops the server with SIGTERM.
* train — a synthetic KITTI-layout tree from ``--seed``, then
  ``python -m raftstereo_tpu.cli.train --mixed_precision --remat`` at 320x720,
  16 iterations, for a few steps; checks the losses and the final checkpoint.

``--chips 4`` runs only the data-parallel ``cli.train`` over four devices and
the one-device run it is compared with.

This process never initialises a JAX backend: every device fact it prints
comes from the ``runtime:`` line the phase's own process logged.  It exits
non-zero, and prints no result line, unless every phase ran on a TPU with
compiled (not interpreted) kernels.  The last line of stdout is then
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The batch the README's KITTI recipe (8) was cut to: the b8 train step at
# 320x720 / 16 iters / bf16 / --remat needs 16.17 GiB of temporaries on a
# described v5e (PR 24 rehearsal), b4 needs 8.98 GiB.
TRAIN_BATCH = 4
# One device against four, same weights, same batches, bf16 compute: the
# first loss is one forward pass and differs only by summation order; later
# losses follow updates that differ by as much, which the unrolled GRU
# amplifies (1.7 % by step 4 at toy size on CPU devices).
BF16_RTOL_FIRST, BF16_RTOL_LATER = 2e-2, 1e-1
REQUESTS, STEPS = 5, 4
PHASE_TIMEOUT = 900.0  # seconds a phase may take, its compiles included


class SmokeFailure(Exception):
    pass


# --rehearse only: device checks that would have failed the run, collected
# so the rest of the script still gets exercised off the chip.
_rehearsal_failures = None


def device_check_failed(msg: str) -> None:
    if _rehearsal_failures is None:
        raise SmokeFailure(msg)
    say(f"[rehearse] would fail here: {msg}")
    _rehearsal_failures.append(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def runtime_line(log_path: str) -> dict:
    """The ``runtime: {...}`` line the phase's process logged at start-up
    (serve/server.build_server, cli/train.train)."""
    with open(log_path, errors="replace") as f:
        for line in f:
            _, sep, rest = line.partition("] runtime: ")
            if sep:
                return json.loads(rest)
    raise SmokeFailure(f"no 'runtime:' line in {log_path}")


def log_tail(log_path: str, n: int = 40) -> str:
    with open(log_path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def check_runtime(phase: str, rt: dict, count: int) -> None:
    say(f"[{phase}] device: platform={rt['platform']} "
        f"kind={rt['device_kind']} count={rt['device_count']}")
    say(f"[{phase}] gates: corr={rt['corr']} auto->{rt['corr_auto']} "
        f"corr_matmul={rt['corr_matmul']} "
        f"fused_stem(cnet)={rt['fused_stem_cnet']} "
        f"fused_stem(fnet)={rt['fused_stem_fnet']} "
        f"pallas_interpret={rt['pallas_interpret']} "
        f"compile_cache={rt['compile_cache']}")
    if rt["platform"] != "tpu":
        device_check_failed(
            f"{phase}: platform is {rt['platform']!r}, not 'tpu' — "
            "chip_smoke needs the chip and has no CPU fallback")
    if rt["pallas_interpret"]:
        device_check_failed(
            f"{phase}: Pallas kernels are in interpret mode")
    if rt["corr_auto"] != "pallas_alt" or rt["corr"] != "pallas_alt":
        device_check_failed(f"{phase}: corr 'auto' resolved to a CPU branch "
                            f"({rt['corr']!r})")
    if rt["corr_matmul"] != "bf16_exact_1+3":
        # both phases run --mixed_precision with float32 corr operands
        device_check_failed(f"{phase}: the lookup's matmul resolved to "
                            f"{rt['corr_matmul']!r}, not the exact bf16 form")
    if rt["device_count"] != count:
        device_check_failed(f"{phase}: {rt['device_count']} devices, "
                            f"expected {count}")


def check_no_fallback_warning(phase: str, log_path: str) -> None:
    with open(log_path, errors="replace") as f:
        bad = [ln.rstrip() for ln in f if "RuntimeWarning" in ln]
    if bad:
        raise SmokeFailure(f"{phase}: the process warned that it fell back: "
                           + " | ".join(bad[:3]))


_cache_seen = set()


def report_cache(when: str, cache_dir) -> None:
    """How many programs the persistent compile cache holds and which ones
    are new since the last report — a second run that adds none compiled
    nothing the cache could have held."""
    names = (set(os.listdir(cache_dir))
             if cache_dir and os.path.isdir(cache_dir) else set())
    new = collections.Counter(
        n.rsplit("-", 2)[0] for n in names - _cache_seen)
    _cache_seen.update(names)
    say(f"[cache] {when}: dir={cache_dir} entries={len(names)} "
        f"new={sum(new.values())} largest groups={new.most_common(6)}")


# --------------------------------------------------------------------- probe

def probe_platform() -> None:
    """Ask a throw-away child what JAX finds, so a machine without a chip
    fails in seconds and before any phase starts.  The child has exited —
    and released the chip — before the first phase starts."""
    code = ("import json, jax; d = jax.devices(); "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=child_env(), timeout=300)
    if r.returncode != 0:
        raise SmokeFailure("JAX found no usable device:\n" + r.stderr[-2000:])
    dev = json.loads(r.stdout.strip().splitlines()[-1])
    say(f"[probe] device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        device_check_failed(
            f"platform is {dev['platform']!r}, not 'tpu' — chip_smoke needs "
            "the chip and has no CPU fallback")


# --------------------------------------------------------------------- serve

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop(proc: subprocess.Popen, grace: float = 60.0) -> int:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    return proc.returncode


def serve_phase(args, out: str) -> dict:
    import numpy as np

    from raftstereo_tpu.serve.client import ServeClient  # no backend init

    h, w = args.serve_size
    port = free_port()
    log_path = os.path.join(out, "serve.log")
    cmd = [sys.executable, "-m", "raftstereo_tpu.cli.serve",
           "--port", str(port), "--buckets", f"{h}x{w}",
           "--max_batch_size", "1",
           # equal levels: one program per bucket (config.py clamps
           # degraded_iters to iters)
           "--serve_iters", str(args.serve_iters),
           "--degraded_iters", str(args.serve_iters),
           "--mixed_precision", "--corr_implementation", "auto"]
    say("[serve] cmd: " + " ".join(cmd[1:]))
    t_start = time.time()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=child_env(),
                                stdout=log, stderr=subprocess.STDOUT)
    client = None
    try:
        client = ServeClient("127.0.0.1", port, timeout=120.0)
        deadline = t_start + PHASE_TIMEOUT
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"serve: server exited {proc.returncode} before it was "
                    f"ready:\n{log_tail(log_path)}")
            if time.time() > deadline:
                raise SmokeFailure(
                    f"serve: not ready after {PHASE_TIMEOUT}s:\n"
                    f"{log_tail(log_path)}")
            try:
                health = client.healthz()
                if health.get("ready"):
                    break
            except (OSError, ConnectionError):
                client.close()
            time.sleep(1.0)
        ready_s = time.time() - t_start
        rt = runtime_line(log_path)
        check_runtime("serve", rt, 1)
        say(f"[serve] compiled_buckets={health['compiled_buckets']}")

        rng = np.random.default_rng(args.seed)
        pairs = [(rng.integers(0, 255, (h, w, 3)).astype(np.float32),
                  rng.integers(0, 255, (h, w, 3)).astype(np.float32))
                 for _ in range(2)]
        lat, outs = [], []
        for i in range(REQUESTS):
            left, right = pairs[i % 2]
            t0 = time.perf_counter()
            disp, meta = client.predict(left, right)
            lat.append(time.perf_counter() - t0)
            if disp.shape != (h, w):
                raise SmokeFailure(f"serve: reply {i} has shape "
                                   f"{disp.shape}, expected {(h, w)}")
            if not np.isfinite(disp).all():
                raise SmokeFailure(f"serve: reply {i} is not finite")
            outs.append(disp)
        # The same pair through the same executable answers bitwise the
        # same; two different pairs do not answer the same.
        for i in range(2, len(outs)):
            if not np.array_equal(outs[i], outs[i - 2]):
                raise SmokeFailure(f"serve: replies {i - 2} and {i} to the "
                                   "same pair differ")
        if len(outs) > 1 and np.array_equal(outs[0], outs[1]):
            raise SmokeFailure("serve: two different pairs got one answer")
        metrics = client.metrics_text()
        counted = [ln for ln in metrics.splitlines() if ln.startswith(
            'serve_requests_total{endpoint="predict",outcome="ok"}')]
        if not counted or float(counted[0].split()[-1]) != REQUESTS:
            raise SmokeFailure(f"serve: /metrics counts {counted}, expected "
                               f"{REQUESTS} ok /predict requests")
        vars_ = client.debug_vars()
        n_compiled = len(client.healthz()["compiled_buckets"])
        if n_compiled != len(health["compiled_buckets"]):
            raise SmokeFailure("serve: a request compiled a new program "
                               f"({n_compiled} after warm-up's "
                               f"{len(health['compiled_buckets'])})")
        if vars_["build"].get("jax_backend") != rt["platform"]:
            raise SmokeFailure("serve: /debug/vars and the runtime line "
                               "disagree on the backend")
    finally:
        if client is not None:
            client.close()
        rc = stop(proc)
    if rc not in (0, -signal.SIGTERM):
        raise SmokeFailure(f"serve: server exited {rc} on SIGTERM:\n"
                           f"{log_tail(log_path)}")
    check_no_fallback_warning("serve", log_path)
    with open(log_path, errors="replace") as f:
        compile_s = [float(ln.rsplit("compiled in ", 1)[1].rstrip("s\n"))
                     for ln in f if "warmup:" in ln and "compiled in " in ln]
    n_metric_lines = sum(1 for ln in metrics.splitlines()
                         if ln and not ln.startswith("#"))
    say(f"[serve] {len(outs)} replies of {h}x{w}, finite, deterministic; "
        f"disparity mean |d|={float(np.abs(outs[0]).mean()):.3f}; "
        f"/metrics has {n_metric_lines} samples; server-side p50 "
        f"(/debug/vars)={(vars_['latency'] or {}).get('p50_ms')} ms")
    say(f"[serve] seconds: start-to-ready={ready_s:.1f} "
        f"warmup-compile={compile_s} "
        f"request-steady(min/median)={min(lat):.3f}/"
        f"{sorted(lat)[len(lat) // 2]:.3f} first={lat[0]:.3f}")
    return rt


# --------------------------------------------------------------------- train

def train_phase(args, out: str, tag: str, extra=(), count: int = 1) -> dict:
    """One ``cli.train`` run; returns its runtime line plus the losses."""
    data_root = os.path.join(out, "kitti")
    if not os.path.exists(data_root):
        from raftstereo_tpu.data.synthetic import make_learnable_kitti

        import numpy as np

        make_learnable_kitti(data_root, n=4 * args.batch,
                             hw=(96, 160) if args.rehearse else (352, 744),
                             rng=np.random.default_rng(args.seed))
    run_dir = os.path.join(out, tag)
    os.makedirs(run_dir, exist_ok=True)
    log_path = os.path.join(out, f"{tag}.log")
    metrics_path = os.path.join(run_dir, "runs", tag, "metrics.jsonl")
    ckpt_dir = os.path.join(run_dir, "ckpt")
    cmd = [sys.executable, "-m", "raftstereo_tpu.cli.train",
           "--name", tag, "--train_datasets", "kitti",
           "--dataset_root", data_root,
           "--batch_size", str(args.batch),
           "--image_size", str(args.train_size[0]), str(args.train_size[1]),
           "--train_iters", str(args.train_iters),
           "--num_steps", str(STEPS - 1),
           "--validation_frequency", "100000",
           "--checkpoint_dir", ckpt_dir,
           "--no_validation", "--num_workers", "0",
           "--mixed_precision", "--remat",
           "--corr_implementation", "auto",
           "--nan_policy", "abort", "--seed", str(args.seed), *extra]
    say(f"[{tag}] cmd: " + " ".join(cmd[1:]))
    t_start = time.time()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=run_dir, env=child_env(),
                                stdout=log, stderr=subprocess.STDOUT)
    # The trainer flushes one live_loss record per finished step
    # (train/logger.py); when each appears is the step's wall time.
    seen, step_at = 0, []
    try:
        while proc.poll() is None:
            if time.time() - t_start > PHASE_TIMEOUT:
                raise SmokeFailure(f"{tag}: still running after "
                                   f"{PHASE_TIMEOUT}s:\n"
                                   f"{log_tail(log_path)}")
            time.sleep(0.05)
            if os.path.exists(metrics_path):
                with open(metrics_path) as f:
                    n = sum(1 for ln in f if "live_loss" in ln)
                step_at += [time.time()] * (n - seen)
                seen = n
    finally:
        rc = stop(proc)
    if rc != 0:
        raise SmokeFailure(f"{tag}: trainer exited {rc}:\n"
                           f"{log_tail(log_path)}")
    rt = runtime_line(log_path)
    check_runtime(tag, rt, count)
    check_no_fallback_warning(tag, log_path)
    with open(metrics_path) as f:
        recs = [json.loads(ln) for ln in f if "live_loss" in ln]
    losses = [r["live_loss"] for r in recs]
    if len(losses) < STEPS:
        raise SmokeFailure(f"{tag}: {len(losses)} steps logged, "
                           f"expected {STEPS}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{tag}: non-finite loss in {losses}")
    final = os.path.join(ckpt_dir, tag, f"{tag}-final")
    if not os.path.isdir(final) or not os.listdir(final):
        raise SmokeFailure(f"{tag}: no final checkpoint at {final}")
    shutil.rmtree(ckpt_dir)  # checked; too large to bring back from the chip
    say(f"[{tag}] batch={args.batch} size={args.train_size} "
        f"steps={len(losses)} losses={losses}")
    say(f"[{tag}] batch rows by device: {rt['batch_rows_by_device']}")
    if len(step_at) >= 2:
        gaps = [b - a for a, b in zip(step_at, step_at[1:])]
        say(f"[{tag}] seconds: start-to-first-step(compile included)="
            f"{step_at[0] - t_start:.1f} "
            f"step-steady(min/median)={min(gaps):.3f}/"
            f"{sorted(gaps)[len(gaps) // 2]:.3f} total="
            f"{time.time() - t_start:.1f}")
    rt["losses"] = losses
    return rt


def four_chip_phase(args, out: str) -> dict:
    """Data-parallel cli.train over the default mesh of all four devices
    against the same steps and global batch on a one-device mesh."""
    rt4 = train_phase(args, out, "train_dp4", count=4)
    rt1 = train_phase(args, out, "train_dp1", extra=("--data_parallel", "1"),
                      count=4)
    holders = {d for d, rows in rt4["batch_rows_by_device"].items()
               if rows[1] > rows[0]}
    say(f"[4chip] losses dp4={rt4['losses']}")
    say(f"[4chip] losses dp1={rt1['losses']}")
    say(f"[4chip] batch shards on devices {sorted(holders)} (dp4) vs "
        f"{sorted(rt1['batch_rows_by_device'])} (dp1)")
    if len(holders) != 4:
        raise SmokeFailure(f"4chip: batch shards sit on {sorted(holders)}, "
                           "not on four devices")
    for i, (a, b) in enumerate(zip(rt4["losses"], rt1["losses"])):
        rtol = BF16_RTOL_LATER if i else BF16_RTOL_FIRST
        if abs(a - b) > rtol * max(abs(a), abs(b)):
            raise SmokeFailure(
                f"4chip: step {i + 1} loss {a} (4 devices) vs {b} "
                f"(1 device) disagree beyond rtol {rtol}")
    say(f"[4chip] losses agree within rtol {BF16_RTOL_FIRST} (first step) "
        f"/ {BF16_RTOL_LATER} (later steps)")
    return rt4


# ---------------------------------------------------------------------- main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=[1, 4], default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                 "chip_smoke"))
    p.add_argument("--rehearse", action="store_true",
                   help="debug the script off the chip: tiny sizes, phases "
                        "run wherever JAX lands; still exits non-zero")
    args = p.parse_args(argv)
    args.serve_size, args.serve_iters = (540, 960), 32
    args.train_size, args.train_iters, args.batch = (320, 720), 16, TRAIN_BATCH
    if args.rehearse:
        args.serve_size, args.serve_iters = (64, 96), 2
        args.train_size, args.train_iters = (64, 96), 2

    if args.rehearse:
        global _rehearsal_failures
        _rehearsal_failures = []
    t0 = time.time()
    out = os.path.join(args.out, f"run_{int(t0)}_{os.getpid()}")
    os.makedirs(out)
    sys.path.insert(0, HERE)
    try:
        probe_platform()
        try:
            from raftstereo_tpu.utils.platform import setup_compile_cache
        except ImportError as e:
            raise SmokeFailure(f"the repo is not beside chip_smoke.py: {e}")

        # Children place their own cache the same way; the parent only
        # needs the directory, to count what each phase added to it.
        cache_dir = setup_compile_cache()
        report_cache("at start", cache_dir)
        if args.chips == 4:
            rt = four_chip_phase(args, out)
        else:
            rt = serve_phase(args, out)
            report_cache("after serve", cache_dir)
            rt_train = train_phase(args, out, "train")
            if rt_train["device_kind"] != rt["device_kind"]:
                raise SmokeFailure("the two phases saw different devices")
        report_cache("at end", cache_dir)
        shutil.rmtree(os.path.join(out, "kitti"), ignore_errors=True)
    except SmokeFailure as e:
        say(f"chip_smoke FAILED after {time.time() - t0:.0f}s: {e}")
        return 1
    say(f"[done] {time.time() - t0:.0f}s, logs under {out}")
    if _rehearsal_failures:
        say(f"chip_smoke FAILED (rehearsal): {_rehearsal_failures[0]}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": rt["platform"], "kind": rt["device_kind"],
        "count": rt["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
