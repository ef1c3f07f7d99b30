"""What the two serve drivers share: start ``cli.serve`` on the chip with the
seeded checkpoint, bring up the client processes, run the window, and gather
spans, counters, the device trace and the sampled replies.
"""

from __future__ import annotations

import glob
import json
import multiprocessing as mp
import os
import queue
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List

import numpy as np

from benchmark import child
from benchmark.child import BenchFailure, say
from benchmark.loadgen.stats import lateness, percentile
from benchmark.weights import make_weights, write_pth

READY_TIMEOUT = 1100.0      # a first run compiles
STRAGGLER_WAIT = 60.0       # past the window's close, then a request is lost
WARM_LEAD_S = 0.03          # a warm-up round's first request ahead of the rest
CLIENT_TIMEOUT_S = 90.0     # then a client gives a request up


def _get(port: int, path: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.read()


def _post(port: int, path: str, obj: Dict) -> Dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30.0) as r:
        return json.loads(r.read())


def parse_counters(text: str) -> Dict[str, float]:
    """Prometheus text -> {sample name with labels: value}."""
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            name, _, val = ln.rpartition(" ")
            try:
                out[name] = float(val)
            except ValueError:
                pass
    return out


def counter_sum(counters: Dict[str, float], family: str) -> float:
    return sum(v for k, v in counters.items()
               if k == family or k.startswith(family + "{"))


def server_cmd(ctx, port: int, ckpt: str) -> List[str]:
    w = ctx.cell
    h, wd = w["image_hw"]
    return [sys.executable, "-m", "raftstereo_tpu.cli.serve",
            "--port", str(port), "--restore_ckpt", ckpt,
            "--buckets", f"{h}x{wd}",
            "--serve_iters", str(ctx.config["iters"]),
            "--degraded_iters", str(ctx.config["iters"]),
            "--trace_buffer", "131072",
            *ctx.config["flags"], *w.get("flags", [])]


def run_serve(ctx, mode: str, plan) -> Dict:
    """``plan(ctx, n_workers)`` gives, for ``open``, the task list
    [(i, due, pair, keep)], and for ``closed`` the per-worker ``keep``
    sets {wid: [k, ...]}."""
    w = ctx.cell
    hw = tuple(w["image_hw"])
    n_workers = int(w["clients"])
    max_batch = int(w["max_batch_size"])
    if n_workers <= max_batch:
        # a warm-up round for n rows is one client that keeps the engine
        # busy and n that send meanwhile; and a server whose batch its
        # callers cannot fill runs on padding
        raise BenchFailure(f"{n_workers} clients cannot fill and warm "
                           f"max_batch_size {max_batch}: the cell needs more "
                           "clients than rows in a batch")
    run_dir = ctx.run_dir
    ckpt = os.path.join(run_dir, "weights.pth")
    t = time.time()
    weights = make_weights(ctx.config["model"], ctx.seed)
    write_pth(weights, ckpt)
    del weights
    say(f"[setup] seeded checkpoint written in {time.time() - t:.1f}s")

    port = child.free_port()
    log_path = os.path.join(run_dir, "serve.log")
    mem_path = os.path.join(run_dir, "memstats.json")
    entries_before = child.cache_entries()
    server = child.start(server_cmd(ctx, port, ckpt), run_dir, log_path,
                         mem_path)
    mpc = mp.get_context("spawn")
    task_q, result_q = mpc.Queue(), mpc.Queue()
    warm_gen = mpc.Value("i", 0)
    warm_lo, warm_hi = mpc.Value("i", 0), mpc.Value("i", 0)
    t0 = mpc.Value("d", 0.0)
    planned = plan(ctx, n_workers)
    procs = []
    out: Dict = {"mode": mode}
    try:
        for wid in range(n_workers):
            p = {"root": child.ROOT, "host": "127.0.0.1", "port": port,
                 "hw": hw, "pool": int(w["pair_pool"]), "seed": ctx.seed,
                 "seconds": ctx.seconds, "mode": mode,
                 "n_workers": n_workers, "run_dir": run_dir,
                 "keep": planned.get(wid, []) if mode == "closed" else [],
                 "timeout": CLIENT_TIMEOUT_S}
            pr = mpc.Process(target=_worker_entry,
                             args=(wid, p, task_q, result_q, warm_gen, warm_lo,
                                   warm_hi, t0),
                             daemon=True)
            pr.start()
            procs.append(pr)

        # ---- the server: ready, and running as a chip run
        t_wait = time.time()
        while True:
            if server.poll() is not None:
                raise BenchFailure(f"cli.serve exited {server.returncode} "
                                   f"before ready:\n{child.log_tail(log_path)}")
            if time.time() - t_wait > READY_TIMEOUT:
                raise BenchFailure("cli.serve not ready after "
                                   f"{READY_TIMEOUT}s:\n"
                                   f"{child.log_tail(log_path)}")
            try:
                if json.loads(_get(port, "/healthz", 5.0)).get("ready"):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        out["ready_s"] = time.time() - ctx.t_start
        rt = child.runtime_line(log_path)
        if rt is None:
            raise BenchFailure("cli.serve logged no runtime: line")
        child.check_runtime(rt, ctx.chips, ctx.rehearse)
        out["runtime"] = rt
        health = json.loads(_get(port, "/healthz"))
        say(f"[setup] ready after {out['ready_s']:.1f}s; compiled_buckets="
            f"{health.get('compiled_buckets')}")

        # ---- the clients: every one has sent a request before the window
        # Every client sends once; then, for n = 1 .. max_batch_size, one
        # client sends a request that keeps the engine busy and n others
        # send while it runs, so that they are dispatched together.  The
        # engine stages a batch of n real rows with eager ops whose shapes
        # depend on n: each occupancy is a program of its own to compile or
        # load, and the window must meet none for the first time.
        _collect(result_q, "ready", n_workers, procs, 300.0)

        def warm_round(lo, hi):
            warm_lo.value, warm_hi.value = lo, hi
            warm_gen.value += 1

        def settle(n):
            warm = _collect(result_q, "warm", n, procs, 300.0)
            bad = [r for r in warm.values() if not r.get("ok")]
            if bad:
                raise BenchFailure(f"warm-up request failed: {bad[0]}")

        def occupancies_met():
            return {s["args"].get("batch_size") for s in json.loads(
                _get(port, "/debug/trace", 60.0))["traceEvents"]
                if s.get("name") == "dispatch"}

        warm_round(0, n_workers)
        settle(n_workers)
        wanted = list(range(1, max_batch + 1))
        for _ in range(3):          # a round whose rows split is made again
            for n in wanted:
                warm_round(0, 1)
                time.sleep(WARM_LEAD_S)
                warm_round(1, n + 1)
                settle(n + 1)
            seen = occupancies_met()
            wanted = [n for n in wanted if n not in seen]
            if not wanted:
                break
        say(f"[setup] warm-up rounds done; batch occupancies met: "
            f"{sorted(seen)}; not met: {wanted}")
        counters0 = parse_counters(_get(port, "/metrics").decode())
        compiled0 = len(json.loads(_get(port, "/healthz"))
                        .get("compiled_buckets", []))
        entries_warm = child.cache_entries()

        # ---- the window
        if mode == "open":
            for task in planned["tasks"]:
                task_q.put(task)
            for _ in range(n_workers):
                task_q.put(None)
        start = time.time() + 0.25
        t0.value = start
        out["setup_s"] = start - ctx.t_start
        out["t0"] = start
        if ctx.trace:
            lead = min(2.0, ctx.seconds / 4)
            span = max(min(float(w.get("trace_seconds", 3.0)),
                           ctx.seconds - 2 * lead), 0.5)
            time.sleep(max(start + lead - time.time(), 0))
            out["trace_started_at"] = time.time()
            info = _post(port, "/debug/profile", {"seconds": span})
            out["trace_dir"] = os.path.join(run_dir, info["log_dir"])
            say(f"[trace] device trace of {span:.1f}s started: {info}")
        done = _collect(result_q, "done", n_workers, procs,
                        ctx.seconds + STRAGGLER_WAIT + CLIENT_TIMEOUT_S)
        out["t_end"] = start + ctx.seconds
        records = sorted((r for recs in done.values() for r in recs),
                         key=lambda r: r["due"])
        out["records"] = records
        out["pairs_per_s"] = sum(
            1 for r in records if r.get("ok")
            and start <= r["done"] <= out["t_end"]) / ctx.seconds

        # ---- what the program recorded
        counters1 = parse_counters(_get(port, "/metrics").decode())
        out["counters"] = {k: counters1[k] - counters0.get(k, 0.0)
                           for k in counters1}
        compiled1 = len(json.loads(_get(port, "/healthz"))
                        .get("compiled_buckets", []))
        out["compiles_in_window"] = (
            counter_sum(out["counters"], "serve_compile_cache_misses_total")
            + (compiled1 - compiled0))
        out["cache_entries"] = (entries_before, entries_warm,
                                child.cache_entries())
        spans = json.loads(_get(port, "/debug/trace", 60.0))["traceEvents"]
        out["spans"] = [s for s in spans if s.get("ph") == "X"]
        if ctx.trace:
            t_lim = time.time() + 240.0     # the profiler writes for a while
            while json.loads(_get(port, "/debug/vars"))["profile_running"]:
                if time.time() > t_lim:
                    raise BenchFailure("the device trace never finished")
                time.sleep(0.2)
            found = glob.glob(os.path.join(out["trace_dir"], "plugins",
                                           "profile", "*", "*.xplane.pb"))
            if not found:
                raise BenchFailure("the device trace left no .xplane.pb")
            out["xplane"] = max(found, key=os.path.getmtime)
        out["memstats"] = child.read_memstats(server, mem_path)
    finally:
        for pr in procs:
            if pr.is_alive():
                pr.terminate()
        for pr in procs:
            pr.join(10.0)
        rc = child.stop(server)
        say(f"[teardown] cli.serve exited {rc}")
    out["setup_split"] = report(ctx, out)
    return out


def _worker_entry(*args):
    from benchmark.loadgen.worker import worker_main
    worker_main(*args)


def _collect(result_q, kind: str, n: int, procs, timeout: float) -> Dict:
    got: Dict = {}
    t_end = time.time() + timeout
    while len(got) < n:
        try:
            k, wid, payload = result_q.get(timeout=1.0)
        except queue.Empty:
            if time.time() > t_end:
                raise BenchFailure(f"{n - len(got)} load clients gave no "
                                   f"'{kind}' within {timeout:.0f}s")
            continue
        if k == "crash":
            raise BenchFailure(f"load client {wid} crashed:\n{payload}")
        if k == kind:
            got[wid] = payload
    return got


def pick_kept(records: List[Dict], seed: int, n: int) -> List[Dict]:
    """The sample of finished replies the reference is run over: drawn from
    the seed among those the clients kept."""
    kept = [r for r in records if r.get("kept")]
    rng = np.random.default_rng([int(seed), 0x5A3])
    idx = rng.permutation(len(kept))[:n]
    return [kept[i] for i in sorted(idx)]


def report(ctx, result: Dict) -> Dict:
    """The lines every run prints before its last: what was sent and what
    came back, compiles and cache entries, lateness, and set-up split."""
    recs = result.get("records", [])
    ok = [r for r in recs if r.get("ok")]
    shed = [r for r in recs if r.get("status") == 503]
    say(f"[requests] sent={len(recs)} answered={len(ok)} "
        f"failed={len(recs) - len(ok)} shed={len(shed)}")
    for r in [r for r in recs if not r.get("ok")][:3]:
        say(f"[requests] failed: {r.get('status')} {r.get('error')}")
    e = result.get("cache_entries")
    say(f"[cache] entries before={e[0]} after warm-up={e[1]} at end={e[2]} "
        f"(new inside the window: {e[2] - e[1]}); compiles the program "
        f"counted inside the window={result['compiles_in_window']}")
    late = lateness(recs)
    if late:
        say(f"[loadgen] lateness p50={percentile(late, 50) * 1e3:.2f}ms "
            f"p95={percentile(late, 95) * 1e3:.2f}ms "
            f"max={max(late) * 1e3:.2f}ms")
    if result["mode"] == "open" and len(recs) >= 8:
        q = len(recs) // 4
        lat = [(r["done"] - r["due"]) * 1e3 for r in recs]
        say(f"[backlog] latency p50 of the first quarter of requests "
            f"{percentile(lat[:q], 50):.0f}ms, of the last "
            f"{percentile(lat[-q:], 50):.0f}ms (a queue that grows shows "
            "here)")
    split = {"start_to_ready_s": result.get("ready_s"),
             "ready_to_first_timed_s": result["setup_s"]
             - result.get("ready_s", 0.0)}
    say(f"[setup] setup_s={result['setup_s']:.2f} = process start -> ready "
        f"{split['start_to_ready_s']:.2f} + ready -> first timed request "
        f"{split['ready_to_first_timed_s']:.2f}")
    if result.get("memstats"):
        say(f"[memory] {json.dumps(result['memstats'])}")
    result["attempted"] = len(recs)
    result["failed"] = len(recs) - len(ok)
    result.setdefault("checks", {})
    result["checks"]["compiles_in_window"] = {
        "value": result["compiles_in_window"], "limit": 0}
    # the program's counter cannot see its eager per-occupancy staging
    # programs; the cache keeps every program, so a new entry is a compile
    result["checks"]["cache_entries_new_in_window"] = {
        "value": e[2] - e[1], "limit": 0}
    never = [r for r in recs if not r.get("ok") and r.get("status") is None]
    result["checks"]["replies_lost_or_malformed"] = {
        "value": len(never), "limit": 0}
    return split


def check(ctx, result: Dict) -> Dict:
    """The reference over the sampled replies, in a child of its own (the
    chip is free: the program has exited).  Returns the numbers compared."""
    cell = ctx.cell
    samples = pick_kept(result["records"], ctx.seed,
                        int(cell["check_samples"]))
    checks: Dict[str, Dict] = {}
    if not samples:
        checks["replies_compared"] = {"value": 0, "at_least": 1}
        return checks
    job = {"model": ctx.config["model"], "seed": ctx.seed,
           "hw": cell["image_hw"], "iters": ctx.config["iters"],
           "divis_by": cell.get("divis_by", 32),
           "bucket_multiple": cell.get("bucket_multiple", 64),
           "control_dtype": ctx.config["check"]["control_dtype"],
           "samples": [{"i": r["i"], "pair": r["pair"], "reply": r["kept"]}
                       for r in samples]}
    job_path = os.path.join(ctx.run_dir, "check_job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    t = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.reference.check", job_path],
        capture_output=True, text=True, cwd=child.ROOT, timeout=900,
        env=child.child_env())
    if r.returncode != 0:
        raise BenchFailure("the reference check failed to run:\n"
                           + r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    say(f"[check] reference over {len(samples)} replies on {out['device']} "
        f"in {time.time() - t:.1f}s (its own {out['seconds']:.1f}s): "
        + ", ".join(f"#{s['i']}: served {s['rel_l1']:.5f} / control "
                    f"{s['control_rel_l1']:.5f}" for s in out["samples"]))
    checks["replies_compared"] = {"value": len(out["samples"]),
                                  "at_least": 1}
    worst = max(s["gap_over_control"] for s in out["samples"])
    checks["gap_over_control_max"] = {
        "value": worst if worst == worst else None,       # NaN fails
        "limit": ctx.config["check"]["gap_over_control_max"]}
    return checks
