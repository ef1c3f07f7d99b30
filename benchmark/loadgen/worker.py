"""One load-generator client: a process of its own that never imports JAX.

Sixteen clients that each encode and decode twelve megabytes a request would
sit behind one interpreter lock as threads, and the cell would measure the
generator.  Each worker drives the program's own client library
(``ServeClient.predict``: wire encode, HTTP, wire decode) and times every
request on the host's clock from when it was *due*.
"""

from __future__ import annotations

import os
import time
import traceback
from typing import Dict, List, Optional

import numpy as np


def _one(client, pool, rec: Dict, hw, keep_path: Optional[str]) -> None:
    """Send one request; fill ``rec`` with what came back."""
    left, right = pool[rec["pair"]]
    rec["sent"] = time.time()
    try:
        disp, meta = client.predict(left.astype(np.float32),
                                    right.astype(np.float32))
        rec["done"] = time.time()
        rec["rid"] = meta.get("request_id")
        rec["iters"] = meta.get("iters")
        ok = disp.shape == tuple(hw) and bool(np.isfinite(disp).all())
        rec["ok"] = ok
        if not ok:
            rec["error"] = f"reply shape {disp.shape} or not finite"
        elif keep_path:
            np.save(keep_path, disp.astype(np.float32))
            rec["kept"] = keep_path
    except Exception as e:   # shed (503), timed out (504), connection lost
        rec["done"] = time.time()
        rec["ok"] = False
        rec["status"] = getattr(e, "status", None)
        rec["error"] = repr(e)[:200]


def worker_main(wid: int, p: Dict, task_q, result_q, warm_gen, warm_lo,
                warm_hi, t0) -> None:
    """``p``: host, port, hw, pool (pairs), seed, seconds, mode
    (closed|open), n_workers, keep (list of request numbers whose reply this
    worker saves, closed loop), run_dir, timeout."""
    try:
        import sys

        sys.path.insert(0, p["root"])
        from benchmark.loadgen.pairs import make_pool
        from raftstereo_tpu.serve.client import ServeClient

        hw = tuple(p["hw"])
        pool = make_pool(p["seed"], p["pool"], hw)
        client = ServeClient(p["host"], p["port"], timeout=p["timeout"])
        result_q.put(("ready", wid, None))
        # warm-up: each time the runner opens a round, the clients numbered
        # ``warm_lo`` to ``warm_hi`` - 1 send one untimed request at once
        seen = 0
        while t0.value <= 0:
            gen = warm_gen.value
            if gen == seen:
                time.sleep(0.002)
                continue
            seen = gen
            if warm_lo.value <= wid < warm_hi.value:
                rec = {"i": -1, "pair": wid % len(pool), "due": time.time()}
                _one(client, pool, rec, hw, None)
                result_q.put(("warm", wid, rec))
        start, end = t0.value, t0.value + p["seconds"]
        records: List[Dict] = []
        if p["mode"] == "closed":
            k = 0
            while True:
                now = time.time()
                if now >= end:
                    break
                # a closed-loop client's request is due when it is free
                rec = {"i": k * p["n_workers"] + wid, "worker": wid,
                       "pair": (wid + k * p["n_workers"]) % len(pool),
                       "due": max(now, start)}
                if rec["due"] > now:
                    time.sleep(rec["due"] - now)
                keep = (os.path.join(p["run_dir"], f"reply_{wid}_{k}.npy")
                        if k in p["keep"] else None)
                _one(client, pool, rec, hw, keep)
                records.append(rec)
                k += 1
        else:
            while True:
                task = task_q.get()
                if task is None:
                    break
                i, due, pair, keep = task
                rec = {"i": i, "worker": wid, "pair": pair,
                       "due": start + due}
                wait = rec["due"] - time.time()
                if wait > 0:
                    time.sleep(wait)
                _one(client, pool, rec, hw,
                     os.path.join(p["run_dir"], f"reply_{i}.npy")
                     if keep else None)
                records.append(rec)
        client.close()
        result_q.put(("done", wid, records))
    except BaseException:
        result_q.put(("crash", wid, traceback.format_exc()))
