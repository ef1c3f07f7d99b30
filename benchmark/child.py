"""Starting, reading and stopping the one child that holds the chip.

The runner itself never initialises a JAX backend (a parent that has touched
JAX holds the chip and the child then fails or hangs), so every device fact
comes from a child: a throw-away probe before the run, the ``runtime:`` line
the program's own entry point logs, and the memory statistics the injected
hook (``inject/sitecustomize.py``) writes when asked.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INJECT = os.path.join(ROOT, "benchmark", "inject")


REHEARSE = False    # set by run.py --rehearse: CPU children keep no compile cache


class BenchFailure(Exception):
    """The run cannot give a result; the runner exits non-zero without one."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cache_dir() -> str:
    """Where the machine says, else a fixed path inside the checkout (the
    path is part of the cache's key, so it never moves)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def cache_entries() -> int:
    d = cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def child_env(memstats_path: Optional[str] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [INJECT, ROOT] + [p for p in env.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    if REHEARSE:
        # executables read back from a CPU cache crashed the program (PR 2)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.setdefault("JAX_PLATFORMS", "cpu")
    else:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
        # every program, however quick its compile: a start is hundreds
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["TPU_LOG_DIR"] = "disabled"
    if memstats_path:
        env["BENCH_MEMSTATS_PATH"] = memstats_path
    return env


def probe_device() -> Dict:
    """What JAX finds, asked of a child that has exited (and released the
    chip) before anything else starts."""
    code = ("import json, jax; d = jax.devices(); print(json.dumps({"
            "'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=child_env())
    if r.returncode != 0:
        raise BenchFailure("JAX found no usable device:\n" + r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start(cmd: List[str], cwd: str, log_path: str,
          memstats_path: Optional[str] = None) -> subprocess.Popen:
    say("[child] " + " ".join(cmd[1:]))
    with open(log_path, "w") as log:
        return subprocess.Popen(cmd, cwd=cwd, env=child_env(memstats_path),
                                stdout=log, stderr=subprocess.STDOUT,
                                start_new_session=True)


def stop(proc: subprocess.Popen, grace: float = 30.0) -> int:
    """SIGTERM, then SIGKILL to the whole group, and wait until it is gone."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)     # loader workers, stragglers
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()
    return proc.returncode


def log_tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def runtime_line(log_path: str) -> Optional[Dict]:
    """The ``runtime: {...}`` line the program logs at start-up
    (``utils/platform.describe_runtime``)."""
    with open(log_path, errors="replace") as f:
        for line in f:
            _, sep, rest = line.partition("] runtime: ")
            if sep:
                return json.loads(rest)
    return None


def check_runtime(rt: Dict, chips: int, rehearse: bool) -> None:
    say(f"[runtime] {json.dumps(rt)}")
    bad = []
    if rt["platform"] != "tpu":
        bad.append(f"platform is {rt['platform']!r}, not 'tpu'")
    if rt["pallas_interpret"]:
        bad.append("Pallas kernels are interpreted")
    if rt["corr"] != "pallas_alt":
        bad.append(f"corr resolved to {rt['corr']!r}, not 'pallas_alt'")
    if rt["device_count"] < chips:
        bad.append(f"{rt['device_count']} devices, the cell needs {chips}")
    if bad and not rehearse:
        raise BenchFailure("the program did not run as a chip run: "
                           + "; ".join(bad))
    for b in bad:
        say(f"[rehearse] a measured run would fail here: {b}")


def read_memstats(proc: subprocess.Popen, path: str,
                  wait_s: float = 20.0) -> Optional[Dict]:
    """Ask the child's injected hook for ``device.memory_stats()``."""
    if os.path.exists(path):
        os.remove(path)
    proc.send_signal(signal.SIGUSR1)
    t_end = time.time() + wait_s
    while time.time() < t_end:
        if os.path.exists(path):
            try:
                with open(path) as f:
                    return json.load(f)
            except ValueError:
                pass                       # still being written
        time.sleep(0.05)
    return None
