"""chip_smoke.py has no CPU fallback: off the chip it exits non-zero, says
why, and prints no result line (the no-fallback rule of PR 24)."""

import ast
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0, r.stdout
    assert "platform is 'cpu', not 'tpu'" in r.stdout, r.stdout + r.stderr
    assert '"ok"' not in r.stdout


_CACHE_PROBE = (
    "import jax\n"
    "from jax._src import xla_bridge\n"
    "from raftstereo_tpu.utils.platform import setup_compile_cache\n"
    "print(repr(setup_compile_cache()))\n"
    "print(repr(jax.config.jax_compilation_cache_dir))\n"
    "assert not xla_bridge._backends, 'initialised a backend'\n")


def _cache_probe(**env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                       env={**base, "PYTHONPATH": REPO, **env},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    returned, configured = r.stdout.strip().splitlines()[-2:]
    return ast.literal_eval(returned), ast.literal_eval(configured)


def test_compile_cache_is_placed_from_outside_or_under_the_checkout():
    """One helper, three cases (utils/platform.setup_compile_cache): a
    directory given from outside is left alone, a process pinned to the CPU
    gets none, anything else caches under <checkout>/.jax_cache — and the
    helper never initialises a backend (chip_smoke's parent calls it)."""
    assert _cache_probe(JAX_PLATFORMS="tpu",
                        JAX_COMPILATION_CACHE_DIR="/given/dir") == (
        "/given/dir", "/given/dir")
    assert _cache_probe(JAX_PLATFORMS="cpu") == (None, None)
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_probe(JAX_PLATFORMS="tpu") == (want, want)
    assert _cache_probe() == (want, want)


def test_runtime_line_has_what_the_benchmark_reads_and_no_gru_backend(capsys):
    """The ``runtime:`` line is an interface: ``benchmark/child.py``'s
    ``check_runtime`` and ``benchmark/run.py`` index it by name, and
    ``chip_smoke.py`` prints every gate.  One GRU step means no
    ``gru_backend`` entry to report."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import child
    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.utils.platform import describe_runtime

    rt = describe_runtime(RAFTStereoConfig(compute_dtype="bfloat16"), 8,
                          (544, 960))
    assert set(rt) == {
        "platform", "device_kind", "device_count", "corr", "corr_auto",
        "corr_matmul", "fused_stem_cnet", "fused_stem_fnet",
        "pallas_interpret", "compile_cache"}
    child.check_runtime(rt, 1, rehearse=True)  # indexes its keys by name
    assert "platform is 'cpu', not 'tpu'" in capsys.readouterr().err
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        gates, = (fn for fn in ast.parse(f.read()).body
                  if getattr(fn, "name", None) == "check_runtime")
    printed = {node.slice.value for node in ast.walk(gates)
               if isinstance(node, ast.Subscript)
               and getattr(node.value, "id", None) == "rt"}
    assert printed and printed <= set(rt)
