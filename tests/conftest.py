"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the idiomatic JAX answer to testing multi-chip code without a pod
(SURVEY.md §4): force the host platform and fan it out into 8 XLA devices so
sharding/collective paths execute for real.

The CPU is forced twice over: the env var for the subprocesses tests start
(CLI children inherit it, which also keeps them compile-cache-free,
utils/platform.setup_compile_cache), and ``jax.config`` for this process.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def retrace_guard():
    """The runtime XLA compile-budget guard
    (raftstereo_tpu/analysis/retrace_guard.py): tests declare a budget
    with ``with retrace_guard(N, what=..., min_duration_s=...):`` and
    fail if the block compiles more executables than declared."""
    from raftstereo_tpu.analysis.retrace_guard import retrace_guard as guard

    return guard


@pytest.fixture(scope="session")
def tiny_model():
    """Small-but-real model bundle (alt corr: O(H*W) memory, exercised by the
    tiled-inference path) shared across test modules to amortize compiles."""
    from raftstereo_tpu import RAFTStereoConfig
    from raftstereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig(corr_implementation="alt", n_gru_layers=2,
                           hidden_dims=(64, 64), corr_levels=2, corr_radius=3)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(7))
    return model, variables


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "torch_parity: parity tests against the reference PyTorch code")
