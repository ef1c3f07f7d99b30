"""Names on the device (ISSUE 26 part A): the ``jax.named_scope`` stages of
the served step reach the optimised HLO's ``op_name`` (which a profiler trace
stores with every operation), every ``pallas_call`` under ``ops/`` carries a
``name=``, and the scopes are metadata — the compiled program has the same
fusions and custom calls with and without them."""

import ast
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
from raftstereo_tpu.models import RAFTStereo
from raftstereo_tpu.models.raft_stereo import STAGES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)
HW = (32, 64)


def _pallas_sites():
    """(file, line, name= or None) of every ``pallas_call(`` under ops/."""
    sites = []
    for path in sorted(glob.glob(os.path.join(REPO, "raftstereo_tpu", "ops",
                                              "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = next((kw.value.value for kw in node.keywords
                             if kw.arg == "name"
                             and isinstance(kw.value, ast.Constant)), None)
                sites.append((os.path.basename(path), node.lineno, name))
    return sites


SITES = _pallas_sites()


class TestPallasNames:
    def test_every_site_was_found(self):
        # the same count `grep -rn "pallas_call(" raftstereo_tpu/ops` gives
        n = 0
        for path in glob.glob(os.path.join(REPO, "raftstereo_tpu", "ops",
                                           "*.py")):
            with open(path) as f:
                n += f.read().count("pallas_call(")
        assert len(SITES) == n >= 17

    @pytest.mark.parametrize(
        "site", SITES, ids=[f"{f}:{n or line}" for f, line, n in SITES])
    def test_site_is_named(self, site):
        fname, line, name = site
        assert name, f"{fname}:{line}: pallas_call without name="
        # <module>_<what>: a kernel is found in a trace by its name
        assert re.fullmatch(r"[a-z][a-z0-9]*(_[a-z0-9]+)+", name), name

    def test_names_are_unique(self):
        names = [n for _, _, n in SITES]
        assert len(set(names)) == len(names), sorted(names)


def _stage_counts(hlo_text):
    out = dict.fromkeys(STAGES + ("loss",), 0)
    for op_name in re.findall(r'op_name="([^"]+)"', hlo_text):
        for part in reversed(op_name.split("/")):
            part = re.sub(r"^(?:\w+\()+|\)+$", "", part)  # jvp(x) -> x
            if part in out:
                out[part] += 1
                break
    return out


def _program_shape(hlo_text):
    """What a change of the compiled program would move: its fusions and
    custom calls."""
    return (len(re.findall(r" fusion\(", hlo_text)),
            len(re.findall(r" custom-call\(", hlo_text)))


@pytest.fixture(scope="module")
def tiny():
    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), HW)
    img = jnp.zeros((1, *HW, 3), jnp.float32)
    return model, variables, img


def _compiled_forward(model, variables, img):
    fn = jax.jit(lambda v, a, b: model.forward(v, a, b, iters=2,
                                               test_mode=True))
    return fn.lower(variables, img, img).compile().as_text()


@pytest.fixture(scope="module")
def forward_hlo(tiny):
    return _compiled_forward(*tiny)


class TestStageScopes:
    def test_served_forward_carries_every_stage(self, forward_hlo):
        counts = _stage_counts(forward_hlo)
        for stage in STAGES:
            assert counts[stage] > 0, counts
        # one inner scope per GRU level, inside the gru stage
        for level in ("level16", "level08"):
            assert re.search(rf'op_name="[^"]*/gru/[^"]*{level}/',
                             forward_hlo), level

    def test_phase_split_step_carries_the_loop_stages(self, tiny):
        model, variables, img = tiny
        state = jax.eval_shape(
            lambda v, a, b: model.forward_prologue(v, a, b), variables, img,
            img)
        text = jax.jit(lambda v, s: model.forward_step(v, s, iters=1)) \
            .lower(variables, state).compile().as_text()
        counts = _stage_counts(text)
        assert counts["lookup"] > 0 and counts["gru"] > 0, counts
        assert counts["encoders"] == 0 and counts["upsample"] == 0, counts

    def test_train_step_carries_loss_and_upsample_in_the_loop(self, tiny):
        """Lowered, not compiled (the backward of even the tiny model is a
        long CPU compile): the scope is on the operations the compiler is
        handed, forward and transposed."""
        from raftstereo_tpu.train.loss import sequence_loss

        model, variables, img = tiny

        def loss_fn(params):
            preds = model.forward({**variables, "params": params}, img, img,
                                  iters=2)
            with jax.named_scope("loss"):
                return sequence_loss(preds, jnp.zeros((1, *HW, 1)),
                                     jnp.ones((1, *HW)))[0]

        text = jax.jit(jax.grad(loss_fn)).lower(variables["params"]) \
            .as_text(debug_info=True)
        # under differentiation a scope reads jvp(loss) going forward and
        # transpose(jvp(loss)) coming back: the backward inherits the name
        assert re.search(r'loc\("[^"]*/jvp\(loss\)/', text)
        assert re.search(r'loc\("[^"]*/transpose\(jvp\(loss\)\)/', text)
        # training runs the mask head inside the update block, in the loop
        assert re.search(r'loc\("[^"]*gru\)*/[^"]*upsample\)*/', text)

    def test_train_step_source_scopes_the_loss(self):
        import inspect

        from raftstereo_tpu.train import step

        assert 'jax.named_scope("loss")' in inspect.getsource(step)
        assert TrainConfig  # the step's config type is importable here

    def test_scopes_do_not_change_the_compiled_program(self, tiny,
                                                       forward_hlo,
                                                       monkeypatch):
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = _compiled_forward(*tiny)
        assert not any(_stage_counts(bare).values())
        assert _program_shape(bare) == _program_shape(forward_hlo)
