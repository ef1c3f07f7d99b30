"""RAFT-Stereo, TPU-first.

Capability mirror of the reference model (reference: core/raft_stereo.py),
re-architected for XLA:

* the entire ``iters``-step GRU refinement loop is ONE ``jax.lax.scan`` —
  the whole inference compiles to a single XLA program instead of the
  reference's Python loop launching kernels per iteration
  (reference: core/raft_stereo.py:108-136)
* disparity is carried as a single channel (the reference zeroes the y-flow
  every iteration anyway: core/raft_stereo.py:120)
* GRU context biases are precomputed once before the loop
  (reference: core/raft_stereo.py:32,88)
* per-iteration coords detach == ``stop_gradient`` at the top of the scan body
  (reference: core/raft_stereo.py:109)

The class composes flax.linen submodules functionally (explicit variables
pytree) so the training step, sharding annotations, and checkpoint conversion
all see a plain dict — no lifted-transform indirection.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config import RAFTStereoConfig
from ..ops.corr import build_corr_state, corr_fn_from_state, make_corr_fn
from ..ops.image import coords_grid_x
from ..ops.upsample import convex_upsample
from .encoders import BasicEncoder, MultiBasicEncoder
from .layers import ResidualBlock, conv
from .update import BasicMultiUpdateBlock


# The ``jax.named_scope`` stages of the served step, as a device trace's
# op names carry them (the innermost one on an op's path is its stage):
# ``encoders`` (all of ``_encode``), ``corr_build`` (what is built once per
# pair), and per iteration ``lookup``, ``gru`` (motion encoder, GRU levels
# ``gru/level32|16|08``, flow head) and ``upsample`` (mask head + convex
# upsampling; inside the loop only when training).  The train step adds
# ``loss`` (train/step.py).  Scopes are metadata: the compiled program is
# the same with and without them (tests/test_trace_names.py).
STAGES = ("encoders", "corr_build", "lookup", "gru", "upsample")


class ContextZQR(nn.Module):
    """Per-level 3x3 convs producing the GRU context biases once
    (reference: core/raft_stereo.py:32).  Output channel order (cz, cr, cq)
    follows the reference's split (core/raft_stereo.py:88)."""

    config: RAFTStereoConfig
    dtype: Any = jnp.float32

    def setup(self):
        hd = self.config.hidden_dims
        self.convs = [conv(hd[i] * 3, 3, dtype=self.dtype, name=f"zqr{i}")
                      for i in range(self.config.n_gru_layers)]

    def __call__(self, inp_list):
        out = []
        for i, (x, c) in enumerate(zip(inp_list, self.convs)):
            h = self.config.hidden_dims[i]
            y = c(x)
            out.append((y[..., :h], y[..., h:2 * h], y[..., 2 * h:]))
        return out


class SLProjection(nn.Module):
    """Pattern-conditioning front for structured-light inputs
    (config.input_mode == "sl", sl/adapter.py, docs/structured_light.md):
    a learned 3x3 projection from the 12-channel stack (ambient RGB + 9
    pattern channels per side) down to the 3 channels the shared feature
    encoders were designed for.  Both images of a pair share one set of
    projection weights — the same weight-sharing contract as fnet."""

    dtype: Any = jnp.float32

    def setup(self):
        self.proj = conv(3, 3, dtype=self.dtype)

    def __call__(self, x):
        return self.proj(x)


class SharedBackboneHead(nn.Module):
    """Feature head for --shared_backbone mode: one residual block + 3x3 conv
    on the context trunk (reference: core/raft_stereo.py:34-37)."""

    dtype: Any = jnp.float32

    def setup(self):
        self.res = ResidualBlock(128, 128, "instance", 1, self.dtype)
        self.out = conv(RAFTStereo.feature_dim, 3, dtype=self.dtype)

    def __call__(self, x):
        return self.out(self.res(x))


def _level_shapes(h: int, w: int, n_levels: int) -> List[Tuple[int, int]]:
    shapes = [(h, w)]
    for _ in range(n_levels - 1):
        h, w = -(-h // 2), -(-w // 2)   # ceil halving (stride-2 k3 p1 convs)
        shapes.append((h, w))
    return shapes


class RAFTStereo:
    """Functional model bundle: submodule definitions + init/forward.

    Usage:
        model = RAFTStereo(config)
        variables = model.init(jax.random.key(0))
        preds = model.forward(variables, img1, img2, iters=16)           # train
        d_low, d_up = model.forward(variables, img1, img2, 32, test_mode=True)

    Images are NHWC, any float/int dtype, value range [0, 255].
    Disparity convention matches the reference: predictions are the x-flow
    from left to right image, i.e. NEGATIVE disparities
    (reference: core/stereo_datasets.py:77).
    """

    # Correlation feature width emitted by fnet / the shared-backbone head
    # (reference: core/extractor.py output_dim=256, core/raft_stereo.py:37).
    feature_dim = 256

    def __init__(self, config: RAFTStereoConfig):
        self.config = config
        self.dtype = (jnp.bfloat16 if config.compute_dtype == "bfloat16"
                      else jnp.float32)
        cfg = config
        self.cnet = MultiBasicEncoder(
            output_dims=(cfg.hidden_dims, cfg.hidden_dims),
            norm_fn=cfg.context_norm, downsample=cfg.n_downsample,
            dtype=self.dtype, fused_stem=cfg.fused_encoder)
        if cfg.shared_backbone:
            self.sb_head = SharedBackboneHead(dtype=self.dtype)
        else:
            self.fnet = BasicEncoder(output_dim=self.feature_dim, norm_fn="instance",
                                     downsample=cfg.n_downsample, dtype=self.dtype,
                                     fused_stem=cfg.fused_encoder)
        self.zqr = ContextZQR(cfg, dtype=self.dtype)
        self.update = BasicMultiUpdateBlock(cfg, dtype=self.dtype)
        # Structured-light front (docs/structured_light.md).  Constructed
        # ONLY in sl mode: the passive path must stay bitwise-identical to
        # pre-SL builds — no extra module, no extra params, no code-path
        # change in _encode (tests/test_sl.py asserts this).
        if cfg.input_mode == "sl":
            self.sl_proj = SLProjection(dtype=self.dtype)

    # ------------------------------------------------------------------ init

    def init(self, rng: jax.Array, image_hw: Tuple[int, int] = (64, 96)) -> Dict:
        cfg = self.config
        h, w = image_hw
        f = cfg.factor
        h0, w0 = h // f, w // f
        lvl = _level_shapes(h0, w0, cfg.n_gru_layers)
        # Passive keeps its historical 4-way split untouched (bitwise-stable
        # init); sl adds a fifth key for the projection front.
        n_keys = 5 if cfg.input_mode == "sl" else 4
        k = jax.random.split(rng, n_keys)
        img = jnp.zeros((1, h, w, 3), jnp.float32)

        variables: Dict[str, Dict] = {"params": {}, "batch_stats": {}}

        def absorb(name, v):
            variables["params"][name] = v["params"]
            if "batch_stats" in v:
                variables["batch_stats"][name] = v["batch_stats"]

        if cfg.input_mode == "sl":
            # The projection maps 12 -> 3 channels, so the encoders below
            # init against the same 3-channel dummy as passive.
            absorb("sl_proj", self.sl_proj.init(
                k[4], jnp.zeros((1, h, w, cfg.input_channels), jnp.float32)))

        if cfg.shared_backbone:
            v = self.cnet.init(k[0], jnp.concatenate([img, img], 0),
                               dual_inp=True, num_layers=cfg.n_gru_layers)
            absorb("cnet", v)
            absorb("fnet", self.sb_head.init(
                k[1], jnp.zeros((2, h0, w0, 128), jnp.float32)))
        else:
            absorb("cnet", self.cnet.init(k[0], img,
                                          num_layers=cfg.n_gru_layers))
            absorb("fnet", self.fnet.init(k[1], img))

        inp_dummy = [jnp.zeros((1, lh, lw, cfg.hidden_dims[i]), jnp.float32)
                     for i, (lh, lw) in enumerate(lvl)]
        absorb("zqr", self.zqr.init(k[2], inp_dummy))

        net_dummy = list(inp_dummy)
        zqr_dummy = [(x, x, x) for x in inp_dummy]
        corr_dummy = jnp.zeros((1, h0, w0, cfg.cor_planes), jnp.float32)
        flow_dummy = jnp.zeros((1, h0, w0, 2), jnp.float32)
        absorb("update", self.update.init(k[3], net_dummy, zqr_dummy,
                                          corr_dummy, flow_dummy))
        if not variables["batch_stats"]:
            del variables["batch_stats"]
        return variables

    # --------------------------------------------------------------- forward

    def _split_vars(self, variables, name):
        out = {"params": variables["params"][name]}
        bs = variables.get("batch_stats", {})
        if name in bs:
            out["batch_stats"] = bs[name]
        return out

    def _encode(self, variables: Dict, image1: jax.Array,
                image2: jax.Array):
        """Encoder phase shared by ``forward`` and ``forward_prologue``:
        normalization, context/feature encoders and the precomputed GRU
        context biases (reference: core/raft_stereo.py:77-88).  All of it
        is the ``encoders`` stage of a device trace (STAGES)."""
        with jax.named_scope("encoders"):
            return self._encode_stage(variables, image1, image2)

    def _encode_stage(self, variables: Dict, image1: jax.Array,
                      image2: jax.Array):
        cfg = self.config
        dtype = self.dtype
        b = image1.shape[0]

        img1 = (2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
        img2 = (2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)

        if cfg.input_mode == "sl":
            # 12-channel SL stacks (sl/adapter.py scales the binary pattern
            # masks to [0, 255] so the shared normalization above needs no
            # special case) projected to the encoders' 3-channel input.
            sl_vars = self._split_vars(variables, "sl_proj")
            img1 = self.sl_proj.apply(sl_vars, img1)
            img2 = self.sl_proj.apply(sl_vars, img2)

        if cfg.shared_backbone:
            outputs, trunk = self.cnet.apply(
                self._split_vars(variables, "cnet"),
                jnp.concatenate([img1, img2], 0), dual_inp=True,
                num_layers=cfg.n_gru_layers)
            fmaps = self.sb_head.apply(self._split_vars(variables, "fnet"), trunk)
        else:
            outputs = self.cnet.apply(self._split_vars(variables, "cnet"),
                                      img1, num_layers=cfg.n_gru_layers)
            fmaps = self.fnet.apply(self._split_vars(variables, "fnet"),
                                    jnp.concatenate([img1, img2], 0))
        fmap1, fmap2 = fmaps[:b], fmaps[b:]

        net_list = [jnp.tanh(o[0]) for o in outputs]
        inp_list = [nn.relu(o[1]) for o in outputs]
        zqr_list = self.zqr.apply(self._split_vars(variables, "zqr"), inp_list)
        return net_list, zqr_list, fmap1, fmap2

    def _corr_setup(self, update_vars: Dict, test_mode: bool):
        """Static correlation-lookup policy shared by the monolithic and
        phase-split forwards: the volume dtype, the int8-quant gate,
        whether the motion encoder's convc1 is fused into the lookup
        kernel (and its parameters), and the lane-friendly channel pad."""
        cfg = self.config
        corr_dtype = (jnp.bfloat16 if cfg.corr_dtype == "bfloat16"
                      else jnp.float32)
        # Int8-quantized volume (ops/quant.py): inference-only — the int8
        # rounding defines no useful gradient, so train-mode traces always
        # build the unquantized volume regardless of the config flag.
        quant = bool(cfg.corr_quant) and test_mode
        # Test mode fuses the motion encoder's convc1 (1x1, cor_planes->64)
        # into the lookup kernel as a relu epilogue: the separate conv
        # re-read the correlation features at 75 GB/s (60 us/iter, round-5
        # trace).  Training keeps the module conv — the fused path defines
        # no VJP (gradients flow through convc1 the ordinary way).
        from ..ops.corr import corr_epilogue_active
        # bf16 compute only: the in-kernel bf16 dot reproduces the module
        # conv BIT-EXACTLY (measured: max |disp| diff 0.0 over a 32-iter
        # forward), while fp32's module conv runs at flax default precision
        # — a different rounding than any Mosaic-loweable policy — and fp32
        # is the certified-parity path, which must keep one numeric form.
        use_epi = (test_mode and self.dtype == jnp.bfloat16
                   and corr_epilogue_active(cfg.corr_implementation, quant))
        epi = (update_vars["params"]["encoder"]["convc1"] if use_epi
               else None)
        # out_channels: the pallas_alt backend zero-pads the correlation
        # features to a lane-multiple-friendly width in-kernel (36 lanes
        # made the motion encoder's 1x1 conv fusion memory-bound); the
        # motion encoder's padded conv accepts either width.
        return corr_dtype, use_epi, epi, -(-cfg.cor_planes // 64) * 64, quant

    def _step_body(self, update_vars: Dict, zqr_list, corr_fn, grid,
                   test_mode: bool, use_epi: bool):
        """The per-iteration refinement body, identical between the
        monolithic ``forward`` scan and the scheduler's single-iteration
        step executable (``forward_step``) — sharing the code is what
        makes the two paths bitwise-comparable."""
        cfg = self.config
        dtype = self.dtype
        sf = cfg.slow_fast_gru
        n = cfg.n_gru_layers

        def step(carry, _):
            nets, d = carry
            d = jax.lax.stop_gradient(d)
            with jax.named_scope("lookup"):
                corr = corr_fn(grid + d)  # already emitted in model dtype
            flow = jnp.concatenate([d, jnp.zeros_like(d)], axis=-1).astype(dtype)

            with jax.named_scope("gru"):
                if n == 3 and sf:
                    nets = self.update.apply(update_vars, nets, zqr_list,
                                             iter2=True, iter1=False,
                                             iter0=False, update=False)
                if n >= 2 and sf:
                    nets = self.update.apply(update_vars, nets, zqr_list,
                                             iter2=(n == 3), iter1=True,
                                             iter0=False, update=False)
                # Test mode skips the mask head inside the loop: only the
                # final mask is consumed and it depends only on net[0], so
                # it is computed ONCE after the scan (measured ~0.18
                # ms/iter saved at flagship shapes: the 128->256 conv, the
                # 1x1 head, the f32 cast, and the carry's HBM round trip).
                # In train mode the mask head runs in here, under its own
                # inner ``upsample`` scope (models/update.py).
                nets, mask, delta = self.update.apply(
                    update_vars, nets, zqr_list, corr, flow,
                    iter2=(n == 3), iter1=(n >= 2), with_mask=not test_mode,
                    corr_preact=use_epi)

            d = d + delta[..., :1].astype(jnp.float32)
            if test_mode:
                return (tuple(nets), d), None
            with jax.named_scope("upsample"):
                up = convex_upsample(d, mask.astype(jnp.float32), cfg.factor)
            return (tuple(nets), d), up

        return step

    def forward(self, variables: Dict, image1: jax.Array, image2: jax.Array,
                iters: int = 12, flow_init: Optional[jax.Array] = None,
                test_mode: bool = False):
        cfg = self.config
        b = image1.shape[0]

        net_list, zqr_list, fmap1, fmap2 = self._encode(variables, image1,
                                                        image2)
        update_vars = self._split_vars(variables, "update")
        corr_dtype, use_epi, epi, out_channels, quant = self._corr_setup(
            update_vars, test_mode)
        with jax.named_scope("corr_build"):
            corr_fn = make_corr_fn(cfg.corr_implementation, fmap1, fmap2,
                                   cfg.corr_levels, cfg.corr_radius,
                                   dtype=corr_dtype,
                                   precision=cfg.corr_precision,
                                   out_dtype=self.dtype,
                                   out_channels=out_channels,
                                   epilogue=epi, quant=quant)

        h0, w0 = net_list[0].shape[1:3]
        grid = coords_grid_x(b, h0, w0)
        disp = jnp.zeros((b, h0, w0, 1), jnp.float32)
        if flow_init is not None:
            disp = disp + flow_init.astype(jnp.float32)

        step = self._step_body(update_vars, zqr_list, corr_fn, grid,
                               test_mode, use_epi)
        body = jax.checkpoint(step) if cfg.remat else step
        (nets, disp), ys = jax.lax.scan(
            body, (tuple(net_list), disp), None, length=iters)
        if test_mode:
            with jax.named_scope("upsample"):
                mask = self.update.apply(update_vars, nets[0],
                                         method="upsample_mask")
                disp_up = convex_upsample(disp, mask.astype(jnp.float32),
                                          cfg.factor)
            return disp, disp_up
        return ys  # (iters, B, H*f, W*f, 1)

    # ------------------------------------------------- phase-split forward
    #
    # The same test-mode computation as ``forward``, split into three
    # separately-compilable phases so a scheduler can advance a running
    # batch one iteration at a time and let requests join/leave at
    # iteration boundaries (serve/sched/, docs/serving.md):
    #
    #   state = forward_prologue(v, i1, i2, flow_init)   # encode + corr
    #   state = forward_step(v, state, iters=k)          # k GRU iterations
    #   low, up = forward_epilogue(v, state)             # mask + upsample
    #
    # ``prologue -> step x (N/k) -> epilogue`` is bitwise-identical to
    # ``forward(iters=N, test_mode=True)`` at the same batch shape: the
    # scan body is the SAME function (``_step_body``), the correlation
    # state is built by the same ops (ops/corr.build_corr_state), and the
    # epilogue repeats the post-scan code (asserted in tests/test_sched.py).

    def forward_prologue(self, variables: Dict, image1: jax.Array,
                         image2: jax.Array,
                         flow_init: Optional[jax.Array] = None) -> Dict:
        """Encode + correlation build + initial refinement state.

        Returns the carried state: a dict pytree whose leaves all keep the
        batch as their leading axis (so a scheduler can merge per-slot
        state across requests with a (B,)-mask select).  ``flow_init`` is
        a (B, H/factor, W/factor, 1) warm-start disparity; None and zeros
        produce bitwise-identical results (same property as
        ``jitted_infer_init``), so one prologue executable serves cold
        requests and warm stream frames alike."""
        cfg = self.config
        net_list, zqr_list, fmap1, fmap2 = self._encode(variables, image1,
                                                        image2)
        corr_dtype, _, _, _, quant = self._corr_setup(
            self._split_vars(variables, "update"), test_mode=True)
        with jax.named_scope("corr_build"):
            corr_state = build_corr_state(cfg.corr_implementation, fmap1,
                                          fmap2, cfg.corr_levels,
                                          dtype=corr_dtype,
                                          precision=cfg.corr_precision,
                                          quant=quant)
        b, h0, w0 = net_list[0].shape[:3]
        disp = jnp.zeros((b, h0, w0, 1), jnp.float32)
        if flow_init is not None:
            disp = disp + flow_init.astype(jnp.float32)
        return {"nets": tuple(net_list),
                "zqr": tuple(tuple(z) for z in zqr_list),
                "corr": tuple(corr_state),
                "disp": disp}

    def forward_step(self, variables: Dict, state: Dict,
                     iters: int = 1) -> Dict:
        """Advance the carried state by ``iters`` GRU iterations (the
        scheduler's single-iteration step executable; test-mode only)."""
        cfg = self.config
        update_vars = self._split_vars(variables, "update")
        _, use_epi, epi, out_channels, quant = self._corr_setup(
            update_vars, test_mode=True)
        with jax.named_scope("corr_build"):
            corr_fn = corr_fn_from_state(cfg.corr_implementation,
                                         state["corr"], cfg.corr_levels,
                                         cfg.corr_radius,
                                         precision=cfg.corr_precision,
                                         out_dtype=self.dtype,
                                         out_channels=out_channels,
                                         epilogue=epi, quant=quant,
                                         feature_dtype=self.dtype)
        disp = state["disp"]
        b, h0, w0 = disp.shape[:3]
        grid = coords_grid_x(b, h0, w0)
        step = self._step_body(update_vars, state["zqr"], corr_fn, grid,
                               test_mode=True, use_epi=use_epi)
        (nets, disp), _ = jax.lax.scan(step, (tuple(state["nets"]), disp),
                                       None, length=iters)
        return dict(state, nets=tuple(nets), disp=disp)

    def forward_epilogue(self, variables: Dict, state: Dict):
        """Final mask head + convex upsampling: ``(disp_low, disp_up)`` —
        the same post-scan code as the monolithic test-mode ``forward``."""
        update_vars = self._split_vars(variables, "update")
        with jax.named_scope("upsample"):
            mask = self.update.apply(update_vars, state["nets"][0],
                                     method="upsample_mask")
            disp_up = convex_upsample(state["disp"],
                                      mask.astype(jnp.float32),
                                      self.config.factor)
        return state["disp"], disp_up

    # ------------------------------------------------------------- interface

    def jitted_infer(self, iters: int = 32):
        """Compiled test-mode forward: (variables, img1, img2) -> (low, up)."""
        return jax.jit(
            lambda v, i1, i2: self.forward(v, i1, i2, iters=iters,
                                           test_mode=True))

    def jitted_infer_init(self, iters: int = 32):
        """Compiled warm-start test-mode forward:
        (variables, img1, img2, flow_init) -> (low, up).

        ``flow_init`` is a (B, H/factor, W/factor, 1) disparity field added
        to the zero initialization, so passing zeros reproduces the plain
        ``jitted_infer`` bitwise (tested) — one executable serves both the
        cold and warm frames of a stream (the serving engine's warm-start
        compile cache wraps this, serve/engine.py)."""
        return jax.jit(
            lambda v, i1, i2, f: self.forward(v, i1, i2, iters=iters,
                                              flow_init=f, test_mode=True))


def count_parameters(variables: Dict) -> int:
    """Total trainable parameter count (reference: evaluate_stereo.py:15-16)."""
    return sum(x.size for x in jax.tree.leaves(variables["params"]))
