"""The training step: loss, grads, update — compiled once, sharded over a mesh.

Replaces the reference's per-batch body (reference: train_stereo.py:162-200):
forward through DataParallel, sequence loss, AMP-scaled backward, clip, step,
scheduler step.  Here the whole thing is ONE jitted function; data parallelism
is expressed by sharding the batch over the mesh's ``data`` axis while state
stays replicated — XLA emits the gradient all-reduce (SURVEY.md §2.7).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from ..config import TrainConfig
from ..parallel import batch_sharded, replicated
from .loss import sequence_loss
from .state import TrainState

Batch = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]  # img1,img2,disp,valid


def merge_skipped_update(finite, params, old_params, opt_state, old_opt_state):
    """The ``nan_policy=skip`` merge: where ``finite`` is False, drop the bad
    update on-device — params and optimizer moments keep their old values,
    but the LR-schedule count still advances — torch semantics, where
    GradScaler skips optimizer.step() while the loop's scheduler.step() runs
    unconditionally (reference: train_stereo.py:175-180).
    """
    keep = lambda new, old: jnp.where(finite, new, old)

    def merge(new, old):
        if isinstance(new, optax.ScaleByScheduleState):
            return new                      # schedule count advances
        if hasattr(new, "_fields"):         # optax NamedTuple states
            return type(new)(*(merge(a, b) for a, b in zip(new, old)))
        if isinstance(new, (tuple, list)):
            return type(new)(merge(a, b) for a, b in zip(new, old))
        if isinstance(new, dict):
            return {k: merge(new[k], old[k]) for k in new}
        return keep(new, old)

    return (jax.tree.map(keep, params, old_params),
            merge(opt_state, old_opt_state))


def make_train_step(model, tx, cfg: TrainConfig, lr_schedule=None,
                    photometric_params: Dict = None
                    ) -> Callable[[TrainState, Batch], Tuple[TrainState, Dict]]:
    """Build the un-jitted (state, batch) -> (state, metrics) step.

    ``photometric_params``: kwargs for ``DevicePhotometric`` when
    ``cfg.device_photometric`` — pass the output of
    ``datasets.take_photometric_params(dataset)`` so the on-device chain
    mirrors the exact host distribution (the CLI does). When None, dense
    FlowAugmentor defaults modulated by cfg's saturation/gamma flags apply.
    """

    def loss_fn(params, batch_stats, img1, img2, disp_gt, valid):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        # No trace-time STEM override here any more (round 5): the fused
        # encoder's backward now consumes the forward's saved residuals
        # (pallas_encoder._stage_bwd_xla) instead of re-linearizing the
        # XLA forward, and measures >= plain under training at the
        # per-shard batches where the auto gate engages it (b1 320x720:
        # 5.806 vs 5.777 steps/sec; at the reference recipe's 16
        # images/shard the gate declines — the Pallas FORWARD loses to
        # XLA's batch-amortized blocked lowering there, 1.205 vs 1.297,
        # same crossover as inference).  The LAYER2 stage still gates off
        # under differentiation — its backward re-linearizes the XLA
        # layer2 (the pattern that was a measured training loss on the
        # stem).  config.fused_encoder=True still forces both.
        from ..ops.pallas_layer2 import override_fused_layer2
        with override_fused_layer2(False):
            preds = model.forward(variables, img1, img2,
                                  iters=cfg.train_iters)
        # ``loss`` joins the model's stage scopes (models/raft_stereo.py
        # STAGES) in a device trace; its backward inherits the name under
        # transpose(jvp(loss)).
        with jax.named_scope("loss"):
            return sequence_loss(preds, disp_gt, valid,
                                 loss_gamma=cfg.loss_gamma,
                                 max_flow=cfg.max_flow)

    if cfg.device_photometric:
        from ..data.device_aug import DevicePhotometric
        photo_kw = photometric_params
        if photo_kw is None:
            from ..data.datasets import expand_img_gamma
            photo_kw = {}
            if cfg.saturation_range is not None:
                photo_kw["saturation"] = cfg.saturation_range
            if cfg.img_gamma is not None:
                photo_kw["gamma"] = expand_img_gamma(cfg.img_gamma)
        device_photo = DevicePhotometric(**photo_kw)
        photo_key = jax.random.key(cfg.seed)
    else:
        device_photo = None

    def step(state: TrainState, batch: Batch):
        img1, img2, disp_gt, valid = batch
        if device_photo is not None:
            # Deterministic per-step randomness: fold the step counter into
            # the seed key, split per sample inside (device_aug.py).
            img1, img2 = device_photo(
                jax.random.fold_in(photo_key, state.step), img1, img2)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.batch_stats, img1, img2, disp_gt, valid)
        grad_norm = optax.global_norm(grads)
        # Failure detection (reference asserts on this, train_stereo.py:49-52).
        # A finite global norm implies every gradient entry is finite.
        finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        if cfg.nan_policy == "skip":
            params, opt_state = merge_skipped_update(
                finite, params, state.params, opt_state, state.opt_state)
        metrics = dict(metrics, loss=loss, grad_norm=grad_norm,
                       nonfinite=1.0 - finite.astype(jnp.float32))
        if lr_schedule is not None:
            metrics["lr"] = lr_schedule(state.step)
        new_state = state.replace(step=state.step + 1, params=params,
                                  opt_state=opt_state)
        return new_state, metrics

    return step


def jit_train_step(step_fn, mesh):
    """Compile the step over a mesh: state/metrics replicated, batch sharded
    on ``data``.  ``donate_argnums=0`` reuses the old state's HBM buffers.

    The mesh is also exposed to tracing via ``use_corr_mesh`` so Pallas corr
    backends partition over it (shard_map) instead of being replicated
    custom-call islands (parallel/context.py)."""
    from ..parallel.context import use_corr_mesh

    repl = replicated(mesh)
    data = batch_sharded(mesh)
    jitted = jax.jit(step_fn,
                     in_shardings=(repl, (data, data, data, data)),
                     out_shardings=(repl, repl),
                     donate_argnums=(0,))

    def call(state, batch):
        with use_corr_mesh(mesh):  # active at (first-call) trace time
            return jitted(state, batch)

    return call
