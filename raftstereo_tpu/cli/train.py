"""Training entry point (reference: train_stereo.py:133-258).

    python -m raftstereo_tpu.cli.train --name raft-stereo --batch_size 8 \
        --train_datasets sceneflow --num_steps 200000 --mixed_precision

Differences from the reference by design (SURVEY.md §5, §7):

* data parallelism = batch sharding over a ``jax.sharding`` mesh; XLA emits
  the gradient all-reduce over ICI/DCN (vs ``nn.DataParallel``)
* checkpoints are full train state via Orbax (params + opt state + step), so
  ``--restore_ckpt``-less restarts resume exactly where they stopped instead
  of restarting the LR schedule; ``--restore_ckpt`` additionally accepts
  reference ``.pth`` files (converted on load) for fine-tuning
* the whole step (fwd + loss + bwd + clip + update) is one jitted program
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import logging
import os
import statistics
import sys
import time

import numpy as np

from ..config import TrainConfig, add_model_args, model_config_from_args
from ..data.datasets import (build_aug_params, fetch_dataset,
                             take_photometric_params)
from ..data.loader import DataLoader, prefetch_to_device
from ..eval import validate_things
from ..eval.validate import validate_sl
from ..models import RAFTStereo
from ..models.raft_stereo import count_parameters
from ..parallel import batch_sharded, make_mesh, replicated
from ..parallel.context import use_corr_mesh
from ..train.checkpoint import CheckpointManager, PreemptionGuard, save_weights
from ..train.logger import Logger
from ..train.optim import make_optimizer
from ..train.state import create_train_state, state_from_variables
from ..train.step import jit_train_step, make_train_step
from ..utils.faults import FaultPlan
from .common import load_variables, setup_logging

logger = logging.getLogger(__name__)


def add_train_args(p: argparse.ArgumentParser) -> None:
    g = p.add_argument_group("training")
    g.add_argument("--name", default="raft-stereo")
    g.add_argument("--workload", choices=["passive", "sl"],
                   default="passive",
                   help="training workload: passive stereo (the default "
                        "pipeline, unchanged) or structured light — "
                        "pattern-conditioned 12-channel inputs with the "
                        "masked sequence loss over the valid-modulation "
                        "region (requires --input_mode sl; "
                        "docs/structured_light.md)")
    g.add_argument("--restore_ckpt", default=None,
                   help=".pth or Orbax weights to start from")
    g.add_argument("--batch_size", type=int, default=6)
    g.add_argument("--train_datasets", nargs="+", default=["sceneflow"])
    g.add_argument("--lr", type=float, default=2e-4)
    g.add_argument("--num_steps", type=int, default=100000)
    g.add_argument("--image_size", type=int, nargs=2, default=[320, 720])
    g.add_argument("--train_iters", type=int, default=16)
    g.add_argument("--valid_iters", type=int, default=32)
    g.add_argument("--wdecay", type=float, default=1e-5)
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--validation_frequency", type=int, default=10000)
    g.add_argument("--checkpoint_dir", default="checkpoints")
    g.add_argument("--dataset_root", default=None)
    g.add_argument("--data_parallel", type=int, default=None,
                   help="devices on the data mesh axis (default: all)")
    g.add_argument("--num_workers", type=int, default=None)
    g.add_argument("--no_validation", action="store_true",
                   help="skip the periodic FlyingThings validation")
    g.add_argument("--profile_steps", type=int, nargs=2, default=None,
                   metavar=("START", "STOP"),
                   help="capture an XLA profiler trace of steps [START, STOP)"
                        " into runs/<name>/profile (view in TensorBoard)")
    g.add_argument("--metrics_port", type=int, default=None,
                   help="serve train telemetry over HTTP on this port "
                        "(/metrics Prometheus scrape, /debug/trace span "
                        "export, /debug/threads, /debug/vars) so long runs "
                        "are observable without the JSONL file; 0 binds an "
                        "ephemeral port (docs/observability.md)")
    g.add_argument("--metrics_host", default="127.0.0.1",
                   help="interface the telemetry exporter binds; the "
                        "default stays loopback-only because /debug/threads "
                        "and /debug/vars expose stacks and resolved paths "
                        "— set 0.0.0.0 deliberately for a remote scraper")
    g.add_argument("--nan_policy", choices=["abort", "skip"], default="abort",
                   help="non-finite loss/grad: abort (reference assert "
                        "semantics) or skip the update and continue")
    g.add_argument("--max_restarts", type=int, default=0,
                   help="auto-restart the loop from the latest checkpoint "
                        "after a crash (elastic recovery); only restarts "
                        "without step progress count against the budget")
    g.add_argument("--restart_backoff", type=float, default=1.0,
                   help="base seconds between restarts (doubles per "
                        "consecutive no-progress restart, capped at 60)")
    g.add_argument("--sample_retries", type=int, default=2,
                   help="per-sample load retries (with backoff) before an "
                        "index is quarantined and resampled")
    g.add_argument("--quarantine_limit", type=int, default=64,
                   help="max persistently-bad dataset indices to quarantine "
                        "before the loader declares the dataset broken")
    g.add_argument("--loader_timeout_s", type=float, default=300.0,
                   help="seconds to wait for a worker batch before the "
                        "worker pool is recycled (0 disables)")
    g.add_argument("--watchdog_factor", type=float, default=10.0,
                   help="flag steps slower than this multiple of the "
                        "running median step time (0 disables)")
    g.add_argument("--faults", default=None,
                   help="deterministic fault-injection plan (chaos testing; "
                        "see utils/faults.py), e.g. 'crash@step=7,"
                        "corrupt@sample=3'; defaults to $RAFTSTEREO_FAULTS")
    a = p.add_argument_group("augmentation (reference: train_stereo.py:244-248)")
    a.add_argument("--img_gamma", type=float, nargs="+", default=None,
                   help="gamma range: GMIN GMAX [GAIN_MIN GAIN_MAX] "
                        "(reference: train_stereo.py:244)")
    a.add_argument("--saturation_range", type=float, nargs=2, default=None)
    a.add_argument("--do_flip", choices=["h", "v"], default=None)
    a.add_argument("--spatial_scale", type=float, nargs=2, default=[0.0, 0.0])
    a.add_argument("--noyjitter", action="store_true")
    a.add_argument("--device_photometric", action="store_true",
                   help="run the photometric chain (jitter + eraser) "
                        "on-device inside the jitted train step instead of "
                        "in host workers — for CPU-starved hosts "
                        "(data/device_aug.py)")


def train_config_from_args(args: argparse.Namespace) -> TrainConfig:
    return TrainConfig(
        name=args.name, batch_size=args.batch_size,
        train_datasets=tuple(args.train_datasets), lr=args.lr,
        num_steps=args.num_steps, image_size=tuple(args.image_size),
        train_iters=args.train_iters, valid_iters=args.valid_iters,
        wdecay=args.wdecay, seed=args.seed,
        validation_frequency=args.validation_frequency,
        checkpoint_dir=args.checkpoint_dir, restore_ckpt=args.restore_ckpt,
        img_gamma=args.img_gamma, saturation_range=args.saturation_range,
        do_flip=args.do_flip, spatial_scale=tuple(args.spatial_scale),
        noyjitter=args.noyjitter, data_parallel=args.data_parallel,
        nan_policy=args.nan_policy, max_restarts=args.max_restarts,
        restart_backoff=args.restart_backoff,
        sample_retries=args.sample_retries,
        quarantine_limit=args.quarantine_limit,
        loader_timeout_s=args.loader_timeout_s,
        watchdog_factor=args.watchdog_factor,
        device_photometric=args.device_photometric)


def train(model_cfg, cfg: TrainConfig, dataset=None,
          num_workers=None, no_validation: bool = False,
          dataset_root=None, profile_steps=None,
          fault_plan=None, metrics_port=None,
          metrics_host="127.0.0.1",
          workload: str = "passive") -> "TrainState":  # noqa: F821
    """The training loop; returns the final state.  ``dataset`` injection
    lets tests run the full loop on synthetic data; ``fault_plan``
    (default: the ``RAFTSTEREO_FAULTS`` env var) injects deterministic
    failures for chaos testing (utils/faults.py).  ``metrics_port`` mounts
    the opt-in telemetry exporter (obs/, docs/observability.md).
    ``workload`` selects the data/validation recipe: "passive" (default,
    unchanged) or "sl" — structured-light training with the modulation
    gate folded into the loss's ``valid`` mask (docs/structured_light.md);
    the loss itself is the standard masked sequence loss either way."""
    import jax

    from ..obs import Tracer, TelemetryServer
    from ..train.telemetry import TrainMetrics

    if workload not in ("passive", "sl"):
        raise ValueError(f"unknown workload {workload!r}")
    if (workload == "sl") != (model_cfg.input_mode == "sl"):
        # A passive model cannot consume 12-channel SL stacks and an SL
        # model cannot consume RGB pairs — catching it here beats a shape
        # error three layers down in the first jitted step.
        raise ValueError(
            f"workload {workload!r} requires a matching model input mode, "
            f"got input_mode={model_cfg.input_mode!r} (pass --workload sl "
            f"together with --input_mode sl)")

    np.random.seed(cfg.seed)
    plan = FaultPlan.from_env() if fault_plan is None else fault_plan
    guard = PreemptionGuard().install()

    # Always-on phase tracing (bounded ring, microseconds per span) +
    # the metrics bundle; the HTTP exporter mounts later, once setup has
    # validated (starting it here would leak the socket when e.g. the
    # batch-size/mesh check below raises before the loop's finally).
    tracer = Tracer(capacity=4096)
    tmetrics = TrainMetrics()
    run_trace = tracer.new_trace_id()
    telemetry = None

    model = RAFTStereo(model_cfg)
    tx, schedule = make_optimizer(cfg)
    mesh = make_mesh(data=cfg.data_parallel)
    n_data = mesh.shape["data"]
    if cfg.batch_size % n_data:
        raise ValueError(f"batch_size {cfg.batch_size} not divisible by "
                         f"{n_data} data-parallel devices")
    logger.info("Mesh: %s", dict(mesh.shape))
    from ..utils.platform import describe_runtime

    # Which devices, what every backend-keyed kernel gate resolves to in
    # THIS process under the mesh the step traces with, and which device
    # holds which rows of the batch (chip_smoke.py holds a chip run to
    # this line).
    with use_corr_mesh(mesh):
        runtime = describe_runtime(model_cfg, cfg.batch_size, cfg.image_size)
    runtime["batch_rows_by_device"] = {
        str(d.id): [idx[0].start or 0, idx[0].stop or cfg.batch_size]
        for d, idx in batch_sharded(mesh).devices_indices_map(
            (cfg.batch_size,)).items()}
    logger.info("runtime: %s", json.dumps(runtime))

    ckpt_dir = os.path.join(cfg.checkpoint_dir, cfg.name)
    manager = CheckpointManager(ckpt_dir, keep=cfg.keep_checkpoints,
                                fault_plan=plan)

    def init_state():
        """Latest VALID checkpoint > --restore_ckpt weights > fresh init.
        Also the recovery path after a crash (--max_restarts); a corrupt
        latest step falls back to older retained steps instead of
        re-restoring the same broken step forever."""
        state = create_train_state(model, jax.random.key(cfg.seed), tx,
                                   image_hw=cfg.image_size)
        if manager.latest_step() is not None:
            restored, step = manager.restore_latest_valid(state)
            if restored is not None:
                # Rebuild the restored leaves as device arrays that OWN
                # their buffers (host round-trip + explicit placement on
                # the mesh): orbax-restored arrays can alias restore-path
                # memory, and the train step DONATES its input state — on
                # this container donating them into a compile-cache
                # deserialized executable is a use-after-free crash.
                restored = jax.device_put(
                    jax.tree.map(np.asarray, restored), replicated(mesh))
                if step != manager.latest_step():
                    logger.error(
                        "latest checkpoint (step %d) is corrupt; resumed "
                        "from retained step %d instead — up to %d steps of "
                        "work will be recomputed",
                        manager.latest_step(), step,
                        manager.latest_step() - step)
                state = restored
                logger.info("Resumed from step %d in %s", int(state.step),
                            ckpt_dir)
                return state
            logger.error("every retained checkpoint in %s is corrupt — "
                         "falling back to %s", ckpt_dir,
                         cfg.restore_ckpt or "a fresh init")
        if cfg.restore_ckpt:
            variables = load_variables(cfg.restore_ckpt, model_cfg, model)
            state = state_from_variables(variables, tx)
            logger.info("Initialised weights from %s", cfg.restore_ckpt)
        return state

    state = init_state()
    logger.info("The model has %.2fM learnable parameters.",
                count_parameters({"params": state.params}) / 1e6)

    if dataset is None:
        if workload == "sl":
            # SL trains from the capture-tree reader + the train view that
            # stacks pattern channels and folds the modulation gate into
            # ``valid`` (sl/adapter.py).  No photometric augmentation by
            # design: it would decorrelate the ambient images from the
            # pattern masks the projector physically produced.
            if not dataset_root:
                raise ValueError(
                    "--workload sl needs --dataset_root pointing at an SL "
                    "capture tree (data/sl.py layout; "
                    "sl.make_learnable_sl writes a synthetic one)")
            from ..data.sl import StructuredLightDataset
            from ..sl import SLTrainView
            dataset = SLTrainView(
                StructuredLightDataset(dataset_root, split="training",
                                       scale=1.0, with_depth=True),
                crop_size=cfg.image_size)
        else:
            aug = build_aug_params(cfg.image_size, cfg.spatial_scale,
                                   cfg.noyjitter, cfg.saturation_range,
                                   cfg.img_gamma, cfg.do_flip)
            roots = ({k: dataset_root for k in
                      ("sceneflow", "kitti", "middlebury", "sintel",
                       "falling_things", "tartanair", "sl")}
                     if dataset_root else None)
            dataset = fetch_dataset(cfg.train_datasets, aug, roots)
    photometric_params = None
    if cfg.device_photometric:
        # Disables host jitter+eraser on EVERY leaf (including
        # caller-supplied datasets — otherwise they'd be augmented twice)
        # and mirrors the host augmentors' exact parameter set on-device.
        photometric_params = take_photometric_params(dataset)
        logger.info("Photometric augmentation on-device "
                    "(--device_photometric): %s", photometric_params)
    loader = DataLoader(dataset, cfg.batch_size, shuffle=True, drop_last=True,
                        num_workers=num_workers, seed=cfg.seed,
                        sample_retries=cfg.sample_retries,
                        quarantine_limit=cfg.quarantine_limit,
                        batch_timeout=cfg.loader_timeout_s or None,
                        fault_plan=plan)
    logger.info("Train loader: %d samples, %d batches/epoch",
                len(dataset), len(loader))
    if len(loader) == 0:
        raise ValueError(
            f"empty train loader: {len(dataset)} samples < batch_size "
            f"{cfg.batch_size} (check --train_datasets/--dataset_root)")

    # Fail fast if the periodic regression check can't run (reference runs
    # validate_things every 10k steps, train_stereo.py:184-191; silently
    # skipping it would let a training run go fully unchecked).  Probing at
    # startup also means the validation dataset is built exactly once.
    val_dataset = None
    if not no_validation and workload == "sl":
        from ..data.sl import StructuredLightDataset
        from ..sl import SLTrainView
        try:
            val_dataset = SLTrainView(StructuredLightDataset(
                dataset_root, split="validation", scale=1.0,
                with_depth=True))
        except Exception as e:
            raise ValueError(
                "in-training SL validation requires the capture tree's "
                f"validation split and it could not be loaded ({e}); fix "
                "--dataset_root or pass --no_validation to opt out "
                "explicitly") from e
        if len(val_dataset) == 0:
            raise ValueError(
                "in-training SL validation dataset is empty; fix "
                "--dataset_root or pass --no_validation to opt out "
                "explicitly")
    elif not no_validation:
        from ..data import datasets as ds
        try:
            val_dataset = ds.SceneFlowDatasets(
                aug_params=None, dstype="frames_finalpass", things_test=True,
                **({"root": dataset_root} if dataset_root else {}))
        except Exception as e:
            raise ValueError(
                "in-training validation requires the FlyingThings3D TEST "
                f"split and it could not be loaded ({e}); fix the dataset "
                "root or pass --no_validation to opt out explicitly") from e
        if len(val_dataset) == 0:
            raise ValueError(
                "in-training validation dataset is empty; fix the dataset "
                "root or pass --no_validation to opt out explicitly")

    step_fn = jit_train_step(
        make_train_step(model, tx, cfg, schedule,
                        photometric_params=photometric_params), mesh)
    metrics_logger = Logger(log_dir=os.path.join("runs", cfg.name),
                            total_steps=int(state.step))
    from ..utils.profiling import StepProfiler
    prof = StepProfiler(os.path.join("runs", cfg.name, "profile"),
                        *(profile_steps or (-1, -1)))

    def maybe_validate(state):
        if no_validation:
            return
        try:
            validator = validate_sl if workload == "sl" else validate_things
            results = validator(
                model, state.variables, iters=cfg.valid_iters,
                dataset=val_dataset, max_images=200)
        except Exception as e:
            # Startup probed the dataset, so this is a genuine runtime
            # failure — make it loud and countable, not a silent skip.
            logger.error("Validation FAILED (counted as "
                         "validation_skipped): %s", e)
            metrics_logger.push({"validation_skipped": 1.0})
            return
        metrics_logger.push({"validation_skipped": 0.0})
        logger.info("Validation: %s", results)
        metrics_logger.write_dict(results)

    # Steps saved BY THIS PROCESS — the dedup key for boundary/final saves.
    # Comparing against manager.latest_step() instead would conflate "we
    # already saved this step" with "a (possibly corrupt, fallback-skipped)
    # step of that number exists on disk" and silently skip the save.
    saved_steps = set()

    def save_ckpt(step, state, wait=False):
        # wait=False saves measure the async dispatch; wait=True (boundary
        # and final saves) the full write.  phase(): a ring span AND, in a
        # --profile_steps capture, a host event over the device's steps.
        t0 = time.perf_counter()
        with tracer.phase("checkpoint", trace_id=run_trace, step=step,
                          wait=wait):
            manager.save(step, state, wait=wait)
        tmetrics.checkpoint_seconds.observe(time.perf_counter() - t0)
        saved_steps.add(step)

    def save_boundary(step, state):
        """Preemption save: idempotent when a periodic save already covered
        this exact step in this process."""
        if step not in saved_steps:
            save_ckpt(step, state, wait=True)

    step_times = collections.deque(maxlen=101)

    def watchdog(dt, total_steps):
        """Flag a device step that took a configurable multiple of the
        running median wall-clock (a hung collective / stuck host looks
        exactly like this before it looks like anything else)."""
        flagged = 0.0
        if (cfg.watchdog_factor > 0 and len(step_times) >= 5
                and dt > cfg.watchdog_factor * statistics.median(step_times)):
            flagged = 1.0
            logger.warning(
                "step watchdog: step %d took %.2fs (> %gx the running "
                "median %.3fs over %d steps)", total_steps, dt,
                cfg.watchdog_factor, statistics.median(step_times),
                len(step_times))
        step_times.append(dt)
        return flagged

    _EPOCH_DONE = object()

    def run_loop(state):
        """Returns (state, preempted)."""
        total_steps = int(state.step)
        should_keep_training = total_steps <= cfg.num_steps
        while should_keep_training:
            # Prefetch: the host->HBM copy (and mesh sharding) of the next
            # batch overlaps the current step's compute — the TPU analogue
            # of the reference's pin_memory loader (core/stereo_datasets.py:311).
            batches = iter(prefetch_to_device(loader, size=2,
                                              devices=batch_sharded(mesh)))
            while True:
                # Explicit next(): the wait for the prefetched batch IS the
                # data-starvation signal (span + train_data_wait_seconds).
                # (The wait that ends an epoch is recorded too.)
                t_d0 = time.perf_counter()
                with tracer.phase("data_wait", trace_id=run_trace,
                                  step=total_steps + 1):
                    batch = next(batches, _EPOCH_DONE)
                t_d1 = time.perf_counter()
                if batch is _EPOCH_DONE:
                    break
                # The watchdog clock starts before the fault hooks so an
                # injected slow@step is measured like a real stall.
                t0 = time.monotonic()
                if plan:
                    # Deterministic chaos hooks for step total_steps+1: may
                    # sleep (slow), SIGTERM ourselves (preempt), raise
                    # (crash), or ask for a poisoned batch (nan).
                    fired = plan.at_step(total_steps + 1)
                    if "nan" in fired:
                        img1 = jax.numpy.asarray(batch[0])
                        batch = (img1.at[(0,) * img1.ndim]
                                 .set(jax.numpy.nan),) + tuple(batch[1:])
                if guard.requested:
                    # Preemption (SIGTERM/SIGINT): save at this step boundary
                    # and exit cleanly inside the grace period.
                    save_boundary(total_steps, state)
                    logger.warning(
                        "preemption: checkpoint at step %d written; exiting "
                        "cleanly", total_steps)
                    return state, True
                in_xla_window = (prof.enabled
                                 and prof.start <= total_steps < prof.stop)
                t_s0 = time.perf_counter()
                # The capture opens (prof.step) before the phase, so the
                # first profiled step is a host event in it too.
                # xla_profile cross-references this span with the
                # StepProfiler capture it overlapped.
                with prof.step(total_steps), \
                        tracer.phase("step", trace_id=run_trace,
                                     step=total_steps + 1,
                                     xla_profile=in_xla_window):
                    state, metrics = step_fn(state, batch)
                    total_steps += 1
                    # float() blocks on the device result, so the phase
                    # covers the step's execution, not just its dispatch.
                    metrics = {k: float(v) for k, v in metrics.items()}
                t_s1 = time.perf_counter()
                tmetrics.observe_step(step_s=t_s1 - t_s0,
                                      data_s=t_d1 - t_d0)
                health = loader.health_metrics()
                health["watchdog_slow"] = watchdog(time.monotonic() - t0,
                                                   total_steps)
                tmetrics.observe_health(health)
                if metrics.pop("nonfinite", 0.0) >= 0.5:
                    if cfg.nan_policy == "abort":
                        # Reference assert semantics (train_stereo.py:49-52).
                        raise FloatingPointError(
                            f"non-finite loss/gradient at step {total_steps}")
                    logger.warning("step %d: non-finite loss/gradient — "
                                   "update skipped", total_steps)
                    tmetrics.skipped.inc()
                    # Don't push the NaN metrics: one skipped step would turn
                    # the whole running-mean window NaN.  Record the skip.
                    metrics_logger.push({"skipped": 1.0, **health})
                else:
                    metrics["skipped"] = 0.0
                    metrics_logger.write_scalar("live_loss",
                                                metrics.get("loss", 0.0),
                                                total_steps)
                    if "lr" in metrics:
                        metrics_logger.write_scalar("lr", metrics["lr"],
                                                    total_steps)
                    metrics_logger.push({**metrics, **health})

                if total_steps % cfg.validation_frequency == 0:
                    save_ckpt(total_steps, state)
                    maybe_validate(state)

                if total_steps > cfg.num_steps:
                    should_keep_training = False
                    break

            # Per-epoch checkpoint for very long epochs
            # (reference: train_stereo.py:202-205).
            if len(loader) >= 10000 and total_steps not in saved_steps:
                save_ckpt(total_steps, state)
        return state, False

    # Elastic recovery: resume from the latest valid checkpoint (the
    # reference's only recovery is a manual restart with --restore_ckpt,
    # train_stereo.py:143-148).  Only restarts WITHOUT step progress count
    # against max_restarts, and consecutive no-progress restarts back off
    # exponentially, so a crash loop can't thrash the pod.
    preempted = False
    restarts_np = 0
    last_resume_step = int(state.step)
    if metrics_port is not None:
        telemetry = TelemetryServer(
            tmetrics.registry, tracer,
            vars_fn=lambda: {"config": dataclasses.asdict(cfg),
                             "model_config": dataclasses.asdict(model_cfg)},
            host=metrics_host, port=metrics_port).start()
        logger.info("telemetry exporter on %s:%d", metrics_host,
                    telemetry.port)
    try:
        while True:
            try:
                state, preempted = run_loop(state)
                break
            except (KeyboardInterrupt, FloatingPointError):
                # FloatingPointError = nan_policy abort: deterministic given
                # the data — replaying from a checkpoint would hit it again.
                raise
            except Exception as e:
                if cfg.max_restarts <= 0:
                    raise
                state = init_state()
                resume_step = int(state.step)
                if resume_step > last_resume_step:
                    # Progress since the previous restart: this one is free
                    # and the no-progress budget resets in full.
                    restarts_np = 0
                    delay = min(cfg.restart_backoff, 60.0)
                    logger.warning(
                        "training loop failed (%s); restarting after "
                        "progress (resuming at step %d, no-progress budget "
                        "reset to %d) after %.1fs backoff",
                        e, resume_step, cfg.max_restarts, delay)
                else:
                    restarts_np += 1
                    if restarts_np > cfg.max_restarts:
                        raise
                    delay = min(cfg.restart_backoff * 2 ** (restarts_np - 1),
                                60.0)
                    logger.warning(
                        "training loop failed (%s); restart %d/%d without "
                        "progress, resuming at step %d after %.1fs backoff",
                        e, restarts_np, cfg.max_restarts, resume_step, delay)
                last_resume_step = resume_step
                time.sleep(delay)
    finally:
        # Flush any in-flight profiler trace even when the loop dies between
        # profiled steps (the step-internal handler only covers exceptions
        # raised inside the step itself).
        prof.close()
        guard.uninstall()
        if telemetry is not None:
            telemetry.close()

    if preempted:
        # The boundary checkpoint is already on disk (save_boundary waited);
        # skip the final-weights export — the grace period is for getting
        # out, and the relaunch resumes exactly where we stopped.
        metrics_logger.close()
        manager.close()
        return state

    if int(state.step) not in saved_steps:
        save_ckpt(int(state.step), state, wait=True)
    final = os.path.join(ckpt_dir, f"{cfg.name}-final")
    save_weights(final, state.variables)
    logger.info("Saved final weights to %s", final)
    metrics_logger.close()
    manager.close()
    return state


def main(argv=None) -> int:
    setup_logging()
    p = argparse.ArgumentParser(description=__doc__)
    add_train_args(p)
    add_model_args(p)
    args = p.parse_args(argv)
    plan = FaultPlan.parse(args.faults) if args.faults else None
    train(model_config_from_args(args), train_config_from_args(args),
          num_workers=args.num_workers, no_validation=args.no_validation,
          dataset_root=args.dataset_root, profile_steps=args.profile_steps,
          fault_plan=plan, metrics_port=args.metrics_port,
          metrics_host=args.metrics_host, workload=args.workload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
