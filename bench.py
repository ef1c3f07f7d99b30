"""Benchmark CLI: stereo-pairs/sec on the flagship inference path.

Measures the BASELINE.json headline metric — stereo pairs/sec/chip at
960x540 with 32 GRU iterations — on whatever accelerator JAX sees (the
real TPU chip under the driver; CPU with ``--quick`` for development).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "pairs/sec", "vs_baseline": N}

``vs_baseline`` compares against the PyTorch reference model running the same
config, measured once on this machine's CPU (the only hardware the torch
reference runs on here — no CUDA) and cached in BENCH_BASELINE.json.  Refresh
with ``--measure-baseline``.  Like the reference's FPS measurement
(evaluate_stereo.py:77-81,105-107) the result is mean wall-clock over warm
repeats; the repeats run inside one compiled device loop (see bench_jax).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BASELINE_CACHE = os.path.join(REPO, "BENCH_BASELINE.json")
METRIC = "stereo-pairs/sec/chip @960x540, 32 GRU iters"


def resolve_corr(corr: str) -> str:
    """'auto' -> the fastest backend for the active platform (the package's
    single resolver — ops/corr.py): the on-demand Pallas kernel on TPU
    (fastest measured AND O(H*W) memory), the XLA gather path elsewhere."""
    from raftstereo_tpu.ops.corr import resolve_implementation

    return resolve_implementation(corr)


def measure_matmul_peak_tflops(reps: int = 2000, n: int = 4096) -> float:
    """The chip's *achievable* bf16 matmul ceiling, measured on the spot.

    MFU against this number answers "how close is the model to what this
    silicon can actually do" (the v5e spec sheet says 197 TFLOP/s).  The
    repeat loop runs on device (same dispatch rationale as bench_jax) and
    the per-dispatch fixed latency — same order as the compute at small
    reps — is measured with a null program and subtracted, so the probe
    reports device throughput, not dispatch latency.
    """
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (n, n), jnp.bfloat16)

    def run(n_reps):
        def body(i, carry):
            acc, bb = carry
            c = jax.lax.dot(a, bb, precision=None,
                            preferred_element_type=jnp.float32)
            # Consume EVERY element of c and feed it back into next bb:
            # anything less and XLA legally deletes the FLOPs — `acc +
            # c[0,0]` alone reduces the "matmul" to one row-dot via
            # dot-slice fusion, and `i * 0` / `0.0 * acc` perturbations get
            # constant-folded, collapsing the loop entirely (both bugs made
            # earlier "peak" numbers pure dispatch noise).  The feedback
            # scalar is runtime data far below bf16 resolution, so bb's
            # value never changes.
            s = c.sum()
            acc = acc + s
            bb = bb + (s * 1e-38).astype(bb.dtype)
            return acc, bb
        acc, _ = jax.lax.fori_loop(0, n_reps, body, (jnp.float32(0), b))
        return acc

    fn = jax.jit(run, static_argnums=(0,))
    lo = max(reps // 5, 1)
    float(fn(lo)), float(fn(reps))  # compile both trip counts + warm

    def timed(k):
        t0 = time.perf_counter()
        float(fn(k))
        return time.perf_counter() - t0

    # Two-point difference with medians: rate from the DELTA between rep
    # counts, so the per-dispatch fixed latency (can be seconds under host
    # load) cancels; median-of-3 at each point defends
    # against its run-to-run variance, and the large rep count keeps the
    # device-time delta well above that variance.
    t_lo = sorted(timed(lo) for _ in range(3))[1]
    t_hi = sorted(timed(reps) for _ in range(3))[1]
    dt = max(t_hi - t_lo, 1e-9)
    return 2 * n * n * n * (reps - lo) / dt / 1e12


def _cost_model_flops(compiled) -> float:
    return float(compiled.cost_analysis().get("flops", 0.0))


def pallas_corr_flops_per_iter(model, batch: int, height: int,
                               width: int) -> float:
    """Analytic per-iteration FLOPs of the Pallas correlation kernels —
    custom calls are invisible to XLA's cost model, so without this the
    default TPU path's corr work would be missing from MFU.

    Counts the on-demand matmul (pallas_alt: rows x W1p x W2cat x C x 2) and
    the hat-weight tap reduction (~4 flops per swept element: subtract, hat,
    multiply, accumulate) using the kernels' real padded shapes."""
    from raftstereo_tpu.ops.pallas_corr import (LANE, _BLOCK_ROWS, _block_w1)

    cfg = model.config
    impl = cfg.corr_implementation
    if impl == "auto":
        impl = resolve_corr(impl)
    if impl not in ("pallas", "pallas_alt"):
        return 0.0

    def rup(x, m):
        return -(-x // m) * m

    # Ceil division matches the encoders' ceil-halving per stride (and thus
    # both callers: bench_jax pre-pads to a 32-multiple, where this is
    # exact division; the train path feeds raw crops like the reference's
    # 320x720, where rounding the IMAGE up to 32 first would overcount).
    f = cfg.factor
    h0 = -(-height // f)
    w0 = -(-width // f)
    n = rup(batch * h0, _BLOCK_ROWS)
    w1p = rup(w0, _block_w1(w0))
    widths = [w0]
    for _ in range(cfg.corr_levels - 1):
        widths.append(widths[-1] // 2)
    padded = [rup(w, LANE) for w in widths]
    w2cat = sum(padded)
    k = 2 * cfg.corr_radius + 1
    hat = 4.0 * n * w1p * k * sum(padded)
    if impl == "pallas_alt":
        # fnet feature channels, from the model (not a literal — a config
        # variant changing the encoder width must not skew MFU silently).
        c = model.feature_dim
        return 2.0 * n * w1p * w2cat * c + hat
    return hat  # pallas: volume matmul is XLA-side (cost model sees it)


def analyze_forward_flops(model, variables, img1, img2, iters) -> float:
    """True FLOPs for ONE forward execution (the whole batch).

    XLA's cost model counts a rolled scan/while body ONCE regardless of trip
    count (verified: a scanned matmul reports identical flops for length
    1/4/16 — this undercounted round-2 MFU by ~5x), so the per-iteration
    body cost is measured from the DIFFERENCE of two fully-unrolled
    compilations (1 vs 2 iterations) and scaled to ``iters``; Pallas corr
    kernel flops (custom calls, also invisible) are added analytically.
    Returns 0.0 if the backend exposes no cost analysis."""
    import jax

    def flops_at(n):
        fwd = jax.jit(lambda v, a, b: model.forward(
            v, a, b, iters=n, test_mode=True, unroll=n))
        return _cost_model_flops(fwd.lower(variables, img1, img2).compile())

    try:
        f1, f2 = flops_at(1), flops_at(2)
    except Exception as e:
        print(f"cost analysis unavailable: {e}", file=sys.stderr)
        return 0.0
    body = f2 - f1
    fixed = max(f1 - body, 0.0)
    body += pallas_corr_flops_per_iter(model, img1.shape[0], img1.shape[1],
                                       img1.shape[2])
    return fixed + iters * body


def bench_jax(height: int, width: int, batch: int, iters: int, corr: str,
              reps: int, compute_dtype: str,
              corr_dtype: str = "float32", corr_precision: str = "highest",
              realtime: bool = False, mfu: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.ops.image import InputPadder

    corr = resolve_corr(corr)
    model_kw = {}
    if realtime:
        # The reference's realtime configuration (reference: README.md:82-84):
        # shared backbone, 1/8 disparity field, 2 GRU layers, slow-fast.
        model_kw = dict(shared_backbone=True, n_downsample=3, n_gru_layers=2,
                        hidden_dims=(128, 128), slow_fast_gru=True)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype,
                           corr_dtype=corr_dtype,
                           corr_precision=corr_precision, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))

    rng = np.random.default_rng(0)
    img1 = rng.integers(0, 255, (batch, height, width, 3)).astype(np.float32)
    img2 = rng.integers(0, 255, (batch, height, width, 3)).astype(np.float32)
    padder = InputPadder((batch, height, width, 3), divis_by=32)
    img1, img2 = padder.pad(jnp.asarray(img1), jnp.asarray(img2))
    img1, img2 = jax.device_put(img1), jax.device_put(img2)

    # Throughput protocol: the repeat loop runs ON DEVICE (lax.fori_loop over
    # full forward passes), so one dispatch measures ``reps`` back-to-back
    # pairs and per-call dispatch latency does not cap the fast configs.
    # The ``img1 + i*0`` dependency stops XLA hoisting the loop-invariant
    # forward out of the loop; the final fetch of the scalar accumulator is
    # the fence.
    def run_reps(v, a, b, n):
        def body(i, acc):
            lo, up = model.forward(v, a + i.astype(a.dtype) * 0, b,
                                   iters=iters, test_mode=True)
            return acc + up.sum().astype(jnp.float32)
        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    fn = jax.jit(run_reps, static_argnums=(3,))
    float(fn(variables, img1, img2, reps))    # compile + warm run
    t0 = time.perf_counter()
    float(fn(variables, img1, img2, reps))
    dt = time.perf_counter() - t0
    pairs_per_sec = batch * reps / dt
    if not mfu:
        return pairs_per_sec, None

    flops_exec = analyze_forward_flops(model, variables, img1, img2, iters)
    flops_per_pair = flops_exec / batch
    model_tflops = flops_per_pair * pairs_per_sec / 1e12
    extras = {
        "flops_per_pair": flops_per_pair,
        "model_tflops": round(model_tflops, 3),
        "measured_peak_tflops": None,
        "mfu_vs_measured_peak": None,
    }
    if jax.default_backend() == "tpu":
        peak = measure_matmul_peak_tflops()
        extras["measured_peak_tflops"] = round(peak, 2)
        extras["mfu_vs_measured_peak"] = (round(model_tflops / peak, 4)
                                          if peak else 0.0)
    # On CPU the two-point probe delta is of the same order as timer noise
    # (a small probe once emitted absurd peaks when t_hi < t_lo), so the
    # peak/MFU fields stay null rather than carrying a noise-derived number.
    return pairs_per_sec, extras


def analyze_train_flops(model, tx, tcfg, state, batch_data, iters) -> float:
    """True FLOPs for ONE training step (fwd + loss + bwd + update), by the
    same unrolled two-point method as analyze_forward_flops (the rolled scan
    body is counted once by the cost model; with remat the unrolled HLO also
    contains the recompute, so rematerialisation cost is included).  The
    Pallas corr kernels are invisible custom calls; per iteration they
    execute the forward lookup (twice under remat) plus a backward whose two
    feature-gradient matmuls cost ~2x the forward matmul."""
    import jax
    import optax

    from raftstereo_tpu.train.loss import sequence_loss

    def make_step(n):
        def loss_fn(params, img1, img2, disp_gt, valid):
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            preds = model.forward(variables, img1, img2, iters=n, unroll=n)
            return sequence_loss(preds, disp_gt, valid,
                                 loss_gamma=tcfg.loss_gamma,
                                 max_flow=tcfg.max_flow)

        def step(st, batch):
            img1, img2, disp_gt, valid = batch
            (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                st.params, img1, img2, disp_gt, valid)
            updates, opt_state = tx.update(grads, st.opt_state, st.params)
            params = optax.apply_updates(st.params, updates)
            return params, opt_state, loss

        return step

    def flops_at(n):
        compiled = jax.jit(make_step(n)).lower(state, batch_data).compile()
        return _cost_model_flops(compiled)

    try:
        f1, f2 = flops_at(1), flops_at(2)
    except Exception as e:
        print(f"cost analysis unavailable: {e}", file=sys.stderr)
        return 0.0
    body = f2 - f1
    fixed = max(f1 - body, 0.0)
    img1 = batch_data[0]
    corr_fwd = pallas_corr_flops_per_iter(model, img1.shape[0], img1.shape[1],
                                          img1.shape[2])
    corr_mult = (2.0 if model.config.remat else 1.0) + 2.0
    return fixed + iters * (body + corr_mult * corr_fwd)


def bench_train(height: int, width: int, batch: int, iters: int, corr: str,
                reps: int, compute_dtype: str,
                corr_dtype: str = "float32", corr_precision: str = "highest",
                mfu: bool = False):
    """Training throughput: full fwd+loss+bwd+clip+update steps/sec, the
    whole repeat loop compiled on-device (same dispatch rationale as
    bench_jax).  The reference recipe trains on 320x720 crops
    (train_stereo.py:245), so pass --height 320 --width 720 for that config.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.train import (create_train_state, make_optimizer,
                                      make_train_step)

    corr = resolve_corr(corr)
    # remat: the recipe (batch 8, 320x720, 16 iters) needs ~29 GB of stored
    # activations without it — far past one chip's HBM.
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype,
                           corr_dtype=corr_dtype,
                           corr_precision=corr_precision, remat=True)
    tcfg = TrainConfig(batch_size=batch, train_iters=iters,
                       image_size=(height, width))
    model = RAFTStereo(cfg)
    tx, sched = make_optimizer(tcfg)
    state = create_train_state(model, jax.random.key(0), tx, (height, width))
    step = make_train_step(model, tx, tcfg, lr_schedule=sched)

    rng = np.random.default_rng(0)
    batch_data = (
        jnp.asarray(rng.integers(0, 255, (batch, height, width, 3))
                    .astype(np.float32)),
        jnp.asarray(rng.integers(0, 255, (batch, height, width, 3))
                    .astype(np.float32)),
        jnp.asarray(-np.abs(rng.normal(size=(batch, height, width, 1)))
                    .astype(np.float32) * 8),
        jnp.ones((batch, height, width), jnp.float32),
    )

    def run_reps(st, data, n):
        def body(i, s):
            s, _ = step(s, data)
            return s
        return jax.lax.fori_loop(0, n, body, st)

    # FLOP accounting first: the timed loop donates the state's buffers.
    flops_step = (analyze_train_flops(model, tx, tcfg, state, batch_data,
                                      iters) if mfu else 0.0)

    fn = jax.jit(run_reps, static_argnums=(2,), donate_argnums=(0,))
    state = fn(state, batch_data, reps)
    jax.block_until_ready(state.params)
    _ = float(jax.tree.leaves(state.params)[0].sum())  # fence
    t0 = time.perf_counter()
    state = fn(state, batch_data, reps)
    _ = float(jax.tree.leaves(state.params)[0].sum())
    dt = time.perf_counter() - t0
    steps_per_sec = reps / dt
    if not mfu:
        return steps_per_sec, None
    model_tflops = flops_step * steps_per_sec / 1e12
    extras = {
        "flops_per_step": flops_step,
        "model_tflops": round(model_tflops, 3),
        "measured_peak_tflops": None,
        "mfu_vs_measured_peak": None,
    }
    if jax.default_backend() == "tpu":
        peak = measure_matmul_peak_tflops()
        extras["measured_peak_tflops"] = round(peak, 2)
        extras["mfu_vs_measured_peak"] = (round(model_tflops / peak, 4)
                                          if peak else 0.0)
    return steps_per_sec, extras


def bench_tiled(height: int, width: int, iters: int, corr: str,
                compute_dtype: str, tile_batch: int,
                tile_hw=(1536, 1568), overlap: int = 128,
                margin: int = 512):
    """BASELINE config #5: Middlebury-4K-scale tiled inference on the chip.

    Runs a synthetic ``height x width`` pair (default 4000x6000 — the
    Middlebury 4K shape, BASELINE.json:11) through eval/tiled.py with the
    on-demand correlation backend: fixed-shape overlapping tiles, one
    compiled program, host-side accumulation so peak HBM is
    O(tile_batch x tile) regardless of image size.  The reference has no
    tiling at all — its answer to large images is the slow ``alt`` path
    plus downsampling (reference: README.md:111,121).

    Returns (pairs_per_sec, extras): the rate 1/wall of the SECOND (warm)
    full-pair pass, plus tile bookkeeping (including the raw ``wall_s``)
    and the device's peak-HBM reading."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.eval.tiled import plan_geometry, tiled_infer
    from raftstereo_tpu.models.raft_stereo import RAFTStereo

    corr = resolve_corr(corr)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))

    rng = np.random.default_rng(0)
    img1 = rng.integers(0, 255, (height, width, 3)).astype(np.float32)
    img2 = rng.integers(0, 255, (height, width, 3)).astype(np.float32)

    # The plan comes from the SAME helper tiled_infer executes
    # (plan_geometry), so the reported tile count cannot drift from the run.
    th, tw, ys, xs, _, _ = plan_geometry(height, width, tile_hw, overlap,
                                         margin)
    # ONE compile, reused for both the memory analysis and every tile
    # dispatch (AOT executable passed as infer_fn — a second jit would
    # recompile the identical program).
    comp = jax.jit(
        lambda v, a, b: model.forward(v, a, b, iters=iters,
                                      test_mode=True)).lower(
        variables,
        jax.ShapeDtypeStruct((tile_batch, th, tw, 3), jnp.float32),
        jax.ShapeDtypeStruct((tile_batch, th, tw, 3), jnp.float32),
    ).compile()
    # Peak device memory from XLA's own allocator analysis: peak = args +
    # outputs + temp — everything resident during a tile dispatch.
    mem_gb = None
    try:
        ma = comp.memory_analysis()
        mem_gb = round((ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes) / 2**30, 3)
    except Exception as e:
        print(f"memory analysis unavailable: {e}", file=sys.stderr)
    kw = dict(iters=iters, tile_hw=(th, tw), overlap=overlap,
              disp_margin=margin, infer_fn=lambda v, a, b: comp(v, a, b),
              tile_batch=tile_batch)
    tiled_infer(model, variables, img1, img2, **kw)     # warm
    t0 = time.perf_counter()
    disp = tiled_infer(model, variables, img1, img2, **kw)
    wall = time.perf_counter() - t0
    assert disp.shape == (height, width) and np.isfinite(disp).all()

    extras = {
        "image": f"{width}x{height}",
        "tiles": len(ys) * len(xs),
        "tile_hw": [th, tw],
        "tile_batch": tile_batch,
        "wall_s": round(wall, 2),
        "megapixels_per_sec": round(height * width / wall / 1e6, 2),
        "peak_hbm_gb": mem_gb,
    }
    return 1.0 / wall, extras


def bench_data(batch: int, num_workers: int,
               device_photometric: bool = False) -> float:
    """Host data-pipeline throughput: KITTI-size decode + full sparse
    augmentation to the training crop, multiprocess workers, samples/sec.
    (KITTI is a sparse-GT dataset, so this exercises SparseFlowAugmentor.)

    The number to beat is the train step's consumption rate (steps/sec x
    batch); the pipeline feeds the TPU (SURVEY.md §7 hard part 6 — the
    reference leans on torch DataLoader workers, core/stereo_datasets.py:311).

    ``device_photometric`` measures the MITIGATED pipeline: photometric
    jitter + eraser moved into the jitted train step (data/device_aug.py,
    --device_photometric), so the host does decode + spatial-only
    augmentation — what a real training host pays when the chip absorbs
    the color work."""
    import shutil
    import tempfile

    import numpy as np
    from PIL import Image

    from raftstereo_tpu.data.codecs import write_disp_kitti
    from raftstereo_tpu.data.datasets import KITTI
    from raftstereo_tpu.data.loader import DataLoader

    rng = np.random.default_rng(0)
    root = tempfile.mkdtemp(prefix="bench_data_")
    try:
        for sub in ("image_2", "image_3", "disp_occ_0"):
            os.makedirs(os.path.join(root, "training", sub))
        for i in range(32):  # KITTI native resolution
            for cam in ("image_2", "image_3"):
                img = rng.integers(0, 255, (375, 1242, 3), dtype=np.uint8)
                Image.fromarray(img).save(os.path.join(
                    root, "training", cam, f"{i:06d}_10.png"))
            disp = (rng.uniform(1, 60, (375, 1242)) * 256).astype(np.uint16)
            write_disp_kitti(os.path.join(
                root, "training", "disp_occ_0", f"{i:06d}_10.png"), disp)
        ds = KITTI(aug_params={"crop_size": (320, 720)}, root=root) * 8
        if device_photometric:
            from raftstereo_tpu.data.datasets import take_photometric_params
            take_photometric_params(ds)  # host: decode + spatial only
        loader = DataLoader(ds, batch_size=batch, num_workers=num_workers)
        n = 0
        it = iter(loader)
        next(it)  # warm the worker pool before timing
        t0 = time.perf_counter()
        for b in it:
            n += b[0].shape[0]
        dt = time.perf_counter() - t0
        return n / dt
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_serve(height: int, width: int, iters: int, max_batch: int,
                requests: int, concurrency: int, corr: str,
                compute_dtype: str, quick: bool):
    """Serving-path smoke benchmark: spin the HTTP server up in-process,
    drive closed-loop traffic through the real wire format via the load-gen
    client, and report achieved pairs/sec + p99 latency.  Exercises the
    whole subsystem — bucketed compile cache, micro-batcher, admission
    control, metrics — not just the forward (docs/serving.md).  Runs the
    same traffic under BOTH /predict dialects (binary wire frames, then
    the legacy base64 JSON) so the record states the measured
    wire-bytes/pair reduction (docs/wire_format.md)."""
    import threading

    from raftstereo_tpu.config import RAFTStereoConfig, ServeConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.serve import (build_server, run_load,
                                      synthetic_pair_pool)

    import jax

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    serve_cfg = ServeConfig(
        port=0, buckets=((height, width),), max_batch_size=max_batch,
        max_wait_ms=5.0, queue_limit=max(4 * max_batch, 16),
        # quick: one warmup compile, not two — degradation has its own test.
        iters=iters, degraded_iters=iters if quick else max(1, iters // 2),
        degrade_queue_depth=max(4 * max_batch, 16))
    server = build_server(model, variables, serve_cfg)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        stats = run_load(serve_cfg.host, server.port,
                         synthetic_pair_pool(height, width),
                         requests=requests, concurrency=concurrency)
        stats_json = run_load(serve_cfg.host, server.port,
                              synthetic_pair_pool(height, width),
                              requests=requests, concurrency=concurrency,
                              wire_format="json")
    finally:
        server.close()
        thread.join(10)
    # Primary keys stay the binary run (the default dialect); the JSON
    # rerun of the same traffic makes the reduction a measured number.
    if "wire_bytes_per_pair" in stats and "wire_bytes_per_pair" in stats_json:
        stats["wire_reduction_x"] = round(
            stats_json["wire_bytes_per_pair"]
            / max(stats["wire_bytes_per_pair"], 1.0), 2)
    stats["json"] = {k: stats_json[k]
                     for k in ("pairs_per_sec", "ok", "p99_ms",
                               "wire_bytes_per_pair", "wire_mb_sent",
                               "wire_mb_received")
                     if k in stats_json}
    return stats


def bench_cluster(height: int, width: int, iters: int, replicas: int,
                  max_batch: int, requests: int, concurrency: int,
                  corr: str, compute_dtype: str, quick: bool):
    """Replicated-serving smoke benchmark (mirrors --serve): N engine
    replicas on N virtual CPU devices (or real chips) behind ONE HTTP
    server — the in-process cluster dispatcher spreads cold traffic by
    least outstanding work and pins session frames (serve/cluster/,
    docs/serving.md "Cluster").  Drives mixed cold + session traffic and
    reports achieved pairs/sec plus the per-replica dispatch split (a
    single hot replica means placement is broken)."""
    import threading

    from raftstereo_tpu.config import (ClusterConfig, RAFTStereoConfig,
                                       ServeConfig, StreamConfig)
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.serve import (build_server, run_load,
                                      synthetic_pair_pool)

    import jax

    if len(jax.devices()) < replicas:
        sys.exit(f"bench: --cluster needs {replicas} devices, have "
                 f"{len(jax.devices())} (on CPU set XLA_FLAGS="
                 f"--xla_force_host_platform_device_count={replicas})")
    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    iters = max(iters, 2)
    serve_cfg = ServeConfig(
        port=0, buckets=((height, width),), max_batch_size=max_batch,
        max_wait_ms=5.0, queue_limit=max(4 * max_batch, 16),
        iters=iters, degraded_iters=iters,  # one warmup compile/replica
        degrade_queue_depth=max(4 * max_batch, 16),
        stream=StreamConfig(ladder=(iters, max(1, iters // 2)),
                            demote_threshold=0.0, promote_threshold=1e6,
                            cold_reset_threshold=2e6),
        stream_warmup=True,
        cluster=ClusterConfig(replicas=replicas))
    server = build_server(model, variables, serve_cfg)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        # Mixed traffic, the cluster acceptance shape: a cold burst
        # spread by least-outstanding-work, then session sequences that
        # must stay pinned (client retries ride out transient 503s the
        # way a router-fronted deployment would).
        cold = run_load(serve_cfg.host, server.port,
                        synthetic_pair_pool(height, width),
                        requests=requests, concurrency=concurrency,
                        retries=2)
        seq_len = max(2, requests // 4)
        stream = run_load(serve_cfg.host, server.port,
                          synthetic_pair_pool(height, width),
                          requests=requests, concurrency=concurrency,
                          sequence_len=seq_len, retries=2)
        per_replica = {
            f"{labels[0]}/{labels[1]}": child.value
            for labels, child in
            server.cluster.cluster_metrics.dispatch.series()}
    finally:
        server.close()
        thread.join(10)
    return {
        "replicas": replicas,
        "cold": cold,
        "stream": stream,
        "dispatch_by_replica": per_replica,
        "pairs_per_sec": round(
            (cold["ok"] + stream["ok"])
            / max(cold["wall_s"] + stream["wall_s"], 1e-9), 4),
    }


def bench_slo(height: int, width: int, iters: int, replicas: int,
              max_batch: int, requests: int, concurrency: int,
              corr: str, compute_dtype: str, quick: bool):
    """Trace-driven SLO harness smoke (loadgen/, docs/slo_harness.md):
    the full gen -> replay -> evaluate -> fit chain in one process.  A
    seeded bursty trace with session churn, a default+certified tier
    mix, priorities and deadlines is open-loop replayed over HTTP
    against a 2-replica scheduler-mode cluster server; the SLO verdict
    (deadline-hit / shed / error bounds + a validator-clean /metrics
    scrape) and the fitted capacity model's "N chips serve M users"
    answer come back in one record.  Refuses a dirty analysis baseline
    like every other smoke mode."""
    import threading
    import time as _time

    from raftstereo_tpu.config import (ClusterConfig, RAFTStereoConfig,
                                       SchedConfig, ServeConfig,
                                       StreamConfig)
    from raftstereo_tpu.loadgen import capacity as lg_capacity
    from raftstereo_tpu.loadgen import replay as lg_replay
    from raftstereo_tpu.loadgen import slo as lg_slo
    from raftstereo_tpu.loadgen import trace as lg_trace
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.serve import build_server
    from raftstereo_tpu.serve.client import ServeClient

    import jax

    if len(jax.devices()) < replicas:
        sys.exit(f"bench: --slo needs {replicas} devices, have "
                 f"{len(jax.devices())} (on CPU set XLA_FLAGS="
                 f"--xla_force_host_platform_device_count={replicas})")
    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    iters = max(iters, 2)
    serve_cfg = ServeConfig(
        port=0, buckets=((height, width),), max_batch_size=max_batch,
        max_wait_ms=5.0, queue_limit=max(4 * max_batch, 32),
        iters=iters, degraded_iters=iters,
        degrade_queue_depth=max(4 * max_batch, 32),
        # Scheduler mode: deadlines + priorities are first-class on
        # /predict (the trace carries both); session frames ride the
        # scheduler as high-priority short jobs.
        sched=SchedConfig(iters_per_step=1, max_iters=max(8, iters)),
        stream=StreamConfig(ladder=(iters, max(1, iters // 2)),
                            demote_threshold=0.0, promote_threshold=1e6,
                            cold_reset_threshold=2e6),
        # certified = fp32: advertised without a manifest, so the trace
        # can mix explicit-tier traffic into the smoke.
        tiers=("certified",),
        cluster=ClusterConfig(replicas=replicas))
    server = build_server(model, variables, serve_cfg)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        spec = lg_trace.TraceSpec(
            seed=0, requests=requests,
            duration_s=max(2.0, requests / 8.0), shape="burst",
            resolutions=((height, width),),
            session_fraction=0.25, sequence_len=3,
            tier_mix=(("default", 3.0), ("certified", 1.0)),
            priority_mix=(("normal", 3.0), ("high", 1.0)),
            # Generous on CPU; the smoke proves the chain, not the bound.
            deadlines=(("high", 60000.0),),
            iters_choices=(iters,), iters_fraction=0.3)
        events = lg_trace.generate(spec)
        rcfg = lg_replay.ReplayConfig(host=serve_cfg.host, port=server.port,
                                      concurrency=concurrency)
        # Same trace under the legacy JSON dialect first (comparison run
        # — its sessions re-run cold on the binary pass, a documented
        # out_of_order frame, not an error); the verdict and the metric
        # scrapes bracket the BINARY replay, the default dialect.
        rcfg_json = lg_replay.ReplayConfig(
            host=serve_cfg.host, port=server.port,
            concurrency=concurrency, wire_format="json")
        rows_json = lg_replay.replay(events, rcfg_json).rows()
        scraper = ServeClient(serve_cfg.host, server.port, timeout=120.0)
        try:
            before = scraper.metrics_text()
            t0 = _time.perf_counter()
            recorder = lg_replay.replay(events, rcfg)
            wall_s = _time.perf_counter() - t0
            after = scraper.metrics_text()
        finally:
            scraper.close()
        rows = recorder.rows()
        slo_spec = lg_slo.SLOSpec(classes=(
            lg_slo.SLOClass(max_error_rate=0.0, max_shed_rate=0.0),
            lg_slo.SLOClass(priority="high", min_deadline_hit_rate=1.0)))
        verdict = lg_slo.evaluate(slo_spec, rows, wall_s=wall_s,
                                  metrics_before=before,
                                  metrics_after=after)
        capacity = lg_capacity.fit(rows, chips=replicas, wall_s=wall_s)
        answer = lg_capacity.whatif(capacity, chips=replicas,
                                    rps_per_user=1.0)
    finally:
        server.close()
        thread.join(10)
    from raftstereo_tpu.loadgen.records import wire_bytes as lg_wire_bytes
    ok = sum(1 for r in rows if r.outcome == "ok")
    wb_bin = verdict.get("wire")
    wb_json = lg_wire_bytes(rows_json)
    wire = {"binary": wb_bin, "json": wb_json}
    if wb_bin and wb_json:
        wire["reduction_x"] = round(
            wb_json["wire_bytes_per_pair"]
            / max(wb_bin["wire_bytes_per_pair"], 1.0), 2)
    return {
        "replicas": replicas,
        "trace_events": len(events),
        "slo_pass": verdict["pass"],
        "checks": verdict["checks"],
        "groups": verdict["groups"],
        "wire": wire,
        "metric_deltas": verdict["metrics"]["deltas"],
        "per_chip_rps": capacity["per_chip_rps"],
        "utilization": capacity["utilization"],
        "whatif": answer,
        "pairs_per_sec": round(ok / max(wall_s, 1e-9), 4),
        "wall_s": round(wall_s, 3),
    }


def bench_chaos(height: int, width: int, iters: int, requests: int,
                concurrency: int, corr: str, compute_dtype: str,
                quick: bool):
    """Chaos-mode serving smoke (docs/fault_tolerance.md): a burst trace
    open-loop replayed against a real 2-backend router cluster while a
    ChaosPlan blackholes one backend mid-replay.  The verdict is the
    degraded-mode SLO machinery end to end — steady bounds on the
    unfaulted slices, relaxed bounds inside the declared window, and a
    recovery check after it — plus the router's breaker/hedge counters
    and a validator-clean /metrics scrape.  Refuses a dirty analysis
    baseline like every other smoke mode."""
    import threading
    import time as _time

    from raftstereo_tpu.config import (RAFTStereoConfig, RouterConfig,
                                       ServeConfig)
    from raftstereo_tpu.loadgen import chaos as lg_chaos
    from raftstereo_tpu.loadgen import replay as lg_replay
    from raftstereo_tpu.loadgen import slo as lg_slo
    from raftstereo_tpu.loadgen import trace as lg_trace
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.obs.prom import parse_text
    from raftstereo_tpu.serve import build_server
    from raftstereo_tpu.serve.client import ServeClient
    from raftstereo_tpu.serve.cluster import build_router

    import jax

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    iters = max(iters, 2)
    serve_cfg = ServeConfig(port=0, buckets=((height, width),),
                            max_batch_size=2, max_wait_ms=5.0,
                            queue_limit=64, iters=iters,
                            degraded_iters=iters, degrade_queue_depth=64)
    servers, threads = [], []
    router = None
    try:
        for _ in range(2):
            srv = build_server(model, variables, serve_cfg)
            th = threading.Thread(target=srv.serve_forever, daemon=True)
            th.start()
            servers.append(srv)
            threads.append(th)
        router = build_router(RouterConfig(
            port=0, backends=tuple(("127.0.0.1", s.port) for s in servers),
            probe_interval_s=0.1, probe_timeout_s=0.3, fail_after=1,
            breaker_reset_s=0.4, retries=2, retry_backoff_ms=20.0,
            request_timeout_s=60.0))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        threads.append(rt)
        spec = lg_trace.TraceSpec(
            seed=0, requests=requests, duration_s=4.0, shape="burst",
            resolutions=((height, width),), iters_choices=(iters,),
            iters_fraction=0.0)
        events = lg_trace.generate(spec)
        # One blackhole on b0 starting 800 ms into the trace, open for
        # 800 ms; probes time out, the breaker opens, traffic spills to
        # b1, and the held requests drain when the window closes (late,
        # never lost).
        plan = lg_chaos.ChaosPlan(
            actions=(lg_chaos.ChaosAction(
                t_ms=800.0, target="b0",
                faults="blackhole_backend@t_ms=0:0.8"),),
            windows=(lg_slo.DegradedWindow(
                t_start_ms=800.0, t_end_ms=2200.0, label="blackhole_b0",
                max_error_rate=0.5, recover_by_ms=300.0,
                recovery_max_error_rate=0.0),))
        controller = lg_chaos.ChaosController(
            plan, {"b0": ("127.0.0.1", servers[0].port),
                   "router": ("127.0.0.1", router.port)})
        rcfg = lg_replay.ReplayConfig(host="127.0.0.1", port=router.port,
                                      concurrency=concurrency)
        scraper = ServeClient("127.0.0.1", router.port, timeout=120.0)
        try:
            before = scraper.metrics_text()
            t0 = _time.perf_counter()
            recorder = lg_replay.replay(events, rcfg, chaos=controller)
            wall_s = _time.perf_counter() - t0
            after = scraper.metrics_text()
        finally:
            scraper.close()
        rows = recorder.rows()
        slo_spec = lg_slo.SLOSpec(
            classes=(lg_slo.SLOClass(max_error_rate=0.0,
                                     max_shed_rate=0.0),),
            windows=plan.degraded_windows())
        verdict = lg_slo.evaluate(slo_spec, rows, wall_s=wall_s,
                                  metrics_before=before,
                                  metrics_after=after)
    finally:
        if router is not None:
            router.close()
        for srv in servers:
            srv.close()
        for th in threads:
            th.join(10)
    fams = parse_text(after)
    breaker_transitions = (fams.total("cluster_breaker_transitions_total")
                           if "cluster_breaker_transitions_total" in fams
                           else 0.0)
    ok = sum(1 for r in rows if r.outcome == "ok")
    return {
        "trace_events": len(events),
        "slo_pass": verdict["pass"],
        "checks": verdict["checks"],
        "windows": verdict.get("windows", {}),
        "chaos": {k: controller.summary()[k]
                  for k in ("actions", "armed", "failed")},
        "breaker_transitions": breaker_transitions,
        "metric_deltas": verdict["metrics"]["deltas"],
        "pairs_per_sec": round(ok / max(wall_s, 1e-9), 4),
        "wall_s": round(wall_s, 3),
    }


def bench_sessions(height: int, width: int, iters: int, sessions: int,
                   frames_per_session: int, corr: str, compute_dtype: str,
                   quick: bool):
    """Durable-session smoke (docs/streaming.md "Durable sessions"): a
    churny many-session trace through a real 2-backend router fleet
    wired to a real in-process session tier, with the busier backend
    SIGKILLed mid-replay.  Reports the warm-rate (cold frames only at
    sequence heads — the kill costs zero thanks to the tier's
    write-behind snapshots), the zero-lost-session outcome, and the
    int8 snapshot wire-byte reduction against the bitwise f32 form.
    Refuses a dirty analysis baseline like every other smoke mode."""
    import collections as _collections
    import threading
    import time as _time

    from raftstereo_tpu.config import (RAFTStereoConfig, RouterConfig,
                                       ServeConfig, StreamConfig,
                                       TierConfig)
    from raftstereo_tpu.data.synthetic import StereoVideoSequence
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.serve import build_server
    from raftstereo_tpu.serve.client import ServeClient
    from raftstereo_tpu.serve.cluster import build_router
    from raftstereo_tpu.serve.server import snapshot_to_wire
    from raftstereo_tpu.stream.tier import build_session_tier

    import jax

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    iters = max(iters, 2)
    tier = build_session_tier(TierConfig(port=0))
    tier_thread = threading.Thread(target=tier.serve_forever, daemon=True)
    tier_thread.start()
    serve_cfg = ServeConfig(
        port=0, buckets=((height, width),), max_batch_size=2,
        max_wait_ms=5.0, queue_limit=64, iters=iters,
        degraded_iters=iters, degrade_queue_depth=64, warmup=True,
        stream=StreamConfig(ladder=(iters, max(1, iters // 2)),
                            demote_threshold=0.0, promote_threshold=1e6,
                            cold_reset_threshold=2e6,
                            tier=("127.0.0.1", tier.port),
                            tier_timeout_s=2.0, tier_backoff_ms=20.0),
        stream_warmup=True)
    # A temporally coherent sequence (realistic ~d0-px disparities, not
    # random-noise garbage planes): what a streaming fleet actually
    # serves, and what the int8 snapshot codec is bounded for.
    seq_frames = StereoVideoSequence(n_frames=frames_per_session,
                                     hw=(height, width), d0=4.0,
                                     drift=0.25, pan=1)
    frames = [(left, right) for left, right, _flow in seq_frames]
    servers, threads = [], []
    router = None
    warm = cold = errors = 0
    try:
        for _ in range(2):
            srv = build_server(model, variables, serve_cfg)
            th = threading.Thread(target=srv.serve_forever, daemon=True)
            th.start()
            servers.append(srv)
            threads.append(th)
        router = build_router(RouterConfig(
            port=0, backends=tuple(("127.0.0.1", s.port) for s in servers),
            probe_interval_s=0.1, probe_timeout_s=0.5, fail_after=1,
            retries=2, retry_backoff_ms=20.0, request_timeout_s=120.0,
            session_tier=("127.0.0.1", tier.port)))
        rt = threading.Thread(target=router.serve_forever, daemon=True)
        rt.start()
        threads.append(rt)
        client = ServeClient("127.0.0.1", router.port, timeout=120,
                             retries=2)
        names = {i: f"b{i}" for i in range(len(servers))}
        sids = [f"cam{i}" for i in range(sessions)]
        homes = {}  # sid -> serving backend name (sticky until killed)
        t0 = _time.perf_counter()

        def run_round(seq: int):
            nonlocal warm, cold, errors
            left, right = frames[seq % len(frames)]
            for sid in sids:  # interleaved round-robin: churny, sticky
                try:
                    _, meta = client.predict(left, right,
                                             session_id=sid, seq_no=seq)
                    homes[sid] = meta["backend"]
                    if meta["warm"]:
                        warm += 1
                    else:
                        cold += 1
                except Exception:
                    errors += 1

        half = max(1, frames_per_session // 2)
        for seq in range(half):
            run_round(seq)
        # SIGKILL the busier backend once its write-behind pushes have
        # landed (flush only bounds the wait; frames never did).
        counts = _collections.Counter(homes.values())
        victim_name = counts.most_common(1)[0][0]
        victim = servers[int(victim_name[1:])]
        migrated = [s for s, h in homes.items() if h == victim_name]
        if victim.tier_publisher is not None:
            victim.tier_publisher.flush(timeout_s=60)
        victim.close()  # no drain, no handoff sweep
        for seq in range(half, frames_per_session):
            run_round(seq)
        wall_s = _time.perf_counter() - t0

        survivor = next(s for s in servers if s is not victim)
        # int8 snapshot reduction, measured on a REAL live session's
        # exported state (what the publisher would push).
        snap = None
        for sid in sids:
            snap = survivor.export_session(sid)
            if snap is not None:
                break
        reduction = None
        if snap is not None:
            import numpy as np

            raw_b = len(json.dumps(snapshot_to_wire(snap)))
            # The quick smoke serves an UNTRAINED model whose outputs
            # have arbitrary dynamic range, so the production bound
            # (0.05 px) would correctly force the bitwise fallback.
            # Scale the measurement bound to 1% of the plane's range so
            # the codec itself is what gets measured; the bound used is
            # reported alongside.
            amax = float(np.max(np.abs(np.asarray(
                snap["prev_disp_low"], np.float32))))
            bound = max(0.05, amax / 100.0)
            int8_b = len(json.dumps(snapshot_to_wire(
                snap, compress="int8", compress_bound=bound)))
            reduction = {"f32_bytes": raw_b, "int8_bytes": int8_b,
                         "reduction_x": round(raw_b / max(int8_b, 1), 2),
                         "bound_px": round(bound, 4)}
        client.close()
    finally:
        if router is not None:
            router.close()
        tier.close()
        tier_thread.join(10)
        for srv in servers:
            try:
                srv.close()
            except Exception:
                pass
        for th in threads:
            th.join(10)
    total = warm + cold
    # Cold frames belong at sequence heads ONLY: the mid-replay kill is
    # invisible because every migrated session resumed warm from the
    # tier's snapshot.
    expected_cold = len(sids)
    return {
        "sessions": len(sids),
        "frames": total,
        "warm_rate": round(warm / max(total - expected_cold, 1), 4),
        "cold_frames": cold,
        "expected_cold_frames": expected_cold,
        "killed_backend": victim_name,
        "migrated_sessions": len(migrated),
        "lost_sessions": errors,
        "tier_sessions": len(tier.store),
        "tier_bytes": tier.store.total_bytes(),
        "snapshot": reduction,
        "pairs_per_sec": round(total / max(wall_s, 1e-9), 4),
        "wall_s": round(wall_s, 3),
    }


def bench_stream(height: int, width: int, frames: int, iters: int,
                 corr: str, compute_dtype: str, quick: bool):
    """Streaming smoke benchmark (mirrors --serve): replay an N-frame
    temporally coherent synthetic sequence through the temporal warm-start
    subsystem (stream/, docs/streaming.md) and through the cold-start
    full-iteration baseline — same engine, same executables — reporting
    warm vs cold mean frame latency, mean iters/frame, and the final-frame
    EPE ratio (the warm start's accuracy cost, ~1.0 when it tracks)."""
    import jax

    from raftstereo_tpu.config import RAFTStereoConfig, StreamConfig
    from raftstereo_tpu.data.synthetic import StereoVideoSequence
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.stream import build_stream_engine, compare_warm_cold

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    # Ladder derived from --iters: cold/full plus the half-count warm
    # level.  Controller thresholds are pinned far out of reach so every
    # warm frame runs exactly iters/2 — the benchmark measures steady-state
    # warm cost, not controller policy (and the random-weights update
    # magnitudes here would otherwise trip the trained-checkpoint-scale
    # cold-reset threshold).
    iters = max(iters, 2)  # a ladder needs a warm level below the cold one
    ladder = (iters, max(1, iters // 2))
    stream_cfg = StreamConfig(ladder=ladder, demote_threshold=0.0,
                              promote_threshold=1e6,
                              cold_reset_threshold=2e6)
    seq = StereoVideoSequence(n_frames=frames, hw=(height, width))
    engine = build_stream_engine(model, variables, (height, width),
                                 stream_cfg)
    return compare_warm_cold(engine, seq.frames, stream_cfg)["summary"]


def bench_spatial(height: int, width: int, iters: int, shards: int,
                  corr: str, reps: int, quick: bool):
    """Spatial-sharding A/B smoke (mirrors --stream): ONE pair at the
    given resolution through the (1, N) sharded forward
    (parallel/spatial.py) and through the single-device jit — same
    weights, same iteration count — reporting mean latency both ways and
    the max |disparity| gap between them.  Runs at fp32 (the precision
    the sharded program is certified at, v1): on the CPU mesh the gap is
    0.0 by construction, so any nonzero value is a halo/replication bug,
    not noise."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.parallel.spatial import (check_spatial_shape,
                                                 jitted_spatial_infer_init,
                                                 spatial_mesh,
                                                 validate_spatial_config)

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr, **model_kw)
    validate_spatial_config(cfg)
    check_spatial_shape(cfg, shards, height, width)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.standard_normal((1, height, width, 3)) * 50 + 120,
                     jnp.float32)
    i2 = jnp.asarray(rng.standard_normal((1, height, width, 3)) * 50 + 120,
                     jnp.float32)
    zeros = jnp.zeros((1, height // cfg.factor, width // cfg.factor, 1),
                      jnp.float32)

    single = model.jitted_infer(iters=iters)
    sharded = jitted_spatial_infer_init(model, spatial_mesh(shards),
                                        iters=iters)

    def timed(fn):
        out = jax.block_until_ready(fn())  # compile outside the clock
        t0 = _time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(fn())
        return out, (_time.perf_counter() - t0) / reps * 1e3

    (_, up_single), single_ms = timed(lambda: single(variables, i1, i2))
    (_, up_sharded), sharded_ms = timed(
        lambda: sharded(variables, i1, i2, zeros))
    gap = float(jnp.max(jnp.abs(up_sharded - up_single)))
    return {
        "shards": shards,
        "iters": iters,
        "single_ms": round(single_ms, 2),
        "sharded_ms": round(sharded_ms, 2),
        "speedup": round(single_ms / sharded_ms, 3) if sharded_ms else 0.0,
        "max_abs_gap": gap,
    }


def bench_sched(height: int, width: int, long_iters: int, max_batch: int,
                corr: str, compute_dtype: str, quick: bool):
    """Iteration-level-scheduler smoke benchmark (mirrors --serve): a
    mixed workload of long (``--iters``) and short (7/32 of it) requests
    through the continuous-batching scheduler AND through the monolithic
    micro-batcher path — same engine, same compile cache — reporting the
    short jobs' p50/p99 both ways.  The short-job p99 gap IS the
    head-of-line blocking the scheduler removes (docs/serving.md)."""
    import threading
    import time as _time

    import numpy as np

    from raftstereo_tpu.config import (RAFTStereoConfig, SchedConfig,
                                       ServeConfig)
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.serve import (BatchEngine, DynamicBatcher,
                                      IterationScheduler, ServeMetrics)

    import jax

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    long_iters = max(long_iters, 2)
    short_iters = max(1, long_iters * 7 // 32)
    serve_cfg = ServeConfig(
        port=0, buckets=((height, width),), max_batch_size=max_batch,
        max_wait_ms=2.0, queue_limit=max(4 * max_batch, 16),
        iters=long_iters, degraded_iters=short_iters,
        degrade_queue_depth=10 ** 6,  # degradation off: explicit iters only
        sched=SchedConfig(iters_per_step=1,
                          max_iters=max(64, long_iters)))
    metrics = ServeMetrics()
    engine = BatchEngine(model, variables, serve_cfg, metrics)
    # Warm BOTH paths so neither measurement charges an XLA compile:
    # monolithic (long + short executables) and the four phase executables.
    engine.warmup(iters_list=[short_iters, long_iters])
    engine.warmup_sched()
    rng = np.random.default_rng(0)
    pair = tuple(rng.integers(0, 255, (height, width, 3)).astype(np.float32)
                 for _ in range(2))
    n_long, n_short = (2, 6) if quick else (4, 12)

    def run(submit):
        """Submit longs, then shorts mid-flight; per-class latencies."""
        t0 = _time.perf_counter()
        longs = [submit(long_iters) for _ in range(n_long)]
        _time.sleep(0.05)  # the longs are running when the shorts arrive
        lat_short = []
        for _ in range(n_short):
            t = _time.perf_counter()
            submit(short_iters).result(timeout=600)
            lat_short.append((_time.perf_counter() - t) * 1e3)
        for f in longs:
            f.result(timeout=600)
        wall = _time.perf_counter() - t0
        return {
            "short_p50_ms": round(float(np.percentile(lat_short, 50)), 3),
            "short_p99_ms": round(float(np.percentile(lat_short, 99)), 3),
            "wall_s": round(wall, 3),
            "pairs_per_sec": round((n_long + n_short) / wall, 3),
        }

    with IterationScheduler(engine, serve_cfg, metrics) as sched:
        sched_stats = run(lambda it: sched.submit(*pair, iters=it))
    with DynamicBatcher(engine, serve_cfg, metrics) as batcher:
        mono_stats = run(lambda it: batcher.submit(*pair, iters=it))
    return {
        "long_iters": long_iters, "short_iters": short_iters,
        "n_long": n_long, "n_short": n_short,
        "sched": sched_stats, "mono": mono_stats,
        "short_p99_speedup": round(
            mono_stats["short_p99_ms"] / max(sched_stats["short_p99_ms"],
                                             1e-9), 3),
    }


def bench_cascade(height: int, width: int, schedule: str, max_batch: int,
                  corr: str, compute_dtype: str, quick: bool):
    """Speculative-tier-cascade A/B smoke (serve/cascade/,
    docs/serving.md "Tier cascade"): the SAME weights and engine answer
    synthetic exact-GT pairs twice through the iteration scheduler — as
    cascade requests on ``schedule`` and as monolithic default-precision
    requests at the same TOTAL iteration count — reporting the
    fp32-iteration fraction, the masked-EPE gap and per-path latency.
    The cascade's pitch is "most iterations drafted on the cheap tier,
    certified answer": the fraction quantifies the cost side, the EPE
    gap the accuracy side.  Committed negative (docs/perf_notes_r08.md):
    on CPU the int8 leg dequantizes per step, so wall-clock parity — not
    speedup — is the expected latency_ratio here; the fraction is the
    TPU-facing cost metric."""
    import time as _time

    import numpy as np

    from raftstereo_tpu.config import (RAFTStereoConfig, SchedConfig,
                                       ServeConfig)
    from raftstereo_tpu.data.synthetic import ShiftStereoDataset
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.serve import (BatchEngine, IterationScheduler,
                                      ServeMetrics)
    from raftstereo_tpu.serve.cascade import parse_schedule

    import jax

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        # CPU-feasible model, same shrink as the test suite's tiny configs.
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    sched = parse_schedule(schedule)
    cfg = RAFTStereoConfig(corr_implementation=corr,
                           compute_dtype=compute_dtype, **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    serve_cfg = ServeConfig(
        port=0, buckets=((height, width),), max_batch_size=max_batch,
        max_wait_ms=2.0, queue_limit=max(4 * max_batch, 16),
        iters=sched.total_iters,
        sched=SchedConfig(iters_per_step=1,
                          max_iters=max(64, sched.total_iters)),
        cascades=(sched.schedule,))
    metrics = ServeMetrics()
    engine = BatchEngine(model, variables, serve_cfg, metrics)
    # Warm both paths so neither measurement charges an XLA compile: the
    # monolithic comparison rides the default mode's phase executables;
    # warmup_cascade warms both tiers' phases, the four cascade
    # executables AND the handoff transition pair.
    engine.warmup_sched()
    engine.warmup_cascade(iters_per_step=1, schedules=[sched])

    n_pairs = 4 if quick else 8
    ds = ShiftStereoDataset(n=n_pairs, hw=(height, width), seed=0)
    pairs = [(ds[i][1], ds[i][2]) for i in range(n_pairs)]
    gts = np.stack([ds[i][3] for i in range(n_pairs)])
    valid = np.stack([np.asarray(ds[i][4], np.float32)[..., None]
                      for i in range(n_pairs)])
    n_valid = max(float(valid.sum()), 1.0)

    def run(submit):
        """Serve every pair; masked EPE + per-request latency."""
        lat, preds = [], []
        t0 = _time.perf_counter()
        for left, right in pairs:
            t = _time.perf_counter()
            res = submit(left, right).result(timeout=600)
            lat.append((_time.perf_counter() - t) * 1e3)
            preds.append(np.asarray(res.disparity, np.float32))
        wall = _time.perf_counter() - t0
        pred = np.stack(preds)[..., None]
        epe = float((np.abs(pred - gts) * valid).sum() / n_valid)
        return {
            "epe": round(epe, 6),
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "wall_s": round(wall, 3),
            "pairs_per_sec": round(n_pairs / wall, 3),
        }

    with IterationScheduler(engine, serve_cfg, metrics) as scheduler:
        casc = run(lambda a, b: scheduler.submit(a, b, cascade=sched))
        mono = run(lambda a, b: scheduler.submit(a, b,
                                                 iters=sched.total_iters))
    return {
        "schedule": sched.schedule,
        "total_iters": sched.total_iters,
        "fp32_iter_fraction": round(sched.fp32_fraction, 4),
        "n_pairs": n_pairs,
        "cascade": casc, "mono_fp32": mono,
        "epe_gap": round(casc["epe"] - mono["epe"], 6),
        "latency_ratio": round(casc["p50_ms"] / max(mono["p50_ms"], 1e-9),
                               3),
    }


def bench_gru(height: int, width: int, batch: int, iters: int, corr: str,
              compute_dtype: str, reps: int, quick: bool):
    """GRU-backend A/B smoke (mirrors --serve/--sched's shape policy):
    the SAME weights through the test-mode forward with gru_backend
    pinned to "xla" and to "fused" (ops/pallas_gru.py), reporting
    per-pair time for both, the speedup, and the max |disparity| gap —
    so the megakernel's flagship contribution and its numeric envelope
    are measurable in one process.  --quick runs the tiny model with the
    interpret-mode kernel on CPU (a parity smoke, not a perf number)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.integers(0, 255, (batch, height, width, 3)),
                     jnp.float32)
    i2 = jnp.asarray(rng.integers(0, 255, (batch, height, width, 3)),
                     jnp.float32)
    variables = None
    out = {}
    ups = {}
    for backend in ("xla", "fused"):
        cfg = RAFTStereoConfig(corr_implementation=corr,
                               compute_dtype=compute_dtype,
                               gru_backend=backend, **model_kw)
        model = RAFTStereo(cfg)
        if variables is None:   # shared weights: a real A/B
            variables = model.init(jax.random.key(0), (height, width))
        fn = jax.jit(lambda v, a, b, m=model: m.forward(
            v, a, b, iters=iters, test_mode=True))
        up = fn(variables, i1, i2)[1]
        jax.block_until_ready(up)
        ups[backend] = np.asarray(up, np.float32)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(variables, i1, i2))
        dt = (time.perf_counter() - t0) / max(reps, 1)
        out[f"{backend}_ms_per_batch"] = round(dt * 1e3, 3)
        out[f"{backend}_pairs_per_sec"] = round(batch / dt, 3)
    out["speedup"] = round(out["xla_ms_per_batch"]
                           / max(out["fused_ms_per_batch"], 1e-9), 3)
    out["max_abs_diff"] = float(np.abs(ups["fused"] - ups["xla"]).max())
    return out


def bench_quant(height: int, width: int, batch: int, iters: int, corr: str,
                reps: int, quick: bool):
    """Accuracy-tier A/B smoke (mirrors --gru): the SAME weights through
    the test-mode forward at each precision mode — fp32 (the certified
    reference), bf16 (the 'fast' tier) and int8-corr+bf16 (the 'turbo'
    tier, ops/quant.py) — reporting per-pair time for each, the speedups
    over fp32 and the max |disparity| gap vs the fp32 reference, so the
    quantized fast path's contribution and numeric envelope are
    measurable in one process.  --quick runs the tiny model on CPU (a
    parity smoke, not a perf number)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.ops.quant import MODES, config_for_mode

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.integers(0, 255, (batch, height, width, 3)),
                     jnp.float32)
    i2 = jnp.asarray(rng.integers(0, 255, (batch, height, width, 3)),
                     jnp.float32)
    base = RAFTStereoConfig(corr_implementation=corr, **model_kw)
    variables = None
    out = {}
    ups = {}
    for mode in MODES:
        model = RAFTStereo(config_for_mode(base, mode))
        if variables is None:   # shared weights: a real A/B
            variables = model.init(jax.random.key(0), (height, width))
        fn = jax.jit(lambda v, a, b, m=model: m.forward(
            v, a, b, iters=iters, test_mode=True))
        up = fn(variables, i1, i2)[1]
        jax.block_until_ready(up)
        ups[mode] = np.asarray(up, np.float32)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(variables, i1, i2))
        dt = (time.perf_counter() - t0) / max(reps, 1)
        out[f"{mode}_ms_per_batch"] = round(dt * 1e3, 3)
        out[f"{mode}_pairs_per_sec"] = round(batch / dt, 3)
    for mode in ("bf16", "int8"):
        out[f"{mode}_speedup_vs_fp32"] = round(
            out["fp32_ms_per_batch"]
            / max(out[f"{mode}_ms_per_batch"], 1e-9), 3)
        out[f"{mode}_max_abs_diff_vs_fp32"] = float(
            np.abs(ups[mode] - ups["fp32"]).max())
    return out


def bench_sl(height: int, width: int, batch: int, iters: int, corr: str,
             reps: int, quick: bool):
    """Structured-light vs passive forward A/B at one bucket (mirrors
    --gru/--quant): the passive model on random RGB pairs and the SL
    model (12-channel pattern-conditioned inputs through the learned
    projection front, sl/) on exact-GT synthetic SL stacks, reporting
    per-batch time for both and the SL slowdown factor — the cost of the
    pattern front is one extra 3x3 conv per image, so the ratio should
    stay near 1.  --quick runs the tiny model on CPU (a wiring smoke,
    not a perf number)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.sl import SLShiftStereoDataset

    corr = resolve_corr(corr)
    model_kw = {}
    if quick:
        model_kw = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
                        corr_radius=2)
    rng = np.random.default_rng(0)
    ds = SLShiftStereoDataset(n=batch, hw=(height, width))
    inputs = {
        "passive": tuple(
            jnp.asarray(rng.integers(0, 255, (batch, height, width, 3)),
                        jnp.float32) for _ in range(2)),
        "sl": tuple(
            jnp.asarray(np.stack([ds[i][j] for i in range(batch)]))
            for j in (1, 2)),
    }
    out = {}
    for name, (i1, i2) in inputs.items():
        cfg = RAFTStereoConfig(corr_implementation=corr, input_mode=name,
                               **model_kw)
        model = RAFTStereo(cfg)
        variables = model.init(jax.random.key(0), (height, width))
        fn = jax.jit(lambda v, a, b, m=model: m.forward(
            v, a, b, iters=iters, test_mode=True))
        jax.block_until_ready(fn(variables, i1, i2))  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(variables, i1, i2))
        dt = (time.perf_counter() - t0) / max(reps, 1)
        out[f"{name}_ms_per_batch"] = round(dt * 1e3, 3)
        out[f"{name}_pairs_per_sec"] = round(batch / dt, 3)
    out["sl_slowdown_vs_passive"] = round(
        out["sl_ms_per_batch"] / max(out["passive_ms_per_batch"], 1e-9), 3)
    return out


def measure_torch_baseline(height: int, width: int, batch: int, iters: int,
                           reps: int) -> float:
    """Run the reference PyTorch model (random weights) on CPU at the same
    config.  Imported from /root/reference, never copied."""
    import torch

    sys.path.insert(0, "/root/reference")
    sys.path.insert(0, "/root/reference/core")
    from core.raft_stereo import RAFTStereo as TorchRAFTStereo

    ns = argparse.Namespace(
        corr_implementation="reg", corr_levels=4, corr_radius=4,
        n_downsample=2, n_gru_layers=3, hidden_dims=[128, 128, 128],
        slow_fast_gru=False, shared_backbone=False, context_norm="batch",
        mixed_precision=False)
    model = TorchRAFTStereo(ns).eval()
    pad_h = (32 - height % 32) % 32
    pad_w = (32 - width % 32) % 32
    img = torch.zeros(batch, 3, height + pad_h, width + pad_w)
    with torch.no_grad():
        model(img, img, iters=iters, test_mode=True)  # warmup
        t0 = time.perf_counter()
        for _ in range(reps):
            model(img, img, iters=iters, test_mode=True)
        dt = time.perf_counter() - t0
    return batch * reps / dt


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--height", type=int, default=None,
                   help="image height (default 540; 4000 with --tiled)")
    p.add_argument("--width", type=int, default=None,
                   help="image width (default 960; 6000 with --tiled)")
    p.add_argument("--batch", type=int, default=None,
                   help="batch size (default 1; with --serve: "
                        "max_batch_size, default 8)")
    p.add_argument("--iters", type=int, default=None,
                   help="GRU iterations (default 32; --quick lowers it "
                        "only when not given explicitly)")
    p.add_argument("--corr", default="auto",
                   choices=["auto", "reg", "alt", "pallas", "pallas_alt"])
    p.add_argument("--reps", type=int, default=None,
                   help="timed repeats (default 20; 3 under --quick "
                        "unless given explicitly)")
    p.add_argument("--compute_dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--corr_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="correlation volume/fmap storage dtype for the "
                        "pallas and pallas_alt backends (the CUDA kernel's "
                        "fp16 dispatch equivalent); reg/alt pin fp32, "
                        "mirroring the reference's fp32-volume torch paths")
    p.add_argument("--corr_precision", default="highest",
                   choices=["highest", "high", "default"],
                   help="MXU multiply precision for fp32 correlation matmuls")
    p.add_argument("--quick", action="store_true",
                   help="tiny shapes / few reps (CPU development)")
    p.add_argument("--mfu", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="emit FLOP accounting + MFU next to pairs/sec "
                        "(XLA cost model + on-the-spot matmul-ceiling "
                        "measurement; default: on unless --quick)")
    p.add_argument("--realtime", action="store_true",
                   help="benchmark the realtime configuration (shared "
                        "backbone, n_downsample 3, 2 GRU layers, slow_fast, "
                        "7 iters — BASELINE.json config #2)")
    p.add_argument("--measure-baseline", action="store_true",
                   help="re-measure the torch reference baseline (slow)")
    p.add_argument("--train", action="store_true",
                   help="measure training steps/sec (full fwd+bwd+update) "
                        "instead of inference; use with --height 320 "
                        "--width 720 --batch 8 for the reference recipe")
    p.add_argument("--tiled", action="store_true",
                   help="benchmark BASELINE config #5: tiled 4K inference "
                        "(synthetic 6000x4000 pair through eval/tiled.py, "
                        "on-demand corr, host-HBM streaming); --height/"
                        "--width override the image shape")
    p.add_argument("--tile_batch", type=int, default=None,
                   help="tiles per device dispatch for --tiled, default 2 "
                        "(2 under --quick); amortizes "
                        "per-dispatch latency; peak HBM is "
                        "O(tile_batch x tile))")
    p.add_argument("--serve", action="store_true",
                   help="benchmark the serving subsystem end to end: "
                        "in-process HTTP server + closed-loop load-gen "
                        "client; reports achieved pairs/sec and p99 "
                        "latency (--reps = request count, --batch = "
                        "max_batch_size)")
    p.add_argument("--serve_concurrency", type=int, default=4,
                   help="closed-loop load-gen workers for --serve")
    p.add_argument("--sched", action="store_true",
                   help="benchmark the iteration-level continuous-batching "
                        "scheduler: a mixed workload of long (--iters) and "
                        "short (7/32 of it) requests through the scheduler "
                        "vs the monolithic micro-batcher path, reporting "
                        "short-job p50/p99 both ways (the head-of-line "
                        "blocking gap)")
    p.add_argument("--cascade", action="store_true",
                   help="benchmark the speculative tier cascade: cascade "
                        "requests vs monolithic default-precision requests "
                        "through the scheduler at equal total iterations, "
                        "reporting fp32-iteration fraction, masked-EPE gap "
                        "and latency (serve/cascade/, docs/serving.md)")
    p.add_argument("--cascade_schedule", default=None, metavar="SCHEDULE",
                   help="cascade schedule for --cascade (default: "
                        "int8:24+fp32:8; int8:6+fp32:2 under --quick)")
    p.add_argument("--gru", action="store_true",
                   help="A/B the GRU step backends: the same weights "
                        "through the test-mode forward with gru_backend "
                        "pinned to 'xla' and to 'fused' (the Pallas "
                        "megakernel, ops/pallas_gru.py), reporting both "
                        "timings, the speedup and the max |disparity| "
                        "gap; --quick = interpret-mode parity smoke")
    p.add_argument("--quant", action="store_true",
                   help="A/B the accuracy-tier precision modes: the same "
                        "weights through the test-mode forward at fp32, "
                        "bf16 and int8-corr+bf16 (the serving tiers, "
                        "ops/quant.py), reporting all three timings, the "
                        "speedups over fp32 and the max |disparity| gaps; "
                        "--quick = CPU parity smoke")
    p.add_argument("--sl", action="store_true",
                   help="A/B the structured-light workload: the passive "
                        "model on RGB pairs vs the SL model on 12-channel "
                        "pattern-conditioned stacks (sl/, "
                        "docs/structured_light.md), reporting both "
                        "timings and the SL slowdown factor; --quick = "
                        "CPU wiring smoke")
    p.add_argument("--cluster", action="store_true",
                   help="benchmark replicated serving: N engine replicas "
                        "(one per device; --replicas, default 2) behind "
                        "one server, mixed cold + session traffic, "
                        "reporting pairs/sec and the per-replica "
                        "dispatch split (docs/serving.md \"Cluster\")")
    p.add_argument("--replicas", type=int, default=2,
                   help="engine replicas for --cluster/--slo (needs that "
                        "many devices; on CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count)")
    p.add_argument("--slo", action="store_true",
                   help="run the trace-driven SLO harness end to end "
                        "(loadgen/, docs/slo_harness.md): seeded burst "
                        "trace with sessions + tiers + deadlines, "
                        "open-loop replay against a --replicas cluster "
                        "server in scheduler mode, SLO verdict + fitted "
                        "capacity model (--reps = request count)")
    p.add_argument("--chaos", action="store_true",
                   help="run the chaos-mode serving smoke "
                        "(docs/fault_tolerance.md): burst trace replayed "
                        "against a 2-backend router cluster while a "
                        "ChaosPlan blackholes one backend; emits the "
                        "degraded-mode SLO verdict JSON (--reps = "
                        "request count)")
    p.add_argument("--sessions", action="store_true",
                   help="run the durable-session smoke (docs/streaming.md "
                        "\"Durable sessions\"): churny many-session trace "
                        "over a 2-backend router fleet wired to a real "
                        "session tier, busier backend SIGKILLed "
                        "mid-replay; emits warm-rate, zero-lost-session "
                        "and int8 snapshot-byte-reduction JSON (--reps = "
                        "session count)")
    p.add_argument("--stream", action="store_true",
                   help="benchmark the temporal warm-start streaming "
                        "subsystem: N-frame synthetic video sequence, "
                        "warm-started adaptive-iters session vs cold-start "
                        "full-iteration baseline (--frames = sequence "
                        "length, --iters = cold/full count; the ladder is "
                        "iters, iters/2)")
    p.add_argument("--frames", type=int, default=None,
                   help="sequence length for --stream (default 16; 8 "
                        "under --quick unless given explicitly)")
    p.add_argument("--spatial", action="store_true",
                   help="benchmark spatial sharding: ONE pair through the "
                        "(1, N) height-sharded forward vs the "
                        "single-device jit (--shards = mesh width), "
                        "reporting A/B latency and the max |disparity| "
                        "gap (0.0 expected: the sharded program is "
                        "bitwise-identical at fp32)")
    p.add_argument("--shards", type=int, default=4,
                   help="spatial mesh width for --spatial (default 4; on "
                        "a CPU host the devices are virtualized via "
                        "xla_force_host_platform_device_count)")
    p.add_argument("--data", action="store_true",
                   help="measure host data-pipeline throughput (KITTI-size "
                        "decode + sparse augmentation, multiprocess workers) "
                        "in samples/sec")
    p.add_argument("--num_workers", type=int, default=None,
                   help="worker processes for --data (default: SLURM-aware)")
    p.add_argument("--device_photometric", action="store_true",
                   help="with --data: measure the mitigated host pipeline "
                        "(photometric jitter + eraser moved on-device, "
                        "host does decode + spatial aug only)")
    args = p.parse_args(argv)

    # Perf rounds must not land on top of known hazards: the smoke modes
    # refuse to run while the static-analysis baseline has entries
    # (python -m raftstereo_tpu.analysis; docs/static_analysis.md).
    if args.quick or args.serve or args.stream or args.sched \
            or args.cluster or args.gru or args.quant or args.sl \
            or args.spatial or args.slo or args.chaos or args.sessions \
            or args.cascade:
        from raftstereo_tpu.analysis import (baseline_entries,
                                             default_baseline_path)
        try:
            n_dirty = sum(baseline_entries().values())
        except ValueError as e:  # hand-edited baseline gone bad
            sys.exit(f"bench: refusing to run: {e}")
        if n_dirty:
            sys.exit(f"bench: refusing to run: the static-analysis "
                     f"baseline ({default_baseline_path()}) is dirty — "
                     f"{n_dirty} known finding(s).  Fix them (or "
                     "regenerate the baseline) before benchmarking; see "
                     "docs/static_analysis.md.")

    explicit_hw = args.height is not None or args.width is not None
    explicit_iters = args.iters is not None
    explicit_reps = args.reps is not None
    if args.iters is None:
        args.iters = 32
    if args.reps is None:
        args.reps = 20
    if args.batch is None and not args.serve and not args.sched \
            and not args.cluster and not args.slo and not args.cascade:
        args.batch = 1  # --serve/--sched/--cluster/--cascade resolve
        # their own default (8; 4 or 2 in --quick)
    # Defaults keyed on the mode, resolved only when the flag was NOT
    # given — an explicit --height/--width always wins (also under --tiled,
    # also with --quick).
    if args.height is None:
        args.height = 4000 if args.tiled else 540
    if args.width is None:
        args.width = 6000 if args.tiled else 960

    if args.data:
        value = bench_data(args.batch, args.num_workers,
                           args.device_photometric)
        aug = ("spatial-only aug (photometric on device)"
               if args.device_photometric else "sparse aug")
        print(json.dumps({
            "metric": f"data-pipeline samples/sec, KITTI decode + {aug} "
                      f"to 320x720, batch {args.batch}",
            "value": round(value, 2),
            "unit": "samples/sec",
            "vs_baseline": 0.0,
        }))
        return

    if args.quick:
        # Honor the contract stated above: an explicitly given flag wins
        # even under --quick (the old unconditional clobber silently
        # benchmarked 256x320/8 iters whatever the user asked for).
        if not explicit_hw:
            args.height, args.width = 256, 320
        if not explicit_iters:
            args.iters = 8
        if not explicit_reps:
            args.reps = 3
    if args.realtime and not explicit_iters:
        args.iters = 7  # the reference's realtime protocol iteration count

    from raftstereo_tpu.utils import setup_compile_cache

    if (args.cluster or args.spatial or args.slo) \
            and "jax" not in sys.modules \
            and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # A CPU host shows one device by default; fan it out so N
        # replicas (or N spatial shards) exist to place on (no-op under
        # a real TPU runtime, where JAX_PLATFORMS selects the chips).
        # Must happen before the first jax import freezes XLA_FLAGS.
        n_dev = args.shards if args.spatial else args.replicas
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()
    setup_compile_cache()

    if args.slo:
        h, w = args.height, args.width
        batch = args.batch if args.batch is not None else 8
        requests = args.reps
        if args.quick:
            # Tiny model + shape; still crosses trace gen -> open-loop
            # HTTP replay -> verdict -> capacity fit on 2 warmed
            # replicas.  An explicitly given flag wins, as ever.  24
            # requests give every (tier, priority) group members and the
            # session slots 2 full streams.
            if not explicit_hw:
                h, w = 64, 96
            batch = args.batch if args.batch is not None else 2
            requests = max(args.reps, 24)
            if not explicit_iters:
                args.iters = min(args.iters, 2)
        summary = bench_slo(h, w, args.iters, args.replicas, batch,
                            requests, args.serve_concurrency, args.corr,
                            args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"SLO harness pairs/sec @{w}x{h}, {args.replicas} "
                      f"replicas, burst trace (sessions+tiers+deadlines) "
                      f"over HTTP",
            "value": summary["pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.chaos:
        h, w = args.height, args.width
        requests = args.reps
        if args.quick:
            # Tiny model + shape; still crosses trace -> chaos arming ->
            # blackhole -> breaker -> degraded verdict over real HTTP.
            if not explicit_hw:
                h, w = 64, 96
            requests = max(args.reps, 24)
            if not explicit_iters:
                args.iters = min(args.iters, 2)
        summary = bench_chaos(h, w, args.iters, requests,
                              args.serve_concurrency, args.corr,
                              args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"chaos-mode pairs/sec @{w}x{h}, 2 backends behind "
                      f"the router, one blackhole window mid-replay",
            "value": summary["pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.sessions:
        h, w = args.height, args.width
        n_sessions = args.reps
        frames_per_session = 6
        if args.quick:
            # Tiny model + shape; still crosses router + tier + kill +
            # warm tier resume over real HTTP.
            if not explicit_hw:
                h, w = 64, 96
            n_sessions = max(4, min(args.reps, 8))
            if not explicit_iters:
                args.iters = min(args.iters, 2)
        summary = bench_sessions(h, w, args.iters, n_sessions,
                                 frames_per_session, args.corr,
                                 args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"durable-session pairs/sec @{w}x{h}, "
                      f"{summary['sessions']} churny sessions over 2 "
                      f"backends + session tier, busier backend killed "
                      f"mid-replay",
            "value": summary["pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.cluster:
        h, w = args.height, args.width
        batch = args.batch if args.batch is not None else 8
        requests = args.reps
        if args.quick:
            # Tiny model + shape; still crosses HTTP + dispatcher +
            # per-replica warmup with enough traffic to hit BOTH
            # replicas.  An explicitly given flag wins, as ever.  The
            # floor is lower than --serve's 12: the mode runs TWO load
            # phases (cold + sessions) on N warmed replicas, so 8 each
            # already exercises every path.
            if not explicit_hw:
                h, w = 64, 96
            batch = args.batch if args.batch is not None else 2
            requests = max(args.reps, 8)
            if not explicit_iters:
                args.iters = min(args.iters, 2)
        summary = bench_cluster(h, w, args.iters, args.replicas, batch,
                                requests, args.serve_concurrency,
                                args.corr, args.compute_dtype,
                                quick=args.quick)
        record = {
            "metric": f"cluster pairs/sec @{w}x{h}, {args.replicas} "
                      f"replicas, mixed cold+session traffic over HTTP",
            "value": summary["pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.serve:
        h, w = args.height, args.width
        # None = flag not given (an explicit --batch 1 means max_batch 1:
        # the no-batching baseline for quantifying the batcher's gain).
        batch = args.batch if args.batch is not None else 8
        requests = args.reps
        if args.quick:
            # Tiny model + shape; still crosses the full HTTP + batcher
            # path with enough requests to coalesce real batches.
            if not explicit_hw:
                h, w = 64, 96
            batch = args.batch if args.batch is not None else 4
            requests = max(args.reps, 12)
            if not explicit_iters:
                args.iters = min(args.iters, 4)  # keep the smoke fast
        stats = bench_serve(h, w, args.iters, batch, requests,
                            args.serve_concurrency, args.corr,
                            args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"serve pairs/sec @{w}x{h}, {args.iters} GRU iters, "
                      f"max_batch {batch}, dynamic batching over HTTP",
            "value": stats.get("pairs_per_sec", 0.0),
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        for k in ("p50_ms", "p99_ms", "ok", "shed", "timeout", "error",
                  "wall_s", "concurrency", "wire_format",
                  "wire_bytes_per_pair", "wire_mb_sent",
                  "wire_mb_received", "wire_reduction_x", "json"):
            if k in stats:
                record[k] = stats[k]
        print(json.dumps(record))
        return

    if args.sched:
        h, w = args.height, args.width
        batch = args.batch if args.batch is not None else 8
        if args.quick:
            # Tiny model + shape; still runs the full scheduler-vs-
            # monolithic comparison with real join/leave traffic.  An
            # explicitly given flag wins, same contract as --height.
            if not explicit_hw:
                h, w = 64, 96
            batch = args.batch if args.batch is not None else 4
            if not explicit_iters:
                args.iters = 8
        summary = bench_sched(h, w, args.iters, batch, args.corr,
                              args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"sched short-job p99 ms @{w}x{h}, mixed "
                      f"{summary['short_iters']}/{summary['long_iters']}-"
                      f"iter workload, iteration-level continuous batching",
            "value": summary["sched"]["short_p99_ms"],
            "unit": "ms",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.cascade:
        h, w = args.height, args.width
        batch = args.batch if args.batch is not None else 8
        schedule = args.cascade_schedule
        if args.quick:
            # Tiny model + shape; still runs the full cascade-vs-
            # monolithic comparison with a real handoff per request.  An
            # explicitly given flag wins, same contract as --height.
            if not explicit_hw:
                h, w = 64, 96
            batch = args.batch if args.batch is not None else 4
            if schedule is None:
                schedule = "int8:6+fp32:2"
        if schedule is None:
            schedule = "int8:24+fp32:8"
        summary = bench_cascade(h, w, schedule, batch, args.corr,
                                args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"cascade masked-EPE gap @{w}x{h}, "
                      f"{summary['schedule']} vs monolithic at "
                      f"{summary['total_iters']} total iters, "
                      f"iteration-level scheduler",
            "value": summary["epe_gap"],
            "unit": "px",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.gru:
        h, w = args.height, args.width
        batch = args.batch
        reps = args.reps
        if args.quick:
            # Tiny model + shape: the fused kernel runs in interpret
            # mode on CPU, so this is a parity smoke, not a perf
            # number.  An explicitly given flag wins, same contract as
            # --height everywhere else.
            if not explicit_hw:
                h, w = 64, 96
            if not explicit_iters:
                args.iters = 4
            if not explicit_reps:
                reps = 2
        summary = bench_gru(h, w, batch, args.iters, args.corr,
                            args.compute_dtype, reps, quick=args.quick)
        record = {
            "metric": f"gru fused-vs-xla pairs/sec @{w}x{h}, "
                      f"{args.iters} GRU iters, batch {batch}",
            "value": summary["fused_pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.quant:
        h, w = args.height, args.width
        batch = args.batch
        reps = args.reps
        if args.quick:
            # Tiny model + shape: the int8 path runs the XLA integer
            # einsum on CPU, so this is a parity smoke, not a perf
            # number.  An explicitly given flag wins, same contract as
            # --height everywhere else.
            if not explicit_hw:
                h, w = 64, 96
            if not explicit_iters:
                args.iters = 4
            if not explicit_reps:
                reps = 2
        summary = bench_quant(h, w, batch, args.iters, args.corr,
                              reps, quick=args.quick)
        record = {
            "metric": f"quant tier A/B pairs/sec @{w}x{h}, "
                      f"{args.iters} GRU iters, batch {batch} "
                      f"(fp32 vs bf16 vs int8-corr)",
            "value": summary["int8_pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.sl:
        h, w = args.height, args.width
        batch = args.batch
        reps = args.reps
        if args.quick:
            # Tiny model + shape: CPU wiring smoke, not a perf number.
            # An explicitly given flag wins, same contract as --height
            # everywhere else.
            if not explicit_hw:
                h, w = 64, 96
            if not explicit_iters:
                args.iters = 4
            if not explicit_reps:
                reps = 2
        summary = bench_sl(h, w, batch, args.iters, args.corr,
                           reps, quick=args.quick)
        record = {
            "metric": f"sl-vs-passive pairs/sec @{w}x{h}, "
                      f"{args.iters} GRU iters, batch {batch}",
            "value": summary["sl_pairs_per_sec"],
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.stream:
        h, w = args.height, args.width
        frames = args.frames
        if args.quick:
            # Tiny model + shape; still runs the full warm-vs-cold
            # comparison with enough frames for the controller to settle.
            # An explicitly given flag wins, same contract as --height.
            if not explicit_hw:
                h, w = 64, 96
            if not explicit_iters:
                args.iters = 8
            if frames is None:
                frames = 8
        if frames is None:
            frames = 16
        summary = bench_stream(h, w, frames, args.iters, args.corr,
                               args.compute_dtype, quick=args.quick)
        record = {
            "metric": f"stream warm-start ms/frame @{w}x{h}, ladder "
                      f"{summary['ladder']}, {frames} frames",
            "value": summary.get("warm_mean_latency_ms") or 0.0,
            "unit": "ms/frame",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.spatial:
        h, w = args.height, args.width
        reps = args.reps
        if args.quick:
            # Tiny model + a shape that still splits into real slabs on
            # every shard.  An explicitly given flag wins, as ever.
            if not explicit_hw:
                h, w = 64, 96
            if not explicit_iters:
                args.iters = 4
            if not explicit_reps:
                reps = 2
        elif not explicit_hw:
            # The plain default 540 is not slab-divisible; 512 splits
            # into row-multiple slabs for 2/4/8 shards of the flagship
            # config (row multiple 16).
            h = 512
        summary = bench_spatial(h, w, args.iters, args.shards, args.corr,
                                reps, quick=args.quick)
        record = {
            "metric": f"spatial sharded-vs-single ms/pair @{w}x{h}, "
                      f"{args.shards}-shard (1, N) mesh, {args.iters} "
                      f"GRU iters",
            "value": summary["sharded_ms"],
            "unit": "ms",
            "vs_baseline": 0.0,
        }
        record.update(summary)
        print(json.dumps(record))
        return

    if args.tiled:
        h, w = args.height, args.width
        tile_kw = {}
        if args.quick:
            # CPU-feasible geometry that still exercises multi-tile
            # stitching, the batched dispatch, and the tail-group pad;
            # an explicitly passed --height/--width still wins.
            if not explicit_hw:
                h, w = 288, 800
            if args.tile_batch is None:
                args.tile_batch = 2
            tile_kw = dict(tile_hw=(256, 384), overlap=32, margin=64)
        if args.tile_batch is None:
            # 2 tiles/dispatch = 4 images: the fused-encoder gate's
            # crossover (<= 4 images/shard) — tb=3 measured 10% slower
            # because the 6-image dispatch pushes the encoder back to
            # XLA.
            args.tile_batch = 2
        value, extras = bench_tiled(h, w, args.iters, args.corr,
                                    args.compute_dtype, args.tile_batch,
                                    **tile_kw)
        record = {
            "metric": f"tiled 4K pairs/sec @{w}x{h}, {args.iters} GRU "
                      f"iters, host-HBM streaming",
            "value": round(value, 4),
            "unit": "pairs/sec",
            "vs_baseline": 0.0,
        }
        record.update(extras)
        print(json.dumps(record))
        return

    if args.train:
        if args.realtime:
            p.error("--train does not support --realtime (no realtime "
                    "training recipe exists in the reference)")
        if args.measure_baseline:
            p.error("--train does not support --measure-baseline (the torch "
                    "baseline covers the inference path only)")
        mfu = (not args.quick) if args.mfu is None else args.mfu
        value, mfu_stats = bench_train(args.height, args.width, args.batch,
                                       args.iters, args.corr, args.reps,
                                       args.compute_dtype, args.corr_dtype,
                                       args.corr_precision, mfu=mfu)
        record = {
            "metric": f"train-steps/sec/chip @{args.width}x{args.height}, "
                      f"batch {args.batch}, {args.iters} GRU iters",
            "value": round(value, 4),
            "unit": "steps/sec",
            "vs_baseline": 0.0,
        }
        if mfu_stats:
            record.update(mfu_stats)
        print(json.dumps(record))
        return

    mfu = (not args.quick) if args.mfu is None else args.mfu
    value, mfu_stats = bench_jax(args.height, args.width, args.batch,
                                 args.iters, args.corr, args.reps,
                                 args.compute_dtype, args.corr_dtype,
                                 args.corr_precision,
                                 realtime=args.realtime, mfu=mfu)

    baseline = None
    if not args.quick and not args.realtime:
        # (--realtime has its own model config; the cached torch baseline is
        # the flagship config and would not be comparable.)
        if args.measure_baseline or not os.path.exists(BASELINE_CACHE):
            try:
                bval = measure_torch_baseline(args.height, args.width,
                                              args.batch, args.iters, reps=2)
                with open(BASELINE_CACHE, "w") as f:
                    json.dump({"pairs_per_sec": bval,
                               "config": f"{args.width}x{args.height}/"
                                         f"{args.iters}it torch-cpu reg"},
                              f, indent=1)
            except Exception as e:  # baseline is best-effort
                print(f"baseline measurement failed: {e}", file=sys.stderr)
        if os.path.exists(BASELINE_CACHE):
            with open(BASELINE_CACHE) as f:
                baseline = json.load(f)["pairs_per_sec"]

    metric = METRIC
    if args.realtime:
        metric = (f"stereo-pairs/sec/chip @{args.width}x{args.height}, "
                  f"realtime config, {args.iters} GRU iters")
    record = {
        "metric": metric,
        "value": round(value, 4),
        "unit": "pairs/sec",
        "vs_baseline": round(value / baseline, 4) if baseline else 0.0,
    }
    if mfu_stats:
        record.update(mfu_stats)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
