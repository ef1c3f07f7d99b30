"""Microbenchmark: 3x3-conv formulations inside a Pallas TPU kernel.

De-risks the fused GRU-loop kernel (VERDICT r2 item 1): the round-2
prototypes died at ~72 TF/s because shifting ACTIVATION slices along the
lane-tiled W axis forces Mosaic relayouts.  The data-stationary form tested
here never shifts a matmul operand:

    y[r, w] = sum_{dy,dx} x[r+dy, w+dx] @ W[dy, dx]
            = sum_dx u_dx[r, w+dx],   u_dx[r] = sum_dy x[r+dy] @ W[dy, dx]

* dy reads are row slices on the UNTILED outer axis (free),
* the 9 matmuls take contiguous operands,
* only the three ACCUMULATED outputs are realigned (2 rolls + masks).

Variants:
  xla        — jax.lax XLA conv (the ceiling: ~172 TF/s at gru0 shapes)
  rowslab    — grid over R-row slabs + 2 halo rows per slab
  resident   — whole image resident in VMEM (H+2 zero-padded rows), grid=1

``--fused`` instead runs the SHIPPED production megakernel
(ops/pallas_gru.fused_update — motion encoder + gru0 gates + flow head)
against its XLA reference at the same shapes, so microbench-vs-flagship
divergence is measurable with the real kernel, not just the conv probe.

Usage: python scripts/mb_gru_kernel.py [--h 136] [--w 240] [--cin 384]
                                       [--cout 256] [--reps 50] [--rows 8]
       python scripts/mb_gru_kernel.py --fused [--hd 128] [--corr_ch 64]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--h", type=int, default=136)
    p.add_argument("--w", type=int, default=240)
    p.add_argument("--cin", type=int, default=384)
    p.add_argument("--cout", type=int, default=256)
    p.add_argument("--rows", type=int, default=8)
    p.add_argument("--reps", type=int, default=50)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--fused", action="store_true",
                   help="bench the shipped ops/pallas_gru megakernel vs "
                        "its XLA reference instead of the conv probes")
    p.add_argument("--hd", type=int, default=128,
                   help="--fused: gru0 hidden width")
    p.add_argument("--corr_ch", type=int, default=64,
                   help="--fused: correlation feature width as emitted by "
                        "the lookup (pallas_alt lane pad)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, W, CIN, COUT, R = args.h, args.w, args.cin, args.cout, args.rows
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)

    if args.fused:
        return _run_fused(args, jax, jnp, np, rng, H, W, dtype)
    x = jnp.asarray(rng.normal(size=(H, W, CIN)), dtype)
    # Weights in (dy, dx, CIN, COUT) order, flattened to (9, CIN, COUT).
    wts = jnp.asarray(rng.normal(size=(3, 3, CIN, COUT)) * 0.05, dtype)
    w9 = wts.reshape(9, CIN, COUT)
    flops = 2.0 * H * W * 9 * CIN * COUT

    def bench(fn, *inputs, name):
        f = jax.jit(lambda *a: _loop(fn, args.reps, *a))
        lo = max(args.reps // 5, 1)
        flo = jax.jit(lambda *a: _loop(fn, lo, *a))
        try:
            float(f(*inputs)); float(flo(*inputs))  # compile + warm
        except Exception as e:
            print(f"{name:10s}: FAILED {type(e).__name__}: {str(e)[:200]}")
            return None

        def timed(g):
            t0 = time.perf_counter(); float(g(*inputs))
            return time.perf_counter() - t0

        # Median-of-3 at each rep count: single-shot deltas are dominated
        # by host/dispatch noise.
        t_hi = sorted(timed(f) for _ in range(3))[1]
        t_lo = sorted(timed(flo) for _ in range(3))[1]
        dt = max(t_hi - t_lo, 1e-9) / (args.reps - lo)
        tf = flops / dt / 1e12
        print(f"{name:10s}: {dt*1e6:8.1f} us  {tf:7.1f} TF/s", flush=True)
        return fn(*inputs)

    def _loop(fn, n, *inputs):
        x0 = inputs[0]

        def body(i, carry):
            acc, xx = carry
            y = fn(xx, *inputs[1:])
            s = y.astype(jnp.float32).sum()
            xx = xx + (s * 1e-30).astype(xx.dtype)
            return acc + s, xx

        acc, _ = jax.lax.fori_loop(0, n, body, (jnp.float32(0), x0))
        return acc

    # ---------------------------------------------------------------- XLA
    def xla_conv(xx, wfull):
        return jax.lax.conv_general_dilated(
            xx[None], wfull, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)[0]

    y_ref = bench(xla_conv, x, wts, name="xla")

    # ---------------------------------------------------- shared kernel math
    def accumulate_conv(get_rows, w_ref, W, COUT):
        """sum_dx shift_dx( sum_dy rows(dy) @ W[dy,dx] ) with f32 accum.

        get_rows(dy) -> the (R, W, CIN) slab of input rows r+dy (top/bottom
        rows already included by the caller's halo/pad layout)."""
        col = jax.lax.broadcasted_iota(jnp.int32, (1, W, 1), 1)
        y = None
        for dxi in range(3):
            u = None
            for dyi in range(3):
                m = jax.lax.dot_general(
                    get_rows(dyi - 1), w_ref[dyi * 3 + dxi],
                    (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                u = m if u is None else u + m
            o = dxi - 1
            if o == 0:
                shifted = u
            else:
                # y[:, w] += u[:, w+o]  ->  roll u by -o (mod W: pltpu.roll
                # requires a non-negative shift) and zero the column that
                # wrapped (outside the image = zero padding).
                shifted = pltpu.roll(u, (-o) % W, 1)
                if o == 1:
                    shifted = jnp.where(col < W - 1, shifted, 0.0)
                else:
                    shifted = jnp.where(col > 0, shifted, 0.0)
            y = shifted if y is None else y + shifted
        return y

    # ------------------------------------------------------------- rowslab
    nblk = H // R
    assert H % R == 0

    def rowslab_kernel(x_ref, halo_ref, w_ref, out_ref):
        xx = x_ref[...]

        def get_rows(dy):
            if dy == 0:
                return xx
            if dy == -1:
                return jnp.concatenate([halo_ref[0, 0:1], xx[:-1]], axis=0)
            return jnp.concatenate([xx[1:], halo_ref[0, 1:2]], axis=0)

        out_ref[...] = accumulate_conv(get_rows, w_ref, xx.shape[1],
                                       out_ref.shape[-1])

    def make_halo(xx):
        top = jnp.concatenate([jnp.zeros((1, W, CIN), xx.dtype),
                               xx[R - 1::R][: nblk - 1]], 0)
        bot = jnp.concatenate([xx[R::R], jnp.zeros((1, W, CIN), xx.dtype)], 0)
        return jnp.stack([top, bot], axis=1)  # (nblk, 2, W, CIN)

    def rowslab(xx, w9_):
        halo = make_halo(xx)
        return pl.pallas_call(
            rowslab_kernel,
            out_shape=jax.ShapeDtypeStruct((H, W, COUT), jnp.float32),
            grid=(nblk,),
            in_specs=[
                pl.BlockSpec((R, W, CIN), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 2, W, CIN), lambda i: (i, 0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((9, CIN, COUT), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((R, W, COUT), lambda i: (i, 0, 0),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
        )(xx, halo, w9_)

    y1 = bench(rowslab, x, w9, name="rowslab")

    # ------------------------------------------------------------ resident
    def resident_kernel(x_ref, w_ref, out_ref):
        def get_rows(dy):
            return x_ref[pl.ds(1 + dy, H)]

        out_ref[...] = accumulate_conv(get_rows, w_ref, W, COUT)

    def resident(xx, w9_):
        xp = jnp.pad(xx, ((1, 1), (0, 0), (0, 0)))
        return pl.pallas_call(
            resident_kernel,
            out_shape=jax.ShapeDtypeStruct((H, W, COUT), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
        )(xp, w9_)

    y2 = bench(resident, x, w9, name="resident")

    import numpy as np
    for name, y in (("rowslab", y1), ("resident", y2)):
        if y is not None and y_ref is not None:
            d = float(jnp.abs(y - y_ref).max())
            print(f"  max|{name} - xla| = {d:.3e}")


def _run_fused(args, jax, jnp, np, rng, H, W, dtype):
    """Bench the production megakernel (ops/pallas_gru.fused_update) vs
    its XLA reference at GRU-block shapes: one iteration's finest-level
    update (motion encoder + gates + flow head), corr lookup excluded —
    the same work the flagship loop pays per iteration per level-0 row."""
    import time

    from raftstereo_tpu.ops import pallas_gru as pg

    hd, ck, ext = args.hd, args.corr_ch, args.hd
    cor_planes = min(36, ck)

    def arr(*shape, scale=0.05):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    params = {
        "encoder": {
            "convc1": {"kernel": arr(1, 1, cor_planes, 64),
                       "bias": arr(64)},
            "convc2": {"kernel": arr(3, 3, 64, 64), "bias": arr(64)},
            "convf1": {"kernel": arr(7, 7, 2, 64), "bias": arr(64)},
            "convf2": {"kernel": arr(3, 3, 64, 64), "bias": arr(64)},
            "conv": {"kernel": arr(3, 3, 128, 126), "bias": arr(126)},
        },
        "gru0": {
            "convzr": {"kernel": arr(3, 3, hd + 128 + ext, 2 * hd),
                       "bias": arr(2 * hd)},
            "convq": {"kernel": arr(3, 3, hd + 128 + ext, hd),
                      "bias": arr(hd)},
        },
        "flow_head": {
            "conv1": {"kernel": arr(3, 3, hd, 256), "bias": arr(256)},
            "conv2": {"kernel": arr(3, 3, 256, 2), "bias": arr(2)},
        },
    }
    wpack = pg.pack_update_params(params, ck, ext, dtype)
    h = arr(1, H, W, hd, scale=1.0)
    e = arr(1, H, W, ext, scale=1.0)
    corr = arr(1, H, W, ck, scale=1.0)
    disp = jnp.asarray(rng.normal(size=(1, H, W, 1)), jnp.float32)
    cz, cr, cq = (arr(1, H, W, hd, scale=1.0) for _ in range(3))

    xin = hd + 128 + ext
    flops = 2.0 * H * W * (cor_planes * 64 + 9 * 64 * 64 + 49 * 64
                           + 9 * 64 * 64 + 9 * 128 * 126
                           + 9 * xin * 2 * hd + 9 * xin * hd
                           + 9 * hd * 256 + 9 * 256 * 2)

    def run(f):
        def g(hh):
            hn, dl = f(hh, e, corr, disp, cz, cr, cq, wpack)
            return hn + dl[..., :1]   # keep both outputs live
        return g

    def timed(name, f):
        g = jax.jit(run(f))
        lo = max(args.reps // 5, 1)

        def loop(n):
            def body(i, carry):
                acc, hh = carry
                y = g(hh)
                s = y.astype(jnp.float32).sum()
                return acc + s, hh + (s * 1e-30).astype(hh.dtype)
            return jax.jit(lambda hh: jax.lax.fori_loop(
                0, n, body, (jnp.float32(0), hh))[0])

        f_hi, f_lo = loop(args.reps), loop(lo)
        try:
            float(f_hi(h)); float(f_lo(h))
        except Exception as exc:  # noqa: BLE001 — report, keep going
            print(f"{name:10s}: FAILED {type(exc).__name__}: "
                  f"{str(exc)[:200]}")
            return None

        def once(fn):
            t0 = time.perf_counter(); float(fn(h))
            return time.perf_counter() - t0

        t_hi = sorted(once(f_hi) for _ in range(3))[1]
        t_lo = sorted(once(f_lo) for _ in range(3))[1]
        dt = max(t_hi - t_lo, 1e-9) / max(args.reps - lo, 1)
        print(f"{name:10s}: {dt*1e6:8.1f} us  {flops/dt/1e12:7.1f} TF/s",
              flush=True)
        return f(h, e, corr, disp, cz, cr, cq, wpack)

    y_ref = timed("xla_ref", pg._xla_reference_update)
    y_fused = timed("fused", pg.fused_update)
    if y_ref is not None and y_fused is not None:
        d = max(float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).max())
                for a, b in zip(y_fused, y_ref))
        print(f"  max|fused - xla_ref| = {d:.3e}")


if __name__ == "__main__":
    main()
