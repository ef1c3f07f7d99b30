"""Microbenchmark: the fixed-stage encoders at flagship resolution.

The device trace shows the ~50 ms fixed stage is
~90% data movement around the half-resolution 64-channel convs.  This
harness times the encoder subgraphs in isolation so layout/packing
experiments get a fast measured verdict (the round-2 lesson: microbenches
are hypotheses, the flagship bench is the final verdict — confirm winners
E2E).

Usage: python scripts/mb_encoder.py [--height 540] [--width 960] [--reps 20]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--height", type=int, default=540)
    p.add_argument("--width", type=int, default=960)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--norms", default=None,
                   help="comma list of stem norm variants to run")
    p.add_argument("--stem_only", action="store_true")
    args = p.parse_args()


    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.models.encoders import BasicEncoder, MultiBasicEncoder
    from raftstereo_tpu.ops.image import InputPadder

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (args.batch, args.height, args.width, 3))
    img = jnp.asarray(img.astype(np.float32))
    padder = InputPadder(img.shape, divis_by=32)
    img, _ = padder.pad(img, img)
    img = (2.0 * (img / 255.0) - 1.0).astype(dtype)
    both = jnp.concatenate([img, img], 0)

    def bench(make_fn, x, name):
        fn, variables = make_fn(x)
        jitted = jax.jit(lambda v, a: fn(v, a))

        def run(v, a, n):
            def body(i, acc):
                y = fn(v, a + i.astype(a.dtype) * 0)
                return acc + jax.tree.leaves(y)[0].astype(jnp.float32).sum()
            return jax.lax.fori_loop(0, n, body, jnp.float32(0))

        r = jax.jit(run, static_argnums=(2,))
        lo = max(args.reps // 5, 1)
        float(r(variables, x, lo)); float(r(variables, x, args.reps))
        t0 = time.perf_counter(); float(r(variables, x, args.reps))
        t1 = time.perf_counter(); float(r(variables, x, lo))
        t2 = time.perf_counter()
        dt = max((t1 - t0) - (t2 - t1), 1e-9) / (args.reps - lo)
        print(f"{name:28s}: {dt*1000:8.2f} ms")
        return dt

    def full_fnet(x):
        enc = BasicEncoder(output_dim=256, norm_fn="instance", downsample=2,
                           dtype=dtype)
        v = enc.init(jax.random.key(0), x[:1])
        return (lambda vv, a: enc.apply(vv, a)), v

    def full_cnet(x):
        enc = MultiBasicEncoder(output_dims=((128,) * 3, (128,) * 3),
                                norm_fn="batch", downsample=2, dtype=dtype)
        v = enc.init(jax.random.key(0), x[:1])
        return (lambda vv, a: enc.apply(vv, a)), v

    def make_stem(norm):
        """conv1 + norm + relu + layer1 (the half-res 64-channel stage)
        with a swappable norm, to isolate what makes this stage ~25x off
        its bandwidth floor."""
        import flax.linen as nn

        from raftstereo_tpu.models.layers import conv, make_norm

        class DirectIN(nn.Module):
            """Instance norm with NO lane-packed view: plain reduces."""

            @nn.compact
            def __call__(self, a):
                m = jnp.mean(a, axis=(1, 2), keepdims=True)
                c = a - m
                v = jnp.mean(jnp.square(c), axis=(1, 2), keepdims=True)
                return c * jax.lax.rsqrt(v.astype(jnp.float32) + 1e-5
                                         ).astype(a.dtype)

        class F32StatsIN(nn.Module):
            """Packed view but fp32 stat reduces (materializes fp32 copy)."""

            @nn.compact
            def __call__(self, a):
                m = jnp.mean(a, axis=(1, 2), keepdims=True,
                             dtype=jnp.float32)
                c = a - m.astype(a.dtype)
                v = jnp.mean(jnp.square(c.astype(jnp.float32)), axis=(1, 2),
                             keepdims=True)
                return c * jax.lax.rsqrt(v + 1e-5).astype(a.dtype)

        class MatStatsIN(nn.Module):
            """Stats via MXU: sum(x) and sum(x^2) as ones-vector matmuls
            (fp32 accumulation on the MXU; the elementwise square fuses
            into the second matmul's operand read)."""

            @nn.compact
            def __call__(self, a):
                b, h, w, c = a.shape
                af = a.reshape(b, h * w, c)
                ones = jnp.ones((h * w,), a.dtype)
                s1 = jax.lax.dot_general(
                    ones, af, (((0,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # (b, c)
                s2 = jax.lax.dot_general(
                    ones, af * af, (((0,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # (b, c)
                n = jnp.float32(h * w)
                m = s1 / n
                v = jnp.maximum(s2 / n - m * m, 0.0)
                scale = jax.lax.rsqrt(v + 1e-5)
                mb = m.astype(a.dtype)[:, None, None, :]
                sb = scale.astype(a.dtype)[:, None, None, :]
                return (a - mb) * sb

        class PallasIN(nn.Module):
            fuse_relu: bool = False

            @nn.compact
            def __call__(self, a):
                from raftstereo_tpu.ops.pallas_norm import instance_norm_act
                return instance_norm_act(a, self.fuse_relu)

        # "pad128:<base>" runs the same stage at 128 channels — the
        # zero-weight channel-padding candidate (layout hypothesis: C=128
        # matches the lane width, so the conv and reduce layouts agree and
        # the 4x-padded formatting copies disappear).
        ch = 64
        base = norm
        if norm.startswith("pad128:"):
            ch, base = 128, norm.split(":", 1)[1]

        def picked():
            if base == "pallas":
                return PallasIN()
            if base == "direct":
                return DirectIN()
            if base == "f32stats":
                return F32StatsIN()
            if base == "matstats":
                return MatStatsIN()
            return make_norm(base, ch, dtype)

        class Res(nn.Module):
            @nn.compact
            def __call__(self, a):
                y = nn.relu(picked()(conv(ch, 3, dtype=dtype)(a)))
                y = nn.relu(picked()(conv(ch, 3, dtype=dtype)(y)))
                return nn.relu(a + y)

        class Stem(nn.Module):
            @nn.compact
            def __call__(self, a):
                a = conv(ch, 7, stride=1, padding=3, dtype=dtype)(a)
                a = nn.relu(picked()(a))
                a = Res()(a)
                a = Res()(a)
                return a

        def f(x):
            m = Stem()
            v = m.init(jax.random.key(0), x[:1])
            return (lambda vv, a: m.apply(vv, a)), v

        return f

    norms = (args.norms.split(",") if args.norms
             else ["instance", "none", "direct", "f32stats", "batch"])
    if not args.stem_only:
        bench(full_fnet, both, "fnet (2 imgs, instance)")
        bench(full_cnet, img, "cnet (1 img, frozen batch)")
    for norm in norms:
        bench(make_stem(norm), both, f"stem+layer1 norm={norm}")


if __name__ == "__main__":
    main()
