"""matmul_colstats kernel probe at the ResNet 1x1-conv shapes.

Compares, fwd+bwd chained (8 calls inside one jit, host dispatch
amortized):
  a) lax.conv (NCHW) + separate shifted-stat reduction  (composed path)
  b) NCHW -> transpose -> matmul_colstats -> transpose  (fused-NCHW)
  c) matmul_colstats on channels-last rows directly     (fused-NHWC)
  d) plain XLA matmul + separate stats (channels-last)  (XLA control)
"""

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.ops.matmul_stats import matmul_colstats


def time_fn(name, fn, *args, iters=10, windows=5):
    f = jax.jit(fn)
    r = f(*args)
    float(jnp.sum(r))
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(*args)
        float(jnp.sum(r))
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    med = times[len(times) // 2]
    print("%-34s %8.3f ms" % (name, med * 1000), flush=True)
    return med


def main():
    CHAIN = 8
    shapes = [
        # (N, H, W, Cin, Cout)  — resnet50 bs256 1x1 shapes
        (256, 56, 56, 64, 256),
        (256, 56, 56, 256, 64),
        (256, 14, 14, 1024, 256),
        (256, 7, 7, 512, 2048),
    ]
    for (n, h, w, ci, co) in shapes:
        rng = np.random.RandomState(0)
        x_nchw = jnp.asarray(rng.randn(n, ci, h, w), jnp.bfloat16) * 0.5
        x_rows = jnp.asarray(
            rng.randn(n * h * w, ci), jnp.bfloat16) * 0.5
        wt = jnp.asarray(rng.randn(ci, co), jnp.bfloat16) * 0.1
        w4 = wt.T.reshape(co, ci, 1, 1)
        c = jnp.zeros((co,), jnp.float32)
        print("== shape N%d %dx%d %d->%d" % (n, h, w, ci, co), flush=True)

        def conv_stats(x, w4):
            tot = 0.0
            cur = x
            for _ in range(CHAIN):
                y = jax.lax.conv_general_dilated(
                    cur, w4, (1, 1), [(0, 0), (0, 0)],
                    dimension_numbers=("NCHW", "OIHW", "NCHW"))
                yf = y.astype(jnp.float32)
                s1 = jnp.sum(yf, axis=(0, 2, 3))
                s2 = jnp.sum(yf * yf, axis=(0, 2, 3))
                tot = tot + jnp.sum(s1) + jnp.sum(s2)
                cur = y[:, :ci] if co >= ci else jnp.concatenate(
                    [y] * (ci // co), axis=1)
            return tot

        def fused_nchw(x, wt):
            tot = 0.0
            cur = x
            for _ in range(CHAIN):
                xt = jnp.transpose(cur, (0, 2, 3, 1)).reshape(-1, ci)
                y2, s1, s2 = matmul_colstats(xt, wt, c)
                y = jnp.transpose(y2.reshape(n, h, w, co), (0, 3, 1, 2))
                tot = tot + jnp.sum(s1) + jnp.sum(s2)
                cur = y[:, :ci] if co >= ci else jnp.concatenate(
                    [y] * (ci // co), axis=1)
            return tot

        def fused_rows(xr, wt):
            tot = 0.0
            cur = xr
            for _ in range(CHAIN):
                y2, s1, s2 = matmul_colstats(cur, wt, c)
                tot = tot + jnp.sum(s1) + jnp.sum(s2)
                cur = y2[:, :ci] if co >= ci else jnp.concatenate(
                    [y2] * (ci // co), axis=1)
            return tot

        def xla_rows(xr, wt):
            tot = 0.0
            cur = xr
            for _ in range(CHAIN):
                y2 = cur @ wt
                yf = y2.astype(jnp.float32)
                s1 = jnp.sum(yf, axis=0)
                s2 = jnp.sum(yf * yf, axis=0)
                tot = tot + jnp.sum(s1) + jnp.sum(s2)
                cur = y2[:, :ci] if co >= ci else jnp.concatenate(
                    [y2] * (ci // co), axis=1)
            return tot

        def g(fn):
            return lambda *a: jax.grad(
                lambda *aa: fn(*aa))(*a)[0].astype(jnp.float32).sum()

        time_fn("conv+stats NCHW (composed)",
                lambda x, w4: jax.value_and_grad(conv_stats)(x, w4)[0],
                x_nchw, w4)
        time_fn("fused NCHW (transposes)",
                lambda x, wt: jax.value_and_grad(fused_nchw)(x, wt)[0],
                x_nchw, wt)
        time_fn("fused rows (channels-last)",
                lambda xr, wt: jax.value_and_grad(fused_rows)(xr, wt)[0],
                x_rows, wt)
        time_fn("XLA matmul+stats rows",
                lambda xr, wt: jax.value_and_grad(xla_rows)(xr, wt)[0],
                x_rows, wt)


if __name__ == "__main__":
    main()
