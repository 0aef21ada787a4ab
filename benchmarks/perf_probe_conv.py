"""Conv-ceiling probe (round-2 verdict #2c): is the measured ~26% MFU
fwd+bwd conv ceiling an XLA-conv artifact, or the chip's real limit?

Tests, per representative ResNet-50 layer shape, fwd+bwd throughput of:
  a) lax.conv_general_dilated (the framework's lowering),
  b) im2col (conv_general_dilated_patches) + MXU matmul,
and a pure-matmul control with the SAME FLOP count as (b)'s GEMM.
Run on the real chip: python benchmarks/perf_probe_conv.py
"""

import os
import sys
import time
import functools

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

PEAK = 197e12

# (name, N, H, W, Cin, Cout, k, stride) — ResNet-50 working set
SHAPES = [
    ("conv2_3x3", 64, 56, 56, 64, 64, 3, 1),
    ("conv3_3x3", 64, 28, 28, 128, 128, 3, 1),
    ("conv4_3x3", 64, 14, 14, 256, 256, 3, 1),
    ("conv2_1x1", 64, 56, 56, 64, 256, 1, 1),
    ("conv4_1x1", 64, 14, 14, 1024, 256, 1, 1),
]


def time_fn(fn, *args, rounds=3, min_window=1.5):
    """One block_until_ready per window; reps grow until one window is
    >= min_window seconds of enqueued work; median over `rounds`."""
    def window(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    jax.block_until_ready(fn(*args))      # compile + settle
    reps = 64
    t = window(reps)
    while t < min_window and reps < 1 << 16:
        reps *= 2
        t = window(reps)
    est = [t / reps] + [window(reps) / reps for _ in range(rounds - 1)]
    est.sort()
    return est[len(est) // 2]


def conv_flops(n, h, w, cin, cout, k, stride):
    oh, ow = h // stride, w // stride
    return 2 * n * oh * ow * cin * cout * k * k


def main():
    rng = np.random.RandomState(0)
    print("%-11s %10s %10s %10s  (fwd+bwd TF/s, MFU at %.0f TF/s peak)"
          % ("shape", "lax.conv", "im2col+mm", "matmul", PEAK / 1e12))
    for name, n, h, w, cin, cout, k, stride in SHAPES:
        x = jnp.asarray(rng.randn(n, h, w, cin).astype(np.float32),
                        dtype=jnp.bfloat16)
        wt = jnp.asarray(rng.randn(k, k, cin, cout).astype(np.float32)
                         * 0.1, dtype=jnp.bfloat16)
        pad = "SAME" if k > 1 else "VALID"
        dn = lax.conv_dimension_numbers(x.shape, wt.shape,
                                        ("NHWC", "HWIO", "NHWC"))

        def conv_loss(x, wt):
            y = lax.conv_general_dilated(x, wt, (stride, stride), pad,
                                         dimension_numbers=dn)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        def conv_probe(x, wt):
            dx, dw = jax.grad(conv_loss, argnums=(0, 1))(x, wt)
            return jnp.float32(jnp.sum(dx.astype(jnp.float32))
                               + jnp.sum(dw.astype(jnp.float32)))

        t_conv = time_fn(jax.jit(conv_probe), x, wt)

        oh, ow = h // stride, w // stride

        def im2col_loss(x, wt):
            # patches: [N, OH, OW, k*k*Cin] then one MXU GEMM
            p = lax.conv_general_dilated_patches(
                x, (k, k), (stride, stride), pad,
                dimension_numbers=dn)
            p2 = p.reshape(n * oh * ow, k * k * cin)
            w2 = wt.transpose(2, 0, 1, 3).reshape(k * k * cin, cout)
            y = p2 @ w2
            return jnp.sum(y.astype(jnp.float32) ** 2)

        def im2col_probe(x, wt):
            dx, dw = jax.grad(im2col_loss, argnums=(0, 1))(x, wt)
            return jnp.float32(jnp.sum(dx.astype(jnp.float32))
                               + jnp.sum(dw.astype(jnp.float32)))

        t_im2col = time_fn(jax.jit(im2col_probe), x, wt)

        # control: the same GEMM with materialized inputs
        a = jnp.asarray(rng.randn(n * oh * ow, k * k * cin)
                        .astype(np.float32), dtype=jnp.bfloat16)
        b = jnp.asarray(rng.randn(k * k * cin, cout).astype(np.float32),
                        dtype=jnp.bfloat16)

        def mm_loss(a, b):
            return jnp.sum((a @ b).astype(jnp.float32) ** 2)

        def mm_probe(a, b):
            da, db = jax.grad(mm_loss, argnums=(0, 1))(a, b)
            return jnp.float32(jnp.sum(da.astype(jnp.float32))
                               + jnp.sum(db.astype(jnp.float32)))

        t_mm = time_fn(jax.jit(mm_probe), a, b)

        fl = 3 * conv_flops(n, h, w, cin, cout, k, stride)  # fwd+bwd
        print("%-11s %7.1f/%2.0f%% %7.1f/%2.0f%% %7.1f/%2.0f%%"
              % (name,
                 fl / t_conv / 1e12, 100 * fl / t_conv / PEAK,
                 fl / t_im2col / 1e12, 100 * fl / t_im2col / PEAK,
                 fl / t_mm / 1e12, 100 * fl / t_mm / PEAK))


if __name__ == "__main__":
    main()
