"""Standalone flash-attention kernel probe (round-4 directive #2).

The transformer ablation (perf_probe_transformer.py) attributes ~46% of
the 8L/d1024 step to attention whose FLOP share is 13% — the kernel runs
at ~12% MFU while FFN matmuls hit 61%. This probe times fwd+bwd of one
attention call at the bench shape across kernel variants to pick the fix.

Sync protocol: one block_until_ready per window.
"""

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def time_fn(name, fn, *args, iters=20, windows=5):
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(*args)
        jax.block_until_ready(r)
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    med = times[len(times) // 2]
    print("%-34s %8.3f ms  (best %.3f worst %.3f)"
          % (name, med * 1000, times[0] * 1000, times[-1] * 1000),
          flush=True)
    return med


def main():
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--h", type=int, default=8)
    p.add_argument("--t", type=int, default=1024)
    p.add_argument("--d", type=int, default=128)
    args = p.parse_args()
    B, H, T, D = args.b, args.h, args.t, args.d

    from paddle_tpu.ops import flash_attention as FA

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    dy = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)

    # CHAIN = stacked attention calls inside ONE jit, so per-call host
    # dispatch does not swamp the kernel; the chain mirrors the model's
    # 8 layers
    CHAIN = 8
    # causal attention FLOPs (block-skipped ideal): fwd 2 matmuls, bwd 5
    full_fwd = 2 * 2 * B * H * T * T * D
    causal_fwd = full_fwd / 2 * CHAIN
    causal_tot = causal_fwd * 3.5          # fwd + bwd(2.5x)
    print("shape [%d,%d,%d,%d] x%d chained: causal fwd+bwd useful "
          "FLOPs %.1f GF" % (B, H, T, D, CHAIN, causal_tot / 1e9),
          flush=True)

    def fwdbwd(attn_fn):
        def loss(q, k, v):
            c = q
            for _ in range(CHAIN):
                # re-project c through a cheap elementwise twist so XLA
                # cannot CSE the chained calls
                c = attn_fn(c, k, v) + 1e-6 * c
            return jnp.sum(c.astype(jnp.float32) * dy.astype(jnp.float32))

        def run(q, k, v):
            l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return l
        return run

    def report(name, med):
        print("   -> %s: %.1f TF/s = %.1f%% MFU (causal-useful)"
              % (name, causal_tot / med / 1e12,
                 causal_tot / med / 197e12 * 100), flush=True)

    variants = [
        ("ours 256x256 (current)", functools.partial(
            FA.flash_attention, causal=True, force="pallas")),
        ("ours 512x512", functools.partial(
            FA.flash_attention, causal=True, force="pallas",
            block_q=512, block_k=512)),
        ("ours 1024x1024", functools.partial(
            FA.flash_attention, causal=True, force="pallas",
            block_q=1024, block_k=1024)),
        ("dense XLA", functools.partial(
            FA.flash_attention, causal=True, force="dense")),
    ]
    for name, fn in variants:
        try:
            med = time_fn(name, fwdbwd(fn), q, k, v)
            report(name, med)
        except Exception as e:
            print("%s FAILED: %s" % (name, str(e)[:200]), flush=True)

    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_fa, BlockSizes)

        def bundled(q, k, v):
            return jax_fa(q, k, v, causal=True,
                          sm_scale=float(D) ** -0.5)
        med = time_fn("jax bundled flash", fwdbwd(bundled), q, k, v)
        report("jax bundled", med)
    except Exception as e:
        print("jax bundled FAILED: %s" % str(e)[:200], flush=True)

    # fwd-only splits for the winner diagnosis
    def fwd_chain(attn_fn):
        def run(q, k, v):
            c = q
            for _ in range(CHAIN):
                c = attn_fn(c, k, v) + 1e-6 * c
            return jnp.sum(c)
        return run

    for name, fn in [
            ("fwd-only ours 256", functools.partial(
                FA.flash_attention, causal=True, force="pallas")),
            ("fwd-only dense", functools.partial(
                FA.flash_attention, causal=True, force="dense"))]:
        med = time_fn(name, fwd_chain(fn), q, k, v)
        print("   -> fwd: %.1f TF/s (causal-useful %.1f GF)"
              % (causal_fwd / med / 1e12, causal_fwd / 1e9), flush=True)


if __name__ == "__main__":
    main()
