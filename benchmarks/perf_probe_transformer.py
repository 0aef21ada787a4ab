"""Transformer-large step-time breakdown (round-4 directive #2).

Ablation protocol (same as the ResNet delta breakdown, PERF.md round 3):
build the SAME framework LM program with one component removed per
variant, time each on the real chip, and attribute the step-time delta
to that component. A pure-jax twin of the full step bounds framework
overhead; a d_model sweep finds the best honest MFU config for bench.py.

Timing: every window ends in one sync (a device->host scalar fetch);
median over PADDLE_TPU_BENCH_WINDOWS.
"""

import contextlib
import os
import sys
import time

import numpy as np

from common import parse_args, get_place  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

PEAK = 197e12


def build_lm(vocab, max_len, n_layer, n_head, d_model, d_inner,
             use_attn=True, use_ffn=True, use_ln=True, use_head=True,
             use_qkvo=True):
    """transformer_lm (packed/flash path) with per-component switches."""
    d_key = d_model // n_head
    src = layers.data("src", [max_len], dtype="int64")
    pos = layers.data("pos", [max_len], dtype="int64")
    mask = layers.data("mask", [max_len], dtype="float32")
    label = layers.data("label", [max_len], dtype="int64")

    x = T._embed(src, vocab, d_model, max_len, pos, "lm")
    b, t = x.shape[0], x.shape[1]

    def maybe_ln(z):
        return layers.layer_norm(z, begin_norm_axis=len(z.shape) - 1) \
            if use_ln else z

    for _ in range(n_layer):
        if use_qkvo:
            q = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False)
            k = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False)
            v = layers.fc(x, d_model, num_flatten_dims=2, bias_attr=False)
        else:
            q = k = v = x
        if use_attn:
            def heads(z):
                z = layers.reshape(z, [b, t, n_head, d_key])
                return layers.transpose(z, perm=[0, 2, 1, 3])
            ctx = layers.sequence_parallel_attention(
                heads(q), heads(k), heads(v), causal=True)
            ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
            ctx = layers.reshape(ctx, [b, t, d_model])
        else:
            ctx = v
        if use_qkvo:
            ctx = layers.fc(ctx, d_model, num_flatten_dims=2,
                            bias_attr=False)
        x = maybe_ln(layers.elementwise_add(x, ctx))
        if use_ffn:
            h = layers.fc(x, d_inner, num_flatten_dims=2, act="relu")
            f = layers.fc(h, d_model, num_flatten_dims=2)
            x = maybe_ln(layers.elementwise_add(x, f))

    if use_head:
        logits = layers.fc(x, vocab, num_flatten_dims=2, bias_attr=False)
        flat_logits = layers.reshape(logits, [-1, vocab])
        flat_label = layers.reshape(label, [-1, 1])
        cost = layers.softmax_with_cross_entropy(flat_logits, flat_label)
        flat_mask = layers.reshape(mask, [-1, 1])
        masked = layers.elementwise_mul(cost, flat_mask)
        avg = layers.reduce_sum(masked) / layers.reduce_sum(flat_mask)
    else:
        avg = layers.reduce_mean(x)
    return avg


def time_variant(name, args, build_fn, optimizer="adam", windows=None,
                 fwd_only=False):
    prog = fluid.Program()
    startup = fluid.Program()
    from paddle_tpu.core import scope as scope_mod
    scope = scope_mod.Scope()
    with fluid.program_guard(prog, startup):
        avg = build_fn()
        if not fwd_only:
            if optimizer == "adam":
                fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg)
            elif optimizer == "sgd":
                fluid.optimizer.SGD(learning_rate=1e-4).minimize(avg)
        if args.dtype == "bfloat16":
            fluid.amp.enable_amp()
        exe = fluid.Executor(get_place(args))
        with scope_mod.scope_guard(scope):
            exe.run(startup)
            rng = np.random.RandomState(0)
            feeds = T.make_lm_batch(rng, args.batch_size, args.max_len,
                                    args.vocab)
            feeds["mask"] = np.ones_like(feeds["mask"])
            loader = iter(fluid.reader.DeviceLoader(
                fluid.reader.repeat_feed(feeds, 10_000)))
            last = []

            def step():
                loss, = exe.run(prog, feed=next(loader), fetch_list=[avg],
                                return_numpy=False)
                last[:] = [loss]

            def sync():
                return float(np.asarray(last[0]))

            for _ in range(args.skip_batch_num):
                step()
            sync()
            n_windows = windows or max(1, int(os.environ.get(
                "PADDLE_TPU_BENCH_WINDOWS", "5")))
            times = []
            for _ in range(n_windows):
                t0 = time.perf_counter()
                for _ in range(args.iterations):
                    step()
                sync()
                times.append((time.perf_counter() - t0) / args.iterations)
    fluid.amp.enable_amp(False)
    times.sort()
    med = times[len(times) // 2] if len(times) % 2 else \
        0.5 * (times[len(times) // 2 - 1] + times[len(times) // 2])
    print("%-28s %8.2f ms/step  (best %.2f worst %.2f over %d)"
          % (name, med * 1000, times[0] * 1000, times[-1] * 1000,
             n_windows), flush=True)
    return med


def jax_twin(args):
    """Pure-jax flash-attention LM train step, same shapes — the
    framework-overhead bound."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import flash_attention

    L, D, F, V, Tn, B, H = (args.n_layer, args.d_model, args.d_inner,
                            args.vocab, args.max_len, args.batch_size,
                            args.n_head)
    dk = D // H
    key = jax.random.key(0)
    ks = jax.random.split(key, 16)
    p = {"emb": jax.random.normal(ks[0], (V, D), jnp.float32) * 0.02,
         "head": jax.random.normal(ks[1], (D, V), jnp.float32) * 0.02}
    for i in range(L):
        p["l%d" % i] = {
            "q": jax.random.normal(ks[2], (D, D), jnp.float32) * 0.02,
            "k": jax.random.normal(ks[3], (D, D), jnp.float32) * 0.02,
            "v": jax.random.normal(ks[4], (D, D), jnp.float32) * 0.02,
            "o": jax.random.normal(ks[5], (D, D), jnp.float32) * 0.02,
            "f1": jax.random.normal(ks[6], (D, F), jnp.float32) * 0.02,
            "b1": jnp.zeros((F,), jnp.float32),
            "f2": jax.random.normal(ks[7], (F, D), jnp.float32) * 0.02,
            "b2": jnp.zeros((D,), jnp.float32),
            "g1": jnp.ones((D,)), "c1": jnp.zeros((D,)),
            "g2": jnp.ones((D,)), "c2": jnp.zeros((D,))}

    def ln(x, g, c):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + c

    def fwd(p, src, label):
        x = p["emb"][src].astype(jnp.bfloat16)
        for i in range(L):
            lp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p["l%d" % i])
            q = (x @ lp["q"]).reshape(B, Tn, H, dk).transpose(0, 2, 1, 3)
            k = (x @ lp["k"]).reshape(B, Tn, H, dk).transpose(0, 2, 1, 3)
            v = (x @ lp["v"]).reshape(B, Tn, H, dk).transpose(0, 2, 1, 3)
            a = flash_attention(q, k, v, causal=True)
            a = a.transpose(0, 2, 1, 3).reshape(B, Tn, D)
            x = ln((x + a @ lp["o"]).astype(jnp.float32), lp["g1"],
                   lp["c1"]).astype(jnp.bfloat16)
            h = jax.nn.relu(x @ lp["f1"] + lp["b1"])
            x = ln((x + h @ lp["f2"] + lp["b2"]).astype(jnp.float32),
                   lp["g2"], lp["c2"]).astype(jnp.bfloat16)
        logits = (x @ p["head"].astype(jnp.bfloat16)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, label[..., None], -1)[..., 0]
        return (lse - ll).mean()

    def train_step(p, m, v, src, label, step_i):
        loss, g = jax.value_and_grad(fwd)(p, src, label)
        b1, b2, lr, eps = 0.9, 0.999, 1e-4, 1e-8
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        t_ = step_i + 1
        p = jax.tree.map(
            lambda w, mm, vv: w - lr * (mm / (1 - b1 ** t_))
            / (jnp.sqrt(vv / (1 - b2 ** t_)) + eps), p, m, v)
        return p, m, v, loss

    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(0, V, (B, Tn)), jnp.int32)
    label = jnp.asarray(rng.randint(0, V, (B, Tn)), jnp.int32)
    loss = None
    for i in range(3):
        p, m, v, loss = step(p, m, v, src, label, i)
    float(loss)
    n_windows = max(1, int(os.environ.get("PADDLE_TPU_BENCH_WINDOWS", "5")))
    times = []
    si = 3
    for _ in range(n_windows):
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            p, m, v, loss = step(p, m, v, src, label, si)
            si += 1
        float(loss)
        times.append((time.perf_counter() - t0) / args.iterations)
    times.sort()
    med = times[len(times) // 2] if len(times) % 2 else \
        0.5 * (times[len(times) // 2 - 1] + times[len(times) // 2])
    print("%-28s %8.2f ms/step  (best %.2f worst %.2f over %d)"
          % ("pure-jax twin", med * 1000, times[0] * 1000,
             times[-1] * 1000, n_windows), flush=True)
    return med


def bounds(args):
    """Isolated bf16 matmul rates at the EXACT shapes the d1024 step
    runs (default precision — the training numerics), pairing each
    (m,k)x(k,n) with its (m,n)x(n,k) transpose partner so the chain
    stays data-dependent (no fusion shortcut). These are the
    per-component ROOFS the residual table (PERF.md round 5) holds the
    ablation times against: a component whose ablation-implied rate
    matches its isolated rate is at bound — the gap is the shape's,
    not the framework's."""
    import jax
    import jax.numpy as jnp
    n_tok = args.batch_size * args.max_len
    d, f, v = args.d_model, args.d_inner, args.vocab
    shapes = [
        ("qkvo/attn-proj  %dx%d" % (d, d), n_tok, d, d),
        ("ffn-up  %dx%d" % (d, f), n_tok, d, f),
        ("ffn-down  %dx%d" % (f, d), n_tok, f, d),
        ("vocab-head  %dx%d" % (d, v), n_tok, d, v),
        ("chip-roof  8192^3", 8192, 8192, 8192),
    ]
    windows = max(1, int(os.environ.get("PADDLE_TPU_BENCH_WINDOWS", "5")))
    pairs = 8
    key = jax.random.key(0)
    for name, m, k, n in shapes:
        # generated ON DEVICE: no reason to upload hundreds of MB of
        # host arrays for a probe of the matmul units
        ks = jax.random.split(key, 5)
        a = 0.1 * jax.random.normal(ks[0], (m, k), jnp.bfloat16)
        bs = [0.1 * jax.random.normal(ks[1 + i], (k, n), jnp.bfloat16)
              for i in range(2)]
        cs = [0.1 * jax.random.normal(ks[3 + i], (n, k), jnp.bfloat16)
              for i in range(2)]

        @jax.jit
        def chain(a, bs=tuple(bs), cs=tuple(cs)):
            y = a
            for i in range(pairs):
                y = (y @ bs[i % 2]) @ cs[i % 2]
            return y[0, 0]

        float(chain(a))                       # compile + warm
        times = []
        for _ in range(windows):
            t0 = time.perf_counter()
            float(chain(a))                   # value fetch = sync
            times.append(time.perf_counter() - t0)
        med = sorted(times)[len(times) // 2]
        flops = pairs * 2 * (2.0 * m * k * n)
        print("%-28s %7.1f TF/s  (%4.1f%% of peak; %.2f ms/chain)"
              % (name, flops / med / 1e12, flops / med / PEAK * 100,
                 med * 1000), flush=True)


def main():
    args = parse_args(
        "perf_probe_transformer", batch_size=8, iterations=10, skip=3,
        extra=lambda pr: (
            pr.add_argument("--max_len", type=int, default=1024),
            pr.add_argument("--n_layer", type=int, default=8),
            pr.add_argument("--n_head", type=int, default=8),
            pr.add_argument("--d_model", type=int, default=1024),
            pr.add_argument("--d_inner", type=int, default=4096),
            pr.add_argument("--vocab", type=int, default=8192),
            pr.add_argument("--mode", type=str, default="ablate",
                            choices=["ablate", "sweep", "jax", "bounds"])))
    os.environ.setdefault("PADDLE_TPU_BENCH_WINDOWS", "5")
    L, D, F, V, Tn = (args.n_layer, args.d_model, args.d_inner, args.vocab,
                      args.max_len)
    toks = args.batch_size * Tn
    flops_tok = 3 * (L * (8 * D * D + 4 * D * F + 4 * Tn * D) + 2 * D * V)

    def report_mfu(name, med):
        mfu = toks / med * flops_tok / PEAK
        print("   -> %s: %.1f%% MFU (%.0f tok/s)"
              % (name, mfu * 100, toks / med), flush=True)

    if args.mode == "jax":
        med = jax_twin(args)
        report_mfu("pure-jax twin", med)
        return

    if args.mode == "bounds":
        bounds(args)
        return

    if args.mode == "sweep":
        # best honest config hunt: MFU vs width (ffn = 4*d_model,
        # head dim pinned at 128 — the MXU lane width)
        for (d, bs) in [(1024, 8), (1536, 8), (2048, 4), (2048, 8),
                        (3072, 4)]:
            a2 = args
            a2.d_model, a2.d_inner, a2.batch_size = d, 4 * d, bs
            nh = d // 128
            ftok = 3 * (L * (8 * d * d + 4 * d * 4 * d + 4 * Tn * d)
                        + 2 * d * V)
            try:
                med = time_variant(
                    "d%d bs%d" % (d, bs), a2,
                    lambda d=d, bs=bs, nh=nh: build_lm(
                        V, Tn, L, nh, d, 4 * d))
                mfu = bs * Tn / med * ftok / PEAK
                print("   -> d%d bs%d: %.1f%% MFU (%.0f tok/s)"
                      % (d, bs, mfu * 100, bs * Tn / med), flush=True)
            except Exception as e:
                print("d%d bs%d FAILED: %s" % (d, bs, str(e)[:300]),
                      flush=True)
        return

    full = time_variant("full (adam)", args,
                        lambda: build_lm(V, Tn, L, args.n_head, D, F))
    report_mfu("full", full)
    variants = [
        ("no vocab head+CE", dict(use_head=False)),
        ("no flash attention", dict(use_attn=False)),
        ("no qkvo projections", dict(use_qkvo=False)),
        ("no FFN", dict(use_ffn=False)),
        ("no layernorm", dict(use_ln=False)),
    ]
    for name, kw in variants:
        med = time_variant(
            name, args,
            lambda kw=kw: build_lm(V, Tn, L, args.n_head, D, F, **kw))
        print("   delta vs full: %+.2f ms" % ((full - med) * 1000),
              flush=True)
    sgd = time_variant("sgd optimizer", args,
                       lambda: build_lm(V, Tn, L, args.n_head, D, F),
                       optimizer="sgd")
    print("   adam-sgd delta: %+.2f ms" % ((full - sgd) * 1000), flush=True)
    fwd = time_variant("forward only", args,
                       lambda: build_lm(V, Tn, L, args.n_head, D, F),
                       fwd_only=True)
    print("   fwd/full ratio: %.2f" % (fwd / full), flush=True)


if __name__ == "__main__":
    main()
