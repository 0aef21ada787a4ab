"""Probe: ResNet-50 train-step ceiling in pure JAX, NCHW vs NHWC, bf16.
Isolates the conv layout question from the framework."""

import time
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def conv(x, w, stride, layout):
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape,
        ("NCHW", "OIHW", "NCHW") if layout == "NCHW"
        else ("NHWC", "HWIO", "NHWC"))
    pad = (w.shape[2] // 2, w.shape[2] // 2) if layout == "NCHW" \
        else (w.shape[0] // 2, w.shape[0] // 2)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [pad, pad], dimension_numbers=dn)


def block(params, x, stride, layout, prefix):
    w1, w2, w3, wp = (params[prefix + k] for k in ("w1", "w2", "w3", "wp"))
    c_axis = 1 if layout == "NCHW" else 3
    y = jax.nn.relu(conv(x, w1, 1, layout))
    y = jax.nn.relu(conv(y, w2, stride, layout))
    y = conv(y, w3, 1, layout)
    sc = conv(x, wp, stride, layout) if wp is not None else x
    return jax.nn.relu(y + sc)


DEPTHS = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 23 - 17, 2)]  # 50-layer


def make_params(layout, dtype=jnp.bfloat16):
    rng = np.random.RandomState(0)
    p = {}

    def mk(shape):
        return jnp.asarray(rng.randn(*shape) * 0.05, dtype)

    def cshape(o, i, k):
        return (o, i, k, k) if layout == "NCHW" else (k, k, i, o)

    p["stem"] = mk(cshape(64, 3, 7))
    cin = 64
    stages = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]
    for si, (width, blocks, stride) in enumerate(stages):
        for bi in range(blocks):
            pre = "s%d_b%d_" % (si, bi)
            cout = width * 4
            p[pre + "w1"] = mk(cshape(width, cin, 1))
            p[pre + "w2"] = mk(cshape(width, width, 3))
            p[pre + "w3"] = mk(cshape(cout, width, 1))
            p[pre + "wp"] = mk(cshape(cout, cin, 1)) \
                if (bi == 0) else None
            cin = cout
    p["fc"] = mk((2048, 1000))
    return p


def forward(params, x, layout):
    y = jax.nn.relu(conv(x, params["stem"], 2, layout))
    window = (1, 1, 3, 3) if layout == "NCHW" else (1, 3, 3, 1)
    strides = (1, 1, 2, 2) if layout == "NCHW" else (1, 2, 2, 1)
    y = lax.reduce_window(y, -jnp.inf, lax.max, window, strides, "SAME")
    stages = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]
    for si, (width, blocks, stride) in enumerate(stages):
        for bi in range(blocks):
            y = block(params, y, stride if bi == 0 else 1, layout,
                      "s%d_b%d_" % (si, bi))
    axes = (2, 3) if layout == "NCHW" else (1, 2)
    y = jnp.mean(y, axis=axes)
    logits = y @ params["fc"]
    return logits


def main():
    for layout in ("NCHW", "NHWC"):
        params = make_params(layout)
        bs = 256
        shape = (bs, 3, 224, 224) if layout == "NCHW" \
            else (bs, 224, 224, 3)
        x = jnp.asarray(np.random.rand(*shape), jnp.bfloat16)
        labels = jnp.asarray(np.random.randint(0, 1000, bs))

        def loss_fn(p, x, labels):
            logits = forward(p, x, layout).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], 1))

        @jax.jit
        def step(p, x, labels):
            l, g = jax.value_and_grad(loss_fn)(p, x, labels)
            p2 = jax.tree.map(
                lambda a, b: None if a is None else a - 0.0001 * b,
                p, g, is_leaf=lambda v: v is None)
            return l, p2

        l, p2 = step(params, x, labels)
        jax.block_until_ready(l)
        t0 = time.perf_counter()
        iters = 10
        for _ in range(iters):
            l, params = step(params, x, labels)
        jax.block_until_ready(l)
        dt = (time.perf_counter() - t0) / iters
        ips = bs / dt
        print("%s: %.1f ms/batch, %.1f img/s, MFU %.1f%%"
              % (layout, dt * 1000, ips, ips * 12.3e9 / 197e12 * 100))


if __name__ == "__main__":
    main()


def chained():
    layout = "NCHW"
    params = make_params(layout)
    bs = 256
    x = jnp.asarray(np.random.rand(bs, 3, 224, 224), jnp.bfloat16)
    labels = jnp.asarray(np.random.randint(0, 1000, bs))

    def loss_fn(p, x, labels):
        logits = forward(p, x, layout).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], 1))

    def one(p, _):
        l, g = jax.value_and_grad(loss_fn)(p, x, labels)
        p2 = jax.tree.map(lambda a, b: None if a is None else a - 1e-4 * b,
                          p, g, is_leaf=lambda v: v is None)
        return p2, l

    @jax.jit
    def run10(p):
        p, ls = jax.lax.scan(one, p, None, length=10)
        return p, ls[-1]

    p, l = run10(params)
    np.asarray(l)
    t0 = time.perf_counter()
    p, l = run10(p)
    np.asarray(l)
    dt = (time.perf_counter() - t0) / 10
    ips = bs / dt
    print("chained10: %.1f ms/step, %.1f img/s, MFU %.1f%%"
          % (dt * 1000, ips, ips * 12.3e9 / 197e12 * 100))


if __name__ == "__main__":
    import sys
    if "--chained" in sys.argv:
        chained()
