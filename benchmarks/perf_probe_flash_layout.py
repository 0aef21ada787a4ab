"""One attention call under the profiler: the flash kernels and
everything round them (the probe rows of PRs 29 and 31, PERF.md
section 6).

q, k, v, dy are [B, T, H*D] bf16 as a projection leaves them; the call
is forward and backward (all three gradients), causal. ``--entry bthd``
calls ``flash_bthd`` as the fused model does; ``--entry bhtd`` splits
heads, calls ``flash_attention`` on [B, H, T, D] and merges them again:
what the model did before PR 29, and the only form a tree before it
has (copy this file there). On this tree XLA cancels the split
against the wrapper's own transposes and the two entries read the
same. Prints one JSON line: device ms a call by op kind (numbering
stripped), kernels apart from the rest. The kernels are told by name
(``KERNELS``): every tree has ``flash_fwd``; a tree before PR 31 runs
the backward as ``flash_bwd_dq`` + ``flash_bwd_dkv`` at every shape;
since PR 31 a shape whose T is one block (the default: bf16, T 2048)
runs the one kernel ``flash_bwd``, and a streamed one (``--t 4096``)
the two until PR 39, ``flash_bwd`` since (dq held in VMEM across the
key blocks). A name with no device time reads 0. Needs the chip:
``chiprun -- python benchmarks/perf_probe_flash_layout.py``.
"""

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--entry", choices=["bthd", "bhtd"], default="bthd")
    p.add_argument("--b", type=int, default=4)
    p.add_argument("--t", type=int, default=2048)
    p.add_argument("--h", type=int, default=16)
    p.add_argument("--d", type=int, default=64)
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import tracing
    from paddle_tpu.ops import flash_attention as FA

    b, t, h, d = args.b, args.t, args.h, args.d
    rng = np.random.RandomState(0)
    q, k, v, dy = (jnp.asarray(rng.randn(b, t, h * d) * 0.5, jnp.bfloat16)
                   for _ in range(4))

    if args.entry == "bthd":
        def attend(q, k, v):
            return FA.flash_bthd(q, k, v, h, causal=True)
    else:
        def attend(q, k, v):
            split = lambda x: x.reshape(b, t, h, d).transpose(0, 2, 1, 3)
            out = FA.flash_attention(split(q), split(k), split(v),
                                     causal=True)
            return out.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    @jax.jit
    def call(q, k, v, dy):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(dy)

    jax.block_until_ready(call(q, k, v, dy))
    trace_dir = os.path.join(args.out, "probe_trace_" + args.entry)
    tracing.start(trace_dir)
    for _ in range(args.calls):
        r = call(q, k, v, dy)
    jax.block_until_ready(r)
    tracing.stop()

    ms = collections.Counter()
    for row in tracing.load_rows(trace_dir):
        if row["plane"].startswith("/device:") \
                and row["line"] == tracing.OP_LINE:
            ms[tracing.op_name(row["name"])] += row["dur"] * 1e3 / args.calls
    kernels = {n: round(ms.pop(n, 0.0), 4) for n in KERNELS}
    print(json.dumps({
        "entry": args.entry, "shape": [b, t, h, d],
        "platform": jax.devices()[0].platform,
        "kernels_ms": kernels, "kernels_sum_ms": round(sum(kernels.values()), 4),
        "round_them_ms": {n: round(x, 4) for n, x in ms.most_common()},
        "round_them_sum_ms": round(sum(ms.values()), 4)}))


if __name__ == "__main__":
    main()
