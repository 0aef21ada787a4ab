"""ResNet-50 throughput delta breakdown (round-2 verdict #2b).

The framework trains ResNet-50 at ~2500 img/s while a pure-JAX no-BN
ResNet reaches ~3272 (PERF.md). Attribute the delta by timing the SAME
framework program with components removed:
  full           conv+BN(train)+SGD           (the bench config)
  no_opt         conv+BN(train), no optimizer (grads still computed)
  bn_test        conv+BN(inference stats)+SGD (no batch stats/updates)
  no_bn          conv only (BN layers removed)+SGD
Run on the real chip: python benchmarks/perf_probe_resnet_delta.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import resnet  # noqa: E402
from common import synthetic_feeds  # noqa: E402

BS = 256
ITERS = 12
SKIP = 3
FLOPS_PER_IMG = 3 * 4.1e9
PEAK = 197e12


def bench(tag, use_bn=True, bn_train=True, optimize=True):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        synth = synthetic_feeds({
            "data": ((BS, 3, 224, 224), "float32", 1.0),
            "label": ((BS, 1), "int64", 1000)})
        image, label, avg_cost, acc = resnet.build_train_net(
            model="resnet_imagenet", depth=50,
            image_shape=(3, 224, 224), num_classes=1000,
            learning_rate=0.01, image=synth["data"],
            label=synth["label"], optimize=optimize)
        for op in main.global_block().ops:
            if op.type != "batch_norm":
                continue
            if not bn_train:
                op.attrs["is_test"] = True
            if not use_bn:
                # ablation surgery: BN becomes identity (the act lives
                # in a separate op appended by the layer helper)
                op.type = "assign"
                op.inputs = {"X": op.inputs["X"]}
                op.outputs = {"Out": op.outputs["Y"]}
                op.attrs = {}
        fetch = [avg_cost]
        if not optimize:
            # without optimizer ops nothing consumes the grads — XLA
            # would DCE (part of) the backward. Consume EVERY param grad
            # in-graph via a scalar grad-norm and fetch that: the full
            # backward must run, and only a scalar comes back to the host.
            gb = main.global_block()
            terms = []
            for p in gb.all_parameters():
                gname = p.name + "@GRAD"
                if gname in gb.vars:
                    terms.append(fluid.layers.reduce_sum(
                        fluid.layers.square(gb.var(gname))))
            fetch.append(fluid.layers.sums(terms))
        fluid.amp.enable_amp()
        try:
            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(startup)
            outs = None
            for i in range(SKIP):
                outs = exe.run(main, feed={}, fetch_list=fetch,
                               return_numpy=False)
            float(np.asarray(outs[0]))
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(ITERS):
                    outs = exe.run(main, feed={}, fetch_list=fetch,
                                   return_numpy=False)
                float(np.asarray(outs[0]))
                dt = (time.perf_counter() - t0) / ITERS
                best = dt if best is None else min(best, dt)
        finally:
            fluid.amp.enable_amp(False)
    ips = BS / best
    print("%-8s %7.0f img/s  (%5.1f ms/step, %4.1f%% MFU)"
          % (tag, ips, best * 1e3, 100 * ips * FLOPS_PER_IMG / PEAK))
    return ips


def main():
    full = bench("full")
    no_opt = bench("no_opt", optimize=False)
    bn_test = bench("bn_test", bn_train=False)
    no_bn = bench("no_bn", use_bn=False)
    print("\ndeltas vs full (%.0f img/s):" % full)
    print("  optimizer apply : %+5.0f img/s" % (no_opt - full))
    print("  BN batch stats  : %+5.0f img/s" % (bn_test - full))
    print("  BN entirely     : %+5.0f img/s" % (no_bn - full))


if __name__ == "__main__":
    main()
