"""Shared benchmark harness (reference benchmark/fluid timing protocol:
skip first N batches, report avg; mnist.py:38-50)."""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(name, batch_size=64, iterations=50, skip=5, extra=None):
    p = argparse.ArgumentParser("%s benchmark" % name)
    p.add_argument("--batch_size", type=int, default=batch_size)
    p.add_argument("--iterations", type=int, default=iterations)
    p.add_argument("--skip_batch_num", type=int, default=skip)
    p.add_argument("--pass_num", type=int, default=1)
    p.add_argument("--device", type=str, default="TPU",
                   choices=["CPU", "TPU", "GPU"])
    p.add_argument("--dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"])
    if extra:
        extra(p)
    args = p.parse_args()
    from paddle_tpu import compile_cache
    compile_cache.configure()
    return args


def get_place(args):
    import paddle_tpu as fluid
    return fluid.CPUPlace() if args.device == "CPU" else fluid.TPUPlace(0)


def time_loop(run_step, args, items_per_batch, unit="items", sync=None):
    """Times `iterations` steps after `skip_batch_num` warmup steps.

    Without `sync`, each run_step() is assumed to sync itself (original
    per-batch protocol). With `sync`, steps are dispatched back-to-back and
    synced ONCE after the last — the JAX protocol: a per-step sync
    would put the host round trip inside every step. `sync` must end in
    work that waits for the device (`block_until_ready`, or a
    device→host fetch; chip_smoke.py's train phase prints both timings).
    Returns items/sec."""
    for i in range(args.skip_batch_num):
        run_step(i)
    if sync:
        sync()
    t0 = time.perf_counter()
    for i in range(args.iterations):
        run_step(args.skip_batch_num + i)
    if sync:
        sync()
    per_batch = (time.perf_counter() - t0) / max(1, args.iterations)
    ips = items_per_batch / per_batch
    print("%.4f ms/batch, %.1f %s/sec" % (1000 * per_batch, ips, unit))
    return ips


def synthetic_feeds(specs):
    """Generate benchmark data IN-GRAPH (reference parity:
    operators/reader/create_random_data_generator_op.cc — synthetic data is
    produced by the framework, so steady-state steps measure compute, not
    host→device transfer). specs: {name: (shape, dtype, hi)}.
    Returns {name: Variable}."""
    import paddle_tpu as fluid
    blk = fluid.default_main_program().current_block()
    out = {}
    for name, (shape, dtype, hi) in specs.items():
        v = blk.create_var(name="synth_" + name, dtype=dtype,
                           shape=tuple(shape))
        if dtype.startswith("int"):
            f = blk.create_var(name="synth_f_" + name, dtype="float32",
                               shape=tuple(shape))
            blk.append_op(type="uniform_random", outputs={"Out": [f]},
                          attrs={"shape": list(shape), "min": 0.0,
                                 "max": float(hi) - 1e-3,
                                 "dtype": "float32"})
            blk.append_op(type="cast", inputs={"X": [f]},
                          outputs={"Out": [v]},
                          attrs={"in_dtype": "float32",
                                 "out_dtype": dtype})
        else:
            blk.append_op(type="uniform_random", outputs={"Out": [v]},
                          attrs={"shape": list(shape), "min": 0.0,
                                 "max": float(hi), "dtype": dtype})
        out[name] = v
    return out
