"""C-API serving latency benchmark (round-4 directive #8a).

Saves a ResNet-50 inference model, then drives it from the PURE-C
bench_capi binary (pt_predictor_run per call — the deployment path of
the reference's capi/gradient_machine.h consumers) and reports p50/p99
per-call latency at bs1 and bs16.

Per-call latency INCLUDES the host->device feed and the device->host
fetch — it is the number a serving client would observe, not kernel
time.

A chip belongs to one process at a time, and three kinds of process
here need it: the one that builds and saves the model, each bench_capi
run (it embeds Python), and the in-process Python baseline. So the
parent never initialises a JAX backend: every stage that does runs as
a child, one after the other.

Run: python benchmarks/capi_serving.py [--device TPU|CPU]
"""

import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from common import parse_args, get_place  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "paddle_tpu", "native")


def stage_save(args):
    """Child: build ResNet-50, initialise it, save the inference model."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    image = fluid.layers.data(
        "data", [3, args.image_size, args.image_size])
    logits = resnet.resnet_imagenet(image, depth=50, num_classes=1000)
    if args.dtype == "bfloat16":
        fluid.amp.enable_amp()
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(args.model_dir, ["data"], [logits], exe)


def stage_baseline(args):
    """Child: in-process python baseline on the SAME backend, model and
    per-call protocol (feed upload + run + full fetch per call). Prints
    one ``PY <bs> <p50 ms>`` line per batch size."""
    import paddle_tpu as fluid
    # amp stays OFF here regardless of --dtype: the C binary's embedded
    # interpreter runs the saved program in f32 (it never enables amp),
    # so the delta compares identical numerics — the ABI boundary, not
    # bf16-vs-f32 compute
    exe = fluid.Executor(get_place(args))
    prog, feed_names, fetch_targets = \
        fluid.io.load_inference_model(args.model_dir, exe)
    shape = (3, args.image_size, args.image_size)
    rng = np.random.RandomState(0)
    for bs in sorted({1, args.batch_size}):
        x = rng.rand(bs, *shape).astype(np.float32)
        exe.run(prog, feed={feed_names[0]: x},
                fetch_list=fetch_targets)       # warm/compile
        lat = []
        for _ in range(args.iterations):
            t0 = time.perf_counter()
            r, = exe.run(prog, feed={feed_names[0]: x},
                         fetch_list=fetch_targets)
            np.asarray(r)
            lat.append((time.perf_counter() - t0) * 1000)
        lat.sort()
        print("PY %d %.4f" % (bs, lat[len(lat) // 2]), flush=True)


def main():
    args = parse_args(
        "capi_serving", batch_size=16, iterations=50,
        extra=lambda p: (
            p.add_argument("--image_size", type=int, default=224),
            p.add_argument("--stage", choices=("save", "baseline"),
                           help="internal: run one JAX-touching stage "
                                "(the parent starts these as children)"),
            p.add_argument("--model_dir")))
    if args.stage:
        return {"save": stage_save, "baseline": stage_baseline}[
            args.stage](args)

    subprocess.run(["make", "-C", NATIVE, "build/libcapi.so",
                    "build/bench_capi"], check=True, capture_output=True,
                   text=True)
    bench = os.path.join(NATIVE, "build", "bench_capi")
    env = dict(os.environ)
    # PREPEND the repo: the embedded interpreter imports paddle_tpu
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if args.device == "CPU":
        env["JAX_PLATFORMS"] = "cpu"

    results = {}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model")

        def stage(name):
            return subprocess.run(
                [sys.executable, os.path.abspath(__file__)]
                + sys.argv[1:] + ["--stage", name, "--model_dir", path],
                env=env, check=True, capture_output=True, text=True,
                timeout=1800).stdout

        stage("save")
        for bs in sorted({1, args.batch_size}):
            out = subprocess.run(
                [bench, path, "3", str(args.image_size),
                 str(args.image_size), str(bs), str(args.iterations)],
                env=env, capture_output=True, text=True, timeout=900)
            if out.returncode != 0:
                print("bs%d FAILED: %s" % (bs, out.stderr[-400:]),
                      file=sys.stderr)
                continue
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith("LAT")][0]
            p50, p99, mean = (float(v) for v in line.split()[1:])
            results[bs] = (p50, p99, mean)
            print("bs%-3d p50 %.2f ms  p99 %.2f ms  mean %.2f ms  "
                  "(%.1f img/s at p50)"
                  % (bs, p50, p99, mean, bs / p50 * 1000), flush=True)

        # capi-minus-python isolates the C-ABI + embedded-CPython
        # boundary cost: the absolute table cannot be compared to
        # anything, the DELTA is the durable number
        for ln in stage("baseline").splitlines():
            if not ln.startswith("PY "):
                continue
            bs, p50py = int(ln.split()[1]), float(ln.split()[2])
            if bs in results:
                print("bs%-3d in-process python p50 %.2f ms -> C-ABI "
                      "overhead %+.2f ms/call"
                      % (bs, p50py, results[bs][0] - p50py), flush=True)
    return results


if __name__ == "__main__":
    main()
