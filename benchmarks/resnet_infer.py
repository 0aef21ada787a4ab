"""ResNet-50 inference benchmark — parity with the reference's
IntelOptimizedPaddle.md infer tables (ResNet-50 infer @bs16: 217.69
img/s MKL-DNN; BASELINE.md). Builds the train net, prunes to the logits
via save/load_inference_model, and times test-mode forward."""

import os
import tempfile

import numpy as np

from common import parse_args, get_place, time_loop, synthetic_feeds  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import resnet  # noqa: E402


def main():
    args = parse_args(
        "resnet_infer", batch_size=16, iterations=30,
        extra=lambda p: (
            p.add_argument("--depth", type=int, default=50),
            p.add_argument("--image_size", type=int, default=224)))
    shape = (3, args.image_size, args.image_size)

    image = fluid.layers.data("data", list(shape))
    logits = resnet.resnet_imagenet(image, depth=args.depth,
                                    num_classes=1000)
    if args.dtype == "bfloat16":
        fluid.amp.enable_amp()
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model")
        fluid.io.save_inference_model(path, ["data"], [logits], exe)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            prog, feed_names, fetch_vars = \
                fluid.io.load_inference_model(path, exe)
            x = np.random.RandomState(0).rand(
                args.batch_size, *shape).astype(np.float32)
            # transfer once; steady-state times compute, not the upload
            # (train benches use in-graph data for the same reason)
            import jax
            x = jax.device_put(x, get_place(args).jax_device())

            last = []

            def step(i):
                out, = exe.run(prog, feed={feed_names[0]: x},
                               fetch_list=fetch_vars, return_numpy=False)
                last[:] = [out]

            def sync():
                print("logit[0,0] %.4f"
                      % float(np.asarray(last[0])[0, 0]))

            return time_loop(step, args, args.batch_size, "imgs",
                             sync=sync)


if __name__ == "__main__":
    main()
