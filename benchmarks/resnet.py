"""ResNet benchmark — parity with reference benchmark/fluid/resnet.py
(north star: ResNet-50 images/sec/chip)."""

import numpy as np

from common import parse_args, get_place, time_loop, synthetic_feeds  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import resnet  # noqa: E402


def main():
    args = parse_args(
        "resnet", batch_size=32, iterations=30,
        extra=lambda p: (
            p.add_argument("--model", default="resnet_imagenet",
                           choices=["resnet_imagenet", "resnet_cifar10"]),
            p.add_argument("--depth", type=int, default=50),
            p.add_argument("--image_size", type=int, default=224)))
    shape = ((3, args.image_size, args.image_size)
             if args.model == "resnet_imagenet" else (3, 32, 32))
    classes = 1000 if args.model == "resnet_imagenet" else 10
    # in-graph synthetic data (create_random_data_generator parity) so the
    # steady-state step measures compute, not the host->device copy
    synth = synthetic_feeds({
        "data": ((args.batch_size,) + shape, "float32", 1.0),
        "label": ((args.batch_size, 1), "int64", classes)})
    image, label, avg_cost, acc = resnet.build_train_net(
        model=args.model, depth=args.depth, image_shape=shape,
        num_classes=classes, learning_rate=0.01,
        image=synth["data"], label=synth["label"])
    if args.dtype == "bfloat16":
        fluid.amp.enable_amp()
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())

    last = []

    def step(i):
        loss, = exe.run(feed={}, fetch_list=[avg_cost],
                        return_numpy=False)
        last[:] = [loss]

    def sync():
        # one blocking fetch per timing window (not per step: that would
        # put the host round trip inside every step)
        print("loss %.4f" % float(np.asarray(last[0])))

    return time_loop(step, args, args.batch_size, "imgs", sync=sync)


if __name__ == "__main__":
    main()
