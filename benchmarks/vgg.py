"""VGG-16 benchmark — parity with reference benchmark/fluid/vgg.py."""

import numpy as np

from common import parse_args, get_place, time_loop  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import vgg  # noqa: E402


def main():
    args = parse_args(
        "vgg", batch_size=32, iterations=20,
        extra=lambda p: p.add_argument("--image_size", type=int,
                                       default=32))
    shape = (3, args.image_size, args.image_size)
    image, label, avg_cost, acc = vgg.build_train_net(
        image_shape=shape, num_classes=10, learning_rate=1e-3)
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    # feeds committed to the DEVICE once: re-uploading the same numpy
    # batch every step would measure the host->device copy, not the
    # chip. Real input overlap is benchmarks/input_pipeline.py's job
    # (DeviceLoader prefetch).
    import jax
    dev = get_place(args).jax_device()    # honor --device CPU/TPU
    xs = jax.device_put(rng.rand(args.batch_size,
                                 *shape).astype(np.float32), dev)
    ys = jax.device_put(
        rng.randint(0, 10, (args.batch_size, 1)).astype(np.int64), dev)

    last = []

    def step(i):
        lv, = exe.run(feed={"data": xs, "label": ys},
                      fetch_list=[avg_cost], return_numpy=False)
        last[:] = [lv]

    def sync():
        # one blocking fetch per timing window (a per-step fetch would
        # put the host round trip inside every step)
        if last:
            print("loss %.4f" % float(np.asarray(last[0])))

    return time_loop(step, args, args.batch_size, "imgs", sync=sync)


if __name__ == "__main__":
    main()
