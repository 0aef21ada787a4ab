"""Input-pipeline sustain benchmark: can the host feed the chip?

Measures the full data plane — recordio files on disk → reader.open_files
(threaded multi-file scan + decode) → paddle.batch → DataFeeder (sample
tuples → feed arrays) → DeviceLoader (prefetch thread, host→device
transfer) — as sustained ResNet-shaped images/sec, against the measured
~2500 img/s TPU training rate (BENCH resnet line). Reference parity:
the double-buffer reader chain (operators/reader/
create_double_buffer_reader_op.cc:34 + open_files_op.cc).

Stages reported separately so a gap is attributable:
  raw      open_files scan+decode only
  feeder   + batch + DataFeeder
  device   + DeviceLoader host->device transfer (the full path)
"""

import os
import tempfile
import time

import numpy as np

from common import parse_args  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import reader as reader_mod  # noqa: E402
from paddle_tpu.reader.device_loader import DeviceLoader  # noqa: E402


def _write_files(tmpdir, n_files, per_file, shape, dtype, compressor):
    """recordio files of (image CHW, label i64) samples."""
    from paddle_tpu import recordio
    comp = recordio.COMPRESSOR_NONE if compressor == "none" \
        else recordio.COMPRESSOR_DEFLATE
    paths = []
    rng = np.random.RandomState(0)
    for f in range(n_files):
        p = os.path.join(tmpdir, "part-%03d.recordio" % f)

        def creator(f=f):
            for i in range(per_file):
                img = rng.rand(*shape).astype(np.float32)
                if dtype == "uint8":
                    img = (img * 255).astype(np.uint8)
                yield (img, np.int64(i % 1000))
        recordio.convert_reader_to_recordio_file(p, creator,
                                                 compressor=comp)
        paths.append(p)
    return paths


def _drain(it, n_items_fn):
    t0 = time.perf_counter()
    n = 0
    for item in it:
        n += n_items_fn(item)
    dt = time.perf_counter() - t0
    return n / dt, n


def main():
    args = parse_args(
        "input_pipeline", batch_size=64, iterations=0,
        extra=lambda p: (
            p.add_argument("--n_files", type=int, default=8),
            p.add_argument("--per_file", type=int, default=256),
            p.add_argument("--image_size", type=int, default=224),
            p.add_argument("--thread_num", type=int, default=4),
            p.add_argument("--sample_dtype", type=str,
                           default="float32",
                           choices=["float32", "uint8"]),
            p.add_argument("--compressor", type=str, default="deflate",
                           choices=["deflate", "none"]),
            p.add_argument("--target_rate", type=float, default=2500.0)))
    shape = (3, args.image_size, args.image_size)
    tmpdir = tempfile.mkdtemp(prefix="ipbench_")
    paths = _write_files(tmpdir, args.n_files, args.per_file, shape,
                         args.sample_dtype, args.compressor)
    total = args.n_files * args.per_file

    def open_all():
        return reader_mod.open_files(paths, thread_num=args.thread_num,
                                     buffer_size=128)

    # stage 1: raw scan+decode
    raw_ips, n = _drain(open_all()(), lambda s: 1)
    assert n == total, (n, total)

    # stage 2: + batch + DataFeeder
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        # uint8 samples stay uint8 through feed + transfer (cast to f32
        # on DEVICE in a real step) — 4x fewer bytes to upload
        img = fluid.layers.data("image", list(shape),
                                dtype=args.sample_dtype)
        lbl = fluid.layers.data("label", [1], dtype="int64")
        feeder = fluid.DataFeeder([img, lbl], program=main_p)
    batched = reader_mod.batch(open_all(), args.batch_size)

    def feed_iter(src):
        for samples in src():
            yield feeder.feed(samples)

    feeder_ips, _ = _drain(feed_iter(lambda: batched()),
                           lambda d: d["image"].shape[0])

    # stage 3: + DeviceLoader prefetch + host->device transfer (full
    # path). device_put ENQUEUES asynchronously, so the clock must run
    # until the last transfer COMPLETES (a one-element fetch of the
    # final batch orders the timeline) — counting enqueues would
    # overstate the upload path.
    batched2 = reader_mod.batch(open_all(), args.batch_size)
    loader = DeviceLoader(feed_iter(lambda: batched2()), capacity=2)
    t0 = time.perf_counter()
    n_img, last = 0, None
    for d in loader:
        n_img += d["image"].shape[0]
        last = d["image"]
    if last is not None:
        np.asarray(last.ravel()[:1])
    device_ips = n_img / (time.perf_counter() - t0)

    print("input_pipeline: raw %.0f img/s | +feeder %.0f img/s | "
          "+device %.0f img/s (target: sustain %.0f img/s)"
          % (raw_ips, feeder_ips, device_ips, args.target_rate))
    verdict = "SUSTAINS" if device_ips >= args.target_rate else "GAP"
    print("=> %s: full-path %.0f img/s vs %.0f img/s train rate (%.1fx)"
          % (verdict, device_ips, args.target_rate,
             device_ips / args.target_rate))
    return device_ips


if __name__ == "__main__":
    main()
