"""Stacked dynamic LSTM benchmark — parity with reference
benchmark/fluid/stacked_dynamic_lstm.py (LSTM text classification;
reference baseline: 184 ms/batch @ h=512 bs=64 on K40m)."""

import numpy as np

from common import parse_args, get_place, time_loop  # noqa: E402

import paddle_tpu as fluid  # noqa: E402


def build(vocab, hidden, stacked, classes=2):
    words = fluid.layers.data("words", [1], dtype="int64", lod_level=1)
    label = fluid.layers.data("label", [1], dtype="int64")
    x = fluid.layers.embedding(words, size=[vocab, hidden])
    for _ in range(stacked):
        proj = fluid.layers.fc(x, 4 * hidden)
        h, c = fluid.layers.dynamic_lstm(proj, size=4 * hidden,
                                         use_peepholes=False)
        x = h
    pooled = fluid.layers.sequence_pool(x, "max")
    pred = fluid.layers.fc(pooled, classes, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return words, label, loss


def main():
    args = parse_args(
        "stacked_dynamic_lstm", batch_size=64, iterations=20,
        extra=lambda p: (
            p.add_argument("--hidden_dim", type=int, default=512),
            p.add_argument("--stacked_num", type=int, default=3),
            p.add_argument("--seq_len", type=int, default=80),
            p.add_argument("--vocab", type=int, default=5000)))
    words, label, loss = build(args.vocab, args.hidden_dim,
                               args.stacked_num)
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    lens = rng.randint(args.seq_len // 2, args.seq_len + 1,
                       size=args.batch_size).tolist()
    ids = rng.randint(0, args.vocab, (sum(lens), 1)).astype(np.int64)
    t = fluid.create_lod_tensor(ids, [lens])
    ys = rng.randint(0, 2, (args.batch_size, 1)).astype(np.int64)

    last = []

    def step(i):
        lv, = exe.run(feed={"words": t, "label": ys}, fetch_list=[loss],
                      return_numpy=False)
        last[:] = [lv]

    def sync():
        # one blocking fetch per timing window (not per step: that
        # would put the host round trip inside every step)
        if last:
            print("loss %.4f" % float(np.asarray(last[0])))

    tps = time_loop(step, args, sum(lens), "tokens", sync=sync)
    # the reference anchor is ms/BATCH (benchmark/README.md:108-117,
    # 184 ms/batch at h=512 bs=64) — report in its unit
    ms_per_batch = 1000.0 * sum(lens) / tps
    print("=> %.1f ms/batch (reference K40m anchor: 184 ms/batch)"
          % ms_per_batch)
    return ms_per_batch


if __name__ == "__main__":
    main()
