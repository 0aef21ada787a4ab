"""Machine translation (Transformer NMT) benchmark — parity with reference
benchmark/fluid/machine_translation.py (seq2seq wmt14-style)."""

import numpy as np

from common import parse_args, get_place, time_loop  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402


def main():
    args = parse_args(
        "machine_translation", batch_size=32, iterations=20,
        extra=lambda p: (
            p.add_argument("--max_len", type=int, default=64),
            p.add_argument("--n_layer", type=int, default=2),
            p.add_argument("--d_model", type=int, default=256),
            p.add_argument("--dict_size", type=int, default=8192),
            p.add_argument("--packed", type=int, default=0)))
    avg_cost, _ = T.transformer(
        src_vocab_size=args.dict_size, trg_vocab_size=args.dict_size,
        max_len=args.max_len, n_layer=args.n_layer, n_head=8,
        d_model=args.d_model, d_inner=4 * args.d_model,
        label_smooth_eps=0.1, packed=bool(args.packed))
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    b, t = args.batch_size, args.max_len
    lens = rng.randint(t // 2, t + 1, size=b)
    mask = (np.arange(t)[None, :] < lens[:, None]).astype(np.float32)
    pos = np.tile(np.arange(t, dtype=np.int64), (b, 1))
    mk = lambda: (rng.randint(3, args.dict_size, (b, t)) *
                  mask).astype(np.int64)
    tokens = int(mask.sum())
    # device-committed once: per-step re-upload of the same batch would
    # measure the host->device copy, not the chip (see vgg.py note)
    import jax
    dev = get_place(args).jax_device()    # honor --device CPU/TPU
    feeds = {k: jax.device_put(v, dev) for k, v in
             {"src_word": mk(), "src_pos": pos, "src_mask": mask,
              "trg_word": mk(), "trg_pos": pos, "trg_mask": mask,
              "lbl_word": mk()}.items()}

    last = []

    def step(i):
        lv, = exe.run(feed=feeds, fetch_list=[avg_cost],
                      return_numpy=False)
        last[:] = [lv]

    def sync():
        # one blocking fetch per timing window (a per-step fetch would
        # put the host round trip inside every step)
        if last:
            print("loss %.4f" % float(np.asarray(last[0])))

    return time_loop(step, args, tokens, "tokens", sync=sync)


if __name__ == "__main__":
    main()
