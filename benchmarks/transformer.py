"""Transformer LM benchmark (north star: tokens/sec/chip)."""

import numpy as np

from common import parse_args, get_place, time_loop  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402


def main():
    args = parse_args(
        "transformer", batch_size=16, iterations=30,
        extra=lambda p: (
            p.add_argument("--max_len", type=int, default=256),
            p.add_argument("--n_layer", type=int, default=4),
            p.add_argument("--n_head", type=int, default=8),
            p.add_argument("--d_model", type=int, default=512),
            p.add_argument("--d_inner", type=int, default=2048),
            p.add_argument("--vocab", type=int, default=8192),
            p.add_argument("--packed", type=int, default=1,
                           help="full-length packed sequences (flash "
                                "attention fused path)")))
    avg_cost, _ = T.transformer_lm(
        vocab_size=args.vocab, max_len=args.max_len, n_layer=args.n_layer,
        n_head=args.n_head, d_model=args.d_model, d_inner=args.d_inner,
        packed=bool(args.packed))
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    if args.dtype == "bfloat16":
        fluid.amp.enable_amp()
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    feeds = T.make_lm_batch(rng, args.batch_size, args.max_len, args.vocab)
    if args.packed:
        feeds["mask"] = np.ones_like(feeds["mask"])
    tokens_per_batch = int(feeds["mask"].sum())
    # analytic train FLOPs/token (3x fwd): per layer 8d^2 (qkvo) +
    # 4*d*d_inner (ffn) + 4*T*d (attention); head 2*d*V
    d, t = args.d_model, args.max_len
    flops_tok = 3 * (args.n_layer * (8 * d * d + 4 * d * args.d_inner
                                     + 4 * t * d) + 2 * d * args.vocab)
    total = args.iterations + args.skip_batch_num
    loader = iter(fluid.reader.DeviceLoader(
        fluid.reader.repeat_feed(feeds, total + 1)))

    last = []

    def step(i):
        loss, = exe.run(feed=next(loader), fetch_list=[avg_cost],
                        return_numpy=False)
        last[:] = [loss]

    def sync():
        print("loss %.4f" % float(np.asarray(last[0])))

    tps = time_loop(step, args, tokens_per_batch, "tokens", sync=sync)
    import sys
    print("MFU %.1f%% (%.0f tok/s x %.1f MFLOP/tok / 197 TFLOP/s peak)"
          % (tps * flops_tok / 197e12 * 100, tps, flops_tok / 1e6),
          file=sys.stderr)
    return tps


if __name__ == "__main__":
    main()
