"""KV-cached LM decode benchmark: tokens/sec and per-token latency.

The generation-deployment workload (reference parity: the
RecurrentGradientMachine beam-search path,
gserver/gradientmachines/RecurrentGradientMachine.h:32) on the
decoder-only flagship LM — one jitted XLA while-loop over a static KV
cache (models/transformer_infer.TransformerLMInfer), greedy or beam.
"""

from common import parse_args, get_place, time_loop  # noqa: E402

import jax

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.models.transformer_infer import TransformerLMInfer  # noqa: E402


def main():
    args = parse_args(
        "lm_decode", batch_size=32, iterations=10,
        extra=lambda p: (
            p.add_argument("--max_len", type=int, default=128),
            p.add_argument("--out_len", type=int, default=96),
            p.add_argument("--n_layer", type=int, default=4),
            p.add_argument("--n_head", type=int, default=8),
            p.add_argument("--d_model", type=int, default=512),
            p.add_argument("--beam", type=int, default=1),
            p.add_argument("--vocab", type=int, default=8192)))
    T.transformer_lm(
        vocab_size=args.vocab, max_len=args.max_len,
        n_layer=args.n_layer, n_head=args.n_head, d_model=args.d_model,
        d_inner=args.d_model * 4)
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else None
    infer = TransformerLMInfer(fluid.default_main_program(),
                               fluid.global_scope(), args.n_layer,
                               args.n_head, args.d_model, args.max_len,
                               dtype=dtype)

    gen = jax.jit(lambda: infer.generate(
        args.batch_size, max_out_len=args.out_len,
        beam_size=args.beam))
    out = [gen()]

    def step(i):
        out[:] = [gen()]

    def sync():
        jax.block_until_ready(out[0])

    tps = time_loop(step, args, args.batch_size * args.out_len, "tokens",
                    sync=sync)
    print("=> %.2f ms/token (bs=%d beam=%d)"
          % (1000.0 * args.batch_size / tps, args.batch_size, args.beam))
    return tps


if __name__ == "__main__":
    main()
