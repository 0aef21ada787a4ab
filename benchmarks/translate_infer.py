"""Seq2seq inference benchmark: beam-search translate tokens/sec.

Reference parity: the decode path of test_machine_translation.py — but as
ONE jitted XLA while-loop (models/transformer_infer + models/decoding), so
generation needs no host round-trip per token."""

import time

import numpy as np

from common import parse_args, get_place, time_loop  # noqa: E402

import jax
import jax.numpy as jnp

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.models.transformer_infer import TransformerInfer  # noqa: E402


def main():
    args = parse_args(
        "translate_infer", batch_size=32, iterations=20,
        extra=lambda p: (
            p.add_argument("--max_len", type=int, default=64),
            p.add_argument("--out_len", type=int, default=48),
            p.add_argument("--n_layer", type=int, default=2),
            p.add_argument("--n_head", type=int, default=8),
            p.add_argument("--d_model", type=int, default=256),
            p.add_argument("--beam", type=int, default=4),
            p.add_argument("--vocab", type=int, default=8192)))
    avg_cost, _ = T.transformer(
        src_vocab_size=args.vocab, trg_vocab_size=args.vocab,
        max_len=args.max_len, n_layer=args.n_layer, n_head=args.n_head,
        d_model=args.d_model, d_inner=args.d_model * 4)
    exe = fluid.Executor(get_place(args))
    exe.run(fluid.default_startup_program())
    # bf16 serving mode: CPU-verified, not measured on a TPU
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else None
    infer = TransformerInfer(fluid.default_main_program(),
                             fluid.global_scope(), args.n_layer,
                             args.n_head, args.d_model, args.max_len,
                             dtype=dtype)

    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(3, args.vocab,
                                  (args.batch_size, args.max_len)),
                      dtype=jnp.int32)
    mask = jnp.ones((args.batch_size, args.max_len), jnp.float32)

    translate = jax.jit(lambda s, m: infer.translate(
        s, m, beam_size=args.beam, max_out_len=args.out_len))
    out = [translate(src, mask)]

    def step(i):
        out[:] = [translate(src, mask)]

    def sync():
        jax.block_until_ready(out[0])

    # tokens/sec = generated tokens (batch * out_len), beams explored in
    # parallel are the speedup mechanism, not the deliverable
    tps = time_loop(step, args, args.batch_size * args.out_len, "tokens",
                    sync=sync)
    # per-decode-step latency at this batch (the deployment metric):
    # batch_time / out_len = bs / tps
    print("=> %.2f ms/token (bs=%d beam=%d)"
          % (1000.0 * args.batch_size / tps, args.batch_size, args.beam))
    return tps


if __name__ == "__main__":
    main()
