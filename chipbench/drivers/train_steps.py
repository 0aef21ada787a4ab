"""Driver ``train_steps``: pre-training steps back to back.

The configuration's model through the trainer's normal path
(``transformer_lm`` -> Adam -> bf16 AMP -> ``Executor``), seeded random
packed batches prepared before the window and fed as numpy feeds.

The window: steps are dispatched back to back, at most two in flight
(the host stays ahead of the device and never runs away from it), until
the host clock passes ``seconds``; one ``block_until_ready`` on the
last loss ends it. The rate is all tokens over all of that time.
"""

import math
import time

import numpy as np

from chipbench import tracing, traffic as traffic_mod
from chipbench.reference import compare, opt_lm

IN_FLIGHT = 2


def params_of_program(program, scope, n_layer):
    """The reference's parameter tree from a training program: its
    parameter stream in op order (``extract_params``: roles lookup, mul,
    bias, layer_norm), read as ``transformer_lm`` lays it out."""
    from paddle_tpu.models.transformer_infer import extract_params
    stream = list(extract_params(program, scope))

    def take(role):
        if stream and stream[0][0] == role:
            arrays = stream.pop(0)[1]
            return arrays[0] if len(arrays) == 1 else tuple(arrays)
        raise SystemExit("chipbench: parameter stream wants %r, has %r"
                         % (role, stream[0][0] if stream else None))

    params = {"word_emb": take("lookup"), "pos_emb": take("lookup"),
              "layers": []}
    for _ in range(n_layer):
        p = {k: take("mul") for k in ("wq", "wk", "wv", "wo")}
        p["ln1"] = take("layer_norm")
        p["ffn_w1"], p["ffn_b1"] = take("mul"), take("bias")
        p["ffn_w2"], p["ffn_b2"] = take("mul"), take("bias")
        p["ln2"] = take("layer_norm")
        params["layers"].append(p)
    params["w_out"] = take("mul")
    if stream:
        raise SystemExit("chipbench: %d parameters of the program are "
                         "not in the reference" % len(stream))
    return params


def run(cell, seed, seconds, devices, t_start, trace_dir, log):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    cfg, mix = cell["config_file"], cell["traffic_file"]
    seq, batch = int(mix["seq_len"]), int(mix["batch"])
    rows, n_head = int(mix["check_rows"]), cfg["num_attention_heads"]
    main, startup = fluid.Program(), fluid.Program()
    # the executors fold random_seed * 1000003 into a uint32
    main.random_seed = startup.random_seed = 1 + seed % 4093
    scope = fluid.Scope()
    on_tpu = devices[0].platform == "tpu"
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        avg_cost, logits = T.transformer_lm(
            packed=True, vocab_size=cfg["vocab_size"], max_len=seq,
            n_layer=cfg["num_hidden_layers"], n_head=n_head,
            d_model=cfg["hidden_size"], d_inner=cfg["ffn_dim"])
        forward = main.clone(for_test=True)    # before the backward
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        fluid.amp.enable_amp()
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu
                             else fluid.CPUPlace())
        exe.run(startup)
        step = lambda feed: exe.run(main, feed=feed,
                                    fetch_list=[avg_cost],
                                    return_numpy=False)[0]
        feeds = traffic_mod.lm_batches(seed, int(mix["n_batches"]),
                                       batch, seq, cfg["vocab_size"])
        log("train: %d layers, batch %d x %d tokens, one chip" % (
            cfg["num_hidden_layers"], batch, seq))

        # the reference on the first batch, from the parameters as
        # initialised (the first step donates and updates them): its
        # loss, and the logits of the first sequence's last rows, which
        # see the longest contexts. The loss of a fresh model is about
        # ln V whatever the precision; the logits are what a lower
        # precision than bf16 AMP would move.
        first = feeds[0]
        params = params_of_program(main, scope, cfg["num_hidden_layers"])
        t0 = time.perf_counter()
        ref_loss = float(jax.jit(opt_lm.lm_loss, static_argnums=4)(
            params, first["src"], first["label"], first["mask"], n_head))
        ref_logits = np.asarray(jax.jit(
            opt_lm.logits_at, static_argnums=(3, 4))(
                params, jnp.asarray(first["src"][0]), seq - rows, rows,
                n_head))
        one = {k: v[:1] for k, v in first.items()}
        got_logits = np.asarray(exe.run(
            forward, feed=one, fetch_list=[logits],
            return_numpy=False)[0][0, seq - rows:], np.float32)
        logits_err = compare.logits_error(got_logits, ref_logits)
        log("reference float32 loss on batch 0: %.6f; the program's "
            "bf16-AMP forward on its first sequence, last %d rows of "
            "logits: error %.3e of the largest logit (tolerance %.0e) "
            "(%.1f s)" % (ref_loss, rows, logits_err,
                          compare.TRAIN_LOGITS_RTOL,
                          time.perf_counter() - t0))

        t0 = time.perf_counter()
        warm = [step(feeds[i % len(feeds)])
                for i in range(int(mix["warmup_steps"]))]
        warm = [float(np.asarray(x)) for x in warm]
        log("warm-up: %d steps in %.1f s (first compiles or loads "
            "from the cache), losses %s" % (
                len(warm), time.perf_counter() - t0,
                " ".join("%.4f" % x for x in warm)))
        loss_err = compare.loss_error(warm[0], ref_loss)
        log("first loss %.6f against the reference: relative error "
            "%.2e (tolerance %.0e)" % (warm[0], loss_err,
                                       compare.LOSS_RTOL))

        setup_s = time.perf_counter() - t_start
        trace_steps = int(mix["trace_steps"])
        losses, dispatch_ms = [], []
        tracing_on = False
        traced_s = 0.0     # the traced stretch, profiler start and stop
                           # included: left out of the MFU's rate
        t_win = time.perf_counter()
        i = 0
        while True:
            if trace_dir and i == IN_FLIGHT + 1:
                jax.block_until_ready(losses[-1])
                t_tr = time.perf_counter()
                tracing.start(trace_dir)
                tracing_on = True
            t_a = time.perf_counter()
            losses.append(step(feeds[i % len(feeds)]))
            dispatch_ms.append(1e3 * (time.perf_counter() - t_a))
            i += 1
            if i > IN_FLIGHT:
                jax.block_until_ready(losses[i - 1 - IN_FLIGHT])
            if tracing_on and i == IN_FLIGHT + 1 + trace_steps:
                jax.block_until_ready(losses[-1])
                tracing.stop()
                tracing_on = False
                traced_s = time.perf_counter() - t_tr
            if time.perf_counter() - t_win >= seconds:
                break
        jax.block_until_ready(losses[-1])
        window_s = time.perf_counter() - t_win
        if tracing_on:               # the window ended inside the trace
            tracing.stop()
            traced_s = time.perf_counter() - t_tr
        losses = [float(np.asarray(x)) for x in losses]
        fluid.amp.enable_amp(False)
    finite = all(math.isfinite(x) for x in warm + losses)
    log("window: %d steps in %.3f s; losses %.4f .. %.4f; all finite: "
        "%s" % (len(losses), window_s, losses[0], losses[-1], finite))
    return {"correct": finite and loss_err <= compare.LOSS_RTOL
            and logits_err <= compare.TRAIN_LOGITS_RTOL,
            "attempted": len(losses),
            "failed": sum(not math.isfinite(x) for x in losses),
            "setup_s": setup_s,
            "train": {"steps": len(losses), "window_s": window_s,
                      "tokens_per_step": batch * seq, "batch": batch,
                      "seq_len": seq, "dispatch_ms": dispatch_ms,
                      "traced_steps": trace_steps if trace_dir else 0,
                      "traced_s": traced_s}}
