"""Driver ``open_loop``: requests on a schedule, whatever the system
does with them.

The configuration's model in bfloat16 through ``TransformerLMInfer`` +
``serving.Engine`` with the engine's own defaults (paged pool, radix
cache, 16-token chunks, the gather path for bfloat16); the model's
``end_id`` is one no request can emit, so each request yields exactly
its ``max_new`` tokens.

One generator thread sleeps to each request's absolute due time and
submits it; nothing waits for a reply. Load starts ``pre_roll_s``
before the window and goes on after it while the window's requests
drain, at most ``drain_s``. The metrics cover the requests DUE inside
the window, each timed from when it was due. A request not finished
when the drain limit ends counts as failed.
"""

import threading
import time

import numpy as np

from chipbench import tracing, traffic as traffic_mod
from chipbench.reference import compare, opt_lm

STAT_KEYS = ("decode_steps", "active_slot_steps", "prefill_chunks",
             "prefix_hit_tokens", "preemptions", "tokens", "steps")


def params_of(model):
    """The reference's parameter tree from a ``TransformerLMInfer``."""
    layers = [{"wq": p["attn"]["wq"], "wk": p["attn"]["wk"],
               "wv": p["attn"]["wv"], "wo": p["attn"]["wo"],
               "ln1": tuple(p["ln1"]), "ln2": tuple(p["ln2"]),
               "ffn_w1": p["ffn_w1"], "ffn_b1": p["ffn_b1"],
               "ffn_w2": p["ffn_w2"], "ffn_b2": p["ffn_b2"]}
              for p in model.layers]
    return {"word_emb": model.word_emb, "pos_emb": model.pos_emb,
            "w_out": model.w_out, "layers": layers}


def served_logits(model, tokens, first, count):
    """The served model's own teacher-forced logits: ``count`` rows
    after positions first.. of ``tokens`` [rows, T], from
    ``model._step_logits`` scanned over the positions. The weights ride
    as ARGUMENTS (a shallow copy of the model carries the traced
    arrays), so this one program is small and the compile cache keeps
    it, unlike the engine's, which close over the weights."""
    import copy
    import jax
    import jax.numpy as jnp

    def fn(weights, tokens):
        m = copy.copy(model)
        m.word_emb, m.pos_emb, m.layers, m.w_out = weights

        def body(state, t):
            logits, state = m._step_logits(tokens[:, t], state, t)
            return state, logits
        _, logits = jax.lax.scan(body, m._init_state(tokens.shape[0]),
                                 jnp.arange(tokens.shape[1]))
        return jax.lax.dynamic_slice_in_dim(logits, first, count)
    weights = (model.word_emb, model.pos_emb, model.layers, model.w_out)
    return jax.jit(fn)(weights, tokens)      # [count, rows, V]


def check_sample(model, eng, seed, mix, vocab, n_head, log):
    """Warm-up and correctness in one: a seeded sample of requests
    through the engine (which compiles its prefill, activate and decode
    programs), then the comparisons of ``reference/compare.py``."""
    import jax
    import jax.numpy as jnp
    rows, plen = int(mix["sample_requests"]), int(mix["sample_prompt"])
    new = int(mix["sample_max_new"])
    prompts = [traffic_mod.prompt_ids(seed, -1 - r, plen, vocab)
               for r in range(rows)]
    t0 = time.perf_counter()
    handles = [eng.submit(p, new) for p in prompts]
    outs = [h.result(timeout=900) for h in handles]
    log("sample: %d requests of %d + %d tokens through the engine in "
        "%.1f s (its programs compile here)" % (
            rows, plen, new, time.perf_counter() - t0))
    ok = all(len(toks) == new for toks, _ in outs)
    # teacher-forced along the engine's own tokens
    seqs = np.asarray([p + toks[:-1] for p, (toks, _) in
                       zip(prompts, outs)], np.int32)
    params = params_of(model)
    t0 = time.perf_counter()
    got = np.asarray(served_logits(model, seqs, plen - 1, new),
                     np.float32).transpose(1, 0, 2)     # [rows, new, V]
    ref = np.stack([np.asarray(jax.jit(
        opt_lm.logits_at, static_argnums=(3, 4))(
            params, jnp.asarray(s), plen - 1, new, n_head))
        for s in seqs])
    err = compare.logits_error(got, ref)
    worst_gap, score_err = 0.0, 0.0
    for r, (toks, score) in enumerate(outs):
        worst_gap = max(worst_gap,
                        float(compare.tie_gaps(ref[r], toks).max()))
        logp = np.asarray(jax.nn.log_softmax(ref[r]))
        want = float(logp[np.arange(new), toks].sum())
        score_err = max(score_err, abs(score - want) / new)
    log("served bf16 logits against the float32 reference: error %.3e "
        "of the largest logit (tolerance %.0e); engine tokens sit at "
        "most %.3e below the reference's top logit (near-tie %.3e); "
        "engine log-prob per token off by at most %.3e (%.1f s)" % (
            err, compare.LOGITS_RTOL, worst_gap, compare.NEAR_TIE,
            score_err, time.perf_counter() - t0))
    return ok and err <= compare.LOGITS_RTOL \
        and worst_gap <= compare.NEAR_TIE


def build(cell, seed, devices, log):
    """The configuration's model, served: (model, engine)."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.transformer_infer import TransformerLMInfer

    cfg = cell["config_file"]
    vocab, max_len = cfg["vocab_size"], cfg["max_position_embeddings"]
    n_head = cfg["num_attention_heads"]
    main, startup = fluid.Program(), fluid.Program()
    # the executors fold random_seed * 1000003 into a uint32
    main.random_seed = startup.random_seed = 1 + seed % 4093
    scope = fluid.Scope()
    on_tpu = devices[0].platform == "tpu"
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        T.transformer_lm(
            vocab_size=vocab, max_len=max_len,
            n_layer=cfg["num_hidden_layers"], n_head=n_head,
            d_model=cfg["hidden_size"], d_inner=cfg["ffn_dim"])
        fluid.Executor(fluid.TPUPlace(0) if on_tpu
                       else fluid.CPUPlace()).run(startup)
    if cfg["serve_dtype"] != "bfloat16":
        raise SystemExit("open_loop serves bfloat16; the configuration "
                         "says %r" % (cfg["serve_dtype"],))
    # end_id = vocab: argmax over the vocabulary never yields it
    model = TransformerLMInfer(
        main, scope, cfg["num_hidden_layers"], n_head,
        cfg["hidden_size"], max_len, dtype=jnp.bfloat16, end_id=vocab)
    eng = serving.Engine(model, slots=int(cfg["slots"]),
                         name="chipbench")
    if eng._block_kernel or not eng._paged:
        eng.close()
        raise SystemExit("the engine's defaults no longer send a "
                         "bfloat16 model down the paged gather path")
    t0 = time.perf_counter()
    eng.warmup()
    log("engine: %d slots, %d blocks of %d; decode step compiled in "
        "%.1f s" % (eng.slots, eng._pool.num_blocks, eng._block_size,
                    time.perf_counter() - t0))
    return model, eng


def offer(eng, mix, seconds, seed, vocab, trace_dir, log):
    """One window of the mix against a warm engine: the run's records
    (without ``correct`` and ``setup_s``, which are ``run``'s)."""
    reqs = traffic_mod.schedule(mix, seconds, seed)
    for r in reqs:
        r["prompt"] = traffic_mod.prompt_ids(seed, r["k"],
                                             r["prompt_len"], vocab)
    n, ptok, otok = traffic_mod.totals(reqs)
    log("traffic: %d requests due in the window (%d prompt, %d output "
        "tokens), %d before it, at most %d after; %.3f requests/s" % (
            n, ptok, otok, traffic_mod.totals(reqs, "pre")[0],
            traffic_mod.totals(reqs, "post")[0], mix["rate_per_s"]))
    pre, drain = float(mix["pre_roll_s"]), float(mix["drain_s"])
    t_ready = time.perf_counter()
    t_open = t_ready + pre + 0.05           # the window's start
    stop = threading.Event()

    def generate():
        for r in reqs:
            wait = t_open + r["due"] - time.perf_counter()
            if (wait > 0 and stop.wait(wait)) or stop.is_set():
                return
            r["handle"] = eng.submit(r["prompt"], r["max_new"])

    def sleep_until(t):
        time.sleep(max(0.0, t_open + t - time.perf_counter()))

    gen = threading.Thread(target=generate, name="chipbench-gen")
    gen.start()
    try:
        window = [r for r in reqs if r["segment"] == "window"]
        sleep_until(0.0)
        stats0 = {k: eng.stats[k] for k in STAT_KEYS}
        if trace_dir:
            # the window's last seconds: the profiler's stop holds the
            # interpreter for a while, and there it delays no request
            # that the metrics cover
            sleep_until(seconds - min(float(mix["trace_s"]), seconds / 2))
            tracing.start(trace_dir)
        sleep_until(seconds)
        stats1 = {k: eng.stats[k] for k in STAT_KEYS}
        if trace_dir:
            tracing.stop()
        time.sleep(0.05)                  # the last due request is in
        for r in window:                  # drain, under the limit
            left = t_open + seconds + drain - time.perf_counter()
            try:
                r["handle"].result(timeout=max(0.0, left))
            except (KeyError, TimeoutError, RuntimeError):
                pass                      # counted as failed below
    finally:
        stop.set()
        gen.join()

    records, failed, short = [], 0, 0
    for r in reqs:
        h = r.get("handle")
        done = h is not None and h.done() and h._error is None
        if r["segment"] == "window":
            failed += not done
            short += done and len(h.tokens) != r["max_new"]
        rec = {"segment": r["segment"], "due": r["due"], "done": done,
               "prompt_len": r["prompt_len"], "max_new": r["max_new"]}
        if h is not None:
            rel = lambda t: None if t is None else t - t_open
            rec.update(submit=rel(h.t_enqueue), admit=rel(h.t_admit),
                       first=rel(h.t_first_token),
                       retire=rel(h.t_retire), tokens=len(h.tokens))
        records.append(rec)
    log("window: %d requests due, %d not finished within %.0f s of its "
        "end, %d with another token count than asked"
        % (len(window), failed, drain, short))
    stats = {k: stats1[k] - stats0[k] for k in STAT_KEYS}
    log("engine counters over the window: %s" % (stats,))
    return {"attempted": len(window), "failed": failed, "short": short,
            "t_ready": t_ready, "requests": records,
            "engine": {"stats": stats, "slots": eng.slots,
                       "block_size": eng._block_size}}


def run(cell, seed, seconds, devices, t_start, trace_dir, log):
    cfg, mix = cell["config_file"], cell["traffic_file"]
    model, eng = build(cell, seed, devices, log)
    try:
        correct = check_sample(model, eng, seed, mix, cfg["vocab_size"],
                               cfg["num_attention_heads"], log)
        out = offer(eng, mix, seconds, seed, cfg["vocab_size"],
                    trace_dir, log)
    finally:
        eng.close()                     # fails what is still in flight
    out["correct"] = correct and out.pop("short") == 0
    out["setup_s"] = out.pop("t_ready") - t_start
    return out
