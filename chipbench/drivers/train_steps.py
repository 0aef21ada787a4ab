"""Driver ``train_steps``: pre-training steps back to back.

The configuration's model through the trainer's normal path (its
architecture's program, ``chipbench/archs/<arch>.py`` -> Adam -> bf16
AMP -> ``Executor``), seeded random packed batches prepared before the
window and fed as numpy feeds. Which model it is, how its parameters
map onto the reference's and how close the two must come are the
architecture's; the optimizer, the precision and the window are this
file's, the same for every model.

``correct`` compares the program's forward with the architecture's
float32 reference in ``forward_against_reference``, which
``chipbench/control.py`` calls too (through ``trainer``, the program as
this driver builds it): one comparison, so the tool reads what the
driver reads. A model that CHOOSES (a top-k router; its architecture
exports ``router_choices``) has its choices fetched in the same run of
the forward as its logits, and the reference is handed them: a top-k is
not continuous, and where two experts are a near-tie bf16 arithmetic
below the router tips the choice the other way than float32 would,
which moves a row by a whole expert's output and not by rounding. What
the reference does with a proposal is the reference's
(``README.md``, "an architecture").

The window: steps are dispatched back to back, at most two in flight
(the host stays ahead of the device and never runs away from it), until
the host clock passes ``seconds``; one ``block_until_ready`` on the
last loss ends it. The rate is all tokens over all of that time. After
it the run keeps what the program counted on the device
(``arch.program_counters``, read once) as ``train.counters``, and the
seconds from the process's start to each phase of set-up as
``setup_phases``.
"""

import contextlib
import functools
import math
import time
import types

import numpy as np

from chipbench import cells, tracing, traffic as traffic_mod
from chipbench.reference import compare

IN_FLIGHT = 2


def for_config(fn, cfg):
    """A reference function of the architecture with its configuration
    bound, under its own name: the compile log and the set-up readers
    tell the jitted programs apart by it."""
    bound = functools.partial(fn, cfg=cfg)
    bound.__name__ = fn.__name__
    return bound


def declared_feeds(program, feeds):
    """``feeds`` cut to what the program declares: a model with no
    position table declares no ``pos``."""
    declared = {name for name, var in program.global_block().vars.items()
                if var.is_data}
    return [{k: v for k, v in feed.items() if k in declared}
            for feed in feeds]


@contextlib.contextmanager
def trainer(cell, seed, on_tpu):
    """The cell's program as a trainer builds it, inside its guards and
    with bf16 AMP on until the block is left: ``arch``, ``cfg``,
    ``main`` (with Adam), its ``forward`` clone from before the
    backward, ``avg_cost``, ``logits``, ``scope`` and ``exe``, with the
    start-up program run; ``built_at`` is the clock when the programs
    stood and the executor was about to be made."""
    import paddle_tpu as fluid
    cfg, seq = cell["config_file"], int(cell["traffic_file"]["seq_len"])
    arch = cells.load_arch(cfg["arch"])
    main, startup = fluid.Program(), fluid.Program()
    # the executors fold random_seed * 1000003 into a uint32
    main.random_seed = startup.random_seed = 1 + seed % 4093
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        avg_cost, logits = arch.build(cfg, seq)
        forward = main.clone(for_test=True)    # before the backward
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        fluid.amp.enable_amp()
        try:
            built_at = time.perf_counter()
            exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu
                                 else fluid.CPUPlace())
            exe.run(startup)
            yield types.SimpleNamespace(
                arch=arch, cfg=cfg, main=main, forward=forward,
                scope=scope, exe=exe, avg_cost=avg_cost, logits=logits,
                built_at=built_at)
        finally:
            fluid.amp.enable_amp(False)


def reference_rows(fn, cfg, params, one, rows, choices=None):
    """``fn`` (an architecture's ``logits_at`` or ``control_logits_at``)
    on the last ``rows`` rows of the one sequence in ``one``, jitted
    with its configuration bound for this call alone (its executable
    goes when the call returns), and handed ``choices`` where the
    program's forward made any."""
    import jax
    import jax.numpy as jnp
    seq = one["src"].shape[1]
    handed = {} if choices is None else {"choices": choices}
    return np.asarray(jax.jit(for_config(fn, cfg), static_argnums=3)(
        params, jnp.asarray(one["src"][0]), seq - rows, rows, **handed))


def forward_against_reference(t, params, one, rows):
    """(the program's logits ``[rows, V]``, the reference's, the
    program's choices or None) on the last ``rows`` rows of the one
    sequence in ``one``, from ONE run of the ``for_test`` clone.

    An architecture that chooses nothing: the reference first, then the
    forward fetching its logits alone (OPT's parameter tree is the
    scope's own arrays; the order decides what is alive when). One that
    exports ``router_choices``: the forward first, fetching the
    choices' variables beside the logits, then the reference, handed
    them stacked as fetched (``[layers, ...]``). Logits and choices
    come out of the same executable, so the verdict never rests on a
    second compilation of the program; such an architecture's
    ``params_of_program`` gives host arrays."""
    arch = t.arch
    seq = one["src"].shape[1]
    chooses = hasattr(arch, "router_choices")
    if not chooses:
        ref = reference_rows(arch.logits_at, t.cfg, params, one, rows)
    fetched = t.exe.run(
        t.forward, feed=one, return_numpy=False,
        fetch_list=[t.logits] + (list(arch.router_choices(t.forward))
                                 if chooses else []))
    got = np.asarray(fetched[0][0, seq - rows:], np.float32)
    if not chooses:
        return got, ref, None
    choices = np.stack([np.asarray(c) for c in fetched[1:]])
    return got, reference_rows(arch.logits_at, t.cfg, params, one, rows,
                               choices), choices


def run(cell, seed, seconds, devices, t_start, trace_dir, log):
    import jax
    import paddle_tpu as fluid

    since_start = lambda: time.perf_counter() - t_start
    phases = {"entered": since_start()}
    cfg, mix = cell["config_file"], cell["traffic_file"]
    seq, batch = int(mix["seq_len"]), int(mix["batch"])
    rows = int(mix["check_rows"])
    with trainer(cell, seed, devices[0].platform == "tpu") as t:
        arch, exe, main, avg_cost = t.arch, t.exe, t.main, t.avg_cost
        phases["built"] = t.built_at - t_start
        step = lambda feed: exe.run(main, feed=feed,
                                    fetch_list=[avg_cost],
                                    return_numpy=False)[0]
        feeds = declared_feeds(main, traffic_mod.lm_batches(
            seed, int(mix["n_batches"]), batch, seq, cfg["vocab_size"]))
        log("train: %d layers, batch %d x %d tokens, one chip" % (
            cfg["num_hidden_layers"], batch, seq))
        phases["started"] = since_start()

        # the reference on the first batch, from the parameters as
        # initialised (the first step donates and updates them): its
        # loss, and the logits of the first sequence's last rows, which
        # see the longest contexts. The loss of a fresh model is about
        # ln V whatever the precision; the logits are what a lower
        # precision than bf16 AMP would move.
        first = feeds[0]
        params = arch.params_of_program(main, t.scope, cfg)
        phases["params_read"] = since_start()
        t0 = time.perf_counter()
        ref_loss = float(jax.jit(for_config(arch.lm_loss, cfg))(
            params, first["src"], first["label"], first["mask"]))
        got_logits, ref_logits, choices = forward_against_reference(
            t, params, {k: v[:1] for k, v in first.items()}, rows)
        logits_err = compare.logits_error(got_logits, ref_logits)
        log("reference float32 loss on batch 0: %.6f; the program's "
            "bf16-AMP forward on its first sequence, last %d rows of "
            "logits%s: error %.3e of the largest logit (tolerance %.0e) "
            "(%.1f s)" % (ref_loss, rows, "" if choices is None else
                          ", the reference handed the program's choices",
                          logits_err, arch.TRAIN_LOGITS_RTOL,
                          time.perf_counter() - t0))
        phases["compared"] = since_start()

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
                                       arch.LOSS_RTOL))

        phases["warmed_up"] = since_start()
        log("set-up, seconds after the process began: " + ", ".join(
            "%s %.1f" % kv for kv in phases.items()))

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
        counters = getattr(arch, "program_counters",
                           lambda program, scope: {})(main, t.scope)
        fluid.amp.enable_amp(False)
    finite = all(math.isfinite(x) for x in warm + losses)
    log("window: %d steps in %.3f s; losses %.4f .. %.4f; all finite: "
        "%s" % (len(losses), window_s, losses[0], losses[-1], finite))
    return {"correct": finite and loss_err <= arch.LOSS_RTOL
            and logits_err <= arch.TRAIN_LOGITS_RTOL,
            "attempted": len(losses),
            "failed": sum(not math.isfinite(x) for x in losses),
            "setup_s": setup_s, "setup_phases": phases,
            "train": {"steps": len(losses), "window_s": window_s,
                      "tokens_per_step": batch * seq, "batch": batch,
                      "seq_len": seq, "dispatch_ms": dispatch_ms,
                      "traced_steps": trace_steps if trace_dir else 0,
                      "traced_s": traced_s, "counters": counters}}
