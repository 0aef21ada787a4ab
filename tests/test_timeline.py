"""One timeline (ISSUE 24): the program's phases land in the JAX
profiler's trace, the compile log fills whether or not the monitor is
on, a request keeps a stamp per token, and the serving programs carry
their scopes.

What is pinned here, on the CPU (counts and names, never a speed):

  * under a profiler session ``exe.step`` and ``engine.step`` are in
    the ``.xplane.pb`` with their phases nested inside them and
    numbered like them;
  * with no session and the tracer disarmed the same runs leave no
    span anywhere and give bit-identical results;
  * ``monitor.runtime.compile_log()`` is fed with the monitor off, and
    ``monitor.enable()`` still gets its ``xla_compile`` recorder rows;
  * ``Request.t_tokens`` has one non-decreasing stamp per token, the
    first equal to ``t_first_token``;
  * the kernels' names are in their jaxprs and the six serving scopes
    in the decode step's optimized HLO (the compile for a described
    v5e, where the kernel's name reaches the lowered text, is in
    tests/test_tpu_compile_paged.py).
"""

import glob
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, serving, trace
from paddle_tpu.models import transformer
from paddle_tpu.models.transformer_infer import TransformerLMInfer
from paddle_tpu.monitor import runtime as monrt

N_LAYER, N_HEAD, D_MODEL, MAX_LEN, VOCAB = 2, 2, 32, 64, 40
EXE_PHASES = {"exe.feed", "exe.state", "exe.build", "exe.dispatch",
              "exe.commit"}
ENGINE_PHASES = {"engine.admit", "engine.prefill", "engine.btab",
                 "engine.dispatch", "engine.fetch", "engine.book"}
PROMPTS = [[1, 5, 9, 7, 3, 11, 4], [1, 8, 6], [1, 2, 3, 4, 5, 6, 7, 8, 9]]


@pytest.fixture(scope="module")
def lm():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        transformer.transformer_lm(
            vocab_size=VOCAB, max_len=MAX_LEN, n_layer=N_LAYER,
            n_head=N_HEAD, d_model=D_MODEL, d_inner=64)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        return TransformerLMInfer(main, scope, N_LAYER, N_HEAD, D_MODEL,
                                  MAX_LEN)


def _workload(lm):
    """Two ``Executor.run`` steps of a tiny program (a fresh program,
    scope and executor each call, same seed) and a few iterations of a
    tiny ``Engine``: (losses, parameters after, generated tokens)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():       # the same names each call
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(fluid.layers.fc(x, 16, act="relu"), 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(3)
        feed = {"x": rng.rand(4, 8).astype(np.float32),
                "y": rng.rand(4, 1).astype(np.float32)}
        losses = [exe.run(main, feed=feed, fetch_list=[loss])[0]
                  for _ in range(2)]
        params = {n: np.asarray(scope.find_var(n))
                  for n in sorted(scope.local_var_names())
                  if scope.find_var(n) is not None
                  and n not in ("x", "y")}
    with serving.Engine(lm, slots=2, prefill_chunk=4) as eng:
        out = eng.generate_many(PROMPTS, [5, 6, 4])
    return losses, params, [tokens for tokens, _ in out]


def _host_events(trace_dir):
    """[[(name, start_ns, end_ns, stats)]], one list for each line
    (thread) of the host plane of the one ``.xplane.pb`` under
    ``trace_dir``."""
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            lines.append([
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                 dict(ev.stats)) for ev in line.events])
    return lines


def _children(events, root, prefix):
    """The phases nested inside ``root`` on its own thread's line."""
    _, t0, t1, _ = root
    return [e for e in events if e[0].startswith(prefix)
            and e is not root and t0 <= e[1] and e[2] <= t1]


@pytest.fixture(scope="module")
def profiled(lm, tmp_path_factory):
    """The workload under a profiler session (Python tracer off, as
    chipbench starts it): its results and its host events."""
    trace.disable()
    trace_dir = str(tmp_path_factory.mktemp("timeline"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        results = _workload(lm)
    finally:
        jax.profiler.stop_trace()
    return results, _host_events(trace_dir)


def test_exe_step_and_its_phases_are_in_the_profile(profiled):
    _, lines = profiled
    (events,) = [evs for evs in lines
                 if any(e[0] == "exe.step" for e in evs)]
    roots = [e for e in events if e[0] == "exe.step"]
    assert len(roots) == 3            # startup, then the two steps
    numbers = [r[3]["step"] for r in roots]
    assert numbers == sorted(set(numbers))
    seen = set()
    for root in roots[1:]:
        kids = _children(events, root, "exe.")
        names = [k[0] for k in kids]
        seen.update(names)
        assert names[0] == "exe.feed" and names[1] == "exe.state"
        assert names[-1] == "exe.commit"
        # numbered like their root, and one after the other
        assert all(k[3]["step"] == root[3]["step"] for k in kids)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    # the first step misses the executor's cache (build, and the call
    # that compiles), the second only dispatches
    assert [k[0] for k in _children(events, roots[1], "exe.")].count(
        "exe.build") == 2
    assert "exe.dispatch" in [k[0] for k in _children(
        events, roots[2], "exe.")]
    assert seen == EXE_PHASES


def test_engine_step_and_its_phases_are_in_the_profile(profiled):
    _, lines = profiled
    (events,) = [evs for evs in lines
                 if any(e[0] == "engine.step" for e in evs)]
    roots = [e for e in events if e[0] == "engine.step"]
    assert len(roots) >= 3
    numbers = [r[3]["step"] for r in roots]
    assert numbers == list(range(numbers[0], numbers[0] + len(roots)))
    seen, rids = set(), set()
    for root in roots:
        kids = _children(events, root, "engine.")
        assert kids and kids[0][0] == "engine.admit"
        assert all(k[3]["step"] == root[3]["step"] for k in kids)
        seen.update(k[0] for k in kids)
        rids.update(k[3]["rid"] for k in kids
                    if k[0] == "engine.prefill")
    assert seen == ENGINE_PHASES
    assert len(rids) == len(PROMPTS)      # a request's id on its chunks
    # every phase of the loop thread lies inside some engine.step
    orphans = [e for e in events if e[0] in ENGINE_PHASES and not any(
        r[1] <= e[1] and e[2] <= r[2] for r in roots)]
    assert not orphans


def test_no_session_no_tracer_no_span_and_same_bits(lm, profiled,
                                                    tmp_path):
    (losses_p, params_p, tokens_p), _ = profiled
    trace.disable()
    spans_before = sum(monrt.TRACE_SPANS.snapshot().values())
    losses, params, tokens = _workload(lm)
    assert trace.tracer() is None and trace.tail_dump() == []
    assert sum(monrt.TRACE_SPANS.snapshot().values()) == spans_before
    assert not glob.glob(os.path.join(str(tmp_path), "**", "*.pb"),
                         recursive=True)
    assert tokens == tokens_p
    for a, b in zip(losses, losses_p):
        assert a.tobytes() == b.tobytes()
    assert sorted(params) == sorted(params_p)
    for name in params:
        assert params[name].tobytes() == params_p[name].tobytes()


def test_armed_tracer_rows_keep_roots_and_gain_no_phase(lm, tmp_path):
    """With the Dapper tracer armed the JSONL log holds the roots, as
    before, and none of the phases: those are the profiler's."""
    log = str(tmp_path / "spans.jsonl")
    trace.enable(log_path=log)
    try:
        _workload(lm)
    finally:
        trace.disable()
    with open(log) as f:
        rows = [json.loads(line) for line in f]
    names = {r["name"] for r in rows if r.get("ev") == "span"}
    assert {"exe.step", "engine.step", "serving.request",
            "request.prefill_chunk"} <= names
    assert not names & (EXE_PHASES | ENGINE_PHASES)
    steps = [r for r in rows if r.get("name") == "exe.step"]
    assert all(r["parent"] is None and "step" in r["attrs"]
               for r in steps)


def test_compile_log_fills_with_the_monitor_off(tmp_path):
    monitor.disable()
    since = time.perf_counter()     # not a length: the log is a ring
    def chain(a):                   # long enough to trace that every
        for i in range(300):        # phase is over the log's 1 ms floor
            a = jnp.tanh(a) @ a.T + float(i)
        return a

    jax.jit(chain)(jnp.ones((3, 3)))
    new = [r for r in monrt.compile_log() if r["end"] >= since]
    whats = {r["what"] for r in new}
    assert {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration"} <= whats
    assert any(r["fun_name"] and "chain" in r["fun_name"] for r in new)
    ends = [r["end"] for r in new]
    assert ends == sorted(ends) and all(r["seconds"] >= 0 for r in new)
    # the persistent cache's counts and retrieval time, as JAX reports
    # them (emitted here through its public recording functions)
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    tail = monrt.compile_log()[-2:]
    assert [r["what"] for r in tail] == ["cache_hits",
                                         "cache_retrieval_time_sec"]
    assert tail[1]["seconds"] == 0.25

    # the recorder row stays behind the monitor's switch
    log = str(tmp_path / "monitor.jsonl")
    emit = lambda: jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(f)")
    emit()                                 # monitor off: no row
    monitor.enable(log_path=log)
    try:
        emit()
    finally:
        monitor.disable()
    with open(log) as f:
        rows = [json.loads(line) for line in f]
    compiles = [r for r in rows if r.get("ev") == "xla_compile"]
    assert len(compiles) == 1
    assert compiles[0]["what"] == "backend_compile_duration"
    assert compiles[0]["seconds"] == 0.5
    assert monrt.compile_log()[-1]["fun_name"] == "jit(f)"


def test_request_keeps_a_stamp_per_token(lm):
    with serving.Engine(lm, slots=2, prefill_chunk=4) as eng:
        reqs = [eng.submit(p, n) for p, n in zip(PROMPTS, [5, 6, 4])]
        for r in reqs:
            r.result(timeout=120)
    for r in reqs:
        assert len(r.t_tokens) == len(r.tokens) > 0
        assert r.t_tokens == sorted(r.t_tokens)
        assert r.t_tokens[0] == r.t_first_token
        assert r.t_tokens[-1] == r.t_retire


def _pallas_names(jaxpr):
    """Names of the pallas_call equations of a jaxpr, sub-jaxprs
    included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_names(sub)
    return names


def test_kernels_carry_their_names(monkeypatch):
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops.flash_attention import flash_attention
    from paddle_tpu.ops.paged_attention import paged_attention
    q = jnp.ones((1, 2, 256, 64), jnp.float32)

    # all of T in one block, and streamed within the one kernel's byte
    # bound: one backward kernel; streamed beyond the bound: the two
    for block, bound, names in (
            (None, None, ["flash_bwd", "flash_fwd"]),
            (128, None, ["flash_bwd", "flash_fwd"]),
            (128, 0, ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"])):
        if bound is not None:
            monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", bound)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, force="interpret",
                                   block_q=block, block_k=block).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
        assert sorted(set(_pallas_names(jaxpr.jaxpr))) == names

    pool = jnp.ones((8, 16, 2, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, pk, pv, btab, qpos: paged_attention(
            q, pk, pv, btab, qpos, force="interpret"))(
        jnp.ones((2, 2, 1, 16), jnp.float32), pool, pool,
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 1), jnp.int32))
    assert _pallas_names(jaxpr.jaxpr) == ["paged_decode"]


@pytest.mark.parametrize("block_kernel, scopes", [
    (False, ("kv.read", "kv.write", "attn", "mlp", "head", "sample")),
    (True, ("kv.write", "attn", "mlp", "head", "sample")),
], ids=["gather", "block_kernel"])
def test_serving_step_carries_its_scopes(lm, block_kernel, scopes):
    """The optimized HLO of the decode step (what a profile's device
    ops are named from) keeps the scopes in its ``op_name`` metadata.
    The block kernel reads the pool itself, inside ``attn``."""
    with serving.Engine(lm, slots=2, prefill_chunk=4,
                        block_kernel=block_kernel) as eng:
        state = eng._init_state()
        btab = np.zeros((eng.slots, eng._max_blocks), np.int32)
        text = jax.jit(eng._step_impl, static_argnums=2).lower(
            state, btab, False).compile().as_text()
        chunk = jax.jit(eng._prefill_impl).lower(
            state, np.int32(0), np.zeros((4,), np.int32), np.int32(0),
            np.int32(4), btab[0]).compile().as_text()
    for scope in scopes:
        assert "/%s/" % scope in text, scope
    for scope in set(scopes) - {"head", "sample"}:
        assert "/%s/" % scope in chunk, scope
