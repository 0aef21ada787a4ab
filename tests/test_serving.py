"""paddle_tpu.serving: continuous-batching engine equivalence + the
zero-copy feed path.

The contract pinned here is the ISSUE-5 acceptance story: Engine output
is TOKEN-IDENTICAL to standalone one-at-a-time greedy decode for every
request of a mixed-length workload — through slot recycling, chunked
prefill, EOS retirement and mid-flight admission — and the serving
telemetry (ptpu_serving_* metrics, serving_step recorder rows carrying
the trace id, engine.step spans) plus the core/executor feed-plan cache
(no fresh normalization on a repeated-shape call, committed-buffer
zero-copy reuse) behave as documented.

Since ISSUE 10 the engine default is the PAGED KV layout (shared block
pool + per-slot block tables), so every identity pin in this module —
slot recycling, multi-chunk prefill, mid-flight admission, bf16,
megastep K>1, full ISSUE-6 instrumentation — now gates the paged step.
The EOS test pins paged=False so the PR-5 dense layout keeps its own
token-identity gate; tests/test_kvpool.py holds the paged-only pins
(prefix-cache hit vs cold, COW, preemption-and-resume, sampling).

The LM, its sequential-baseline jit and ONE engine are module-scoped:
each Engine carries three compiled functions, and on this suite's
single-core CPU budget recompiling them per test would cost more than
every assertion combined.
"""

import copy
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.models import transformer
from paddle_tpu.models.transformer_infer import TransformerLMInfer
from paddle_tpu.monitor import runtime as monrt

N_LAYER, N_HEAD, D_MODEL, MAX_LEN, VOCAB = 2, 2, 32, 64, 40


def _build_lm(dtype=None, n_layer=N_LAYER):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        transformer.transformer_lm(
            vocab_size=VOCAB, max_len=MAX_LEN, n_layer=n_layer,
            n_head=N_HEAD, d_model=D_MODEL, d_inner=64)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return TransformerLMInfer(main, scope, n_layer, N_HEAD, D_MODEL,
                                  MAX_LEN, dtype=dtype)


@pytest.fixture(scope="module")
def lm():
    return _build_lm()


@pytest.fixture(scope="module")
def eng4(lm):
    """The shared slots=4 engine (one compile of step/prefill/activate
    for the whole module — slots=4 is also the ISSUE-6 acceptance
    shape, so the lifecycle test rides the same compile)."""
    eng = serving.Engine(lm, slots=4, prefill_chunk=4)
    yield eng
    eng.close()


def _requests(rng, n, max_prompt=13, min_new=4, max_new=20):
    reqs = []
    for _ in range(n):
        plen = int(rng.randint(1, max_prompt + 1))
        prompt = [1] + rng.randint(3, VOCAB, plen - 1).tolist()
        reqs.append((prompt, int(rng.randint(min_new, max_new + 1))))
    return reqs


def _assert_identical(seq, eng):
    for i, ((st, ss), (et, es)) in enumerate(zip(seq, eng)):
        assert st == et, "request %d diverged: %r vs %r" % (i, st, et)
        np.testing.assert_allclose(es, ss, rtol=1e-5, atol=1e-5)


# -- decode equivalence ----------------------------------------------------

def test_engine_token_identical_with_slot_recycling(rng, lm, eng4):
    """8 mixed-length requests through 4 slots: every slot retires and
    refills mid-flight (recycling), prompts longer than the prefill
    chunk exercise chunked prefill, and the outputs must be
    token-identical to the sequential one-at-a-time baseline."""
    reqs = _requests(rng, 8)
    assert max(len(p) for p, _ in reqs) > 4   # multi-chunk prefill real
    seq = serving.sequential_generate(lm, reqs)
    r0, a0 = eng4.stats["retirements"], eng4.stats["admissions"]
    out = eng4.generate_many([p for p, _ in reqs], [m for _, m in reqs])
    assert eng4.stats["retirements"] - r0 == len(reqs)
    assert eng4.stats["admissions"] - a0 == len(reqs)
    assert eng4.occupancy() > 0.5
    _assert_identical(seq, out)


def test_engine_token_identical_mid_flight_admission(rng, lm, eng4):
    """Requests submitted WHILE the engine is decoding others join at a
    step boundary and still decode identically — admission timing must
    never leak into another slot's tokens."""
    reqs = _requests(rng, 5, min_new=10, max_new=18)
    seq = serving.sequential_generate(lm, reqs)
    first = [eng4.submit(p, m) for p, m in reqs[:3]]
    time.sleep(0.03)          # let the first batch get mid-flight
    rest = [eng4.submit(p, m) for p, m in reqs[3:]]
    # both result surfaces: engine-level and the Request handle itself
    out = [eng4.result(r, timeout=60) for r in first]
    out += [r.result(timeout=60) for r in rest]
    _assert_identical(seq, out)


def test_engine_eos_retirement_dense(rng, lm):
    """A request whose greedy continuation hits EOS retires early (its
    slot refills) and the emitted tokens — EOS included — match the
    sequential baseline. The EOS id is picked from an observed
    continuation so the path triggers deterministically; the model copy
    shares weights (and the baseline's compiled step) with ``lm``.
    Runs ``paged=False``: with the engine default now PAGED (ISSUE 10,
    the rest of this module), this is the pin that keeps the PR-5
    dense slot layout token-identical too."""
    probe = ([1, 5, 9], 12)
    [(toks, _)] = serving.sequential_generate(lm, [probe])
    lm_eos = copy.copy(lm)
    # EOS = the first emitted token (past index 0) whose value has not
    # occurred earlier, so the continuation really stops THERE
    j = next(i for i in range(1, len(toks)) if toks[i] not in toks[:i])
    lm_eos.end_id = toks[j]
    reqs = [probe] + _requests(rng, 3, min_new=6, max_new=10)
    seq = serving.sequential_generate(lm_eos, reqs)
    assert len(seq[0][0]) == j + 1 and seq[0][0][-1] == lm_eos.end_id
    with serving.Engine(lm_eos, slots=2, prefill_chunk=4,
                        paged=False) as eng:
        assert eng._paged is False
        out = eng.generate_many([p for p, _ in reqs],
                                [m for _, m in reqs])
    _assert_identical(seq, out)


def test_engine_bf16_serving_mode(rng):
    """The engine composes with the bf16 serving cast (weights + KV
    caches bf16): output stays token-identical to the bf16 sequential
    baseline (both run the same bf16 row math)."""
    bf16 = _build_lm(dtype=jnp.bfloat16, n_layer=1)
    reqs = _requests(rng, 3, max_prompt=6, min_new=4, max_new=8)
    seq = serving.sequential_generate(bf16, reqs)
    with serving.Engine(bf16, slots=2, prefill_chunk=4) as eng:
        out = eng.generate_many([p for p, _ in reqs],
                                [m for _, m in reqs])
    _assert_identical(seq, out)


def test_engine_validation_and_close(lm, eng4):
    with pytest.raises(ValueError, match="max_len"):
        eng4.submit([1] * 10, MAX_LEN)          # 10 + L - 1 > L
    with pytest.raises(ValueError, match="max_new"):
        eng4.submit([1], 0)
    with pytest.raises(ValueError):
        serving.Engine(lm, slots=0)
    # close() fails queued/in-flight requests loudly instead of hanging
    # (jit functions compile lazily, so this throwaway engine is cheap)
    f0 = monrt.SERVING_FAILURES.value()
    eng = serving.Engine(lm, slots=1)
    eng.submit([1], 40)
    r2 = eng.submit([1], 40)                    # queued behind the first
    eng.close()
    with pytest.raises((RuntimeError, TimeoutError)):
        r2.result(timeout=5)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1], 4)
    # failed requests still retire for attribution: stamped + counted
    # into the SLO error budget (ISSUE 6)
    assert r2.t_retire is not None
    assert monrt.SERVING_FAILURES.value() - f0 >= 1


def test_engine_megastep_token_identical_and_telemetry(rng, lm,
                                                       tmp_path):
    """ISSUE-7 serving acceptance: a megastep engine (K=4 decode
    iterations fused into ONE dispatch when no admissions/prefills
    pend) stays token-identical to the sequential baseline — across
    slot recycling, chunked prefill and a mid-flight admission that
    forces a K→1 boundary — while serving_step rows report the
    per-logical-step dt with the fused k and the megastep counters
    tick."""
    from paddle_tpu import monitor
    reqs = _requests(rng, 6, min_new=8, max_new=16)
    seq = serving.sequential_generate(lm, reqs)
    mlog = str(tmp_path / "mega.jsonl")
    d0 = monrt.MEGASTEP_DISPATCHES.value(executor="mega")
    monitor.enable(log_path=mlog)
    try:
        with serving.Engine(lm, slots=2, prefill_chunk=4, megastep=4,
                            name="mega") as eng:
            # warmup compiles BOTH dispatch paths on the all-inactive
            # state without touching decode semantics
            eng.warmup()
            out = eng.generate_many([p for p, _ in reqs[:4]],
                                    [m for _, m in reqs[:4]])
            # mid-flight admission: submit while the engine decodes —
            # the pending request forces the next dispatch back to K=1
            first = [eng.submit(p, m) for p, m in reqs[4:5]]
            with pytest.raises(RuntimeError, match="before traffic"):
                eng.warmup()        # request queued or in flight
            time.sleep(0.02)
            rest = [eng.submit(p, m) for p, m in reqs[5:]]
            out += [h.result(timeout=60) for h in first + rest]
            assert eng.stats["megastep_dispatches"] > 0
            # fusion really reduced dispatches: decode_steps advanced
            # more than once per engine iteration overall
            assert eng.stats["decode_steps"] > eng.stats["steps"]
    finally:
        monitor.disable()
    _assert_identical(seq, out)
    assert monrt.MEGASTEP_DISPATCHES.value(executor="mega") > d0
    rows = [r for r in monitor.read_jsonl(mlog)
            if r["ev"] == "serving_step"]
    fused = [r for r in rows if r.get("k", 1) > 1]
    assert fused, "no fused serving_step rows recorded"
    for r in fused:
        assert r["k"] > 1 and r["megastep_dt"] > 0
        # dt is per logical step: megastep_dt / trips DISPATCHED (a
        # drain-tail megastep consumes fewer steps than it dispatched,
        # but the device still ran every scan trip in megastep_dt)
        assert r["dispatched"] >= r["k"]
        assert abs(r["dt"] - r["megastep_dt"] / r["dispatched"]) < 1e-9


# -- telemetry: metrics, flight recorder, trace ----------------------------

def test_serving_metrics_recorder_and_trace(rng, eng4, tmp_path):
    from paddle_tpu import monitor
    from paddle_tpu.trace import runtime as trt
    mlog = str(tmp_path / "mon.jsonl")
    tlog = str(tmp_path / "spans.jsonl")
    tok0 = monrt.SERVING_TOKENS.value()
    adm0 = monrt.SERVING_ADMISSIONS.value()
    ret0 = monrt.SERVING_RETIREMENTS.value()
    monitor.enable(log_path=mlog)
    trt.enable(log_path=tlog, sample_rate=1.0, proc="test-serving")
    try:
        out = eng4.generate_many([[1], [1, 4, 7, 9], [1, 9]], [5, 6, 4])
    finally:
        trt.disable()
        monitor.disable()
    total = sum(len(t) for t, _ in out)
    assert monrt.SERVING_TOKENS.value() - tok0 == total
    assert monrt.SERVING_ADMISSIONS.value() - adm0 == 3
    assert monrt.SERVING_RETIREMENTS.value() - ret0 == 3
    occ = monrt.SERVING_SLOT_OCCUPANCY.value()
    assert occ is not None and 0.0 <= occ <= 1.0
    assert monrt.SERVING_QUEUE_DEPTH.value() is not None

    rows = monitor.read_jsonl(mlog)
    steps = [r for r in rows if r["ev"] == "serving_step"]
    assert steps, "no serving_step flight-recorder rows"
    assert sum(r["emitted"] for r in steps) == total
    assert sum(r["admitted"] for r in steps) == 3
    assert sum(r["retired"] for r in steps) == 3
    assert all(r["slots"] == 4 for r in steps)
    # every engine iteration ran under an engine.step root span, and the
    # recorder rows carry its trace id — the fleet-timeline join key
    spans = [r for r in monitor.read_jsonl(tlog) if r["ev"] == "span"]
    estep = [s for s in spans if s["name"] == "engine.step"]
    assert len(estep) == len(steps)
    span_traces = {s["trace"] for s in estep}
    for r in steps:
        assert r.get("trace") in span_traces


def test_request_lifecycle_slots4_armed(rng, lm, eng4, tmp_path):
    """ISSUE-6 acceptance: every request of a slots=4 run carries
    queue_wait/TTFT/TPOT on its Request handle (monotonic lifecycle
    stamps), in serving_request recorder rows (with the request's
    trace id + the new histograms), and as a serving.request span with
    prefill-chunk children / first-token mark linked to engine.step
    spans — while the token-identical-to-sequential contract holds
    with the FULL instrumentation armed. Rides the shared slots=4
    engine: no extra compiles on the tier-1 budget."""
    import math
    from paddle_tpu import monitor
    from paddle_tpu.trace import merge as tmerge
    from paddle_tpu.trace import runtime as trt
    reqs = _requests(rng, 8, max_prompt=10, min_new=4, max_new=12)
    assert max(len(p) for p, _ in reqs) > 4   # multi-chunk prefill real
    seq = serving.sequential_generate(lm, reqs)
    mlog, tlog = str(tmp_path / "mon.jsonl"), str(tmp_path / "sp.jsonl")
    ttft0 = monrt.SERVING_TTFT.count(engine="engine")
    monitor.enable(log_path=mlog)
    trt.enable(log_path=tlog, sample_rate=1.0, proc="slo-test")
    try:
        handles = [eng4.submit(p, m) for p, m in reqs]
        out = [h.result(timeout=120) for h in handles]
    finally:
        trt.disable()
        monitor.disable()
    _assert_identical(seq, out)

    # 1) the Request handle: monotonic stamps + derived attribution
    for (prompt, _), h in zip(reqs, handles):
        assert h.t_enqueue <= h.t_admit <= h.t_first_token <= h.t_retire
        assert h.queue_wait >= 0 and h.ttft > 0
        assert h.tpot is not None and h.tpot >= 0
        assert h.prefill_chunks == math.ceil((len(prompt) - 1) / 4)
        lat = h.latency()
        assert lat["tokens"] == len(h.tokens) > 0
    assert monrt.SERVING_TTFT.count(engine="engine") - ttft0 \
        == len(reqs)

    # 2) recorder rows: one serving_request per request, trace-stamped
    rows = monitor.read_jsonl(mlog)
    rreq = [r for r in rows if r["ev"] == "serving_request"]
    assert len(rreq) == len(reqs)
    for r in rreq:
        assert r["ttft"] > 0 and r["queue_wait"] >= 0
        assert r["tpot"] is not None and r["tokens"] > 0
        assert r.get("trace") and "error" not in r
    # serving_step rows now carry the step wall time
    rstep = [r for r in rows if r["ev"] == "serving_step"]
    assert rstep and all(r["dt"] > 0 for r in rstep)

    # 3) spans: request roots + prefill-chunk/first-token children
    #    linked to engine.step spans; rows' trace ids join the lanes
    spans = [r for r in monitor.read_jsonl(tlog) if r["ev"] == "span"]
    rspans = [s for s in spans if s["name"] == "serving.request"]
    assert len(rspans) == len(reqs)
    assert {s["trace"] for s in rspans} == {r["trace"] for r in rreq}
    for s in rspans:
        at = s.get("attrs") or {}
        assert at["ttft"] > 0 and "tpot" in at and "queue_wait" in at
    rids = {s["span"] for s in rspans}
    pf = [s for s in spans if s["name"] == "request.prefill_chunk"]
    ft = [s for s in spans if s["name"] == "request.first_token"]
    assert len(ft) == len(reqs)
    assert len(pf) == sum(math.ceil((len(p) - 1) / 4) for p, _ in reqs)
    assert all(s["parent"] in rids for s in pf + ft)
    estep = {s["span"] for s in spans if s["name"] == "engine.step"}
    assert all((s.get("attrs") or {}).get("step_span") in estep
               for s in ft)

    # 4) trace merge shows the request lanes next to the engine steps
    merged, info = tmerge.merge_files([tlog])
    names = {e.get("name") for e in merged["traceEvents"]}
    assert {"serving.request", "request.prefill_chunk",
            "engine.step"} <= names
    assert info["spans"] == len(spans)

    # 5) the recorded log satisfies a sane SLO spec end to end
    from paddle_tpu import slo
    v = slo.evaluate(
        {"objectives": [
            {"metric": "ttft", "percentile": 0.95, "max_seconds": 60},
            {"metric": "tpot", "percentile": 0.99, "max_seconds": 60},
            {"metric": "queue_wait", "percentile": 0.95,
             "max_seconds": 60},
            {"metric": "error_rate", "max_ratio": 0.0}]},
        slo.samples_from_monitor_log(mlog))
    assert v["pass"] is True and v["requests"] == len(reqs)


# -- zero-copy feed path (core/executor FeedPlanCache) ---------------------

def _tiny_program():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.fc(input=x, size=3)
    loss = fluid.layers.mean(y)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss


def test_feed_plan_second_call_skips_normalization(rng):
    """ISSUE-5 satellite pin: the second same-shape run() performs NO
    fresh normalization (derivation counter flat, hit counter +1)."""
    exe, loss = _tiny_program()
    a = rng.rand(2, 4).astype(np.float32)
    n0, h0 = monrt.FEED_NORMALIZATIONS.value(), \
        monrt.FEED_PLAN_HITS.value()
    r1 = exe.run(feed={"x": a}, fetch_list=[loss])
    n1, h1 = monrt.FEED_NORMALIZATIONS.value(), \
        monrt.FEED_PLAN_HITS.value()
    assert n1 == n0 + 1 and h1 == h0
    r2 = exe.run(feed={"x": a}, fetch_list=[loss])
    n2, h2 = monrt.FEED_NORMALIZATIONS.value(), \
        monrt.FEED_PLAN_HITS.value()
    assert n2 == n1, "second same-shape call re-derived the feed plan"
    assert h2 == h1 + 1
    np.testing.assert_allclose(np.asarray(r1[0]), np.asarray(r2[0]))
    # a DIFFERENT signature derives a fresh plan (no false sharing)
    exe.run(feed={"x": rng.rand(5, 4).astype(np.float32)},
            fetch_list=[loss])
    assert monrt.FEED_NORMALIZATIONS.value() == n2 + 1


def test_feed_plan_committed_buffer_reuse_and_mutation_safety(rng):
    """Frozen (writeable=False) numpy feeds commit a device buffer once
    and reuse it zero-copy; WRITEABLE feeds are never committed — an
    in-place mutation between calls must be honored."""
    exe, loss = _tiny_program()
    frozen = rng.rand(2, 4).astype(np.float32)
    frozen.flags.writeable = False
    exe.run(feed={"x": frozen}, fetch_list=[loss])
    base = exe._feed_plans.buffer_reuses
    r1 = exe.run(feed={"x": frozen}, fetch_list=[loss])
    r2 = exe.run(feed={"x": frozen}, fetch_list=[loss])
    assert exe._feed_plans.buffer_reuses >= base + 2
    np.testing.assert_allclose(np.asarray(r1[0]), np.asarray(r2[0]))

    mut = rng.rand(2, 4).astype(np.float32)
    v1 = np.asarray(exe.run(feed={"x": mut}, fetch_list=[loss])[0])
    mut[:] = mut + 1.0              # in-place mutation, same object
    v2 = np.asarray(exe.run(feed={"x": mut}, fetch_list=[loss])[0])
    assert not np.allclose(v1, v2), \
        "mutated writeable feed served from a stale committed buffer"


def test_feed_plan_lod_parity(rng):
    """Plan-cached LoD normalization (bucketing, @LOD, @MAXLEN) is
    byte-identical to the uncached derivation, hit or miss."""
    from paddle_tpu.core.lod import LoDTensor
    from paddle_tpu.core.executor import _normalize_feeds, FeedPlanCache
    t = LoDTensor(rng.rand(10, 3).astype(np.float32),
                  lod=[[0, 4, 10]])
    cache = FeedPlanCache()
    ref_a, ref_s = _normalize_feeds({"w": t})
    hit_a, hit_s = None, None
    for _ in range(2):                    # miss then hit
        hit_a, hit_s = _normalize_feeds({"w": t}, plan_cache=cache)
    assert cache.hits == 1 and cache.misses == 1
    assert hit_s == ref_s
    assert sorted(hit_a) == sorted(ref_a)
    for k in ref_a:
        np.testing.assert_array_equal(np.asarray(hit_a[k]),
                                      np.asarray(ref_a[k]))
    # different lengths, same shapes → different plan (lengths keyed)
    t2 = LoDTensor(rng.rand(10, 3).astype(np.float32),
                   lod=[[0, 6, 10]])
    _, s2 = _normalize_feeds({"w": t2}, plan_cache=cache)
    assert cache.misses == 2
    assert s2["w@MAXLEN"] == 8            # bucketed max(6, 4)


def test_device_loader_rides_plan_cache(rng):
    """Repeated same-shape loader batches skip re-normalization, and a
    frozen feed is committed once (later batches reuse the buffer)."""
    from paddle_tpu.reader.device_loader import DeviceLoader, repeat_feed
    frozen = rng.rand(2, 4).astype(np.float32)
    frozen.flags.writeable = False
    n0 = monrt.FEED_NORMALIZATIONS.value()
    dl = DeviceLoader(repeat_feed({"x": frozen}, 4))
    batches = list(dl)
    assert len(batches) == 4
    assert all(isinstance(b["x"], jax.Array) for b in batches)
    assert monrt.FEED_NORMALIZATIONS.value() - n0 == 1, \
        "loader re-derived the plan for repeated same-shape batches"
    assert dl._plans.hits == 3 and dl._plans.buffer_reuses == 3
    for b in batches:
        np.testing.assert_allclose(np.asarray(b["x"]), frozen)
