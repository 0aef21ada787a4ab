"""paddle_tpu.serving speculative decode (ISSUE 13).

The contract pinned here is the ISSUE-13 acceptance story: a
speculative engine (γ drafted tokens per live slot verified in ONE
multi-position paged-attention dispatch, accept-longest-prefix against
the model's own tokens) emits output BITWISE-identical to the
non-speculative engine and the sequential one-at-a-time baseline —
at γ∈{0,2,4}, through multi-chunk prefill, EOS inside an accepted
draft, mid-flight admission, megastep composition, pool-dry
preemption/resume and seeded-sampling replay — while the drafting tier
(host n-gram lookup + the radix cache's published chains; flag-gated
truncated-layer pass) only ever moves the ACCEPTANCE RATE, never a
token. Telemetry (ptpu_spec_* counters, serving_step row fields, the
monitor-watch acceptance line) lands day one.

The LM and baseline are module-scoped like test_serving's: every
speculative engine carries an extra compiled scoring program per
(γ, sampled) pair, so engines are built once per γ where possible.
"""

import copy
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.models import transformer
from paddle_tpu.models.transformer_infer import TransformerLMInfer
from paddle_tpu.monitor import runtime as monrt
from paddle_tpu.serving.kvpool import BlockPool, RadixCache
from paddle_tpu.serving.spec import NgramDrafter

N_LAYER, N_HEAD, D_MODEL, MAX_LEN, VOCAB = 2, 2, 32, 64, 40


@pytest.fixture(scope="module")
def lm():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        transformer.transformer_lm(
            vocab_size=VOCAB, max_len=MAX_LEN, n_layer=N_LAYER,
            n_head=N_HEAD, d_model=D_MODEL, d_inner=64)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return TransformerLMInfer(main, scope, N_LAYER, N_HEAD,
                                  D_MODEL, MAX_LEN)


@pytest.fixture(scope="module")
def spec4(lm):
    """The shared γ=4 speculative engine (one compile of the scoring
    program for most of the module). min_n=1 so even weak-evidence
    drafts fire — the identity pins want the REJECTION paths
    exercised, not a high acceptance rate."""
    eng = serving.Engine(lm, slots=4, prefill_chunk=4,
                         speculative=True, spec_gamma=4)
    eng._drafter = NgramDrafter(max_n=3, min_n=1)
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


# -- drafting tier (pure host, device-free) --------------------------------

def test_ngram_drafter_self_chain():
    d = NgramDrafter(max_n=3, min_n=1)
    # period-2 cycle: the strongest (3-gram) suffix match proposes the
    # full continuation from inside the cycle
    assert d.propose([5, 9, 5, 9, 5, 9], 4) == [5, 9, 5, 9]
    # the rightmost match with a FULL γ continuation wins over a more
    # recent match that could only continue shorter
    assert d.propose([7, 1, 2, 3, 4, 1, 2, 3], 3) == [4, 1, 2]
    # no earlier occurrence at any n -> no draft
    assert d.propose([1, 2, 3, 4, 5], 4) == []
    # gamma cap + empty/zero requests
    assert d.propose([5, 5, 5, 5], 2) == [5, 5]
    assert d.propose([5, 5, 5], 0) == []
    assert d.propose([], 4) == []


def test_ngram_drafter_min_n_and_window():
    strict = NgramDrafter(max_n=3, min_n=3)
    # only a 1-gram repeats -> strict (strong-evidence) drafter stays
    # silent where the loose one proposes
    chain = [3, 9, 4, 9]
    assert NgramDrafter(max_n=3, min_n=1).propose(chain, 2) == [4, 9]
    assert strict.propose(chain, 2) == []
    # matches OUTSIDE the search window are invisible
    near = NgramDrafter(max_n=2, min_n=2, window=6)
    far = [1, 2, 8, 8, 8, 8, 8, 1, 2]
    assert near.propose(far, 2) == []
    assert NgramDrafter(max_n=2, min_n=2, window=64).propose(
        far, 2) == [8, 8]


def test_ngram_drafter_published_chains():
    d = NgramDrafter(max_n=3, min_n=2)
    # the request's own chain has no repeat, but a published radix
    # chain continues its suffix — cross-request drafting
    chain = [1, 6, 7]
    pub = [(9, 9, 6, 7, 5, 4, 3, 2)]
    assert d.propose(chain, 3, extra_chains=pub) == [5, 4, 3]
    # self-chain evidence wins when it can serve the full draft
    cyc = [6, 7, 8, 6, 7]
    assert d.propose(cyc, 1, extra_chains=pub) == [8]


def test_radix_cache_token_chains():
    pool = BlockPool(8, 2)
    cache = RadixCache(2, pool)
    b1 = pool.alloc(2)
    b2 = pool.alloc(1)
    cache.insert([1, 2, 3, 4], b1)
    cache.insert([1, 2, 9, 9], [b1[0], b2[0]])
    used0 = pool.used
    chains = cache.token_chains()
    # leaf root-paths, most recently used first; prefixes ride inside
    assert chains == [(1, 2, 9, 9), (1, 2, 3, 4)]
    assert cache.token_chains(limit=1) == [(1, 2, 9, 9)]
    # reading text takes NO pool refs
    assert pool.used == used0
    for b in b1 + b2:
        pool.free(b)


# -- bitwise-greedy identity ----------------------------------------------

def test_spec_identity_gamma_2_and_4(rng, lm, spec4):
    """The ISSUE-13 acceptance pin: speculative output (γ∈{2,4}) is
    token-identical to the sequential baseline across slot recycling
    and multi-chunk prefill, with drafting REAL (dispatches verified
    drafts, some accepted, some rejected)."""
    reqs = _requests(rng, 8)
    assert max(len(p) for p, _ in reqs) > 4   # multi-chunk prefill
    seq = serving.sequential_generate(lm, reqs)
    out = spec4.generate_many([p for p, _ in reqs],
                              [m for _, m in reqs])
    _assert_identical(seq, out)
    assert spec4.stats["spec_dispatches"] > 0
    assert spec4.stats["spec_drafted"] > 0
    # the tiny-LM greedy continuations cycle (seeded), so drafts are
    # verifiably accepted AND rejected — both acceptance branches ran
    assert 0 < spec4.stats["spec_accepted"] \
        < spec4.stats["spec_drafted"]
    with serving.Engine(lm, slots=2, prefill_chunk=4,
                        speculative=True, spec_gamma=2) as eng2:
        eng2._drafter = NgramDrafter(max_n=3, min_n=1)
        out2 = eng2.generate_many([p for p, _ in reqs],
                                  [m for _, m in reqs])
        assert eng2.stats["spec_dispatches"] > 0
    _assert_identical(seq, out2)


def test_spec_gamma0_disables_and_runs_existing_programs(rng, lm):
    """γ=0 (or speculative=False) must run the PR-10 engine
    cost-for-cost: no scoring program is even BUILT, no spec stats
    tick, and output identity holds — the regression the PR-10
    sampled-program tail was caught by."""
    reqs = _requests(rng, 4)
    seq = serving.sequential_generate(lm, reqs)
    with serving.Engine(lm, slots=2, prefill_chunk=4,
                        speculative=True, spec_gamma=0) as eng:
        assert eng._speculative is False
        assert eng._spec_fn is None and eng._draft_fn is None
        out = eng.generate_many([p for p, _ in reqs],
                                [m for _, m in reqs])
        assert eng.stats["spec_dispatches"] == 0
    _assert_identical(seq, out)
    # the default engine builds no speculative machinery either
    with serving.Engine(lm, slots=2) as dflt:
        assert dflt._spec_fn is None
    # and speculation REQUIRES the paged layout (ragged draft lengths
    # ride the block-table gather)
    with pytest.raises(ValueError, match="paged"):
        serving.Engine(lm, slots=2, paged=False, speculative=True)
    with pytest.raises(ValueError, match="drafter"):
        serving.Engine(lm, slots=2, speculative=True,
                       spec_drafter="nope")


def test_spec_mid_flight_admission(rng, lm, spec4):
    """Requests submitted WHILE the engine speculates join at an
    iteration boundary and decode identically — drafting for running
    slots must never leak into an admitted slot's tokens."""
    reqs = _requests(rng, 5, min_new=10, max_new=18)
    seq = serving.sequential_generate(lm, reqs)
    first = [spec4.submit(p, m) for p, m in reqs[:3]]
    time.sleep(0.03)
    rest = [spec4.submit(p, m) for p, m in reqs[3:]]
    out = [r.result(timeout=60) for r in first + rest]
    _assert_identical(seq, out)


def test_spec_eos_inside_accepted_draft(rng, lm):
    """EOS landing INSIDE an accepted draft truncates the emit right
    there (EOS included, nothing after) — pinned deterministically by
    drafting with the TRUNCATED tier at FULL depth (the drafter IS
    the scoring model, so every draft is accepted and the first
    dispatch covers the whole continuation incl. the EOS position).
    Uses the observed-token end_id trick of the PR-5 dense EOS pin."""
    probe = ([1, 5, 9], 12)
    [(toks, _)] = serving.sequential_generate(lm, [probe])
    lm_eos = copy.copy(lm)
    # EOS = the first emitted token (past index 0) whose value has not
    # occurred earlier, so the continuation really stops THERE
    j = next(i for i in range(1, len(toks)) if toks[i] not in toks[:i])
    lm_eos.end_id = toks[j]
    reqs = [probe] + _requests(rng, 2, min_new=4, max_new=8)
    seq = serving.sequential_generate(lm_eos, reqs)
    assert len(seq[0][0]) == j + 1 and seq[0][0][-1] == lm_eos.end_id
    with serving.Engine(lm_eos, slots=2, prefill_chunk=4,
                        speculative=True, spec_gamma=4,
                        spec_drafter="truncated",
                        spec_layers=N_LAYER) as eng:
        out = eng.generate_many([p for p, _ in reqs],
                                [m for _, m in reqs])
        # full-depth drafts accept: the EOS really sat inside one
        assert eng.stats["spec_accepted"] > 0
    _assert_identical(seq, out)


def test_spec_truncated_drafter_identity(rng, lm):
    """Tier B at REDUCED depth (1 of 2 layers): draft quality drops,
    output must not — the truncated pass writes only layer rows the
    scoring dispatch overwrites, and rejected drafts cost nothing."""
    reqs = _requests(rng, 5)
    seq = serving.sequential_generate(lm, reqs)
    with serving.Engine(lm, slots=2, prefill_chunk=4,
                        speculative=True, spec_gamma=3,
                        spec_drafter="truncated",
                        spec_layers=1) as eng:
        assert eng._spec_layers == 1
        out = eng.generate_many([p for p, _ in reqs],
                                [m for _, m in reqs])
        assert eng.stats["spec_dispatches"] > 0
    _assert_identical(seq, out)


def test_spec_megastep_composition(rng, lm):
    """Megastep × speculation (the ISSUE-13 composition pin): drafted
    iterations take the scoring dispatch, draftless ones still fuse K
    steps — K→1 boundary rules unchanged — and output stays
    token-identical through a mid-flight admission."""
    reqs = _requests(rng, 6, min_new=8, max_new=16)
    seq = serving.sequential_generate(lm, reqs)
    with serving.Engine(lm, slots=2, prefill_chunk=4, megastep=4,
                        speculative=True, spec_gamma=2,
                        name="specmega") as eng:
        eng._drafter = NgramDrafter(max_n=3, min_n=1)
        eng.warmup()
        out = eng.generate_many([p for p, _ in reqs[:4]],
                                [m for _, m in reqs[:4]])
        first = [eng.submit(p, m) for p, m in reqs[4:5]]
        time.sleep(0.02)
        rest = [eng.submit(p, m) for p, m in reqs[5:]]
        out += [h.result(timeout=60) for h in first + rest]
        assert eng.stats["spec_dispatches"] > 0
    _assert_identical(seq, out)


def test_spec_warmup_precompiles_scoring_program(lm):
    """Engine.warmup() pre-compiles the speculative scoring program
    (γ is a static shape constant — without this the first drafted
    batch eats the XLA compile mid-traffic, the stall PR 7/10 killed
    twice) and the truncated draft program with tier B; sampled=True
    adds the sampling-tail variant."""
    with serving.Engine(lm, slots=2, prefill_chunk=4,
                        speculative=True, spec_gamma=2) as eng:
        assert eng._spec_fn._cache_size() == 0
        eng.warmup()
        assert eng._spec_fn._cache_size() == 1
        eng.warmup(sampled=True)
        assert eng._spec_fn._cache_size() == 2
    with serving.Engine(lm, slots=2, prefill_chunk=4,
                        speculative=True, spec_gamma=2,
                        spec_drafter="truncated",
                        spec_layers=1) as tr:
        tr.warmup()
        assert tr._spec_fn._cache_size() == 1
        assert tr._draft_fn._cache_size() == 1


# -- seeded sampling + preemption -----------------------------------------

def test_spec_sampled_reproducible_and_matches_nonspec(rng, lm, spec4):
    """Seeded sampling under speculation: the counter-keyed PRNG
    (fold_in(seed, tokens_generated + j), position-indexed inside the
    scoring dispatch) makes sampled output (a) identical to the
    NON-speculative engine's for the same seeds — acceptance verifies
    against the very tokens the plain path would draw — and (b)
    replay-identical on re-execution (the fleet's exactly-once
    resubmission contract for sampled traffic)."""
    reqs = _requests(rng, 4, min_new=8, max_new=14)
    samp = [dict(temperature=0.9, top_k=8, seed=31 + i)
            for i in range(len(reqs))]

    def run(engine):
        hs = [engine.submit(p, m, sampling=s)
              for (p, m), s in zip(reqs, samp)]
        return [h.result(timeout=60) for h in hs]

    a = run(spec4)
    assert spec4.stats["spec_dispatches"] > 0
    b = run(spec4)                       # replica re-execution replay
    with serving.Engine(lm, slots=2, prefill_chunk=4) as plain:
        c = run(plain)
    for (ta, _), (tb, _), (tc, _) in zip(a, b, c):
        assert ta == tb == tc


def test_spec_preemption_resume_identity_and_no_leak(lm):
    """Pool-dry preemption under speculation: mandatory write
    positions walk the SAME pressure ladder as the plain engine (the
    preempted request re-prefills and replays identically), while
    draft positions only grow best-effort — speculation can never
    preempt committed work for a guess. Greedy identity + seeded
    reproduction + zero block leak."""
    long_reqs = [([1] + list(range(3, 15)), 32),
                 ([2] + list(range(5, 17)), 32)]
    seq = serving.sequential_generate(lm, long_reqs)
    eng = serving.Engine(lm, slots=2, prefill_chunk=4, block_size=8,
                         num_blocks=9, prefix_cache=False,
                         speculative=True, spec_gamma=4,
                         name="spec-tiny-pool")
    eng._drafter = NgramDrafter(max_n=3, min_n=1)
    try:
        out = eng.generate_many([p for p, _ in long_reqs],
                                [m for _, m in long_reqs])
        _assert_identical(seq, out)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["spec_dispatches"] > 0
        assert eng._pool.used == 0       # every block came back
        samp = [dict(temperature=0.8, top_k=6, seed=21 + i)
                for i in range(2)]

        def run():
            hs = [eng.submit(p, m, sampling=s)
                  for (p, m), s in zip(long_reqs, samp)]
            return [h.result(timeout=60) for h in hs]

        p0 = eng.stats["preemptions"]
        a, b = run(), run()
        assert eng.stats["preemptions"] > p0   # the sampled pass
        for (ta, _), (tb, _) in zip(a, b):     # itself preempted
            assert ta == tb
        assert eng._pool.used == 0
    finally:
        eng.close()


# -- telemetry -------------------------------------------------------------

def test_spec_telemetry_counters_rows_and_watch(rng, lm, tmp_path):
    """Day-one telemetry: ptpu_spec_* counters tick, serving_step
    rows carry CUMULATIVE spec_drafted/spec_accepted/spec_emitted/
    spec_dispatches, and monitor watch renders the acceptance-rate
    line (plain mode + --fleet merged counters)."""
    from paddle_tpu import monitor
    from paddle_tpu.monitor.watch import (WatchState, render_frame,
                                          fleet_lines)
    reqs = _requests(rng, 4, min_new=8, max_new=14)
    mlog = str(tmp_path / "spec.jsonl")
    d0 = monrt.SPEC_DISPATCHES.value()
    dr0 = monrt.SPEC_DRAFTED.value()
    ac0 = monrt.SPEC_ACCEPTED.value()
    monitor.enable(log_path=mlog)
    try:
        with serving.Engine(lm, slots=2, prefill_chunk=4,
                            speculative=True, spec_gamma=4,
                            name="spectel") as eng:
            eng._drafter = NgramDrafter(max_n=3, min_n=1)
            eng.generate_many([p for p, _ in reqs],
                              [m for _, m in reqs])
            stats = dict(eng.stats)
    finally:
        monitor.disable()
    assert monrt.SPEC_DISPATCHES.value() - d0 \
        == stats["spec_dispatches"] > 0
    assert monrt.SPEC_DRAFTED.value() - dr0 == stats["spec_drafted"]
    assert monrt.SPEC_ACCEPTED.value() - ac0 == stats["spec_accepted"]
    rows = [r for r in monitor.read_jsonl(mlog)
            if r["ev"] == "serving_step" and r["engine"] == "spectel"]
    assert rows
    last = rows[-1]
    assert last["spec_drafted"] == stats["spec_drafted"]
    assert last["spec_accepted"] == stats["spec_accepted"]
    assert last["spec_emitted"] == stats["spec_emitted"]
    assert last["spec_dispatches"] == stats["spec_dispatches"]
    # cumulative discipline: monotone across rows
    seqs = [r["spec_dispatches"] for r in rows]
    assert seqs == sorted(seqs)
    # watch (plain): the acceptance line renders from the last row
    st = WatchState()
    for r in rows:
        st.feed_event(r)
    frame = render_frame(st, mlog)
    assert "accept rate" in frame and "tok/dispatch" in frame
    # watch --fleet: merged ptpu_spec_* counters render the fleet line
    snap = {
        "ptpu_spec_drafted_tokens_total":
            {"kind": "counter", "series": {"": 10}},
        "ptpu_spec_accepted_tokens_total":
            {"kind": "counter", "series": {"": 4}},
        "ptpu_spec_dispatches_total":
            {"kind": "counter", "series": {"": 6}},
    }
    lines = "\n".join(fleet_lines(snap))
    assert "spec" in lines and "40%" in lines and "dispatches 6" in lines


# -- soak (slow tier) ------------------------------------------------------

@pytest.mark.slow
def test_spec_soak_identity_and_replay(rng, lm):
    """Seeded soak: repeated mixed greedy+sampled workloads through
    fresh speculative engines at γ∈{2,4} stay identical to the
    baseline / replay-identical across engines."""
    for trial in range(3):
        reqs = _requests(rng, 10, max_prompt=13, min_new=4,
                         max_new=24)
        seq = serving.sequential_generate(lm, reqs)
        g = 2 if trial % 2 else 4
        with serving.Engine(lm, slots=4, prefill_chunk=4,
                            speculative=True, spec_gamma=g) as eng:
            eng._drafter = NgramDrafter(max_n=3, min_n=1)
            out = eng.generate_many([p for p, _ in reqs],
                                    [m for _, m in reqs])
        _assert_identical(seq, out)
        samp = [dict(temperature=1.1, top_k=6, top_p=0.9,
                     seed=100 * trial + i) for i in range(4)]
        outs = []
        for _ in range(2):
            with serving.Engine(lm, slots=2, prefill_chunk=4,
                                speculative=True, spec_gamma=4) as e2:
                hs = [e2.submit(p, m, sampling=s)
                      for (p, m), s in zip(reqs[:4], samp)]
                outs.append([h.result(timeout=120)[0] for h in hs])
        assert outs[0] == outs[1]
