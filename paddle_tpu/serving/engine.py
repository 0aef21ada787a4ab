"""Continuous-batching decode engine: slot state + iteration scheduler.

Reference parity: the reference served generation through the C-API's
one-request-at-a-time ``GradientMachine::forward`` loop (capi/
gradient_machine.h) — PERF.md round 4/5 measured the equivalent path
here (bs1 KV-cached decode) at the per-step dispatch floor, ~23x below
the same chip's bs32 throughput. The engine is the standard fix, after
Orca (iteration-level scheduling) and vLLM (slot/block-managed caches):

  * **Slot-based decode state** — ONE compiled step over a fixed
    [slots, ...] KV cache (models/transformer_infer
    ``_step_logits_slots``) with per-slot write positions, active masks
    and sampling state (greedy + cumulative log-prob). The compiled
    shape never changes as requests of different lengths come and go.
  * **Iteration-level scheduler** — a thread-safe queue feeding
    admissions at step boundaries: slots retire on EOS / max_new and
    refill mid-flight; an admitted prompt prefills CHUNK by chunk
    (``_prefill_chunk_slot``, one chunk per engine iteration) so one
    long prompt cannot stall the running batch; the admission policy is
    greedy fill by default with an optional wait-for-batch window.
  * **Megastep decode** (ISSUE 7) — with ``megastep=K`` (flag
    ``serving_megastep``) an iteration with no pending admissions or
    prefills fuses K decode steps into ONE dispatch (``lax.scan`` over
    the slot step), attacking the measured bs1 per-step dispatch floor
    (PERF.md rounds 5/6) while staying token-identical; pending work
    forces a K→1 boundary first. ``warmup()`` compiles both dispatch
    paths before traffic.
  * **Paged KV + prefix reuse + sampling** (ISSUE 10, default on; flag
    ``serving_paged``) — instead of a dense per-slot ``max_len``
    stripe, K/V live in a SHARED ``[num_blocks, n_layer, n_head,
    block_size, dk]`` pool addressed through per-slot block tables
    (``serving.kvpool.BlockPool``): blocks allocate at admission /
    as decode crosses block boundaries and free at retirement, so a
    short request no longer reserves ``max_len`` worth of cache. A
    radix prefix cache (``kvpool.RadixCache``) maps full-block prompt
    prefixes to refcounted block chains — an admission whose prompt
    shares a cached prefix SKIPS those prefill chunks entirely
    (copy-on-write resolves the one case a shared block would be
    written; LRU eviction of unreferenced chains bounds the cache at
    the pool size). When the pool runs dry anyway, the LOWEST-priority
    (latest-admitted) request is PREEMPTED: its blocks free, it
    re-queues for re-prefill, and deterministic decode (greedy, or
    counter-keyed seeded sampling) makes the resumed output identical
    — exactly-once survives. Per-request ``SamplingParams``
    (temperature / top-k / top-p / seed) execute in-step with per-slot
    PRNG state; temperature-0 requests stay BITWISE-greedy (the
    megastep/fleet token-identity contracts are untouched).
  * **Speculative decode** (ISSUE 13; flags ``serving_speculative`` /
    ``serving_spec_gamma`` / ``serving_spec_drafter``) — the lever
    PR 10 deferred: a cheap drafter proposes up to γ tokens per live
    slot (tier A: prompt/n-gram lookup over the request's own token
    chain plus the radix cache's published chains, ``serving/spec.py``;
    tier B: a truncated-layer pass over the same weights), the full
    model scores all γ+1 positions in ONE paged-attention dispatch
    (``_spec_logits_paged`` — multi-position masked writes, per-slot
    ragged draft lengths through the block-table gather), and the
    longest prefix of drafts matching the model's own tokens is
    accepted IN-STEP — every dispatch lands 1..γ+1 VERIFIED tokens.
    Correctness never depends on the drafter: temp-0 output stays
    BITWISE the non-speculative engine's (accepted tokens ARE the
    greedy tokens), seeded sampling replays identically (acceptance is
    keyed on the same ``fold_in(seed, tokens_generated)`` draws), and
    megastep / preemption / fleet exactly-once compose unchanged (a
    no-draft iteration runs the existing programs cost-for-cost).

Every engine iteration is instrumented: monitor gauges/counters
(``ptpu_serving_*``), a ``serving_step`` flight-recorder row carrying
the active trace id, and an ``engine.step`` trace span. Every REQUEST
is instrumented too (the unit a user experiences, which Orca-style
iteration scheduling makes a product of policy, not just kernel time):
lifecycle stamps at enqueue/admit/first-token/retire on the ``Request``
handle, derived queue_wait / TTFT / TPOT, a ``serving_request``
recorder row + ``ptpu_serving_{ttft,tpot,queue_wait}_seconds``
histograms at retirement, and a ``serving.request`` trace span (child
spans per prefill chunk, a first-token mark, step-span links) so
``trace merge`` shows request lanes across the fleet timeline.
"""

import collections
import itertools
import os
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..monitor import runtime as _monrt
from ..ops import paged_attention as _paged_ops
from ..trace import runtime as _trc
from . import kvpool as _kvpool
from . import spec as _spec
from .sampling import SamplingParams, sample as _sample, \
    step_keys as _step_keys

__all__ = ["Engine", "Request", "sequential_generate"]


class Request:
    """One submitted generation request; also the result handle.

    ``result()`` blocks until the engine retires the request and returns
    ``(tokens, score)`` — the greedy continuation (EOS included when hit,
    at most ``max_new`` tokens) and the sum of token log-probs.

    Lifecycle attribution (ISSUE 6): the engine stamps four monotonic
    (``time.perf_counter``) timestamps — ``t_enqueue`` (submit),
    ``t_admit`` (decode-slot admission), ``t_first_token`` (first
    decoded token lands), ``t_retire`` (EOS / max_new / failure) — and
    the handle derives the three per-request latency figures a serving
    SLO is written against: ``queue_wait``, ``ttft`` and ``tpot``.
    Stamps later in the lifecycle are ``None`` until reached; reading
    them after ``result()`` returns is race-free (the engine writes
    them before resolving the future). ``t_tokens`` holds one stamp
    per output token (tokens of one dispatch share theirs)."""

    __slots__ = ("prompt", "max_new", "tokens", "score", "_event",
                 "_error", "t_enqueue", "t_admit", "t_first_token",
                 "t_retire", "prefill_chunks", "_span", "rid",
                 "sampling", "preemptions", "_seq", "t_tokens")

    def __init__(self, prompt, max_new, request_id=None, sampling=None):
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        # per-request sampling (ISSUE 10): None = bitwise-greedy (the
        # temperature-0 default every identity pin rides on)
        self.sampling = sampling
        self.preemptions = 0
        # admission priority: set once at FIRST admission and preserved
        # across preemption, so a preempted request re-admits at its
        # original priority instead of re-entering as "newest"
        self._seq = None
        # durable caller-assigned id (serving.fleet router): a request
        # RE-EXECUTED on a second replica after churn carries the SAME
        # id, so its serving.request spans on both replicas share the
        # rid attr — the resubmission hop is joinable in `trace merge`
        self.rid = request_id
        self.tokens = []
        # one perf_counter stamp per output token, appended where
        # ``tokens`` is, from the ``now`` the engine loop took after
        # the dispatch that produced it (t_tokens[0] == t_first_token):
        # the program's half of a per-token gap percentile
        self.t_tokens = []
        self.score = None
        self._event = threading.Event()
        self._error = None
        self.t_enqueue = time.perf_counter()
        self.t_admit = None
        self.t_first_token = None
        self.t_retire = None
        self.prefill_chunks = 0
        attrs = {"prompt_len": len(self.prompt),
                 "max_new": self.max_new}
        if request_id is not None:
            attrs["rid"] = str(request_id)
        self._span = _trc.detached_span("serving.request", **attrs)
        self._span.start()

    @property
    def queue_wait(self):
        """Seconds from submit to decode-slot admission (None until
        admitted)."""
        if self.t_admit is None:
            return None
        return self.t_admit - self.t_enqueue

    @property
    def ttft(self):
        """Time to first token: submit -> first decoded token (the
        latency a streaming user perceives before output starts)."""
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_enqueue

    @property
    def tpot(self):
        """Mean per-token decode latency AFTER the first token (the
        steady streaming rate); 0.0 for single-token requests, None
        until retired."""
        if self.t_first_token is None or self.t_retire is None:
            return None
        n = len(self.tokens)
        if n <= 1:
            return 0.0
        return (self.t_retire - self.t_first_token) / (n - 1)

    def latency(self):
        """The request's lifecycle attribution as one JSON-able dict
        (what the ``serving_request`` recorder row carries)."""
        return {"queue_wait": self.queue_wait, "ttft": self.ttft,
                "tpot": self.tpot, "tokens": len(self.tokens),
                "prefill_chunks": self.prefill_chunks}

    def _finish(self, score):
        self.score = score
        self._event.set()

    def _fail(self, err):
        self._error = err
        self._event.set()

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                "request not finished within %r s" % (timeout,))
        if self._error is not None:
            raise RuntimeError(
                "serving engine failed: %r" % (self._error,))
        return list(self.tokens), self.score


def _flag(name, default):
    from .. import flags
    try:
        return flags.get_flag(name)
    except KeyError:
        return default


# the per-slot sampling state a greedy (default) request activates with
_GREEDY = SamplingParams()


class Engine:
    """Continuous-batching engine over a KV-cached incremental decoder.

    ``model`` is a ``models.transformer_infer.TransformerLMInfer`` (or
    anything exposing the same slot-step protocol: ``_init_state``,
    ``_step_logits_slots``, ``_prefill_chunk_slot``, ``max_len``,
    ``end_id``, ``bos_id``). ``slots`` is the fixed decode batch
    capacity; ``prefill_chunk`` the per-iteration prompt chunk length
    (flag ``serving_prefill_chunk``); ``admission_wait`` an optional
    wait-for-batch window in seconds applied when the engine is idle
    (flag ``serving_admission_wait``; 0 = greedy fill); ``megastep``
    fuses K decode iterations into ONE device dispatch whenever no
    admissions or prefills are pending (flag ``serving_megastep``;
    1 = one dispatch per decode step) — token-identical output with
    K-1 fewer host round-trips per K tokens, at the cost of TTFT/TPOT
    stamps coarsening to megastep granularity and admissions landing
    at megastep boundaries (a pending admission forces a K→1 boundary
    first).

    Paged KV (ISSUE 10; flags ``serving_paged`` /
    ``serving_block_size`` / ``serving_kv_blocks`` /
    ``serving_prefix_cache``): ``paged=True`` (the default) stores K/V
    in a shared block pool with per-slot block tables, a radix prefix
    cache over full-block prompt prefixes, copy-on-write for shared
    blocks, and preemption (lowest-priority request re-queued for
    re-prefill) when the pool runs dry. ``paged=False`` restores the
    PR-5 dense ``[slots, ...]`` layout. ``num_blocks`` defaults to
    ``slots * ceil(max_len / block_size)`` — dense-capacity parity,
    with the savings coming from short requests and shared prefixes.
    Greedy output is token-identical across both layouts; per-request
    ``sampling`` (``SamplingParams``) rides either.

    Speculative decode (ISSUE 13; flags ``serving_speculative`` /
    ``serving_spec_gamma`` / ``serving_spec_drafter`` /
    ``serving_spec_ngram`` / ``serving_spec_layers``):
    ``speculative=True`` drafts up to ``spec_gamma`` tokens per live
    slot each iteration and verifies all of them in one scoring
    dispatch — requires the paged layout (the ragged per-slot draft
    lengths ride the block-table gather). ``spec_drafter``: ``ngram``
    (default; host-side prompt/n-gram lookup, ``serving/spec.py``) or
    ``truncated`` (a ``spec_layers``-deep pass over the same weights,
    one extra fused dispatch per drafted iteration). ``spec_gamma=0``
    disables speculation outright — the engine is program-for-program
    the non-speculative one."""

    def __init__(self, model, slots=8, prefill_chunk=None,
                 admission_wait=None, name="engine", megastep=None,
                 paged=None, block_size=None, num_blocks=None,
                 prefix_cache=None, speculative=None, spec_gamma=None,
                 spec_drafter=None, spec_layers=None,
                 block_kernel=None, kv_quant=None):
        if slots < 1:
            raise ValueError("slots must be >= 1, got %r" % (slots,))
        from .artifact import is_artifact_path, model_from_artifact
        if is_artifact_path(model):
            # serving cold-start (ISSUE 15 / ROADMAP 2(b)): a
            # load_inference_model artifact directory in place of a
            # live model object — fleet.Replica passes its ``model``
            # straight here, so replicas boot from the artifact too
            model = model_from_artifact(model)
        self.model = model
        self.slots = int(slots)
        self.name = name
        # canary analysis plane (serving.fleet / serving.rollout):
        # `shadow` marks every row/metric this engine emits as mirrored
        # traffic — scored, never served — so the incumbent's SLO
        # histograms and the autoscaler's load signals never see it
        # (the PR-6 failed-request exclusion discipline, applied to
        # shadow decodes). `version` stamps the artifact version on
        # serving_request rows so candidate-vs-incumbent delta
        # objectives can split samples by version.
        self.shadow = False
        self.version = None
        self._chunk = int(prefill_chunk
                          if prefill_chunk is not None
                          else _flag("serving_prefill_chunk", 16))
        self._chunk = max(1, min(self._chunk, model.max_len))
        self._admission_wait = float(
            admission_wait if admission_wait is not None
            else _flag("serving_admission_wait", 0.0))
        # megastep K (ISSUE 7): decode iterations fused into ONE device
        # dispatch (lax.scan over _step_impl) whenever no admissions or
        # prefills are pending — K-1 fewer host round-trips per K
        # tokens, attacking the measured bs1 per-step dispatch floor
        # (PERF.md round 5). Admissions/retirement bookkeeping land at
        # megastep boundaries; output stays token-identical (same
        # per-iteration math, composed by scan). TTFT/TPOT attribution
        # coarsens to megastep granularity: all K tokens of one
        # dispatch land at the same host timestamp.
        self._megastep = max(1, int(megastep if megastep is not None
                                    else _flag("serving_megastep", 1)))
        # paged KV (ISSUE 10): host-side block accounting; the device
        # pool arrays live in self._state. Block tables are rebuilt as
        # a small [slots, max_blocks] int32 array per dispatch and
        # passed as a plain (non-donated) argument to the compiled
        # step — the compiled SHAPE never changes as tables do.
        self._paged = bool(paged if paged is not None
                           else _flag("serving_paged", True))
        if self._paged:
            bs = int(block_size if block_size is not None
                     else _flag("serving_block_size", 16))
            self._block_size = max(1, min(bs, model.max_len))
            self._max_blocks = -(-model.max_len // self._block_size)
            nb = int(num_blocks if num_blocks is not None
                     else _flag("serving_kv_blocks", 0))
            if nb <= 0:
                # capacity parity with the dense layout by default —
                # the paged win is that SHORT requests no longer pin
                # max_len worth of it, and shared prefixes share it
                nb = self.slots * self._max_blocks
            if nb < self._max_blocks:
                raise ValueError(
                    "num_blocks %d cannot hold one max_len request "
                    "(%d blocks of %d positions)"
                    % (nb, self._max_blocks, self._block_size))
            self._pool = _kvpool.BlockPool(nb, self._block_size)
            use_prefix = bool(
                prefix_cache if prefix_cache is not None
                else _flag("serving_prefix_cache", True))
            self._prefix = (_kvpool.RadixCache(self._block_size,
                                               self._pool)
                            if use_prefix else None)
            # block-native attention kernel (ISSUE 20): the default
            # decode path walks only each slot's live block chain
            # (ops/paged_attention online softmax); block_kernel=False
            # (flag serving_block_kernel=0) is the PR-10 dense-gather
            # escape hatch. attn_unroll: lax-fallback blocks per loop
            # trip. kv_quant ('int8' / 'fp8', OFF by default): pool
            # stores codes + per-vector scales — validated here so a
            # bad flag fails at construction, not at first trace.
            self._attn_unroll = max(1, int(_flag("serving_attn_unroll",
                                                 1)))
            kvq = (kv_quant if kv_quant is not None
                   else _flag("serving_kv_quant", ""))
            kvq = str(kvq or "").strip().lower()
            self._kv_quant = kvq if kvq not in ("", "none", "off") \
                else None
            _paged_ops.kv_quant_spec(self._kv_quant)   # validate
            # the kernel accumulates in fp32 — a DIFFERENT reduction
            # order than the dense row math, so the bf16 serving
            # cast's bitwise contract (engine == bf16 sequential
            # baseline) only holds on the gather path: low-precision
            # un-quantized pools keep gather by DEFAULT (explicit
            # block_kernel=True still opts in; quantized pools are
            # rtol-pinned, not bitwise, so they stay on the kernel)
            kern_ok = (self._kv_quant is not None
                       or jnp.dtype(model.word_emb.dtype)
                       == jnp.dtype(jnp.float32))
            self._block_kernel = bool(
                block_kernel if block_kernel is not None
                else (_flag("serving_block_kernel", True) and kern_ok))
            dk = model.d_model // model.n_head
            self._block_bytes = _kvpool.bytes_per_block(
                model.n_layer, model.n_head, self._block_size, dk,
                dtype_bytes=jnp.dtype(model.word_emb.dtype).itemsize,
                kv_quant=self._kv_quant)
        else:
            if kv_quant:
                raise ValueError(
                    "kv_quant requires the paged KV layout "
                    "(per-block scales live beside the block pool); "
                    "pass paged=True or drop kv_quant")
            self._pool = None
            self._prefix = None
            self._block_kernel = False
            self._attn_unroll = 1
            self._kv_quant = None
            self._block_bytes = 0
        # speculative decode (ISSUE 13): γ drafted tokens per live slot
        # verified in ONE scoring dispatch. γ is a STATIC shape
        # constant of the scoring program ([S, γ+1] feed), so one γ =
        # one compile (warmup() pays it up front); γ=0 or
        # speculative=False leaves every existing program untouched.
        self._spec_gamma = max(0, int(
            spec_gamma if spec_gamma is not None
            else _flag("serving_spec_gamma", 4)))
        spec_on = bool(speculative if speculative is not None
                       else _flag("serving_speculative", False))
        self._speculative = spec_on and self._spec_gamma > 0
        self._spec_fn = None
        self._draft_fn = None
        self._drafter = None
        self._spec_kind = None
        if self._speculative:
            if not self._paged:
                raise ValueError(
                    "speculative decode requires the paged KV layout "
                    "(per-slot ragged draft lengths ride the "
                    "block-table gather); pass paged=True or drop "
                    "speculative")
            kind = str(spec_drafter if spec_drafter is not None
                       else _flag("serving_spec_drafter", "ngram"))
            if kind not in ("ngram", "truncated"):
                raise ValueError(
                    "serving_spec_drafter must be 'ngram' or "
                    "'truncated', got %r" % (kind,))
            self._spec_kind = kind
            self._drafter = _spec.NgramDrafter(
                max_n=_flag("serving_spec_ngram", 3),
                min_n=_flag("serving_spec_ngram_min", 2))
            if kind == "truncated":
                nl = int(spec_layers if spec_layers is not None
                         else _flag("serving_spec_layers", 0))
                if nl <= 0:
                    nl = max(1, model.n_layer // 2)
                self._spec_layers = min(nl, model.n_layer)
                self._draft_fn = jax.jit(self._draft_truncated_impl,
                                         donate_argnums=0)
            self._spec_fn = jax.jit(self._spec_step_impl,
                                    donate_argnums=0, static_argnums=3)
        self._admit_seq = itertools.count()  # admission priority order
        self._preempted_iter = 0
        self._cv = threading.Condition()
        self._queue = collections.deque()
        self._recs = [None] * self.slots   # loop-thread-only slot records
        self._stop = False
        self._error = None                 # loop-death cause, if any
        self._state = self._init_state()
        # `sampled` is static (arg 2): two cached compiles — the
        # all-greedy program (bitwise PR-5) and, only once stochastic
        # traffic actually lands, the sampling-tail program
        self._step_fn = jax.jit(self._step_impl, donate_argnums=0,
                                static_argnums=2)
        self._megastep_fn = None           # built lazily (jit) at K > 1
        self._prefill_fn = jax.jit(self._prefill_impl, donate_argnums=0)
        self._activate_fn = jax.jit(self._activate_impl, donate_argnums=0)
        self._release_fn = None            # built lazily (preemption)
        self._copy_fn = None               # built lazily (COW)
        self.stats = {"steps": 0, "decode_steps": 0, "tokens": 0,
                      "admissions": 0, "retirements": 0,
                      "active_slot_steps": 0, "prefill_chunks": 0,
                      "megastep_dispatches": 0, "prefix_hits": 0,
                      "prefix_misses": 0, "prefix_hit_tokens": 0,
                      "prefix_evictions": 0, "preemptions": 0,
                      "cow_copies": 0, "kv_peak_blocks": 0,
                      "spec_dispatches": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "spec_emitted": 0}
        # optional completion hook (serving.fleet's ReplicaServer):
        # called with each Request AFTER its future resolves — retired
        # or failed — so an RPC front can deliver results event-driven
        # instead of polling handles. Exceptions are swallowed: a
        # delivery hook must never kill the decode loop.
        self.on_retire = None
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ptpu-" + name)
        self._thread.start()

    # -- public API --------------------------------------------------------
    def warmup(self, sampled=False):
        """Compile the GREEDY decode dispatch paths up front: the
        single step (paged or dense) and, with ``megastep`` > 1, the
        fused K-step twin. One decode over the ALL-INACTIVE slot state
        is semantically a no-op — the active mask gates every cache
        write (paged writes of masked rows drop out of bounds) and
        every sampling-state update — so this pays only the compiles.
        Call before submitting traffic (the scheduler loop never
        touches decode state while the queue and slots are empty).
        Without it a megastep engine compiles the single-step path
        lazily on its first mid-flight admission, stalling that
        iteration by a full XLA compile — and a PAGED K>1 engine
        previously compiled both paged paths mid-traffic (the
        PR-7-measured 660 ms stall). A SPECULATIVE engine additionally
        pre-compiles the γ-position scoring program (and the
        truncated-layer draft program with the tier-B drafter): γ is a
        static shape constant, so the first drafted batch would
        otherwise eat that compile mid-traffic. ``sampled=True`` additionally
        pre-compiles the sampling-tail variants — pass it when the
        workload will carry ``SamplingParams``, otherwise the first
        stochastic request eats those compiles mid-traffic (the
        greedy-only default keeps greedy benches from paying for
        programs they never dispatch)."""
        # the whole body holds _cv: a submit() racing in after the
        # guard would otherwise let the loop thread activate a slot in
        # self._state concurrently with warmup donating it (_step_fn
        # donate_argnums=0) or have the trailing reassignment discard
        # the activation — while _cv is held the loop stays parked in
        # its idle wait and submits block until warmup finishes
        with self._cv:
            if self._queue or any(r is not None for r in self._recs):
                raise RuntimeError(
                    "warmup() must run before traffic is submitted "
                    "(the scheduler loop owns the decode state once a "
                    "request is in flight)")
            btab = self._btab_all()
            variants = (False, True) if sampled else (False,)
            state = self._state
            for v in variants:
                state, _, _ = self._step_fn(state, btab, v)
                if self._megastep > 1:
                    if self._megastep_fn is None:
                        self._megastep_fn = jax.jit(
                            self._megastep_impl, donate_argnums=0,
                            static_argnums=2)
                    state, _, _ = self._megastep_fn(state, btab, v)
                if self._speculative:
                    # the speculative scoring program too (ISSUE 13
                    # satellite): γ is a static shape constant, so
                    # without this the first DRAFTED batch eats the
                    # scoring compile mid-traffic — the exact stall
                    # PR 7/10 killed twice for the step/megastep paths
                    zdn = jnp.zeros(
                        (self.slots, self._spec_gamma + 1), jnp.int32)
                    state, _ = self._spec_fn(state, btab, zdn, v)
            if self._speculative and self._draft_fn is not None:
                state, _ = self._draft_fn(
                    state, btab, jnp.zeros((self.slots,), jnp.int32))
            self._state = state
        return self

    def submit(self, prompt, max_new_tokens, request_id=None,
               sampling=None):
        """Enqueue one request; returns its Request handle. ``prompt``
        is the token-id prefix (≥ 1 token — pass ``[model.bos_id]`` for
        unconditional generation). ``request_id``: optional durable id
        (the fleet router's exactly-once key) stamped on the handle and
        its trace span — admission itself never dedups; the fleet tier
        (ReplicaServer journal) is where resubmitted ids are made
        idempotent BEFORE they reach the engine. ``sampling``: a
        ``SamplingParams`` (or its dict form, the fleet wire shape);
        None / temperature 0 = bitwise-greedy."""
        prompt = [int(t) for t in (prompt or [self.model.bos_id])]
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(
                "max_new_tokens must be >= 1, got %d" % max_new)
        # cache positions used: prompt at 0..P-1, generated tokens
        # continue to P+max_new-2 — past max_len the pos-emb gather and
        # the cache writes would clamp and corrupt state; fail loudly
        if len(prompt) + max_new - 1 > self.model.max_len:
            raise ValueError(
                "prompt len %d + max_new %d exceeds model max_len %d"
                % (len(prompt), max_new, self.model.max_len))
        # validate BEFORE the handle exists (same ValueError surface as
        # the bounds above, so the fleet's BADR typed-reject covers it)
        sp = (SamplingParams.from_dict(sampling)
              if sampling is not None else None)
        if sp is not None and sp.greedy:
            # temperature 0 is argmax no matter what top_k/top_p/seed
            # say — fold to the default so a temp-0 request never
            # forces co-scheduled traffic onto the sampled program
            sp = None
        with self._cv:
            if self._stop:
                err = getattr(self, "_error", None)
                if err is not None:
                    raise RuntimeError(
                        "engine is closed (loop died: %r)" % (err,))
                raise RuntimeError("engine is closed")
            # construct after the closed-check: a rejected submit must
            # not open a request span nobody will ever finish
            req = Request(prompt, max_new, request_id=request_id,
                          sampling=sp)
            self._queue.append(req)
            self._cv.notify_all()
        return req

    @staticmethod
    def result(request, timeout=None):
        return request.result(timeout)

    def generate_many(self, prompts, max_new_tokens):
        """Synchronous convenience: submit every prompt, block for all
        results (in input order). ``max_new_tokens`` is a scalar or a
        per-prompt sequence."""
        n = len(prompts)
        if not hasattr(max_new_tokens, "__len__"):
            max_new_tokens = [max_new_tokens] * n
        reqs = [self.submit(p, m)
                for p, m in zip(prompts, max_new_tokens)]
        return [r.result() for r in reqs]

    def occupancy(self):
        """Mean active-slot fraction over the decode steps run so far."""
        d = self.stats["decode_steps"] * self.slots
        return self.stats["active_slot_steps"] / d if d else 0.0

    def close(self):
        """Stop the engine loop. Requests still queued or in flight are
        failed (their ``result()`` raises)."""
        with self._cv:
            already = self._stop
            self._stop = True
            self._cv.notify_all()
        if already:
            return
        self._thread.join()
        self._fail_all(RuntimeError("engine closed"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- compiled pieces ---------------------------------------------------
    def _init_state(self):
        if self._paged:
            s = self.model._init_paged_state(self._pool.num_blocks,
                                             self._block_size,
                                             kv_quant=self._kv_quant)
        else:
            s = self.model._init_state(self.slots)
        z = lambda dt: jnp.zeros((self.slots,), dt)
        s["tok"], s["pos"], s["count"] = z(jnp.int32), z(jnp.int32), \
            z(jnp.int32)
        s["active"] = z(bool)
        s["score"] = z(jnp.float32)
        s["max_new"] = jnp.ones((self.slots,), jnp.int32)
        # per-slot sampling state (ISSUE 10): zeros = bitwise-greedy
        s["temp"] = z(jnp.float32)
        s["topk"] = z(jnp.int32)
        s["topp"] = jnp.ones((self.slots,), jnp.float32)
        s["seed"] = z(jnp.uint32)
        return s

    def _step_impl(self, state, btab, sampled=False):
        """One decode iteration over all slots: sample every active
        slot (argmax for temperature-0 slots — the bitwise-greedy
        default — a per-slot counter-keyed draw otherwise), advance
        its cache position, flag retirements. ``btab`` is the
        [slots, max_blocks] block-table array in paged mode, None in
        dense mode (the PR-5 layout). ``sampled`` is STATIC (a
        separate compile per value): the host dispatches the sampled
        program only while a stochastic request is live, so the
        all-greedy hot path never pays the per-slot PRNG + two vocab
        sorts (measured ~0.33 ms/step on this CPU — ~2.7x the whole
        greedy step) and stays instruction-for-instruction the PR-5
        program."""
        state = dict(state)
        tok, pos, active = state["tok"], state["pos"], state["active"]
        if self._paged:
            logits, state = self.model._step_logits_paged(
                tok, state, pos, btab, write_mask=active,
                block_kernel=self._block_kernel,
                attn_unroll=self._attn_unroll)
        else:
            logits, state = self.model._step_logits_slots(
                tok, state, pos, write_mask=active)
        with jax.named_scope("sample"):
            logits32 = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits32)
            greedy = jnp.argmax(logp, axis=-1).astype(jnp.int32)
            if sampled:
                # per-slot draw, SELECTED per slot: temperature-0 slots
                # take the greedy value through an elementwise where,
                # so their tokens are bitwise the greedy program's
                keys = _step_keys(state["seed"], state["count"])
                drawn = _sample(logits32, state["temp"], state["topk"],
                                state["topp"], keys)
                nxt = jnp.where(state["temp"] > 0.0, drawn, greedy)
            else:
                nxt = greedy
            tok_logp = jnp.take_along_axis(logp, nxt[:, None],
                                           axis=-1)[:, 0]
        end = jnp.int32(self.model.end_id)
        emit = jnp.where(active, nxt, end)
        count = state["count"] + active.astype(jnp.int32)
        fin = active & ((nxt == end) | (count >= state["max_new"]))
        state["score"] = state["score"] + jnp.where(active, tok_logp, 0.0)
        state["tok"] = jnp.where(active, nxt, tok)
        state["pos"] = pos + active.astype(jnp.int32)
        state["count"] = count
        state["active"] = active & ~fin
        return state, emit, fin

    def _megastep_impl(self, state, btab, sampled=False):
        """K decode iterations fused into one device program: a
        lax.scan over ``_step_impl``, streaming each sub-iteration's
        (emit, fin) rows out as ``[K, S]`` stacks. A slot that retires
        at sub-iteration j goes inactive in the carry, so later
        sub-iterations emit end_id for it and write nothing — the host
        loop skips those rows, keeping output token-identical to K
        single steps. In paged mode the host pre-allocates blocks for
        all K write positions, so one table serves the whole fused
        dispatch. ``sampled`` is static, like ``_step_impl``'s."""
        def body(st, _):
            st, emit, fin = self._step_impl(st, btab, sampled)
            return st, (emit, fin)

        state, (emits, fins) = jax.lax.scan(
            body, dict(state), None, length=self._megastep)
        return state, emits, fins

    def _spec_step_impl(self, state, btab, dn, sampled=False):
        """Speculative scoring + in-step acceptance (ISSUE 13): ONE
        paged-attention dispatch scores every slot's current token plus
        its drafted tokens, then accepts the longest prefix of drafts
        matching the model's OWN next tokens — greedy argmax for
        temperature-0 slots, the counter-keyed draw
        (``fold_in(seed, tokens_generated + j)``) for sampled slots,
        position-indexed exactly as j successive single steps would
        have drawn. Emitting only those tokens is what makes
        speculative output bitwise the non-speculative engine's: a
        WRONG draft costs a rejection, never a wrong token.

        ``dn`` [S, γ+1] int32 packs the per-slot draft length (column
        0, ragged 0..γ) with the γ draft tokens — ONE host→device
        transfer per dispatch; the reply packs emits/n_emit/fin into
        one int32 fetch the same way (the per-dispatch host tax is on
        the bs1 floor this feature exists to break).

        Returns ``(state, out [S, γ+3])``: columns 0..γ are the
        emitted tokens (end_id filler past each slot's count), column
        γ+1 the per-slot emit count (1..γ+1 for active slots — the
        bonus token the scoring logits buy rides every dispatch,
        truncated at EOS inside an accepted draft and at the slot's
        ``max_new`` budget), column γ+2 the retirement flag. Cache
        position / count / score / PRNG counter advance by the emit
        count, so the next dispatch (speculative or not) continues
        exactly where K single steps would have."""
        state = dict(state)
        tok, pos, active = state["tok"], state["pos"], state["active"]
        count = state["count"]
        drafts = dn[:, 1:]
        c = drafts.shape[1] + 1
        toks = jnp.concatenate([tok[:, None], drafts], axis=1)
        nd = jnp.where(active, dn[:, 0], 0)
        logits, state = self.model._spec_logits_paged(
            toks, state, pos, btab, nd, write_mask=active,
            block_kernel=self._block_kernel,
            attn_unroll=self._attn_unroll)
        logits32 = logits.astype(jnp.float32)        # [S, C, V]
        logp = jax.nn.log_softmax(logits32)
        greedy = jnp.argmax(logp, axis=-1).astype(jnp.int32)
        if sampled:
            s = tok.shape[0]
            counts = count[:, None] + jnp.arange(c)[None, :]
            keys = _step_keys(jnp.repeat(state["seed"], c),
                              counts.reshape(-1))
            rep = lambda a: jnp.repeat(a, c)
            drawn = _sample(logits32.reshape(s * c, -1),
                            rep(state["temp"]), rep(state["topk"]),
                            rep(state["topp"]), keys).reshape(s, c)
            target = jnp.where((state["temp"] > 0.0)[:, None], drawn,
                               greedy)
        else:
            target = greedy
        # accept-longest-prefix: draft j+1 must equal the model's own
        # token at position j (cumprod stops at the first mismatch)
        match = (toks[:, 1:] == target[:, :-1]) \
            & (jnp.arange(c - 1)[None, :] < nd[:, None])
        m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                    axis=1)                          # accepted drafts
        ncap = jnp.minimum(m + 1, state["max_new"] - count)
        jj = jnp.arange(c)[None, :]
        is_end = (target == jnp.int32(self.model.end_id)) \
            & (jj < ncap[:, None])
        end_pos = jnp.min(jnp.where(is_end, jj, c), axis=1)
        n_emit = jnp.where(active, jnp.minimum(ncap, end_pos + 1), 0)
        fin = active & ((end_pos < ncap)
                        | (count + n_emit >= state["max_new"]))
        emit_mask = jj < n_emit[:, None]
        tok_logp = jnp.take_along_axis(
            logp, target[:, :, None], axis=-1)[:, :, 0]
        state["score"] = state["score"] + jnp.sum(
            jnp.where(emit_mask, tok_logp, 0.0), axis=1)
        last = jnp.maximum(n_emit - 1, 0)
        new_tok = jnp.take_along_axis(target, last[:, None],
                                      axis=1)[:, 0]
        state["tok"] = jnp.where(active, new_tok, tok)
        state["pos"] = pos + n_emit
        state["count"] = count + n_emit
        state["active"] = active & ~fin
        emits = jnp.where(emit_mask, target,
                          jnp.int32(self.model.end_id))
        out = jnp.concatenate(
            [emits, n_emit[:, None], fin.astype(jnp.int32)[:, None]],
            axis=1)
        return state, out

    def _draft_truncated_impl(self, state, btab, n_draft):
        """Tier-B drafter: γ greedy decode steps through only the
        FIRST ``spec_layers`` transformer layers (same weights, same
        paged pool), scanned into ONE dispatch. Draft K/V lands only
        at the truncated layers of positions the scoring dispatch
        immediately re-writes at FULL depth, so the drafter needs no
        KV state of its own; writes beyond a slot's ``n_draft`` budget
        are masked (they would fall past its block table). Returns
        ``(state, drafts [S, γ])``. Draft quality only moves the
        acceptance rate — never the output."""
        state = dict(state)
        active = state["active"]
        pool = self.model._pool_slice(state)

        def body(carry, _):
            pool, tok, pos, j = carry
            wmask = active & (j <= n_draft)
            logits, pool = self.model._step_logits_paged(
                tok, pool, pos, btab, write_mask=wmask,
                n_layers=self._spec_layers,
                block_kernel=self._block_kernel,
                attn_unroll=self._attn_unroll)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (pool, nxt, pos + 1, j + 1), nxt

        (pool, _, _, _), drafts = jax.lax.scan(
            body,
            (pool, state["tok"], state["pos"],
             jnp.zeros((), jnp.int32)),
            None, length=self._spec_gamma)
        state.update(pool)
        return state, jnp.transpose(drafts)          # [γ,S] → [S,γ]

    def _prefill_impl(self, state, slot, toks, start, n_valid,
                      btab_row):
        if self._paged:
            return self.model._prefill_chunk_paged(
                dict(state), toks, start, n_valid, btab_row,
                block_kernel=self._block_kernel,
                attn_unroll=self._attn_unroll)
        return self.model._prefill_chunk_slot(
            dict(state), slot, toks, start, n_valid)

    def _activate_impl(self, state, slot, tok, pos, max_new, temp,
                       topk, topp, seed):
        state = dict(state)
        at = lambda n, v: state[n].at[slot].set(v)
        state["tok"] = at("tok", tok)
        state["pos"] = at("pos", pos)
        state["active"] = at("active", True)
        state["score"] = at("score", 0.0)
        state["count"] = at("count", 0)
        state["max_new"] = at("max_new", max_new)
        state["temp"] = at("temp", temp)
        state["topk"] = at("topk", topk)
        state["topp"] = at("topp", topp)
        state["seed"] = at("seed", seed)
        return state

    def _release_impl(self, state, slot):
        """Deactivate one slot (preemption): the write mask goes False
        so the slot's stale tok/pos can never write again; everything
        else resets at re-activation."""
        state = dict(state)
        state["active"] = state["active"].at[slot].set(False)
        return state

    def _copy_impl(self, state, src, dst):
        """Copy-on-write: duplicate one physical block's K/V (every
        layer) so a request whose FULLY block-aligned prompt matched
        the cache can write its first decode position privately."""
        state = dict(state)
        for name in ("pool_k", "pool_v", "pool_ks", "pool_vs"):
            if name not in state:
                continue
            a = state[name]
            state[name] = a.at[dst].set(a[src])
        return state

    # -- paged-KV host accounting (loop thread only) -----------------------
    def _btab_all(self):
        """The [slots, max_blocks] int32 block-table array the compiled
        step gathers through (dense mode: None). Unassigned entries
        read block 0, masked by the causal bias."""
        if not self._paged:
            return None
        arr = np.zeros((self.slots, self._max_blocks), np.int32)
        for s, rec in enumerate(self._recs):
            if rec is not None:
                t = rec["table"]
                arr[s, :len(t)] = t
        return arr

    def _btab_row(self, rec):
        row = np.zeros((self._max_blocks,), np.int32)
        t = rec["table"]
        row[:len(t)] = t
        return row

    def _ensure_blocks(self, rec, last_pos):
        """Grow ``rec``'s block table to cover cache position
        ``last_pos``, walking the pressure ladder on a dry pool:
        prefix-cache LRU eviction first, then PREEMPTION of the
        lowest-priority (latest-admitted) request. Returns False when
        ``rec`` itself was the preemption victim (the caller must stop
        touching it — its slot record is gone)."""
        last_pos = min(int(last_pos), self.model.max_len - 1)
        need = last_pos // self._block_size + 1 - len(rec["table"])
        for _ in range(need):
            b = self._alloc_one(rec)
            if b is None:
                return False
            rec["table"].append(b)
            rec["refs"].append(b)
        return True

    def _alloc_one(self, rec, preempt=True):
        """One block for ``rec``, or None when ``rec`` was preempted to
        make room (self-preemption: the pool cannot serve it without
        taking blocks from strictly HIGHER-priority — earlier-admitted
        — requests, so ``rec`` yields instead; with admission
        priorities preserved across preemption this cannot ping-pong,
        the oldest request always keeps its blocks and finishes).
        ``preempt=False`` stops the pressure ladder after the
        prefix-eviction rung and returns None with ``rec`` untouched —
        the speculative soft-growth contract (OPTIONAL draft positions
        must never evict committed work)."""
        while True:
            got = self._pool.alloc(1)
            if got is not None:
                return got[0]
            if self._prefix is not None:
                freed = self._prefix.evict(1)
                if freed:
                    self.stats["prefix_evictions"] += freed
                    _monrt.on_prefix_evictions(freed)
                    continue
            if not preempt:
                return None
            victim = self._pick_victim()
            if victim is None or victim["seq"] <= rec["seq"]:
                # nobody holds blocks, or every holder outranks rec
                # (rec included: victim is rec covers itself here) —
                # rec yields rather than evicting head-of-line work
                self._preempt(rec)
                return None
            self._preempt(victim)

    def _pick_victim(self):
        """Lowest-priority slot record = the latest-admitted (highest
        admission sequence) AMONG records that actually hold blocks:
        FIFO traffic keeps its head-of-line work running and pushes
        the tail back to the queue. A zero-block record (admitted,
        lazy allocation not yet run) cannot relieve pool pressure —
        preempting it would churn the request and inflate the
        preemption telemetry for nothing."""
        victim = None
        for r in self._recs:
            if r is not None and r["refs"] and (
                    victim is None or r["seq"] > victim["seq"]):
                victim = r
        return victim

    def _preempt(self, rec):
        """Free a record's blocks and RE-QUEUE its request (front of
        the queue — it keeps its priority) for re-prefill. Output
        stays identical on resume: greedy decode is deterministic and
        sampled decode draws through fold_in(seed, tokens_generated),
        which restarts with the request — so the caller-visible result
        (and the fleet's exactly-once dedup) cannot tell a preempted
        request from an undisturbed one. The partial tokens are
        discarded; TTFT keeps the FIRST first-token stamp (the user
        saw nothing either way, and a preemption must not flatter
        it)."""
        slot = next(s for s, r in enumerate(self._recs) if r is rec)
        req = rec["req"]
        self._release_blocks(rec)
        self._recs[slot] = None
        if rec["live"]:
            if self._release_fn is None:
                self._release_fn = jax.jit(self._release_impl,
                                           donate_argnums=0)
            self._state = self._release_fn(self._state, np.int32(slot))
        del req.tokens[:]
        del req.t_tokens[:]      # stamps restart with the tokens
        req.score = None
        req.preemptions += 1
        req._span.annotate(preemptions=req.preemptions)
        self.stats["preemptions"] += 1
        self._preempted_iter += 1
        with self._cv:
            self._queue.appendleft(req)

    def _cow(self, rec, bi):
        """Copy-on-write of shared block ``bi`` in ``rec``'s table (the
        fully-block-aligned-prompt case: activation must write the
        last prompt position into a block the prefix cache shares).
        Returns False when the allocation preempted ``rec``."""
        new = self._alloc_one(rec)
        if new is None:
            return False
        old = rec["table"][bi]
        if self._copy_fn is None:
            self._copy_fn = jax.jit(self._copy_impl, donate_argnums=0)
        self._state = self._copy_fn(self._state, np.int32(old),
                                    np.int32(new))
        rec["table"][bi] = new
        rec["refs"][rec["refs"].index(old)] = new
        self._pool.free(old)           # drop the reader ref on the
        rec["shared"] = bi             # shared copy; cache keeps its own
        self.stats["cow_copies"] += 1
        return True

    def _grow_blocks_soft(self, rec, last_pos):
        """Best-effort table growth for SPECULATIVE write positions:
        the shared allocation ladder minus its preemption rung
        (``_alloc_one(preempt=False)``) — drafts are optional work,
        and taking committed blocks for a guess would churn real
        progress (worst case, a request self-preempting for its own
        drafts forever). Returns the highest position the table now
        covers; the caller shrinks the draft to fit."""
        last_pos = min(int(last_pos), self.model.max_len - 1)
        need = last_pos // self._block_size + 1 - len(rec["table"])
        for _ in range(max(0, need)):
            b = self._alloc_one(rec, preempt=False)
            if b is None:
                break
            rec["table"].append(b)
            rec["refs"].append(b)
        return len(rec["table"]) * self._block_size - 1

    def _publish_prefix(self, rec, req):
        """Publish a slot's full prompt blocks to the prefix cache
        after its first decode emit (position P-1 is then complete, so
        every full prompt block is). Refcounted — the request keeps
        its own refs. Keyed on the RECORD (fresh each admission), not
        t_first_token: a request preempted after its first token but
        before publishing must still publish on resume;
        re-publishing an already-cached chain dedups to a no-op."""
        if not self._paged or self._prefix is None or rec["inserted"]:
            return
        rec["inserted"] = True
        bs = self._block_size
        nfull = len(req.prompt) // bs
        if nfull:
            self._prefix.insert(req.prompt[:nfull * bs],
                                rec["table"][:nfull])

    def _release_blocks(self, rec):
        """Drop every pool ref the record holds (own allocations AND
        matched prefix-cache readers — the refcount protocol makes the
        two indistinguishable here)."""
        for b in rec["refs"]:
            self._pool.free(b)
        rec["refs"] = []
        rec["table"] = []

    # -- scheduler loop ----------------------------------------------------
    def _loop(self):
        try:
            while True:
                with self._cv:
                    while (not self._stop and not self._queue
                           and all(r is None for r in self._recs)):
                        self._cv.wait()
                    if self._stop:
                        return
                self._step_once()
        except BaseException as e:      # a dead loop must not hang callers
            with self._cv:
                # later submits must raise, not enqueue into a queue
                # nobody drains
                self._stop = True
                self._error = e
            self._fail_all(e)

    def _choose_k(self):
        """Megastep K for THIS iteration: fuse only when nothing needs
        a host decision between decode steps — no queued admissions, no
        prefilling slot. A pending admission/prefill forces a K→1
        boundary so scheduling latency never stretches to K steps."""
        if self._megastep <= 1:
            return 1
        with self._cv:
            if self._queue:
                return 1
        if any(r is not None and not r["live"] for r in self._recs):
            return 1
        return self._megastep

    def _step_once(self):
        """One engine iteration = admissions + one prefill chunk per
        prefilling slot + one decode dispatch (a single step, or a
        fused K-step megastep when no admissions/prefills pend) over
        the active batch."""
        finished = ()
        self._preempted_iter = 0
        try:
            # the iteration's root, numbered: always an annotation in
            # the JAX profiler's timeline, and the Dapper span too when
            # the tracer is armed. Its phases (engine.admit / prefill /
            # btab / dispatch / fetch / book) are annotations only.
            step = self.stats["steps"]
            with _trc.span("engine.step", step=step) as sp:
                with _trc.phase("engine.admit", step=step):
                    admitted = self._admit()
                # dt clock starts AFTER _admit: the deliberate
                # wait-for-batch window (serving_admission_wait) is
                # admission POLICY, and folding its idle sleep into
                # step latency would fail a step_latency SLO for a
                # batching knob the operator chose
                t0 = time.perf_counter()
                self._advance_prefills()
                k = self._choose_k()
                (active, finished, steps_run, emitted,
                 trips) = self._decode(k)
                with self._cv:
                    depth = len(self._queue)
                self.stats["steps"] += 1
                self.stats["admissions"] += admitted
                self.stats["retirements"] += len(finished)
                dt = time.perf_counter() - t0
                # the span's DURATION covers the whole iteration
                # (admission wait included); the dt attr carries the
                # PER-LOGICAL-STEP figure — the post-admit wall time
                # divided by the scan trips the dispatch ran — same as
                # the recorder row, so the SLO --spans surface gates
                # the identical quantity as --log at any K. k = decode
                # steps actually consumed (a drain-tail megastep can
                # consume fewer than it dispatched).
                per = dt / max(1, trips)
                sp.annotate(active=active, admitted=admitted,
                            retired=len(finished), queue=depth, dt=per,
                            k=steps_run,
                            **({"megastep_dt": dt} if trips > 1
                               else {}))
                kv = {}
                if self._paged:
                    used = self._pool.used
                    self.stats["kv_peak_blocks"] = max(
                        self.stats["kv_peak_blocks"], used)
                    kv = {"kv_used": used,
                          "kv_total": self._pool.num_blocks,
                          "kv_bytes_used": used * self._block_bytes,
                          "kv_bytes_total": (self._pool.num_blocks
                                             * self._block_bytes),
                          "prefix_hits": self.stats["prefix_hits"],
                          "prefix_misses": self.stats["prefix_misses"],
                          "preempted": self._preempted_iter}
                    if self._speculative:
                        # CUMULATIVE like the prefix counters: a
                        # window's acceptance rate is last-row
                        # arithmetic, never a sum
                        kv["spec_drafted"] = self.stats["spec_drafted"]
                        kv["spec_accepted"] = \
                            self.stats["spec_accepted"]
                        kv["spec_emitted"] = self.stats["spec_emitted"]
                        kv["spec_dispatches"] = \
                            self.stats["spec_dispatches"]
                _monrt.on_serving_step(
                    active=active, slots=self.slots, queue_depth=depth,
                    emitted=emitted, admitted=admitted,
                    retired=len(finished), engine=self.name, dt=dt,
                    k=steps_run, dispatched=trips,
                    shadow=self.shadow, version=self.version, **kv)
                for req, _ in finished:
                    self._retire_telemetry(req)
        finally:
            # wake waiters LAST: a caller returning from result() must
            # see this iteration's stats/metrics/lifecycle stamps
            # already landed. finally: a request popped from its slot
            # by _decode is in `finished` ONLY — if instrumentation
            # throws (e.g. a full disk under an armed recorder),
            # _fail_all can no longer see it, so its future MUST
            # resolve here or result() blocks forever.
            for req, score in finished:
                req._finish(score)
            cb = self.on_retire
            if cb is not None:
                for req, _ in finished:
                    try:
                        cb(req)
                    except Exception:
                        pass

    def _retire_telemetry(self, req, error=None):
        """Per-request attribution at retirement: TTFT/TPOT/queue_wait
        histograms + a ``serving_request`` recorder row + the request
        span closed with the same figures annotated. Never raises —
        attribution is telemetry, and an exception here (mid-loop in
        _step_once or _fail_all) would strand the remaining requests'
        futures."""
        try:
            lat = req.latency()
            ctx = req._span.ctx
            _monrt.on_serving_request(
                engine=self.name, queue_wait=lat["queue_wait"],
                ttft=lat["ttft"],
                # a single-token request has NO inter-token interval:
                # its handle reports tpot 0.0 (documented), but 0.0 in
                # the histogram/samples would drag TPOT percentiles
                # toward a rate that was never measured
                tpot=lat["tpot"] if lat["tokens"] > 1 else None,
                tokens=lat["tokens"],
                prefill_chunks=lat["prefill_chunks"],
                prompt_len=len(req.prompt),
                # with the tail ring armed, unsampled traces are still
                # buffered in memory — stamp the id so a later
                # retention promotion can correlate this row to them
                trace_id=(ctx.trace_id
                          if ctx is not None
                          and (ctx.sampled or _trc.tail_armed())
                          else None),
                shadow=self.shadow, version=self.version,
                error=None if error is None else repr(error))
            req._span.annotate(
                **{k: v for k, v in lat.items() if v is not None})
        except Exception:
            pass
        try:
            req._span.finish(error=error)
        except Exception:
            pass

    @staticmethod
    def _step_span_id():
        """The ambient engine.step span id (loop thread), or None —
        stamped on request child spans so the merged timeline can join
        a request's lane to the engine iterations that drove it.
        Mirrors the sampled check _retire_telemetry does for the trace
        id: an UNSAMPLED step span is never written to the span log,
        and a dangling join reference would be worse than none — unless
        the tail ring is armed, in which case the unsampled step span
        IS buffered and a retention promotion can resolve the join."""
        cur = _trc.current_span()
        ctx = getattr(cur, "ctx", None)
        if ctx is None or not (ctx.sampled or _trc.tail_armed()):
            return None
        return ctx.span_id

    def _admit(self):
        admitted = 0
        with self._cv:
            if (self._admission_wait > 0 and self._queue
                    and all(r is None for r in self._recs)
                    and len(self._queue) < self.slots):
                # wait-for-batch window: the engine is idle, so give the
                # queue a beat to fill before compiling a sparse batch
                self._cv.wait_for(
                    lambda: self._stop
                    or len(self._queue) >= self.slots,
                    timeout=self._admission_wait)
            for slot in range(self.slots):
                if not self._queue:
                    break
                if self._recs[slot] is None:
                    req = self._queue.popleft()
                    req.t_admit = time.perf_counter()
                    req._span.annotate(slot=slot,
                                       queue_wait=req.queue_wait,
                                       admit_step=self._step_span_id())
                    if req._seq is None:      # re-admission after a
                        req._seq = next(self._admit_seq)  # preemption
                    rec = {"req": req, "cursor": 0, "live": False,
                           "seq": req._seq}   # keeps its priority
                    if self._paged:
                        self._admit_paged(rec)
                    self._recs[slot] = rec
                    admitted += 1
        return admitted

    def _admit_paged(self, rec):
        """Paged admission: look the prompt up in the radix prefix
        cache. A hit hands the record a refcounted chain of shared
        blocks holding the prefix's K/V, and the prefill cursor jumps
        past them — those chunks are never executed (the measured
        prefill-compute saving for shared-system-prompt traffic).
        Own-block allocation stays lazy (prefill/decode time): an
        admission allocates nothing it has not reached yet."""
        req = rec["req"]
        rec["table"], rec["refs"] = [], []
        rec["shared"] = 0
        rec["inserted"] = False
        rec["next_pos"] = None
        if self._prefix is None:
            return
        blocks, ntok = self._prefix.match(req.prompt)
        hit = bool(blocks)
        self.stats["prefix_hits" if hit else "prefix_misses"] += 1
        _monrt.on_prefix_lookup(hit)
        if not hit:
            return
        rec["table"] = list(blocks)
        rec["refs"] = list(blocks)
        rec["shared"] = len(blocks)
        # the teacher-forced prefill covers positions 0..P-2; a chain
        # covering the WHOLE block-aligned prompt leaves cursor at
        # need, and activation copy-on-writes the last shared block
        rec["cursor"] = min(ntok, len(req.prompt) - 1)
        self.stats["prefix_hit_tokens"] += rec["cursor"]
        req._span.annotate(prefix_hit_tokens=rec["cursor"])

    def _advance_prefills(self):
        """One prompt chunk per prefilling slot per iteration — long
        prompts interleave with the running batch instead of stalling
        it. A slot whose prefix is fully written activates (its LAST
        prompt token seeds the first decode step). Paged mode grows
        the slot's block table just ahead of the chunk's write
        positions (possibly evicting prefix chains / preempting), and
        a prefix-cache hit enters here with its cursor already past
        the cached positions."""
        step = self.stats["steps"]
        for slot, rec in enumerate(self._recs):
            if rec is None or rec["live"]:
                continue
            req = rec["req"]
            need = len(req.prompt) - 1      # teacher-forced prefix
            cur = rec["cursor"]
            if cur < need:
                toks = req.prompt[cur:min(cur + self._chunk, need)]
                btab_row = None
                if self._paged:
                    with _trc.phase("engine.btab", step=step):
                        if not self._ensure_blocks(
                                rec, cur + len(toks) - 1):
                            continue       # rec preempted back to queue
                        btab_row = self._btab_row(rec)
                chunk = np.zeros((self._chunk,), np.int32)
                chunk[:len(toks)] = toks
                # engine.prefill carries the request's id (the
                # caller's, else the admission number);
                # request.prefill_chunk is its Dapper twin
                with _trc.phase("engine.prefill", step=step,
                                rid=req._seq if req.rid is None
                                else req.rid), \
                        _trc.child_span(
                            "request.prefill_chunk", req._span,
                            start=cur, tokens=len(toks),
                            step_span=self._step_span_id()):
                    self._state = self._prefill_fn(
                        self._state, np.int32(slot), chunk,
                        np.int32(cur), np.int32(len(toks)), btab_row)
                rec["cursor"] = cur + len(toks)
                req.prefill_chunks += 1
                self.stats["prefill_chunks"] += 1
            if rec["cursor"] >= need:
                if self._paged:
                    # the first decode step writes position `need`
                    if not self._ensure_blocks(rec, need):
                        continue
                    bi = need // self._block_size
                    if bi < rec["shared"] and not self._cow(rec, bi):
                        continue
                    rec["next_pos"] = need
                sp = req.sampling or _GREEDY
                self._state = self._activate_fn(
                    self._state, np.int32(slot),
                    np.int32(req.prompt[-1]), np.int32(need),
                    np.int32(req.max_new),
                    np.float32(sp.temperature), np.int32(sp.top_k),
                    np.float32(sp.top_p), np.uint32(sp.seed))
                rec["live"] = True

    def _spec_cap(self, rec):
        """How many draft tokens this live slot can USE: bounded by γ,
        by its remaining ``max_new`` budget (n accepted drafts emit
        n+1 tokens), and by ``max_len`` (the scoring dispatch writes
        positions ``next_pos .. next_pos+n``)."""
        req = rec["req"]
        return min(self._spec_gamma,
                   req.max_new - len(req.tokens) - 1,
                   self.model.max_len - 1 - rec["next_pos"])

    def _build_drafts(self):
        """The drafting tier of one speculative iteration: propose up
        to γ tokens per live slot (tier A: host n-gram lookup over the
        request's own chain + the radix cache's published chains;
        tier B: one truncated-layer dispatch), then grow block tables
        to cover every drafted write position (the pressure ladder may
        preempt here — a vanished record's drafts are zeroed). Returns
        ``(drafts [S, γ] int32, n_draft [S] int32)``, or ``(None,
        None)`` when NO slot drafted — the caller then runs the
        existing plain/megastep programs, so a draftless iteration
        costs exactly what a non-speculative engine pays."""
        g = self._spec_gamma
        nd = np.zeros((self.slots,), np.int32)
        drafts = np.zeros((self.slots, g), np.int32)
        if self._spec_kind == "truncated":
            for slot in range(self.slots):
                rec = self._recs[slot]
                if rec is not None and rec["live"]:
                    nd[slot] = max(0, self._spec_cap(rec))
        else:
            chains = None
            for slot in range(self.slots):
                rec = self._recs[slot]
                if rec is None or not rec["live"]:
                    continue
                req = rec["req"]
                cap = self._spec_cap(rec)
                if cap <= 0:
                    continue
                if chains is None:       # one trie walk per iteration
                    chains = (self._prefix.token_chains()
                              if self._prefix is not None else ())
                prop = self._drafter.propose(req.prompt + req.tokens,
                                             cap, extra_chains=chains)
                if prop:
                    drafts[slot, :len(prop)] = prop
                    nd[slot] = len(prop)
        if not nd.any():
            return None, None
        # block coverage for the WHOLE dispatch, in two tiers. EVERY
        # live slot writes its next position even with zero drafts (it
        # rides the scoring dispatch as a plain step), so the
        # mandatory single-step coverage walks the full pressure
        # ladder exactly like the plain path — skipping a draftless
        # slot here would let its boundary-crossing write land in an
        # uncovered table entry (block 0: ANOTHER request's cache).
        # Draft positions are OPTIONAL work and only grow best-effort
        # (never preempting): evicting committed progress — worst
        # case, self-preempting in a loop — to make room for a guess
        # would turn speculation into churn. Re-read each record per
        # slot: an earlier slot's mandatory growth may have preempted
        # this one.
        for slot in range(self.slots):
            rec = self._recs[slot]
            if rec is None or not rec["live"]:
                nd[slot] = 0
                continue
            if not self._ensure_blocks(rec, rec["next_pos"]):
                nd[slot] = 0           # rec yielded its own slot
                continue
            if nd[slot]:
                covered = self._grow_blocks_soft(
                    rec, rec["next_pos"] + int(nd[slot]))
                nd[slot] = max(0, min(int(nd[slot]),
                                      covered - rec["next_pos"]))
        for slot in range(self.slots):  # a LATER slot's mandatory
            rec = self._recs[slot]      # growth may have preempted an
            if rec is None or not rec["live"]:  # earlier drafted one
                nd[slot] = 0
        if not nd.any():
            return None, None
        if self._spec_kind == "truncated":
            self._state, dr = self._draft_fn(
                self._state, self._btab_all(), jnp.asarray(nd))
            drafts = np.asarray(dr)
        return drafts, nd

    def _decode_spec(self, drafts, nd):
        """One speculative scoring dispatch over the active batch:
        γ+1 positions per slot verified at once, the accepted prefix
        (plus the bonus token) committed host-side. Counts as ONE
        decode step for occupancy/latency purposes — the whole point
        is that it emits MORE THAN ONE token."""
        live = [s for s, r in enumerate(self._recs)
                if r is not None and r["live"]]
        if not live:
            return 0, [], 0, 0, 0
        step = self.stats["steps"]
        with _trc.phase("engine.btab", step=step):
            btab = self._btab_all()
        sampled = any(
            self._recs[s]["req"].sampling is not None for s in live)
        # ONE packed upload (draft lengths + tokens) and ONE packed
        # fetch (emits + counts + fins): per-dispatch host transfers
        # are exactly the tax this path exists to amortize
        dn = np.concatenate([nd[:, None], drafts], axis=1)
        with _trc.phase("engine.dispatch", step=step):
            self._state, out = self._spec_fn(self._state, btab,
                                             jnp.asarray(dn), sampled)
        with _trc.phase("engine.fetch", step=step):
            out = np.asarray(out)
        with _trc.phase("engine.book", step=step):
            return self._book_spec(out, nd, live)

    def _book_spec(self, out, nd, live):
        """Host bookkeeping of one speculative dispatch: commit each
        live slot's accepted prefix (plus the bonus token), stamp and
        retire."""
        g1 = self._spec_gamma + 1
        emits, n_emit, fins = out[:, :g1], out[:, g1], out[:, g1 + 1]
        drafted = int(nd.sum())
        accepted = 0
        emitted = 0
        scores = None
        finished = []
        self.stats["spec_dispatches"] += 1
        self.stats["spec_drafted"] += drafted
        self.stats["decode_steps"] += 1
        self.stats["active_slot_steps"] += len(live)
        now = time.perf_counter()
        for slot in live:
            rec = self._recs[slot]
            req = rec["req"]
            ne = int(n_emit[slot])
            for t in emits[slot, :ne]:
                req.tokens.append(int(t))
                req.t_tokens.append(now)
            emitted += ne
            accepted += max(0, ne - 1)
            rec["next_pos"] += ne
            self._publish_prefix(rec, req)
            if ne and req.t_first_token is None:
                req.t_first_token = now
                try:
                    # guarded like _decode's: an escaping span-log
                    # write must not strand earlier-popped slots
                    with _trc.child_span(
                            "request.first_token", req._span,
                            step_span=self._step_span_id()):
                        pass
                    req._span.annotate(ttft=req.ttft)
                except Exception:
                    pass
            if fins[slot]:
                req.t_retire = now
                if scores is None:  # one [S] fetch per dispatch
                    scores = np.asarray(self._state["score"])
                finished.append((req, float(scores[slot])))
                self._release_blocks(rec)
                self._recs[slot] = None
        self.stats["spec_accepted"] += accepted
        self.stats["spec_emitted"] += emitted
        self.stats["tokens"] += emitted
        _monrt.on_spec(drafted=drafted, accepted=accepted)
        return len(live), finished, 1, emitted, 1

    def _decode(self, k=1):
        """One decode dispatch over the active batch: a single step
        (k=1, the PR-5 path), or a fused K-step megastep — ONE device
        program, one emit/fin fetch, K logical steps. Paged mode first
        grows every live slot's block table to cover its next k write
        positions (one table serves the whole fused dispatch; the
        pressure ladder may preempt here). Returns (slots active at
        dispatch, finished, steps run, tokens emitted).

        A speculative engine first drafts (ISSUE 13): when any live
        slot has draft tokens this iteration, ONE scoring dispatch
        verifies them all and the plain/megastep paths don't run; a
        draftless iteration falls through to the EXISTING programs
        cost-for-cost (the all-greedy/no-draft contract megastep K
        composition rides — a fused dispatch still serves iterations
        the drafter has nothing for)."""
        if self._speculative:
            drafts, nd = self._build_drafts()
            if drafts is not None:
                return self._decode_spec(drafts, nd)
        step = self.stats["steps"]
        with _trc.phase("engine.btab", step=step):
            live, btab = self._grow_tables(k)
        if not live:
            return 0, [], 0, 0, 0
        # dispatch the sampling-tail program only while a stochastic
        # request is actually live (static per-variant compile): the
        # all-greedy path stays the PR-5 program, bit for bit and
        # cost for cost
        sampled = any(
            self._recs[s]["req"].sampling is not None for s in live)
        with _trc.phase("engine.dispatch", step=step):
            if k > 1:
                if self._megastep_fn is None:
                    self._megastep_fn = jax.jit(self._megastep_impl,
                                                donate_argnums=0,
                                                static_argnums=2)
                self._state, emits, fins = self._megastep_fn(
                    self._state, btab, sampled)
                self.stats["megastep_dispatches"] += 1
            else:
                self._state, emits, fins = self._step_fn(
                    self._state, btab, sampled)
        with _trc.phase("engine.fetch", step=step):
            emits, fins = np.asarray(emits), np.asarray(fins)
            if k == 1:
                # host-side axis add: [None] on the DEVICE array would
                # dispatch a reshape per step on the k=1 hot path
                emits, fins = emits[None], fins[None]
        with _trc.phase("engine.book", step=step):
            return self._book(emits, fins, live)

    def _grow_tables(self, k):
        """Before a decode dispatch: grow every live slot's block table
        to cover its next ``k`` write positions, then build the
        [slots, max_blocks] table the dispatch uploads. Returns the
        live slots and the table (None in dense mode)."""
        if self._paged:
            for slot in range(self.slots):
                # re-read per iteration: an earlier slot's allocation
                # may have PREEMPTED this one — allocating for its
                # stale record would leak the blocks it appends
                rec = self._recs[slot]
                if rec is not None and rec["live"]:
                    # cover only the write positions this slot can
                    # actually consume: a request with 1 token left
                    # must not trigger the pressure ladder (evicting
                    # chains / preempting a peer) for K-1 positions
                    # its retirement will never write
                    rem = max(1, rec["req"].max_new
                              - len(rec["req"].tokens))
                    # a False return means rec was preempted — its
                    # slot record is already gone from _recs
                    self._ensure_blocks(
                        rec, rec["next_pos"] + min(k, rem) - 1)
        live = [s for s, r in enumerate(self._recs)
                if r is not None and r["live"]]
        return live, (self._btab_all() if live else None)

    def _book(self, emits, fins, live):
        """Host bookkeeping of one decode dispatch: append each live
        slot's tokens with their stamps, mark first tokens, retire.
        ``emits`` / ``fins`` are the fetched ``[K, S]`` rows."""
        scores = None
        finished = []
        emitted = 0
        steps_run = 0
        active0 = len(live)
        now = time.perf_counter()
        # replay the K sub-iterations host-side: a slot retired at
        # sub-iteration j stops consuming rows (its later emits are
        # end_id filler from the inactive carry)
        for j in range(emits.shape[0]):
            if not live:
                break
            steps_run += 1
            self.stats["decode_steps"] += 1
            self.stats["active_slot_steps"] += len(live)
            for slot in list(live):
                rec = self._recs[slot]
                req = rec["req"]
                req.tokens.append(int(emits[j, slot]))
                req.t_tokens.append(now)
                emitted += 1
                if self._paged:
                    rec["next_pos"] += 1   # mirrors the device pos
                self._publish_prefix(rec, req)
                if req.t_first_token is None:
                    req.t_first_token = now
                    try:
                        # guarded: by this point in the loop EARLIER
                        # slots may already be popped into the local
                        # `finished` — an exception escaping here
                        # (span-log write) would lose them to both
                        # _step_once's finally and _fail_all,
                        # stranding their result() forever
                        with _trc.child_span(
                                "request.first_token", req._span,
                                step_span=self._step_span_id()):
                            pass        # zero-width timeline mark
                        req._span.annotate(ttft=req.ttft)
                    except Exception:
                        pass
                if fins[j, slot]:
                    req.t_retire = now
                    if scores is None:  # one [S] fetch per dispatch
                        # safe across sub-iterations: a retired slot's
                        # score is frozen by its inactive mask
                        scores = np.asarray(self._state["score"])
                    finished.append((req, float(scores[slot])))
                    if self._paged:
                        # retirement frees the request's pool refs;
                        # prefix-published blocks survive on the
                        # cache's own refs (evictable once cold)
                        self._release_blocks(rec)
                    self._recs[slot] = None
                    live.remove(slot)
        self.stats["tokens"] += emitted
        # trips = scan trips the DEVICE ran this dispatch (a drain-tail
        # megastep may consume fewer: every live slot can retire before
        # the last sub-iteration, the rest is inactive filler) — per-
        # step latency must divide by trips, not steps consumed
        return active0, finished, steps_run, emitted, emits.shape[0]

    def _fail_all(self, err):
        with self._cv:
            slotted = [r for r in self._recs if r is not None]
            pending = [r["req"] for r in slotted]
            pending += list(self._queue)
            self._queue.clear()
            self._recs = [None] * self.slots
        if self._paged:
            for rec in slotted:        # pool accounting stays clean
                self._release_blocks(rec)
        cb = self.on_retire
        for req in pending:
            # failed requests still retire for attribution purposes:
            # their row/span carries the error, and the SLO error
            # budget counts them
            if req.t_retire is None:
                req.t_retire = time.perf_counter()
            self._retire_telemetry(req, error=err)
            req._fail(err)
            if cb is not None:
                try:
                    cb(req)
                except Exception:
                    pass


# -- sequential baseline ---------------------------------------------------

def _seq_step_fn(model):
    """The jitted single-token greedy step (batch 1), cached on the
    model so repeated baselines share one compile."""
    fn = getattr(model, "_serving_seq_step", None)
    if fn is None:
        def _impl(tok, state, t):
            logits, state = model._step_logits(tok, state, t)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            nxt = jnp.argmax(logp, axis=-1).astype(jnp.int32)
            lp = jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]
            return nxt, lp, state

        fn = model._serving_seq_step = jax.jit(_impl)
    return fn


def sequential_generate(model, requests):
    """One-at-a-time greedy decode — the pre-engine serving loop (the
    shape the C-API predictor and PERF.md's bs1 line measure): one
    jitted single-token step at batch 1, a host round-trip per token,
    requests processed back to back. ``requests``: iterable of
    ``(prompt, max_new_tokens)``. Returns ``[(tokens, score), ...]``,
    token-identical to ``Engine`` output (same per-row math)."""
    step = _seq_step_fn(model)
    out = []
    for prompt, max_new in requests:
        prompt = [int(t) for t in prompt]
        if len(prompt) + int(max_new) - 1 > model.max_len:
            # same loud bound as Engine.submit: past max_len the pos-emb
            # gather and cache writes clamp and silently corrupt output
            raise ValueError(
                "prompt len %d + max_new %d exceeds model max_len %d"
                % (len(prompt), int(max_new), model.max_len))
        state = model._init_state(1)
        for t, tk in enumerate(prompt[:-1]):    # teacher-forced prefix
            _, _, state = step(jnp.full((1,), tk, jnp.int32), state,
                               np.int32(t))
        tok, pos = prompt[-1], len(prompt) - 1
        toks, score = [], 0.0
        for _ in range(int(max_new)):
            nxt, lp, state = step(jnp.full((1,), tok, jnp.int32), state,
                                  np.int32(pos))
            tok = int(np.asarray(nxt)[0])
            score += float(np.asarray(lp)[0])
            toks.append(tok)
            pos += 1
            if tok == model.end_id:
                break
        out.append((toks, score))
    return out
