"""Executor: runs a Program by compiling it to one XLA computation.

Reference parity: python/paddle/fluid/executor.py:181 + framework/executor.cc:133.
The reference interprets a Program op-by-op, re-running shape inference and
kernel dispatch per op per step (executor.cc:333 — its hot loop). Here the
whole block is *traced once* through the op lowering registry into a pure
function ``step(state, feeds, key) -> (fetches, new_state)`` and jit-compiled;
subsequent runs with the same (program version, feed signature, fetch list)
hit the compiled-step cache (the analog of executor.py:165's program cache,
but caching an XLA executable instead of a cloned ProgramDesc).

State threading: persistable variables (parameters, optimizer accumulators)
live in a Scope between steps and are passed through the jitted function as a
donated pytree, so in-place optimizer updates reuse device buffers instead of
reallocating (the role the reference's buddy allocator + in-place var reuse
played).

Autodiff: a ``backward_marker`` op recorded by append_backward (core/backward.py)
switches the tracer into ``jax.value_and_grad`` over the forward segment —
replacing the reference's per-op GradOpDescMaker machinery (backward.py:425)
with JAX's program transform.

The step is written once: ``Executor._step`` is the skeleton under
``Executor.run``, ``Executor.run_steps`` and ParallelExecutor's two, and it
owns what every call of a compiled step does — the cache key
(``_cache_key``: every trace-time toggle is listed there and nowhere else),
the random key of a logical step (``_step_keys``; the eager path's too), the
build, ``jax.jit`` and the compile ahead of a train step's first call
(``_first_compile``: the executable the kernel ledger reads and a
regions' plan is confirmed by), the monitor's timing and the profiler's
event, and the commit of state and fetches. An entry hands it an
``_Entry`` that says only what differs: the root's and the monitor's label, what joins the key, an AMP
value to pin, gradient accumulation, whether host (IO) ops go to the eager
path, a check of the feeds, and how state and feeds are laid out on the
devices and fetched values brought back. A new toggle goes into
``_cache_key``; a new way to run a step is a new ``_Entry``.
"""

import contextlib
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from . import registry
from .enforce import EnforceError, op_error
from .program import Parameter, Program, Variable, default_main_program
from .scope import Scope, global_scope
from .places import CPUPlace, Place, _default_place
from .lod import LoDTensor
from ..trace import runtime as _trc

_NANGUARD = "__nanguard__"
_NO_CONTEXT = contextlib.nullcontext()


def _flag_on(name):
    """Env-flag lookup through the central flag table (paddle_tpu.flags;
    gflags semantics — '0'/'false'/'off'/'no' mean OFF). Flags must be
    registered there; the table is the single source of parsing truth."""
    from .. import flags
    return bool(flags.get_flag(name.replace("PADDLE_TPU_", "").lower()))


def _normalize_feeds(feed, accum_steps=1, plan_cache=None):
    """LoDTensor/array feeds → (feed_arrays, static_info).

    Sequence (LoD) feeds become FLAT row buffers + ``<name>@LOD`` length
    vectors, with the flat total BUCKETED to the next power of two (zero
    pad rows at the tail). Bucketing keeps the compiled-step signature
    stable across batches whose token totals differ — without it every
    batch of a text model recompiles (the shape-key design of SURVEY §7).
    Pad rows carry segment id N (out of range), which every lengths-aware
    sequence op drops (jax segment_* ignore out-of-range ids; packers mask
    by lengths). Programs that apply a raw elementwise reduction straight
    over flat LoD rows should disable via PADDLE_TPU_LOD_BUCKETING=0.
    static_info additionally carries ``<name>@MAXLEN`` — the bucketed max
    per-sequence length that bounds scan depth in the RNN packers.

    accum_steps > 1: LoD feeds are pre-split HOST-SIDE into that many
    microbatches of equal SEQUENCE count (the ragged split is
    data-dependent, so it cannot happen inside the jit): the flat buffer
    becomes [k, bucket, ...] (every microbatch zero-padded to one shared
    bucketed total) and the lengths [k, n_seqs/k]; static_info marks the
    feed ``<name>@ACCUM_LOD`` so the accumulation scan indexes
    microbatch i instead of reshape-chunking a dense batch dim.

    ``plan_cache`` (a FeedPlanCache) skips the derivation on repeated
    feed signatures — the fix for the measured per-call re-marshal tax
    of the in-process serving path (PERF.md round 5).
    """
    if plan_cache is not None and _flag_on("PADDLE_TPU_FEED_PLAN_CACHE"):
        return plan_cache.normalize(feed, accum_steps)
    return _apply_feed_plan(_derive_feed_plan(feed, accum_steps), feed,
                            None)


class _FeedPlan:
    """One cached _normalize_feeds derivation: the per-feed transform
    instructions, the trace-time static_info, the derived length
    vectors (valid because the LoD lengths are part of the cache key),
    and any committed device buffers."""

    __slots__ = ("instrs", "static_info", "lods", "buffers")

    def __init__(self):
        self.instrs = []       # (kind, feed_name, *params)
        self.static_info = {}
        self.lods = {}         # @LOD / @ACCUM_TOKENS arrays
        self.buffers = {}      # feed_name -> (source obj, device array)


def _derive_feed_plan(feed, accum_steps=1):
    """Full normalization derivation (the feed-plan cache MISS path);
    see _normalize_feeds for the semantics each instruction encodes."""
    plan = _FeedPlan()
    bucket_on = _flag_on("PADDLE_TPU_LOD_BUCKETING")
    k_acc = max(1, int(accum_steps))
    for k, v in feed.items():
        if isinstance(v, LoDTensor):
            if v.lod:
                arr = v.data
                # sequence ops consume per-sequence LENGTHS (not offsets)
                lengths = np.asarray(
                    v.recursive_sequence_lengths()[-1], np.int32)
                mx = max(1, int(lengths.max(initial=1)))
                plan.static_info[k + "@MAXLEN"] = 1 << (mx - 1).bit_length()
                if k_acc > 1:
                    if len(lengths) % k_acc:
                        raise ValueError(
                            "feed %r has %d sequences, not divisible "
                            "into %d accumulation microbatches"
                            % (k, len(lengths), k_acc))
                    per = len(lengths) // k_acc
                    offs = np.concatenate(
                        [[0], np.cumsum(lengths)]).astype(np.int64)
                    totals = [int(offs[(g + 1) * per] - offs[g * per])
                              for g in range(k_acc)]
                    bucket = max(1, max(totals))
                    if bucket_on:
                        bucket = 1 << max(0, int(bucket - 1).bit_length())
                    plan.lods[k + "@LOD"] = lengths.reshape(k_acc, per)
                    # true (pre-bucket) token totals per microbatch: the
                    # loss-normalization weights for ragged accumulation
                    # (runtime VALUES, not trace constants — same shape
                    # every batch, so the compile cache stays stable)
                    plan.lods[k + "@ACCUM_TOKENS"] = np.asarray(
                        totals, np.float32)
                    plan.static_info[k + "@ACCUM_LOD"] = True
                    plan.instrs.append(("lod_accum", k, bucket, offs,
                                        per, totals))
                else:
                    plan.lods[k + "@LOD"] = lengths
                    total = int(arr.shape[0])
                    bucket = 1 << max(0, int(total - 1).bit_length())
                    pad_to = bucket if (bucket_on and bucket > total) \
                        else None
                    plan.instrs.append(("lod_pad", k, pad_to))
            else:
                plan.instrs.append(("lod_data", k))
        else:
            plan.instrs.append(("dense", k))
    from .. import monitor as _mon
    _mon.on_feed_plan(False)
    return plan


def _apply_feed_plan(plan, feed, cache):
    """Run a plan's mechanical transforms over THIS call's values."""
    feed_arrays = {}
    for instr in plan.instrs:
        kind, k = instr[0], instr[1]
        v = feed[k]
        if kind == "dense":
            if isinstance(v, jax.Array):
                feed_arrays[k] = v
                continue
            arr = np.asarray(v)
            dev = cache._committed(plan, k, v, arr) \
                if cache is not None else None
            feed_arrays[k] = arr if dev is None else dev
        elif kind == "lod_data":
            feed_arrays[k] = v.data
        elif kind == "lod_pad":
            arr, pad_to = v.data, instr[2]
            if pad_to is not None:
                pad = np.zeros((pad_to - arr.shape[0],) + arr.shape[1:],
                               arr.dtype)
                arr = np.concatenate([arr, pad], axis=0)
            feed_arrays[k] = arr
        else:                  # lod_accum
            _, _, bucket, offs, per, totals = instr
            arr = v.data
            stacked = np.zeros((len(totals), bucket) + arr.shape[1:],
                               arr.dtype)
            for g in range(len(totals)):
                stacked[g, :totals[g]] = \
                    arr[offs[g * per]:offs[(g + 1) * per]]
            feed_arrays[k] = stacked
    feed_arrays.update(plan.lods)
    return feed_arrays, dict(plan.static_info)


class FeedPlanCache:
    """Zero-copy host feed path: cached normalization plans + committed
    device feed buffers, keyed by feed signature (names, shapes, dtypes,
    LoD lengths, accumulation split, bucketing flag).

    Fixes the measured in-process serving re-marshal (PERF.md round 5:
    the pure-C predictor loop beat the python path because the latter
    re-ran _normalize_feeds + a fresh transfer every call): on a plan
    HIT only the mechanical per-call work runs. A dense feed value is
    additionally COMMITTED to a device buffer and reused zero-copy when
    it is the SAME numpy object as last call with ``writeable=False``
    (freeze with ``arr.flags.writeable = False``). Freezing is the
    caller's CONTRACT that the contents are final: numpy does allow an
    owning array to re-enable writeable, mutate, and re-freeze — doing
    that serves the stale committed buffer, exactly like mutating a
    buffer handed to any zero-copy API. Plain writeable feeds are never
    committed, so ordinary in-place mutation between calls stays
    correct. Values that are already jax.Arrays are inherently
    zero-copy.

    Counters: ``ptpu_feed_normalizations_total`` ticks per derivation,
    ``ptpu_feed_plan_hits_total`` per skipped one (monitor registry);
    instance fields ``hits/misses/buffer_reuses`` serve tests."""

    def __init__(self, capacity=64, device_fn=None):
        import collections
        import threading
        self._plans = collections.OrderedDict()
        self._lock = threading.Lock()
        self._capacity = int(capacity)
        self._device_fn = device_fn    # lazy: resolving may init jax
        self.hits = 0
        self.misses = 0
        self.buffer_reuses = 0

    def normalize(self, feed, accum_steps=1):
        key = self._key(feed, accum_steps)
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
                self.hits += 1
        if plan is None:
            plan = _derive_feed_plan(feed, accum_steps)  # ticks the miss
            with self._lock:
                self.misses += 1
                self._plans[key] = plan
                while len(self._plans) > self._capacity:
                    self._plans.popitem(last=False)
        else:
            from .. import monitor as _mon
            _mon.on_feed_plan(True)
        return _apply_feed_plan(plan, feed, self)

    @staticmethod
    def _key(feed, accum_steps):
        from .. import flags
        items = []
        for k, v in feed.items():
            if isinstance(v, LoDTensor):
                if v.lod:
                    items.append(
                        (k, "lod", tuple(v.data.shape), str(v.data.dtype),
                         tuple(v.recursive_sequence_lengths()[-1])))
                else:
                    items.append((k, "lodd", tuple(v.data.shape),
                                  str(v.data.dtype)))
            else:
                dt = getattr(v, "dtype", None)
                items.append(
                    (k, "d", tuple(np.shape(v)),
                     str(dt) if dt is not None
                     else str(np.asarray(v).dtype)))
        return (int(accum_steps), bool(flags.get_flag("lod_bucketing")),
                tuple(sorted(items)))

    def _committed(self, plan, name, src, arr):
        """Device buffer for a frozen dense feed, reused by identity;
        None = not committable (writeable, or no device binding)."""
        if not isinstance(arr, np.ndarray) or arr.flags.writeable \
                or self._device_fn is None:
            return None
        ent = plan.buffers.get(name)
        if ent is not None and ent[0] is src:
            with self._lock:
                self.buffer_reuses += 1
            return ent[1]
        try:
            dev = jax.device_put(arr, self._device_fn())
        except Exception:
            return None            # advisory: fall back to the host array
        plan.buffers[name] = (src, dev)
        return dev

    def clear(self):
        with self._lock:
            self._plans.clear()


def _stack_step_feeds(feeds, plan_cache=None):
    """Normalize K per-step feed dicts and stack them into the
    ``[k, ...]`` megastep staging layout ``run_steps`` scans in-graph.

    Every per-step feed must land on ONE compiled-step signature (same
    names/shapes/dtypes and the same trace-time static_info): the scan
    body is compiled once, so a step whose bucketed LoD total or MAXLEN
    bucket differs cannot share the megastep. That is checked here with
    a loud error instead of a shape mismatch inside the scan. LoD feeds
    are supported — each step's flat buffer/length vectors normalize
    through the (shared) feed-plan cache exactly as ``run()`` would,
    then stack; only the PRE-STACKED staging path (DeviceLoader
    ``megabatches``) excludes them.

    Returns (feeds_k, static_info, per_step_signature)."""
    normed = [_normalize_feeds(dict(f or {}), plan_cache=plan_cache)
              for f in feeds]
    arrays0, info0 = normed[0]
    sig0 = _feed_signature(arrays0)
    for i, (arrays_i, info_i) in enumerate(normed[1:], 1):
        if _feed_signature(arrays_i) != sig0 or info_i != info0:
            raise ValueError(
                "run_steps feeds must share ONE compiled-step "
                "signature (the megastep scan body is compiled once): "
                "feed %d normalizes to %s / static %s, feed 0 to %s / "
                "static %s. Pad or re-bucket the odd batch, or run() "
                "it separately." % (i, _feed_signature(arrays_i),
                                    sorted(info_i.items()), sig0,
                                    sorted(info0.items())))
    feeds_k = {}
    for name in arrays0:
        vals = [a[name] for a, _ in normed]
        if all(isinstance(v, jax.Array) for v in vals):
            feeds_k[name] = jnp.stack(vals)
        else:
            feeds_k[name] = np.stack([np.asarray(v) for v in vals])
    return feeds_k, dict(info0), sig0


def _stage_prestacked_feeds(feeds, k):
    """Validate a pre-stacked ``[k, ...]`` staging dict (the
    DeviceLoader ``megabatches`` layout). Dense arrays only: a
    LoDTensor's normalization needs trace-time static_info only the
    per-step host path can derive, so it gets a clear error here
    instead of a shape mismatch inside the scan."""
    feeds_k = {}
    for name, v in feeds.items():
        if isinstance(v, LoDTensor):
            raise ValueError(
                "LoD feed %r cannot ride the pre-stacked [k, ...] "
                "megastep staging path: its flat/bucketed form and "
                "@LOD/@MAXLEN static_info must be derived per step by "
                "the executor's own normalization. Pass run_steps a "
                "LIST of per-step feed dicts instead (the host path "
                "normalizes and stacks LoD feeds correctly)." % name)
        arr = v if isinstance(v, jax.Array) else np.asarray(v)
        if getattr(arr, "ndim", 0) < 1 or arr.shape[0] != k:
            raise ValueError(
                "pre-stacked megastep feed %r must have leading dim "
                "k=%d, got shape %s" % (name, k, np.shape(arr)))
        feeds_k[name] = arr
    sig = tuple(sorted((n, tuple(np.shape(v)[1:]), str(v.dtype))
                       for n, v in feeds_k.items()))
    return feeds_k, {}, sig


def as_numpy(value):
    """Convert a fetched value (jax.Array / LoDTensor / list) to numpy."""
    from .selected_rows import SelectedRows
    if isinstance(value, (LoDTensor, SelectedRows)):
        return value  # structured values pass through
    if isinstance(value, (list, tuple)):
        return [as_numpy(v) for v in value]
    return np.asarray(value)


def _feed_signature(feed):
    return tuple(sorted(
        (k, tuple(np.shape(v)), str(np.asarray(v).dtype) if not hasattr(v, "dtype") else str(v.dtype))
        for k, v in feed.items()))


def _stage_feeds(feeds, k, accum_steps, plan_cache):
    """One call's feeds as the compiled step takes them: (arrays,
    static_info, signature of ONE logical step). ``k`` None: one feed
    dict, normalized; else a megastep's, a list of k feed dicts stacked
    or one pre-stacked ``[k, ...]`` dict checked."""
    if k is None:
        arrays, static_info = _normalize_feeds(feeds, accum_steps,
                                               plan_cache=plan_cache)
        return arrays, static_info, _feed_signature(arrays)
    if isinstance(feeds, dict):
        return _stage_prestacked_feeds(feeds, k)
    return _stack_step_feeds(feeds, plan_cache=plan_cache)


def _cache_key(program, feed_sig, fetch_names, state_keys, amp, check_nan,
               static_info, k, extras):
    """The key of a compiled step in an executor's cache, for every
    entry. EVERY toggle a lowering consults at trace time is listed
    here, and nowhere else, or flipping it after a run silently reuses
    a stale trace. The Program object itself is part of the key (kept
    alive by the cache) so id-reuse after GC can never alias two
    programs; ``k`` parts a megastep's scan from the one step, and
    ``extras`` is what the entry's own lowering reads beside (a
    ParallelExecutor's sharding hints and accumulation)."""
    return (program, program._version, feed_sig, fetch_names, state_keys,
            amp, check_nan, tuple(sorted(static_info.items())), k, extras)


def _step_keys(program, n, k=None):
    """The random key of logical step ``n`` of ``program`` — k of them,
    stacked, for a megastep's steps n .. n+k-1: one RNG stream position
    per LOGICAL step under every entry, so a megastep's output is
    bitwise equal to k sequential run() calls (dropout masks included).
    The seed is folded to 32 bits, so any ``random_seed`` runs."""
    base = program.random_seed * 1000003 + n
    if k is None:
        return jax.random.key(np.uint32(base & 0xFFFFFFFF))
    return jax.vmap(jax.random.key)(jnp.asarray(
        [np.uint32((base + i) & 0xFFFFFFFF) for i in range(k)]))


def _megastep(step):
    """Wrap a built step in a lax.scan over K stacked batches: ONE
    compile unit keyed on K, one dispatch per K logical steps."""
    def mega(state, feeds_k, keys):
        def body(carry, xs):
            feeds_i, key_i = xs
            fetches, new_state, guards, fetch_lods = step(
                carry, feeds_i, key_i)
            extra = sorted(set(new_state) - set(carry))
            if extra:       # trace-time check, not a runtime branch
                raise ValueError(
                    "run_steps: the program materializes new "
                    "persistable vars %s inside the step — the "
                    "scan carry pytree must be stable. run() the "
                    "startup/first step once, then megastep."
                    % extra)
            carry = {n: new_state[n] for n in carry}
            return carry, (fetches, guards, fetch_lods)

        final, (fetches_k, guards_k, lods_k) = jax.lax.scan(
            body, state, (feeds_k, keys))
        return fetches_k, final, guards_k, lods_k

    return mega


def _amp_pinned(fn, amp):
    """``fn`` under AMP held at ``amp`` whenever it is traced: a
    lowering reads the AMP flag at TRACE time, so an entry that keys
    its cache on a value of its own pins it for the trace and restores
    the ambient one (no global leak)."""
    from ..amp import amp_guard

    def step(state, feeds, key):
        with amp_guard(amp):
            return fn(state, feeds, key)

    return step


class _Entry:
    """What an entry point hands ``Executor._step``: all of a step that
    is not the skeleton's. The class is ``Executor.run``'s and
    ``.run_steps``'s own entry; ParallelExecutor's derives from it."""

    prefix = "exe"        # of the root and the phases; the monitor's label
    devices = 1           # chips that run the step (the MFU's denominator)
    key_extras = ()       # joins the cache key
    amp = None            # None: the ambient AMP; else pinned for the trace
    accum = (1, None)     # gradient accumulation: steps, loss norm
    # a program with host (IO) ops goes to the eager path (a megastep
    # of one is refused); False: its ops are traced as any other's
    host_ops = True
    # check_feeds(feeds, feed_arrays) raises on feeds the entry cannot
    # take; place(state, feed_arrays) -> (state, feeds) laid out on the
    # devices, under the phase "place" (None: the call runs under
    # jax.default_device(the executor's place)); pull(fetches,
    # fetch_lods, guards) -> the same as the host sees them, under the
    # phase "pull"
    check_feeds = place = pull = None


_EXE = _Entry()


class Executor:
    """Single-device executor (CPU or one TPU chip).

    Multi-device execution is ParallelExecutor (paddle_tpu/parallel/),
    which shards the same traced step over a jax Mesh.
    """

    def __init__(self, place=None):
        if place is None:
            place = _default_place()
        if not isinstance(place, Place):
            raise TypeError("place must be a Place, got %r" % (place,))
        self.place = place
        place.jax_device()        # no such device here: raise now
        self._cache = {}          # cache key -> (jitted fn, state_keys, static info)
        # zero-copy host feed path: repeated-shape run() calls skip the
        # per-call normalization derivation and reuse committed device
        # buffers (PERF.md round-5 in-process serving re-marshal fix)
        self._feed_plans = FeedPlanCache(device_fn=self.place.jax_device)
        self._rng_counter = 0     # logical steps run: the next one's number
        self._inflight = []       # megasteps dispatched and not yet fetched
        self._mesh = None         # a ParallelExecutor's; lowerings read it
        self._keep_nothing = False    # _first_compile's fallback is lowering
        import uuid
        import weakref
        # per-PROGRAM step counters for host-op send tags (retry
        # idempotency): another host-op program run on this executor
        # (e.g. an eval recv) must not advance a training program's
        # round sequence. Entry: program -> [seq, program_nonce].
        self._run_seqs = weakref.WeakKeyDictionary()
        # incarnation id: a RESTARTED trainer's seq restarts at 0 —
        # servers evict pending grads from the dead incarnation by it.
        # The 16-hex-digit time_ns prefix ORDERS incarnations, so a
        # server can drop a dead incarnation's straggler (its epoch is
        # below the replacement's) instead of letting it evict the live
        # replacement's pending state; the nonce suffix breaks ties.
        import time as _time
        self._incarnation = ("%016x" % _time.time_ns()
                             + uuid.uuid4().hex[:8])

    def _reincarnate(self, min_epoch):
        """A pserver judged our incarnation stale (possible after an
        elastic reschedule onto a host whose clock is behind the old
        one): mint a new incarnation with an epoch past the server's
        max, so retried sends are accepted instead of deadlocking."""
        import time as _time
        import uuid
        epoch = max(_time.time_ns(), int(min_epoch) + 1)
        self._incarnation = "%016x" % epoch + uuid.uuid4().hex[:8]
        return self._incarnation

    # ------------------------------------------------------------------
    def close(self):
        self._cache.clear()
        self._feed_plans.clear()

    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name="feed", fetch_var_name="fetch", scope=None,
            return_numpy=True, use_program_cache=True):
        return self._step(_EXE, program, scope, fetch_list, feed, None,
                          return_numpy, use_program_cache)

    # -- megastep execution (ISSUE 7) ----------------------------------
    def run_steps(self, program=None, feeds=None, fetch_list=None,
                  scope=None, return_numpy=True, k=None,
                  use_program_cache=True):
        """K logical steps in ONE device dispatch (the megastep path).

        The per-step body ``run()`` compiles — forward, backward AND
        optimizer/persistable-state update — is scanned (``lax.scan``)
        over K batches, so one host dispatch advances K real training
        steps; per-step fetches (losses, NaN guards, fetch LoDs) stream
        out of the scan. The contract is numeric identity with K
        sequential ``run()`` calls on the same feeds (same per-step RNG
        stream included) — pinned in tests/test_megastep.py.

        ``feeds``: either a LIST of K per-step feed dicts (LoD feeds
        supported; each normalizes through the feed-plan cache and all
        K must share one signature), or ONE pre-stacked ``[k, ...]``
        dict (the DeviceLoader ``megabatches`` staging layout; dense
        only) together with ``k``.

        Returns a list of K per-step fetch lists. With
        ``return_numpy=False`` the fetches stay device-resident and the
        dispatch is ASYNC: up to ``PADDLE_TPU_MEGASTEP_INFLIGHT``
        (default 2 = double buffering) megastep dispatches may be in
        flight before the next call blocks on the oldest, so the host
        feed of megastep N+1 overlaps device compute of megastep N.

        Semantic differences vs K sequential runs, by design: NaN
        guards are checked after the whole dispatch (the error names
        the first failing logical step, but state has advanced all K
        steps), and programs with host (IO) ops or newly-materialized
        persistables (startup programs) are rejected — run() those."""
        feeds, k = self._check_run_steps_args(feeds, k)
        return self._step(_EXE, program, scope, fetch_list, feeds, k,
                          return_numpy, use_program_cache)

    @staticmethod
    def _check_run_steps_args(feeds, k):
        if isinstance(feeds, dict):
            if k is None:
                raise ValueError(
                    "run_steps(feeds=<pre-stacked dict>) needs k= (the "
                    "leading staging dim); pass a list of per-step "
                    "feed dicts to infer it")
            k = int(k)
        else:
            feeds = list(feeds or [])
            if k is not None and int(k) != len(feeds):
                raise ValueError(
                    "run_steps got k=%r but %d per-step feeds"
                    % (k, len(feeds)))
            k = len(feeds)
        if k < 1:
            raise ValueError("run_steps needs k >= 1, got %d" % k)
        return feeds, k

    def _step(self, how, program, scope, fetch_list, feeds, k,
              return_numpy, use_program_cache):
        """One call of a compiled step: the skeleton under ``run`` and
        ``run_steps`` here and under ParallelExecutor's two. ``how`` is
        the entry's ``_Entry``; ``k`` is None for one step and the
        number of logical steps for a megastep (``feeds`` then a list of
        k feed dicts or one pre-stacked dict)."""
        from .. import monitor as _mon
        from .. import profiler as _prof
        from ..amp import amp_enabled
        from ..flags import get_flag
        step = self._rng_counter
        pre = how.prefix + "."
        # the step's root, numbered: always an annotation in the JAX
        # profiler's timeline (it records only while a profiler session
        # runs); with the tracer armed also the distributed-trace root
        # span: RPC verb spans issued while this step runs (pserver
        # sends/gets, prefetches) nest under it, making the step the
        # unit of the fleet timeline. The phases of a step (feed /
        # state / build / place / dispatch / pull / commit; place and
        # pull where the entry has them) are annotations numbered like
        # their root; what lies between them is the root's self time
        attrs = {"step": step} if k is None else {"step": step, "k": k}
        with _trc.span(pre + "step", **attrs):
            with _trc.phase(pre + "feed", step=step):
                program = program or default_main_program()
                scope = scope or global_scope()
                fetch_names = tuple(
                    f.name if isinstance(f, Variable) else str(f)
                    for f in (fetch_list or ()))
                host = how.host_ops and any(
                    registry.is_host_op(o.type)
                    for o in program.global_block().ops)
                if host and k is not None:
                    raise NotImplementedError(
                        "run_steps cannot fuse programs with host (IO) "
                        "ops — send/recv/prefetch must hit the wire "
                        "once per step; use run() per step")
                if k is None:
                    feeds = dict(feeds or {})
                feed_arrays, static_info, sig = _stage_feeds(
                    feeds, k, how.accum[0], self._feed_plans)
                if how.check_feeds is not None:
                    how.check_feeds(feeds, feed_arrays)

            # State = persistable vars of this program that exist in scope.
            with _trc.phase(pre + "state", step=step):
                state, state_keys = _gather_state(program, scope)
            if host:
                return self._run_host_ops(program, feed_arrays,
                                          fetch_names, scope, static_info,
                                          return_numpy)

            check_nan = _flag_on("PADDLE_TPU_CHECK_NAN_INF")
            use_amp = amp_enabled() if how.amp is None else how.amp
            key = _cache_key(program, sig, fetch_names, state_keys,
                             use_amp, check_nan, static_info, k,
                             how.key_extras)
            mon_on = _mon.enabled()
            entry = self._cache.get(key) if use_program_cache else None
            rng_key = _step_keys(program, step, k)
            # a cache miss is the build phase twice: here, and round the
            # first call below, which traces, lowers and compiles
            fresh = entry is None
            if fresh:
                build = lambda: self._build_entry(
                    how, program, tuple(sorted(feed_arrays)), fetch_names,
                    state_keys, static_info, check_nan, k)
                with _trc.phase(pre + "build", step=step):
                    fn = build()
                    # Shardings are established by COMMITTING the inputs
                    # (an entry's place), not by in_shardings:
                    # constraining the jit would force a reshard of
                    # step-2 state (whose committed sharding is whatever
                    # step 1 produced), which multi-process arrays
                    # cannot do. Committed-input propagation is the
                    # standard JAX training-loop pattern and keeps
                    # single- and multi-host behavior identical.
                    entry = jax.jit(fn, donate_argnums=(0,))
                    if use_program_cache:
                        self._cache[key] = entry
                    if mon_on and use_program_cache:
                        # price the step with the static cost model
                        # (traced once here, at compile time) so per-step
                        # MFU is derivable; classify the compile against
                        # this program's history.
                        # use_program_cache=False is a DELIBERATE cache
                        # bypass — counting each of its runs as a
                        # recompile would report key churn that isn't
                        # there
                        _mon.on_compile(
                            program, key, sig,
                            cost_fn=lambda: _step_costs_safe(
                                fn, dict(state), dict(feed_arrays),
                                rng_key),
                            executor=how.prefix,
                            tokens=_mon.tokens_in_feeds(feed_arrays),
                            devices=how.devices)
            elif mon_on:
                _mon.on_cache_hit()
            self._rng_counter += k or 1

            if how.place is None:
                args = (state, feed_arrays, rng_key)
                on_place = jax.default_device(self.place.jax_device())
            else:
                # state is placed per its sharding once; jit keeps the
                # placement on subsequent steps
                with _trc.phase(pre + "place", step=step):
                    args = how.place(state, feed_arrays) + (rng_key,)
                on_place = _NO_CONTEXT
            if k is not None:
                window = max(1, int(get_flag("megastep_inflight")))
                while len(self._inflight) >= window:
                    # double-buffer window full: the OLDEST dispatch
                    # must retire before another joins the pipeline
                    jax.block_until_ready(self._inflight.pop(0))

            do_sync = False
            if mon_on:
                # monitor_sync_every=N amortization: sync once per N
                # steps so async dispatch pipelines keep pipelining; the
                # synced step reports the window-average as per-step
                # latency. With the profiler on every step blocks
                # anyway — keep the already-paid exact latencies
                # instead of window-averaging
                t0 = time.perf_counter()
                timer = _mon.step_timer(self)
                do_sync = timer.begin(t0) or _prof._enabled
            # step-level profiler event; sync INSIDE the event so the
            # row records real step time, not async dispatch; with
            # profile_memory on it also samples live/peak HBM per
            # compiled step (the step IS the op)
            event = _prof.RecordEvent(pre + "run(compiled)") \
                if _prof._enabled else _NO_CONTEXT
            with _trc.phase(pre + ("build" if fresh else "dispatch"),
                            step=step), on_place:
                if fresh:
                    entry = self._first_compile(program, entry, args,
                                                build)
                    if use_program_cache:
                        self._cache[key] = entry
                with event:
                    fetches, new_state, guards, fetch_lods = entry(*args)
                    if do_sync or _prof._enabled:
                        # sync inside the phase: the histogram must
                        # record real step latency, not async dispatch
                        jax.block_until_ready(fetches)
                _trc.fetched(fetches)
            if mon_on:
                now = time.perf_counter()
                dt = timer.end_synced(now, t0) if do_sync else now - t0
                said = dict(feed_bytes=_mon.feed_nbytes(feed_arrays),
                            tokens=_mon.tokens_in_feeds(feed_arrays),
                            executor=how.prefix, synced=do_sync)
                if k is None:
                    _mon.on_step(key, dt, **said)
                else:
                    _mon.on_megastep(key, dt, k, **said)

            if how.pull is not None:
                with _trc.phase(pre + "pull", step=step):
                    fetches, fetch_lods, guards = how.pull(
                        fetches, fetch_lods, guards)
            with _trc.phase(pre + "commit", step=step):
                # Commit updated persistable state back to the scope.
                # New persistable vars materialized by this run (e.g.
                # startup program initializers) are committed too —
                # _build returns them in new_state.
                for n, v in new_state.items():
                    scope.set(n, v)
                if k is None:
                    if check_nan:
                        self._check_guards(guards)
                    out = self._trim_fetches(fetch_names, fetches,
                                             fetch_lods)
                    if return_numpy:
                        out = [as_numpy(v) for v in out]
                else:
                    if check_nan:
                        self._check_guards_steps(guards, k)
                    out = self._split_step_fetches(
                        fetch_names, fetches, fetch_lods, k, return_numpy)
                    if not return_numpy:
                        # async dispatch: hand back device handles and
                        # track the un-fetched dispatch in the in-flight
                        # window
                        self._inflight.append(fetches)
                if check_nan:
                    for one in (out,) if k is None else out:
                        self._check_nan_inf(fetch_names, one)
                return out

    def _build_entry(self, how, program, feed_names, fetch_names,
                     state_keys, static_info, check_nan, k):
        """The function an entry's cache holds jitted: the step ``_build``
        traces, as it is for ``Executor.run`` (device ops read
        ``jit(step)/...``: no closure goes round it), scanned over k
        batches for a megastep, and with AMP held at the entry's value
        for the trace where the entry pins it."""
        fn = self._build(program, feed_names, fetch_names, state_keys,
                         static_info, check_nan, *how.accum)
        if k is not None:
            fn = _megastep(fn)
        if how.amp is not None:
            fn = _amp_pinned(fn, how.amp)
        return fn

    def _run_host_ops(self, program, feed_arrays, fetch_names, scope,
                      static_info, return_numpy):
        """Programs containing host (IO) ops — send/recv/listen_and_serv
        — run in eager-interpreter mode: each lowering executes
        immediately on concrete values, so IO happens for real. This is
        the reference's op-by-op interpreter, kept ONLY for the
        distributed edge where the reference also left graph land."""
        # the send-tag sequence advances only on SUCCESS and is
        # per-program: a retried step reuses its tag, so the server
        # replaces (not doubles) the pending grad — elastic-recovery
        # idempotency. The program nonce keeps two programs' tags
        # distinct (a second SENDING program of the same grad names
        # within one round is not supported).
        import uuid
        entry = self._run_seqs.get(program)
        if entry is None:
            entry = self._run_seqs.setdefault(
                program, [0, uuid.uuid4().hex[:4]])
        # seq/incarnation travel as ARGUMENTS, not instance state: a
        # shared Executor driven from two threads must not cross-tag
        # rounds
        result = self._run_eager(
            program, feed_arrays, fetch_names, scope, static_info,
            return_numpy, run_seq=entry[0],
            incarnation=self._incarnation + entry[1])
        entry[0] += 1
        return result

    @staticmethod
    def _split_step_fetches(fetch_names, fetches_k, lods_k, k,
                            return_numpy):
        """[k, ...]-stacked scan outputs → K per-step fetch lists, with
        per-step LoD bucket-pad trimming (the run() contract)."""
        out = []
        for i in range(k):
            fi = [f[i] for f in fetches_k]
            lodi = {n: v[i] for n, v in lods_k.items()}
            fi = Executor._trim_fetches(fetch_names, fi, lodi)
            out.append([as_numpy(v) for v in fi] if return_numpy
                       else fi)
        return out

    @staticmethod
    def _check_guards_steps(guards_k, k):
        """Per-logical-step NaN-guard check over the [k]-stacked guard
        outputs; names the FIRST failing step. Unlike K sequential
        runs, state has already advanced all K steps by the time this
        raises (documented run_steps semantics)."""
        if not guards_k:
            return
        guards_k = jax.device_get(guards_k)
        for i in range(k):
            try:
                Executor._check_guards(
                    {g: np.asarray(v)[i] for g, v in guards_k.items()})
            except FloatingPointError as e:
                raise FloatingPointError(
                    "%s (at megastep logical step %d of %d; state has "
                    "advanced the full megastep)" % (e, i, k)) from None

    def _first_compile(self, program, entry, args, rebuild):
        """Lower and compile, ahead of its first call, the step of a
        program that trains (its main block has a `backward_marker`, or
        a regions' plan; any other program's `entry`, the start-up
        program's and a `for_test` clone's, is handed back as it is),
        for two readers of the executable. The build's table of the op
        ledger keeps it for the kernel ledger (paddle_tpu.trace.kernels:
        what XLA fused into each kernel of the step), with its
        memory_analysis() and the device's limit: one assignment here,
        read only if asked.
        And where the program's recompute regions keep values by a plan
        (ops/control_flow.py _plan_kept), the plan's reserve is a
        reckoning and the compiled executable is what confirms it: its
        memory_analysis() is said beside the plan's figures
        (control_flow.compiled_step), and where the compile fails with
        RESOURCE_EXHAUSTED the step is built and lowered once more with
        a plan of nothing, which is said and counted too. Returns the
        jitted step to call: `entry`, whose first call finds the
        executable JAX kept for it (one trace, one lowering, one
        compile), or the fallback's."""
        from ..ops import control_flow as _regions
        planned, fell_back = _regions.plans(program), False
        if not planned and not any(o.type == "backward_marker"
                                   for o in program.global_block().ops):
            return entry
        try:
            compiled = entry.lower(*args).compile()
        except Exception as e:        # jaxlib's XlaRuntimeError
            if not planned or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            _regions._LOG.warning(
                "recompute: the step did not compile with the regions' "
                "plan (%s); lowering once more with a plan of nothing",
                str(e).split("\n")[0][:300])
            fell_back = self._keep_nothing = True
            try:
                entry = jax.jit(rebuild(), donate_argnums=(0,))
                compiled = entry.lower(*args).compile()
            finally:
                self._keep_nothing = False
        try:
            memory = compiled.memory_analysis()
        except Exception:             # a backend that gives none
            memory = None
        if planned:
            _regions.compiled_step(memory, fell_back)
        _trc.kernel_table(compiled, memory, _bytes_limit(args))
        return entry

    # ------------------------------------------------------------------
    def _run_eager(self, program, feed_arrays, fetch_names, scope,
                   static_info, return_numpy, run_seq=None,
                   incarnation=None):
        """Execution path for programs containing host (IO) ops.

        The COMPUTE runs between host ops are jit-compiled per segment and
        cached (so a pserver-mode trainer's forward+backward is one XLA
        executable, not an op-by-op interpretation — the reference also
        only left graph land for the RPC ops themselves,
        listen_and_serv_op.cc); the host ops execute eagerly between
        segments on concrete values. Falls back to full op-by-op
        interpretation when a host op feeds the forward of a grad marker
        (autodiff must trace through it — e.g. the sparse prefetch path)
        or when PADDLE_TPU_SEGMENT_COMPILE=0."""
        import time as _time
        from .. import monitor as _mon
        mon_on = _mon.enabled()
        t0 = _time.perf_counter() if mon_on else 0.0
        block = program.global_block()
        ops = list(block.ops)
        persistable = {v.name for v in block.vars.values() if v.persistable}
        env = {n: scope.find_var(n) for n in persistable
               if scope.find_var(n) is not None}
        env.update(feed_arrays)

        counter = [0]
        base_key = _step_keys(program, self._rng_counter)
        self._rng_counter += 1

        def rng_fn():
            counter[0] += 1
            return jax.random.fold_in(base_key, counter[0])

        ctx = registry.LowerContext(env, rng_fn, executor=self, block=block,
                                    mesh=self._mesh,
                                    static_info=static_info,
                                    fetch_names=fetch_names)
        ctx.check_nan = _flag_on("PADDLE_TPU_CHECK_NAN_INF")
        ctx.run_seq = run_seq         # send-tag round id (host ops)
        ctx.incarnation = incarnation or self._incarnation
        bwd_idx = None
        for i, o in enumerate(ops):
            if o.type in ("backward_marker", "calc_gradient_marker"):
                bwd_idx = i
                break
        host_idx = [i for i, o in enumerate(ops)
                    if registry.is_host_op(o.type)]
        segmentable = (_flag_on("PADDLE_TPU_SEGMENT_COMPILE")
                       and (bwd_idx is None
                            or all(i > bwd_idx for i in host_idx)
                            or self._grad_leaves_concrete(ops, bwd_idx)))
        if segmentable:
            self._run_segments(ctx, ops, bwd_idx, program, block,
                               static_info, base_key, fetch_names)
        elif bwd_idx is None:
            for o in ops:
                _lower_op_eager(ctx, o)
        else:
            # interpreter path: pre-marker host ops that PRODUCE a wrt
            # name (prefetch leaves) must run eagerly FIRST — the grad
            # trace skips them and reads their outputs from base_env.
            # Run the minimal dependency slice: the host ops plus any
            # earlier op whose output (transitively) feeds their inputs
            # (e.g. a compute op producing the lookup ids).
            wrt_names, _ = self._parse_marker(ops[bwd_idx])
            wrt = set(wrt_names)
            pre = ops[:bwd_idx]
            run_ids = set()
            needed = set()
            for o in pre:
                if registry.is_host_op(o.type) and any(
                        n in wrt for ns in o.outputs.values() for n in ns):
                    run_ids.add(id(o))
                    needed.update(n for ns in o.inputs.values()
                                  for n in ns)
            for o in reversed(pre):
                if id(o) in run_ids:
                    continue
                if any(n in needed for ns in o.outputs.values()
                       for n in ns):
                    run_ids.add(id(o))
                    needed.update(n for ns in o.inputs.values()
                                  for n in ns)
            # RNG-stateful slice ops must NOT re-run inside the grad
            # trace: the re-traced draw would diverge from the ids the
            # prefetch actually fetched, mispairing rows and gradients.
            # Track which ops drew from the stream and bind their eager
            # outputs as constants in the trace instead.
            rng_ops = set()
            for o in pre:
                if id(o) in run_ids:
                    drawn = counter[0]
                    _lower_op(ctx, o)
                    if counter[0] != drawn:
                        rng_ops.add(id(o))
            self._lower_with_grad(ctx, ops, bwd_idx, program, block,
                                  skip_op_ids=rng_ops)

        for n in persistable:
            if n in env:
                scope.set(n, env[n])
        if ctx.check_nan:
            self._check_guards(
                {k: v for k, v in env.items() if k.startswith(_NANGUARD)})
        fetches = [_fetch_from_env(env, n) for n in fetch_names]
        fetch_lods = {n: env[n + "@LOD"] for n in fetch_names
                      if env.get(n + "@LOD") is not None}
        fetches = self._trim_fetches(fetch_names, fetches, fetch_lods)
        if mon_on:
            # host-op (distributed trainer) step: no cached-step key, so
            # no MFU — latency/throughput telemetry still lands
            _mon.on_step(None, _time.perf_counter() - t0,
                         feed_bytes=_mon.feed_nbytes(feed_arrays),
                         tokens=_mon.tokens_in_feeds(feed_arrays),
                         executor="eager")
        if return_numpy:
            return [as_numpy(v) for v in fetches]
        return fetches

    # ------------------------------------------------------------------
    @staticmethod
    def _is_jit_value(v):
        return isinstance(v, (jax.Array, np.ndarray, np.generic))

    @staticmethod
    def _grad_leaves_concrete(ops, bwd_idx):
        """True when host ops BEFORE the grad marker cannot break gradient
        flow, so the step is still segment-compilable: every marker wrt
        name must enter the marker's compute segment as a concrete leaf
        (a parameter from the scope, or the output of a host op like
        ``prefetch``). If any op at or before the last pre-marker host op
        CONSUMES a wrt name — or a compute op PRODUCES one there — the
        chain from that wrt to the loss would cross a segment boundary
        and its gradient would silently be wrong → not segmentable.

        This is what lifts the full-eager fallback for the distributed
        sparse-embedding path (prefetch → fwd+bwd → sparse send): the
        prefetched rows are a differentiable leaf of the compiled
        segment, exactly like the reference's trainer treats the rows
        fetched from the pserver (distribute_transpiler.py:201-255)."""
        host_before = [i for i in range(bwd_idx)
                       if registry.is_host_op(ops[i].type)]
        if not host_before:
            return True
        h_last = max(host_before)
        wrt_names, _ = Executor._parse_marker(ops[bwd_idx])
        wrt = set(wrt_names)
        for o in ops[:h_last + 1]:
            ins = {n for ns in o.inputs.values() for n in ns}
            if ins & wrt:
                return False
            outs = {n for ns in o.outputs.values() for n in ns}
            if (outs & wrt) and not registry.is_host_op(o.type):
                return False
        return True

    def _run_segments(self, ctx, ops, bwd_idx, program, block, static_info,
                      base_key, fetch_names=()):
        """Interleave jit-compiled compute segments with eager host ops.

        Precondition (checked by the caller): any grad marker precedes the
        first host op, so each compute segment is traceable in isolation.
        A compute segment whose inputs include a non-array value (e.g. a
        SelectedRows produced by a host op) drops to eager for that
        segment only."""
        # greedy partition into ("host", [op]) / ("compute", [ops...])
        segments = []
        for i, o in enumerate(ops):
            kind = "host" if registry.is_host_op(o.type) else "compute"
            if segments and segments[-1][0] == kind == "compute":
                segments[-1][1].append((i, o))
            else:
                segments.append((kind, [(i, o)]))

        # names each segment touches, and what must survive PAST each
        # segment (later segments' refs + fetches + persistable state +
        # grad names the marker binds) — the jitted segment returns only
        # those, so XLA does not materialize every intermediate as output
        def _names(o):
            out = set()
            for coll in (o.inputs, o.outputs):
                for ns in coll.values():
                    out.update(ns)
            return out

        seg_names = [set().union(*(_names(o) for _, o in idx_ops))
                     for _, idx_ops in segments]
        persistable = {v.name for v in block.vars.values() if v.persistable}
        keep = set(fetch_names) | persistable
        keep |= {n + "@GRAD" for n in keep}
        needed_after = []
        acc = set(keep)
        for names in reversed(seg_names):
            needed_after.append(set(acc))
            acc |= names
        needed_after.reverse()

        check_nan = getattr(ctx, "check_nan", False)
        from ..amp import amp_enabled
        for seg_no, (kind, idx_ops) in enumerate(segments):
            if kind == "host":
                for _, o in idx_ops:
                    _lower_op_eager(ctx, o)
                continue
            seg_ops = [o for _, o in idx_ops]
            start = idx_ops[0][0]
            rel_bwd = None
            if bwd_idx is not None and start <= bwd_idx:
                for j, o in enumerate(seg_ops):
                    if o.type in ("backward_marker",
                                  "calc_gradient_marker"):
                        rel_bwd = j
                        break
            # a segment touches its ops' inputs AND outputs (outputs that
            # pre-exist in env: params being updated, feed-op targets),
            # plus the @LOD companions sequence lowerings read implicitly
            refs = {n for o in seg_ops
                    for coll in (o.inputs, o.outputs)
                    for ns in coll.values() for n in ns}
            refs |= {n + "@LOD" for n in refs}
            refs = {n for n in refs if n in ctx.env}
            if any(not self._is_jit_value(ctx.env[n]) for n in refs):
                ctx._nan_idx = start
                if rel_bwd is None:
                    for _, o in idx_ops:
                        _lower_op(ctx, o)
                else:
                    self._lower_with_grad(ctx, seg_ops, rel_bwd, program,
                                          block)
                continue
            array_env = {k: ctx.env[k] for k in refs}
            sig = tuple(sorted((k, tuple(np.shape(v)), str(v.dtype))
                               for k, v in array_env.items()))
            key = ("segment", program, program._version, seg_no, sig,
                   check_nan, amp_enabled(),
                   tuple(sorted(static_info.items())))
            entry = self._cache.get(key)
            if entry is None:
                needed = needed_after[seg_no]

                def seg_fn(array_env, rng_key, _rel_bwd=rel_bwd,
                           _seg_ops=seg_ops, _start=start, _needed=needed):
                    n_splits = [0]

                    def seg_rng():
                        n_splits[0] += 1
                        return jax.random.fold_in(rng_key, n_splits[0])

                    env = dict(array_env)
                    sctx = registry.LowerContext(
                        env, seg_rng, executor=self, block=block,
                        mesh=self._mesh,
                        static_info=static_info,
                        fetch_names=getattr(ctx, "fetch_names", ()))
                    sctx.check_nan = check_nan
                    sctx._nan_idx = _start   # program-order guard keys
                    if _rel_bwd is None:
                        for o in _seg_ops:
                            _lower_op(sctx, o)
                    else:
                        self._lower_with_grad(sctx, _seg_ops, _rel_bwd,
                                              program, block)
                    return {k: v for k, v in env.items()
                            if self._is_jit_value(v)
                            and (k in _needed
                                 or k.startswith(_NANGUARD)
                                 or (k.endswith("@LOD")
                                     and k[:-4] in _needed))}

                entry = self._cache[key] = jax.jit(seg_fn)
            seg_key = jax.random.fold_in(base_key, 1000 + seg_no)
            ctx.env.update(entry(array_env, seg_key))

    # ------------------------------------------------------------------
    def _build(self, program, feed_names, fetch_names, state_keys,
               static_info=None, check_nan=False, accum_steps=1,
               accum_loss_norm=None):
        """Build the pure step function for one (program, signature).

        accum_steps > 1: GRADIENT ACCUMULATION — the feed batch is split
        into that many microbatches, fwd+bwd runs as a lax.scan over them
        accumulating mean grads (and streaming persistable-state updates,
        e.g. batch-norm counters), then the optimizer ops apply once.
        In-graph, so one XLA executable per step regardless of
        accum_steps. Requires a grad marker; LoD feeds are supported via
        the host-side [k, ...] pre-split (_normalize_feeds). Only
        targets and persistables are fetchable (microbatch intermediates
        never leave the scan)."""
        # armed program transform (PADDLE_TPU_TRANSFORM=1): the pass
        # pipeline rewrites a CLONE and the trace below builds from it,
        # while the compile-cache key stays the caller's program +
        # version — a cache hit never re-transforms, and a transformed
        # program recompile is classified by the monitor via the
        # clone's _transform_meta (new program_version), not
        # mystery-counted. Disarmed cost: one flag check.
        from ..transform.passes import maybe_transform_for_build
        program = maybe_transform_for_build(program, fetch_names)
        static_info = static_info or {}
        block = program.global_block()
        ops = list(block.ops)
        persistable_names = {v.name for v in block.vars.values()
                             if v.persistable}

        bwd_idx = None
        for i, op in enumerate(ops):
            if op.type in ("backward_marker", "calc_gradient_marker"):
                bwd_idx = i
                break
        if bwd_idx is not None:
            ops = _without_forward_only(ops, fetch_names)
            bwd_idx = ops.index(block.ops[bwd_idx])
        if accum_steps > 1:
            if bwd_idx is None:
                raise ValueError(
                    "gradient_accumulation_steps=%d needs a grad marker "
                    "(append_backward/minimize) in the program"
                    % accum_steps)
            if ops[bwd_idx].type != "backward_marker":
                # calc_gradient targets are SUM-reduced with unit
                # cotangents; microbatch-mean accumulation would change
                # both scale and (for non-scalar targets) shape
                raise NotImplementedError(
                    "gradient accumulation supports loss training "
                    "(append_backward) only, not calc_gradient")
            # LoD feeds arrive pre-split host-side ([k, ...] stacked by
            # _normalize_feeds(accum_steps=k)) and are scanned by index
            # — see static_info @ACCUM_LOD in _lower_with_grad_accum
        op_log = _OpLog(ops, bwd_idx)

        def step(state, feeds, rng_key):
            n_splits = [0]

            def rng_fn():
                n_splits[0] += 1
                return jax.random.fold_in(rng_key, n_splits[0])

            env = {}
            env.update(state)
            env.update(feeds)
            ctx = registry.LowerContext(env, rng_fn, executor=self,
                                        block=block,
                                        mesh=self._mesh,
                                        static_info=static_info,
                                        fetch_names=fetch_names)
            ctx.check_nan = check_nan
            ctx._op_log = op_log
            if accum_steps > 1:
                self._lower_with_grad_accum(ctx, ops, bwd_idx, block,
                                            feeds, accum_steps,
                                            persistable_names,
                                            loss_norm=accum_loss_norm)
            elif bwd_idx is None:
                for op in ops:
                    _lower_op(ctx, op)
            else:
                self._lower_with_grad(ctx, ops, bwd_idx, program, block)

            fetches = tuple(_fetch_from_env(env, n) for n in fetch_names)
            new_state = {n: env[n] for n in state_keys if n in env}
            # newly-created persistable values (startup initializers)
            for n in persistable_names:
                if n not in new_state and n in env \
                        and not n.startswith(_NANGUARD):
                    new_state[n] = env[n]
            guards = {k: v for k, v in env.items() if k.startswith(_NANGUARD)}
            # per-fetch LoD lengths: the caller trims bucket-pad rows off
            # LoD-carrying fetches host-side (flat totals are bucketed, see
            # _normalize_feeds)
            fetch_lods = {n: env[n + "@LOD"] for n in fetch_names
                          if env.get(n + "@LOD") is not None}
            return fetches, new_state, guards, fetch_lods

        return step

    @staticmethod
    def _trim_fetches(fetch_names, fetches, fetch_lods):
        """Slice bucket-pad rows off fetched LoD values (true total =
        sum of the value's sequence lengths)."""
        if not fetch_lods:
            return list(fetches)
        out = []
        for n, v in zip(fetch_names, fetches):
            lod = fetch_lods.get(n)
            if lod is not None and getattr(v, "ndim", 0) >= 1:
                total = int(np.sum(np.asarray(lod)))
                if v.shape[0] > total:
                    v = v[:total]
            out.append(v)
        return out

    @staticmethod
    def _parse_marker(marker):
        """Grad-marker attrs → (wrt_names, target_names)."""
        if marker.type == "backward_marker":
            return (marker.attr("param_names") or [],
                    [marker.attr("loss_name")])
        # calc_gradient_marker
        return (marker.attr("input_names") or [],
                marker.attr("target_names") or [])

    @staticmethod
    def _lower_with_grad(ctx, ops, bwd_idx, program, block,
                         skip_op_ids=frozenset()):
        """Trace forward ops under value_and_grad, bind param@GRAD vars, then
        trace the remaining (optimizer) ops.

        ``append_backward(..., checkpoint=True)`` wraps the WHOLE forward
        in jax.checkpoint: only the step inputs are saved and the forward
        re-runs during the backward pass (maximal memory saving, ~1.33x
        forward FLOPs). In that mode only targets, persistables, @LOD
        lengths and guards survive the forward — fetching another forward
        intermediate would defeat the remat, so it raises a KeyError at
        fetch. Per-layer granularity is ``layers.recompute()``."""
        marker = ops[bwd_idx]
        wrt_names, target_names = Executor._parse_marker(marker)
        base_env = dict(ctx.env)
        wrt = {n: base_env[n] for n in wrt_names if n in base_env}
        use_ckpt = bool(marker.attr("checkpoint")) \
            if marker.type == "backward_marker" else False
        persistable = {v.name for v in block.vars.values()
                       if v.persistable}
        # post-marker (optimizer) ops may read forward intermediates —
        # computed learning-rate chains — so those survive the keep filter
        post_in = {n for op in ops[bwd_idx + 1:]
                   for ns in op.inputs.values() for n in ns}

        def forward(params):
            env = dict(base_env)
            env.update(params)
            fctx = registry.LowerContext(env, ctx._rng_fn,
                                         is_test=ctx.is_test,
                                         executor=ctx.executor, block=block,
                                         mesh=ctx.mesh,
                                         static_info=ctx.static_info,
                                         fetch_names=getattr(
                                             ctx, "fetch_names", ()))
            fctx.check_nan = getattr(ctx, "check_nan", False)
            fctx._op_log = ctx._op_log
            wrt_set = set(wrt_names)
            for op in ops[:bwd_idx]:
                # a host op (e.g. prefetch) that PRODUCES a wrt name is a
                # gradient LEAF — its value is already bound as a param;
                # re-running it would overwrite the tracer with a concrete
                # value and silently zero that gradient
                if registry.is_host_op(op.type) and any(
                        n in wrt_set for ns in op.outputs.values()
                        for n in ns):
                    continue
                # RNG-stateful ops already run eagerly (prefetch id
                # slice): their concrete outputs sit in base_env; a
                # re-traced draw would produce DIFFERENT ids than the
                # rows the prefetch fetched
                if id(op) in skip_op_ids:
                    continue
                _lower_op(fctx, op)
            # the ops after the marker are numbered on from the forward's,
            # so that a scope names ONE op of a build (the op ledger's key)
            ctx._op_seq = fctx._op_seq
            # scalar objective: mean-reduce each target (loss is already
            # scalar in the common case; calc_gradient uses unit cotangents,
            # i.e. sum of each target's elements)
            total = 0.0
            for tn in target_names:
                t = env[tn]
                total = total + (t if t.ndim == 0 else jnp.sum(t))
            if not use_ckpt:
                return total, env
            # checkpointed: exporting every intermediate as an output
            # would force XLA to store them all — keep only what the
            # post-marker ops and the scope commit can need
            keep = {n: v for n, v in env.items()
                    if n in persistable or n in target_names
                    or n in wrt or n in post_in
                    or n.startswith(_NANGUARD) or n.endswith("@LOD")}
            return total, keep

        fwd = jax.checkpoint(forward) if use_ckpt else forward
        (loss_val, env_after), grads = jax.value_and_grad(
            fwd, has_aux=True)(wrt)
        ctx.env.update(env_after)
        # continue the NaN-guard program-order index past the forward ops
        # (the forward fctx numbered its guards from 0; optimizer-op guards
        # recorded on `ctx` must sort after them, executor.cc:27-94 parity)
        fwd_guard_idx = [int(k[len(_NANGUARD):].split("|", 1)[0])
                         for k in env_after if k.startswith(_NANGUARD)]
        ctx._nan_idx = max(fwd_guard_idx, default=-1) + 1
        if marker.type == "backward_marker":
            ctx.env[target_names[0] + "@GRAD"] = jnp.ones_like(loss_val)
        for p, g in grads.items():
            ctx.env[p + "@GRAD"] = g
        for op in ops[bwd_idx + 1:]:
            _lower_op(ctx, op)

    @staticmethod
    def _lower_with_grad_accum(ctx, ops, bwd_idx, block, feeds,
                               accum_steps, persistable_names,
                               loss_norm=None):
        """Gradient accumulation: lax.scan of fwd+bwd over microbatches.

        Feeds with batch dim > 1 split into accum_steps equal chunks
        (scalar / leading-dim-1 feeds broadcast to every microbatch); the
        scan carry holds (grad sums, loss sum, persistable state) so
        streaming forward-state updates (e.g. batch-norm counters) and
        NaN guards thread through microbatches; grads and the loss are
        WEIGHTED sums over microbatches. The weights depend on how the
        user's loss is normalized (``loss_norm``):

        - ``"sequence"`` (and the dense equal-chunk case): w_i = 1/k.
          Exact when the loss is a mean over per-sequence values — each
          microbatch holds the same number of sequences.
        - ``"token"`` / ``"token:<feed>"``: w_i = T_i / sum(T_j), the
          true (pre-bucket) token totals of the ragged LoD pre-split
          (``<feed>@ACCUM_TOKENS`` from _normalize_feeds). Exact when
          the loss is a mean over TOKENS: full-batch token mean
          = sum_i (T_i/T) * (per-microbatch token mean).

        Ragged splits with UNEQUAL token totals and no explicit
        loss_norm are rejected host-side (ParallelExecutor.run) — equal
        weighting would silently mis-scale token-normalized losses.
        With either exact weighting, an optimizer step after
        accumulation matches the unaccumulated step. Each microbatch
        gets its own RNG stream (dropout masks differ per microbatch)."""
        marker = ops[bwd_idx]
        wrt_names, target_names = Executor._parse_marker(marker)
        base_env = dict(ctx.env)
        wrt = {n: base_env[n] for n in wrt_names if n in base_env}
        use_ckpt = bool(marker.attr("checkpoint"))
        post_in = {n for o in ops[bwd_idx + 1:]
                   for ns in o.inputs.values() for n in ns}

        k = int(accum_steps)
        static_info = getattr(ctx, "static_info", None) or {}
        # LoD feeds (and their @LOD lengths) were pre-split host-side
        # into [k, ...] stacks by _normalize_feeds(accum_steps=k): scan
        # them by leading index instead of reshape-chunking a batch dim
        stacked = {n for n in feeds if static_info.get(n + "@ACCUM_LOD")}
        stacked |= {n + "@LOD" for n in list(stacked)
                    if n + "@LOD" in feeds}
        chunked = {}
        for n in feeds:
            v = base_env[n]
            if n.endswith("@ACCUM_TOKENS"):
                continue          # weight inputs, consumed below
            if n in stacked:
                chunked[n] = v                 # already [k, ...]
                continue
            if getattr(v, "ndim", 0) < 1 or v.shape[0] <= 1:
                continue          # scalar/broadcast feed: replicate
            if v.shape[0] % k:
                raise ValueError(
                    "feed %r batch dim %s not divisible into %d "
                    "microbatches" % (n, getattr(v, "shape", ()), k))
            chunked[n] = v.reshape((k, v.shape[0] // k) + v.shape[1:])
        # persistable values the forward may update (streamed through the
        # scan carry; keys fixed before tracing for a stable carry pytree)
        pstate0 = {n: v for n, v in base_env.items()
                   if n in persistable_names and n not in wrt}
        accum_key = ctx._rng_fn()    # base for per-microbatch streams

        # Per-microbatch loss/grad weights (see docstring). Raggedness
        # and multi-feed ambiguity are checked host-side on the concrete
        # totals (parallel/executor.py); here the totals are tracers.
        _TOK = "@ACCUM_TOKENS"
        tok_arrays = {n[:-len(_TOK)]: base_env[n]
                      for n in feeds if n.endswith(_TOK)}
        norm = loss_norm or "sequence"
        if norm.startswith("token") and not tok_arrays:
            # the user asked for token weighting but no ragged LoD feed
            # carries token counts — silently falling back to 1/k would
            # be the exact mis-scaling this knob exists to prevent
            raise ValueError(
                "gradient_accumulation_loss_norm=%r: this program has no "
                "ragged LoD feeds, so per-microbatch token counts are "
                "unavailable; drop the knob (equal chunks weight equally) "
                "or feed the sequence data as LoDTensor" % (loss_norm,))
        if norm.startswith("token"):
            if ":" in norm:
                src = norm.split(":", 1)[1]
                if src not in tok_arrays:
                    raise ValueError(
                        "gradient_accumulation_loss_norm=%r: %r is not "
                        "a ragged LoD feed of this program (have %s)"
                        % (loss_norm, src, sorted(tok_arrays)))
                tok = tok_arrays[src]
            else:
                tok = next(iter(tok_arrays.values()))
            weights = tok / jnp.sum(tok)
        else:
            weights = jnp.full((k,), 1.0 / k, jnp.float32)

        def forward(params, pstate, feeds_i, key_i):
            env = dict(base_env)
            env.update(pstate)
            env.update(feeds_i)
            env.update(params)
            n_splits = [0]

            def micro_rng():
                n_splits[0] += 1
                return jax.random.fold_in(key_i, n_splits[0])

            fctx = registry.LowerContext(env, micro_rng,
                                         is_test=ctx.is_test,
                                         executor=ctx.executor,
                                         block=block, mesh=ctx.mesh,
                                         static_info=ctx.static_info,
                                         fetch_names=getattr(
                                             ctx, "fetch_names", ()))
            fctx.check_nan = getattr(ctx, "check_nan", False)
            fctx._op_log = ctx._op_log
            for op in ops[:bwd_idx]:
                _lower_op(fctx, op)
            ctx._op_seq = fctx._op_seq    # as in _lower_with_grad
            loss = env[target_names[0]]
            if use_ckpt:
                # checkpoint composes with accumulation: per-microbatch
                # residuals shrink to the microbatch inputs; keep only
                # what the carry/probe consumers read (the whole-forward
                # keep-filter contract of _lower_with_grad)
                env = {n: v for n, v in env.items()
                       if n in pstate0 or n in target_names
                       or n in post_in or n.startswith(_NANGUARD)}
            return (loss if loss.ndim == 0 else jnp.sum(loss)), env

        fwd = jax.checkpoint(forward) if use_ckpt else forward

        def body(carry, xs):
            gsum, lsum, pstate, guards_ok = carry
            feeds_i, idx, w_i = xs
            key_i = jax.random.fold_in(accum_key, idx)
            (loss, env_a), grads = jax.value_and_grad(
                fwd, has_aux=True)(wrt, pstate, feeds_i, key_i)
            gsum = jax.tree.map(
                lambda s, g: s + g * w_i.astype(g.dtype), gsum, grads)
            lsum = lsum + loss * w_i.astype(loss.dtype)
            pstate = {n: env_a.get(n, pstate[n]) for n in pstate}
            guards_ok = {g: guards_ok[g]
                         & env_a.get(g, jnp.asarray(True))
                         for g in guards_ok}
            return (gsum, lsum, pstate, guards_ok), None

        # One probe trace on microbatch 0: discovers the guard names (so
        # the scan carry pytree is fixed) and supplies the post-marker
        # ops' forward inputs — e.g. a computed learning-rate chain. Only
        # the subgraph whose outputs are actually exported below survives
        # XLA dead-code elimination; the heavy model compute in the probe
        # is dropped.
        _, probe_env = forward(wrt, pstate0,
                               {n: c[0] for n, c in chunked.items()},
                               accum_key)
        loss_name = target_names[0]
        if getattr(probe_env[loss_name], "ndim", 0) != 0:
            raise ValueError(
                "gradient accumulation requires a SCALAR (mean-reduced) "
                "loss; %r has shape %s — accumulating a per-element loss "
                "would silently rescale gradients by 1/%d"
                % (loss_name, probe_env[loss_name].shape, k))
        guard_names = [g for g in probe_env if g.startswith(_NANGUARD)]
        init = (jax.tree.map(jnp.zeros_like, wrt),
                jnp.zeros_like(probe_env[loss_name], shape=()),
                pstate0,
                {g: jnp.asarray(True) for g in guard_names})
        (gsum, lsum, pstate, guards_ok), _ = jax.lax.scan(
            body, init, (chunked, jnp.arange(k), weights))

        ctx.env.update(pstate)
        ctx.env.update(guards_ok)
        # Post-marker (optimizer) ops may read forward intermediates —
        # the computed-LR chain is the canonical case. Export those from
        # the PROBE trace, and for persistable vars that chain writes
        # (step counters: @LR_DECAY_COUNTER@) export the probe's
        # once-advanced value too, overriding the scan's k-advanced copy:
        # a counter's contract is one tick per executed STEP, while
        # batch-norm-style stats (not read post-marker) keep the
        # per-microbatch streamed values from the scan.
        producers = {}
        for op in ops[:bwd_idx]:
            for ns in op.outputs.values():
                for n in ns:
                    producers[n] = op
        frontier = [n for n in post_in
                    if n in producers and n in probe_env
                    and n not in base_env]
        seen_ops, stack = set(), list(frontier)
        counter_vars = set()
        while stack:
            nm = stack.pop()
            op = producers.get(nm)
            if op is None or id(op) in seen_ops:
                continue
            seen_ops.add(id(op))
            for ns in op.outputs.values():
                counter_vars.update(n for n in ns
                                    if n in persistable_names)
            for ns in op.inputs.values():
                stack.extend(ns)
        for n in frontier:
            ctx.env[n] = probe_env[n]
        for n in counter_vars:
            if n in probe_env:
                ctx.env[n] = probe_env[n]

        ctx.env[loss_name] = lsum     # weights sum to 1: already a mean
        fwd_guard_idx = [int(g[len(_NANGUARD):].split("|", 1)[0])
                         for g in guard_names]
        ctx._nan_idx = max(fwd_guard_idx, default=-1) + 1
        ctx.env[loss_name + "@GRAD"] = jnp.ones_like(lsum)
        for p in wrt:
            ctx.env[p + "@GRAD"] = gsum[p]
        for op in ops[bwd_idx + 1:]:
            _lower_op(ctx, op)

    @staticmethod
    def _check_nan_inf(names, values):
        # FLAGS_check_nan_inf parity (reference executor.cc:27-94).
        for n, v in zip(names, values):
            arr = np.asarray(v)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                from .. import monitor as _mon
                _mon.on_nan_trip("fetch", detail=n)
                raise FloatingPointError(
                    "NaN/Inf detected in fetched var %r" % n)

    @staticmethod
    def _check_guards(guards):
        """Report the FIRST (program-order) op output that went non-finite."""
        if not guards:
            return
        guards = jax.device_get(guards)  # one transfer for all guard scalars
        bad = [k for k, ok in guards.items() if not bool(np.asarray(ok))]
        if bad:
            k = min(bad, key=lambda s: int(s[len(_NANGUARD):].split("|")[0]))
            _, op_type, var = k[len(_NANGUARD):].split("|", 2)
            from .. import monitor as _mon
            _mon.on_nan_trip("guard", detail="%s/%s" % (op_type, var))
            raise FloatingPointError(
                "NaN/Inf detected in output %r of op %r "
                "(PADDLE_TPU_CHECK_NAN_INF)" % (var, op_type))


def _without_forward_only(ops, fetch_names):
    """`ops` of a block with a gradient marker less those built under
    ``layers.forward_only``, which a train step does not lower; all of
    `ops` where the run fetches one of their results."""
    held_out = [o for o in ops if o.attr("forward_only")]
    if set(fetch_names) & {n for o in held_out for n in o.output_names}:
        return ops
    return [o for o in ops if not o.attr("forward_only")]


def _gather_state(program, scope):
    """The program's persistable vars that exist in the scope, and
    their sorted names (part of every cache key)."""
    state = {}
    for v in program.global_block().vars.values():
        if v.persistable:
            val = scope.find_var(v.name)
            if val is not None:
                state[v.name] = val
    return state, tuple(sorted(state))


def _step_costs_safe(fn, state, feeds, rng_key):
    """Static (flops, bytes) of one step for the monitor's MFU gauge —
    abstract trace only (analysis.cost.step_costs)."""
    from ..analysis.cost import step_costs
    return step_costs(fn, (state, feeds, rng_key))


def _lower_op_eager(ctx, op):
    """_lower_op on CONCRETE values (the interpreter / host-segment
    path) with per-op profiling: each op gets its own RecordEvent, and
    with FLAGS profile_memory on, outputs sync before the memory sample
    so live/peak bytes attribute to THIS op — the reference's
    FLAGS_benchmark per-op wait+log (operator.cc:576-578), which also
    only existed in its interpreter."""
    from .. import profiler as _prof
    if not _prof._enabled:
        _lower_op(ctx, op)
        return
    with _prof.RecordEvent("op:%s" % op.type):
        _lower_op(ctx, op)
        if _prof.memory_enabled():
            outs = [ctx.env[n] for ns in op.outputs.values() for n in ns
                    if n in ctx.env]
            try:
                jax.block_until_ready(
                    [o for o in outs if isinstance(o, jax.Array)])
            except Exception:
                pass


def _lower_op(ctx, op, lower=None):
    """Lower `op` into ctx.env under its device scope, with its row of
    the op ledger, its LoD and its NaN guards. `lower`: what to call in
    place of the op type's registered lowering (a region's head and
    loss in row blocks stand for their ops with values' shapes:
    ops/control_flow.py _loss_in_row_blocks)."""
    if op.type in ("feed", "fetch"):
        _lower_feed_fetch(ctx, op)
        return
    info = registry.lookup(op.type)
    if info is None:
        raise NotImplementedError(
            "no TPU lowering registered for op %r (registered: %d ops)"
            % (op.type, len(registry.registered_ops())))
    try:
        # scope every op's lowering as "<op_type>.<seq>": the name lands
        # in each jaxpr eqn's source_info name stack, which is (a) the
        # op path paddle_tpu.analysis diagnostics report and (b) the
        # metadata XLA profiles attribute — the analog of the
        # reference's per-op RecordEvent naming
        seq = getattr(ctx, "_op_seq", 0)
        ctx._op_seq = seq + 1
        # a recompute region is not an op of the model: the ops inside
        # it name themselves (numbered on from here), so that a trace
        # attributes a layer's time to its ops and not to the region
        # (nor is a `repeat`: its visits' ops name themselves)
        named = op.type not in ("recompute_block", "repeat")
        scope = jax.named_scope("%s.%d" % (op.type, seq)) if named \
            else contextlib.nullcontext()
        # the op ledger's row goes under the scope's own two halves
        log = ctx._op_log if named else None
        row = ctx._op_row = None if log is None else log.row(ctx, op, seq)
        with scope:
            (lower or info.lower)(ctx, op)
    except EnforceError:
        raise
    except Exception as e:  # annotate with op context (enforce.h:203 parity)
        raise op_error(op, ctx.env, e) from e
    _propagate_lod(ctx, op)
    if row is not None:
        row["outputs"] = _described(ctx.env, op.outputs)
    if getattr(ctx, "check_nan", False):
        _record_nan_guards(ctx, op)


def _bytes_limit(args):
    """What the device a step's arguments sit on says it may hold
    (memory_stats()["bytes_limit"]); None where the backend states none
    (the CPU) or the arguments are on no device (shapes alone)."""
    placed = next((leaf for leaf in jax.tree_util.tree_leaves(args)
                   if isinstance(leaf, jax.Array)), None)
    try:
        device = min(placed.devices(), key=lambda d: d.id)
        return (device.memory_stats() or {}).get("bytes_limit")
    except Exception:                 # no array, or a deleted one
        return None


def _described(env, slots):
    """{slot: ((variable, shape, dtype), ...)} of an op's inputs or
    outputs as the trace holds them, in plain values (a value with no
    shape, a tensor array's list, gives None twice)."""
    def one(name):
        v = env.get(name)
        shape, dtype = getattr(v, "shape", None), getattr(v, "dtype", None)
        return (name, None if shape is None else tuple(map(int, shape)),
                None if dtype is None else str(dtype))
    return {slot: tuple(one(n) for n in names)
            for slot, names in slots.items()}


class _OpLog:
    """What one build writes its table of the op ledger with
    (``paddle_tpu.trace.ops``): the table's rows and, from the Program,
    the names the step's gradients flow through. Made where the step
    is built; every context that lowers the build's ops shares it, and
    ``_lower_op`` asks it for each op's row. The eager interpreter's
    contexts have none: it lowers on every run."""

    def __init__(self, ops, bwd_idx):
        marker = None if bwd_idx is None else ops[bwd_idx]
        self.rows = _trc.op_table(
            marker is not None and marker.type == "backward_marker")
        self.reach = frozenset()
        if marker is not None:
            from ..ops.control_flow import reached_from
            self.reach = reached_from(ops[:bwd_idx],
                                      Executor._parse_marker(marker)[0])

    def row(self, ctx, op, seq):
        find = ctx.block._find_var_recursive if ctx.block is not None \
            else lambda name: None
        row = self.rows[seq] = {
            "seq": seq, "type": op.type,
            "inputs": _described(ctx.env, op.inputs), "outputs": {},
            "weights": tuple(n for n in op.input_names
                             if isinstance(find(n), Parameter)),
            "region": ctx._op_region, "kept": None,
            "module": op.attr("module")}
        if op.type in ("mul", "matmul"):
            # which of the product's two gradients the step takes: the
            # operands a differentiated parameter reaches
            row["grads"] = tuple(
                g for g, slot in (("x", "X"), ("w", "Y"))
                if set(op.input(slot)) & self.reach)
        return row


def _record_nan_guards(ctx, op):
    """FLAGS_check_nan_inf parity with the reference's EVERY-op-output scan
    (framework/executor.cc:27-94): one cheap isfinite reduction per float
    output, carried through the jitted step as extra scalar outputs under
    reserved ``__nanguard__`` env names (so they also flow through the
    value_and_grad aux in _lower_with_grad)."""
    for name in op.output_names:
        v = ctx.env.get(name)
        dt = getattr(v, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.floating):
            fin = jnp.isfinite(v)
            lod = ctx.env.get(name + "@LOD")
            if lod is not None and getattr(v, "ndim", 0) >= 1:
                # bucket-pad rows (past sum(lengths)) are zero filler and
                # may legitimately be non-finite downstream of log/div —
                # only the real rows count (executor.cc:27-94 scans real
                # tensor contents only)
                valid = jnp.arange(v.shape[0]) < jnp.sum(lod)
                fin = fin | ~valid.reshape(
                    (v.shape[0],) + (1,) * (v.ndim - 1))
            idx = getattr(ctx, "_nan_idx", 0)
            ctx._nan_idx = idx + 1
            ctx.env["%s%d|%s|%s" % (_NANGUARD, idx, op.type, name)] = \
                fin.all()


def _propagate_lod(ctx, op):
    """LoD (sequence lengths) flow through row-preserving ops.

    The reference's ops copy LoD from input to output inside each kernel
    (ShareLoD in InferShape). Here: if a lowering didn't set ``out@LOD``
    itself (sequence_* ops do), any output with the same leading dim as an
    LoD-carrying input inherits that input's lengths. This is what lets
    ``embedding → sequence_pool`` see per-sequence boundaries."""
    in_lod = None
    lead = None
    src = None
    for name in op.input_names:
        lod = ctx.env.get(name + "@LOD")
        if lod is not None:
            val = ctx.env.get(name)
            if val is not None and getattr(val, "ndim", 0) >= 1:
                in_lod, lead, src = lod, val.shape[0], name
                break
    if in_lod is None:
        return
    maxlen = ctx.static_info.get(src + "@MAXLEN")
    for name in op.output_names:
        if name + "@LOD" in ctx.env:
            continue  # lowering set it explicitly
        val = ctx.env.get(name)
        if val is not None and getattr(val, "ndim", 0) >= 1 \
                and val.shape[0] == lead:
            ctx.env[name + "@LOD"] = in_lod
            if maxlen is not None:
                ctx.static_info.setdefault(name + "@MAXLEN", maxlen)


def _lower_feed_fetch(ctx, op):
    # Feeds are pre-bound into env by var name; a 'feed' op in a loaded
    # inference program is therefore a name passthrough, as is 'fetch'.
    if op.type == "feed":
        out = ctx.out_name(op, "Out")
        if out is not None and out not in ctx.env:
            raise KeyError("feed target %r was not provided in feed dict" % out)
    else:  # fetch
        src = op.input("X")
        out = ctx.out_name(op, "Out")
        if src and out:
            ctx.env[out] = ctx.get(src[0])


def _fetch_from_env(env, name):
    if name not in env:
        raise KeyError(
            "fetch var %r was not produced by the program; "
            "available: %s..." % (name, sorted(env)[:20]))
    val = env[name]
    if isinstance(val, list):     # LoDTensorArray — stack lazily on fetch
        val = jnp.stack(val)
    return val
