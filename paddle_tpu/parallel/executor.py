"""ParallelExecutor: SPMD data(+tensor)-parallel program execution.

Reference parity: python/paddle/fluid/parallel_executor.py:25-130 +
framework/parallel_executor.cc:54-203. The reference replicates the graph
per GPU, broadcasts params, splits the feed batch (SplitLoDTensor) and
inserts NCCL all-reduce per gradient. Here: ONE jitted step function with
input shardings — batch feeds sharded on the mesh's ``dp`` axis, state
replicated (or sharded by `parallel.shard` hints for TP) — and XLA GSPMD
derives every collective, overlapped with compute.
"""

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import (make_mesh, default_mesh, set_default_mesh,
                   spec_to_named_sharding)
from ..core.program import default_main_program, Variable
from ..core.scope import global_scope
from ..core.executor import (Executor, as_numpy, _feed_signature,
                             _gather_state)
from ..core.lod import LoDTensor
from ..trace import runtime as _trc


class ParallelExecutor:
    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, num_trainers=1, trainer_id=0,
                 mesh=None, scope=None, use_tpu=True, strategy=None,
                 **kwargs):
        # `use_cuda` is accepted as the reference's legacy "use accelerator"
        # flag; device choice here is the mesh's. Anything we can't honor is
        # rejected loudly instead of silently dropped.
        if kwargs:
            raise TypeError(
                "unsupported ParallelExecutor arguments: %r"
                % sorted(kwargs))
        if num_trainers != 1 and jax.process_count() != num_trainers:
            raise ValueError(
                "num_trainers=%d but this process group has %d processes; "
                "multi-trainer mode requires jax.distributed.initialize() "
                "across exactly num_trainers hosts"
                % (num_trainers, jax.process_count()))
        self.num_trainers = num_trainers
        self.trainer_id = trainer_id if num_trainers > 1 \
            else jax.process_index()
        self.mesh = mesh or default_mesh() or make_mesh()
        if default_mesh() is None:
            set_default_mesh(self.mesh)
        self._program = main_program or default_main_program()
        if share_vars_from is not None:
            # reference semantics (parallel_executor.py share_vars_from):
            # reuse the parameter scope of an existing executor (e.g. share
            # train params with a test ParallelExecutor).
            scope = share_vars_from._scope
        self._scope = scope or global_scope()
        self._exe = Executor.__new__(Executor)
        from ..core.places import TPUPlace, CPUPlace
        dev = np.ravel(self.mesh.devices)[0]
        self._exe.place = (TPUPlace(0) if dev.platform == "tpu"
                           else CPUPlace())
        self._exe._cache = {}
        self._exe._rng_counter = 0
        self._exe._mesh = self.mesh   # lowerings (sp/pp/ep ops) read this
        self._cache = {}
        # feed-plan cache (plans only, no device commit: pexe feeds get
        # mesh shardings downstream) — repeated-shape batches skip the
        # per-call normalization derivation
        from ..core.executor import FeedPlanCache
        self._feed_plans = FeedPlanCache(device_fn=None)
        self._loss_name = loss_name
        # DistributedStrategy execution knobs (mesh axes are consumed by
        # the model builders; these two belong to the executor)
        self._accum_steps = max(
            1, int(getattr(strategy, "gradient_accumulation_steps", 1)))
        # How the loss is normalized, for ragged-LoD accumulation
        # weighting: None (reject ragged-unequal splits), "sequence",
        # "token", or "token:<feed_name>" — see
        # Executor._lower_with_grad_accum.
        self._accum_loss_norm = getattr(
            strategy, "gradient_accumulation_loss_norm", None)
        if self._accum_loss_norm is not None and not (
                self._accum_loss_norm == "sequence"
                or self._accum_loss_norm == "token"
                or self._accum_loss_norm.startswith("token:")):
            raise ValueError(
                "gradient_accumulation_loss_norm must be 'sequence', "
                "'token', or 'token:<feed_name>'; got %r"
                % (self._accum_loss_norm,))
        # use_bf16_compute=True pins AMP on for THIS executor's traces
        # (restored after each build — the global flag is not leaked);
        # False (the default) leaves the ambient AMP setting alone
        self._force_bf16 = bool(getattr(strategy, "use_bf16_compute",
                                        False)) or None

    @property
    def device_count(self):
        return int(np.prod(self.mesh.devices.shape))

    def _data_sharding(self):
        axes = [a for a in ("dp",) if a in self.mesh.axis_names]
        return NamedSharding(self.mesh,
                             PartitionSpec(axes[0] if axes else None))

    def _state_sharding(self, name):
        spec = self._program._sharding_hints.get(name)
        return spec_to_named_sharding(self.mesh, spec)

    def _check_accum_weights(self, feed_arrays):
        """Host-side guard for ragged gradient accumulation (concrete
        per-microbatch token totals from _normalize_feeds).

        Equal-weight averaging of microbatch losses is only exact when
        every microbatch carries equal weight in the full-batch loss;
        with unequal token totals that holds for per-sequence-mean
        losses but silently mis-scales token-normalized ones. So:
        unequal totals require an explicit loss_norm, and 'token' with
        several disagreeing LoD feeds requires naming the one that
        normalizes the loss."""
        _TOK = "@ACCUM_TOKENS"
        toks = {n[:-len(_TOK)]: np.asarray(v)
                for n, v in feed_arrays.items() if n.endswith(_TOK)}
        norm = self._accum_loss_norm
        if norm is None:
            ragged = sorted(n for n, t in toks.items()
                            if not np.all(t == t[0]))
            if ragged:
                raise ValueError(
                    "gradient accumulation with ragged LoD feeds: "
                    "microbatch token totals are unequal for %s. Equal "
                    "microbatch weighting is only exact for per-"
                    "sequence-mean losses. Set DistributedStrategy."
                    "gradient_accumulation_loss_norm='sequence' (loss "
                    "is a mean over sequences) or 'token' (loss is a "
                    "mean over tokens; microbatches are weighted by "
                    "their true token counts)." % ragged)
        elif norm == "token" and len(toks) > 1:
            rep = {tuple(t.tolist()) for t in toks.values()}
            if len(rep) > 1:
                raise ValueError(
                    "gradient_accumulation_loss_norm='token' is "
                    "ambiguous: LoD feeds %s have different microbatch "
                    "token totals. Name the feed the loss normalizes "
                    "over: 'token:<feed_name>'." % sorted(toks))

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        # the step's root, numbered (see core Executor.run)
        with _trc.span("pexe.step", step=self._exe._rng_counter):
            return self._run_impl(fetch_list, feed, feed_dict,
                                  return_numpy)

    @staticmethod
    def _local_value(v):
        """Host view of one fetched value. A replicated output's
        sharding spans remote devices; its local shard IS the value. A
        dp-SHARDED fetch has no local full value: with FLAGS
        gather_sharded_fetches on, all-gather it so every process
        fetches the merged global array (the reference merged fetched
        tensors across devices, parallel_executor.cc:190-197); default
        stays the loud refusal rather than handing back 1/N of the
        batch."""
        from ..flags import get_flag
        if jax.process_count() > 1 and isinstance(v, jax.Array) \
                and not v.is_fully_addressable:
            if not v.sharding.is_fully_replicated:
                if get_flag("gather_sharded_fetches"):
                    from jax.experimental import multihost_utils
                    return np.asarray(
                        multihost_utils.process_allgather(
                            v, tiled=True))
                raise NotImplementedError(
                    "fetching a cross-process SHARDED value (spec %s) "
                    "is not supported — fetch replicated values "
                    "(losses/metrics), gather in-graph first, or set "
                    "PADDLE_TPU_GATHER_SHARDED_FETCHES=1 to all-"
                    "gather at fetch time" % (v.sharding.spec,))
            return np.asarray(list(v.addressable_shards)[0].data)
        return v

    @staticmethod
    def _to_global(v, sh):
        """Place one host/device value per its target sharding.
        Steady-state device outputs pass through (committed GSPMD
        layouts stay; a multi-process array cannot be resharded
        host-side anyway); an addressable but mis-placed array (e.g. a
        single-device startup output vs a tp sharding hint) is laid
        out per the hint. On a multi-process (multi-host) mesh, host
        values become GLOBAL arrays via make_array_from_callback —
        every process passes the same full array (the reference's
        same-data-every-trainer contract, BCastParamsToGPUs parity)
        and keeps only its addressable shards."""
        multiproc = jax.process_count() > 1
        if isinstance(v, jax.Array):
            if not v.is_fully_addressable or v.sharding == sh:
                return v
            if multiproc:
                v = np.asarray(v)
            else:
                return jax.device_put(v, sh)
        if multiproc:
            arr = np.asarray(v)
            return jax.make_array_from_callback(
                arr.shape, sh, lambda idx, _a=arr: _a[idx])
        return jax.device_put(v, sh)

    # -- megastep execution (ISSUE 7) ----------------------------------
    def run_steps(self, fetch_list, feeds=None, return_numpy=True,
                  k=None):
        """K logical steps in ONE sharded device dispatch — the
        ParallelExecutor twin of ``Executor.run_steps`` (same feeds
        contract: a list of K per-step feed dicts, or one pre-stacked
        ``[k, ...]`` dict plus ``k``). The scanned step body is the
        same GSPMD-sharded program ``run()`` compiles; batch feeds
        shard on the mesh's ``dp`` axis along dim 1 (dim 0 is the scan
        dim). Returns K per-step fetch lists. Async double buffering
        rides the same ``megastep_inflight`` window as the core
        executor when ``return_numpy=False``."""
        from ..core.executor import Executor as _Exe
        feeds, k = _Exe._check_run_steps_args(feeds, k)
        with _trc.span("pexe.step", step=self._exe._rng_counter, k=k):
            return self._run_steps_impl(fetch_list, feeds, k,
                                        return_numpy)

    def _run_steps_impl(self, fetch_list, feeds, k, return_numpy):
        import time as _time
        from ..core.executor import (Executor as _Exe, _flag_on,
                                     _stack_step_feeds,
                                     _stage_prestacked_feeds,
                                     _step_costs_safe)
        if self._accum_steps > 1:
            raise ValueError(
                "run_steps does not compose with gradient_"
                "accumulation_steps=%d: the megastep scan would nest "
                "the accumulation scan and change the optimizer "
                "cadence. Megastep K already amortizes dispatch; use "
                "one or the other." % self._accum_steps)
        program = self._program
        scope = self._scope
        step = self._exe._rng_counter       # phases as in _run_impl
        with _trc.phase("pexe.feed", step=step):
            fetch_names = tuple(
                f.name if isinstance(f, Variable) else str(f)
                for f in (fetch_list or []))
            if isinstance(feeds, dict):
                feeds_k, static_info, sig = _stage_prestacked_feeds(
                    feeds, k)
            else:
                feeds_k, static_info, sig = _stack_step_feeds(
                    feeds, plan_cache=self._feed_plans)

            dp = 1
            if "dp" in self.mesh.axis_names:
                dp = self.mesh.shape["dp"]
            # ragged LoD buffers stay replicated (SplitLoDTensor parity,
            # same classification as _run_impl): the derived
            # @LOD/@ACCUM vectors by suffix AND the flat token buffer
            # itself, found by its original per-step feed value being a
            # LoDTensor — its dim 1 is a data-dependent token total,
            # not a batch dim
            lod_keys = {n for n in feeds_k if n.endswith("@LOD")
                        or n.endswith("@ACCUM_TOKENS")}
            if not isinstance(feeds, dict):
                lod_keys |= {n for f in feeds
                             for n, v in (f or {}).items()
                             if isinstance(v, LoDTensor)}
            for n, v in feeds_k.items():
                if n not in lod_keys and getattr(v, "ndim", 0) >= 2 \
                        and v.shape[1] % dp != 0:
                    raise ValueError(
                        "megastep feed %r per-step batch dim %d not "
                        "divisible by dp=%d" % (n, v.shape[1], dp))

        with _trc.phase("pexe.state", step=step):
            state, state_keys = _gather_state(program, scope)
        hints = tuple(sorted(
            (n, tuple(v)) for n, v in program._sharding_hints.items()))
        from ..amp import amp_enabled, enable_amp
        from ..flags import get_flag
        check_nan = _flag_on("PADDLE_TPU_CHECK_NAN_INF")
        use_amp = self._force_bf16 if self._force_bf16 is not None \
            else amp_enabled()
        key = ("megastep", k, program, program._version, sig,
               fetch_names, state_keys, hints, check_nan, use_amp,
               get_flag("fuse_conv_bn"),
               tuple(sorted(static_info.items())))
        from .. import monitor as _mon
        mon_on = _mon.enabled()
        entry = self._cache.get(key)
        fresh = entry is None
        if not fresh and mon_on:
            _mon.on_cache_hit()
        if fresh:
            with _trc.phase("pexe.build", step=step):
                mega = self._exe._build_megastep(
                    program, tuple(sorted(feeds_k)), fetch_names,
                    state_keys, static_info, check_nan, k)

                def fn(state, feeds, keys, _fn=mega, _amp=use_amp):
                    # pin AMP for the trace, restore after (see run())
                    prev = amp_enabled()
                    enable_amp(_amp)
                    try:
                        return _fn(state, feeds, keys)
                    finally:
                        enable_amp(prev)

                entry = jax.jit(fn, donate_argnums=(0,))
                self._cache[key] = entry
                if mon_on:
                    import jax.numpy as _jnp
                    rng0 = jax.vmap(jax.random.key)(
                        _jnp.zeros((k,), _jnp.uint32))
                    _mon.on_compile(
                        program, key, key[4],
                        cost_fn=lambda: _step_costs_safe(
                            fn, dict(state), dict(feeds_k), rng0),
                        executor="pexe",
                        tokens=_mon.tokens_in_feeds(feeds_k),
                        devices=self.device_count)

        base = program.random_seed * 1000003 + self._exe._rng_counter
        self._exe._rng_counter += k
        import jax.numpy as jnp
        keys = jax.vmap(jax.random.key)(jnp.asarray(
            [np.uint32(base + i) for i in range(k)]))

        repl = NamedSharding(self.mesh, PartitionSpec())
        dp_axis = None
        if "dp" in self.mesh.axis_names:
            dp_axis = "dp"
        # dim 0 is the scan dim: shard each step's batch (dim 1) on dp
        def feed_sharding(n, v):
            if n in lod_keys or getattr(v, "ndim", 0) < 2 \
                    or dp_axis is None:
                return repl
            return NamedSharding(self.mesh,
                                 PartitionSpec(None, dp_axis))

        with _trc.phase("pexe.place", step=step):
            state_dev = {n: self._to_global(v, self._state_sharding(n))
                         for n, v in state.items()}
            feeds_dev = {n: self._to_global(v, feed_sharding(n, v))
                         for n, v in feeds_k.items()}

        window = max(1, int(get_flag("megastep_inflight")))
        inflight = self.__dict__.setdefault("_inflight", [])
        while len(inflight) >= window:
            jax.block_until_ready(inflight.pop(0))

        t0 = _time.perf_counter() if mon_on else 0.0
        if mon_on:
            timer = _mon.step_timer(self)
            do_sync = timer.begin(t0)
        with _trc.phase("pexe.build" if fresh else "pexe.dispatch",
                        step=step):
            fetches_k, new_state, guards_k, lods_k = entry(
                state_dev, feeds_dev, keys)
        if mon_on:
            fb = _mon.feed_nbytes(feeds_k)
            tk = _mon.tokens_in_feeds(feeds_k)
            if do_sync:
                jax.block_until_ready(fetches_k)
                _mon.on_megastep(
                    key, timer.end_synced(_time.perf_counter(), t0), k,
                    feed_bytes=fb, tokens=tk, executor="pexe")
            else:
                _mon.on_megastep(key, _time.perf_counter() - t0, k,
                                 feed_bytes=fb, tokens=tk,
                                 executor="pexe", synced=False)

        with _trc.phase("pexe.pull", step=step):
            _trc.fetched(fetches_k)
            fetches_k = [self._local_value(v) for v in fetches_k]
            lods_k = {n: self._local_value(v) for n, v in lods_k.items()}
            guards_k = {n: self._local_value(v)
                        for n, v in guards_k.items()}
        with _trc.phase("pexe.commit", step=step):
            for n, v in new_state.items():
                scope.set(n, v)
            if check_nan:
                _Exe._check_guards_steps(guards_k, k)
            out = _Exe._split_step_fetches(fetch_names, fetches_k,
                                           lods_k, k, return_numpy)
            if check_nan:
                for fi in out:
                    _Exe._check_nan_inf(fetch_names, fi)
            if not return_numpy:
                inflight.append(fetches_k)
            return out

    def _run_impl(self, fetch_list, feed=None, feed_dict=None,
                  return_numpy=True):
        program = self._program
        scope = self._scope
        # the phases of a step, as core Executor._run_impl has them
        # (pexe.feed / state / build / dispatch / commit), plus
        # pexe.place round what lays state and feeds out on the mesh and
        # pexe.pull round what brings fetched values to the host
        step = self._exe._rng_counter
        with _trc.phase("pexe.feed", step=step):
            feed = dict(feed or feed_dict or {})
            fetch_names = tuple(
                f.name if isinstance(f, Variable) else str(f)
                for f in (fetch_list or []))

            dp = 1
            if "dp" in self.mesh.axis_names:
                dp = self.mesh.shape["dp"]
            # ragged token buffers keep a replicated layout (their row
            # count is data-dependent); GSPMD re-shards downstream.
            # _normalize_feeds also buckets the flat LoD totals so
            # signatures stay cache-stable.
            from ..core.executor import _normalize_feeds
            feed_arrays, static_info = _normalize_feeds(
                feed, accum_steps=self._accum_steps,
                plan_cache=self._feed_plans)
            if self._accum_steps > 1:
                self._check_accum_weights(feed_arrays)
            lod_keys = {k for k in feed_arrays if k.endswith("@LOD")
                        or k.endswith("@ACCUM_TOKENS")}
            lod_keys |= {k for k, v in feed.items()
                         if isinstance(v, LoDTensor)}
            for k, v in feed_arrays.items():
                if k in lod_keys:
                    continue
                if v.ndim >= 1 and v.shape[0] % dp != 0:
                    raise ValueError(
                        "feed %r batch dim %d not divisible by dp=%d "
                        "(SplitLoDTensor parity requires equal chunks)"
                        % (k, v.shape[0], dp))

        with _trc.phase("pexe.state", step=step):
            state, state_keys = _gather_state(program, scope)

        hints = tuple(sorted(
            (k, tuple(v)) for k, v in program._sharding_hints.items()))
        from ..core.executor import _flag_on
        from ..amp import amp_enabled, enable_amp
        check_nan = _flag_on("PADDLE_TPU_CHECK_NAN_INF")
        use_amp = self._force_bf16 if self._force_bf16 is not None \
            else amp_enabled()
        from ..flags import get_flag
        key = (program, program._version, _feed_signature(feed_arrays),
               fetch_names, state_keys, hints, check_nan, use_amp,
               self._accum_steps, self._accum_loss_norm,
               get_flag("fuse_conv_bn"),
               tuple(sorted(static_info.items())))
        from .. import monitor as _mon
        mon_on = _mon.enabled()
        entry = self._cache.get(key)
        repl = NamedSharding(self.mesh, PartitionSpec())
        fresh = entry is None
        if not fresh and mon_on:
            _mon.on_cache_hit()
        if fresh:
            with _trc.phase("pexe.build", step=step):
                built = self._exe._build(
                    program, tuple(sorted(feed_arrays)), fetch_names,
                    state_keys, static_info=static_info,
                    check_nan=check_nan, accum_steps=self._accum_steps,
                    accum_loss_norm=self._accum_loss_norm)
                if mon_on:
                    from ..core.executor import _step_costs_safe
                    rng0 = jax.random.key(0)
                    _mon.on_compile(
                        program, key, key[2],
                        cost_fn=lambda: _step_costs_safe(
                            built, dict(state), dict(feed_arrays), rng0),
                        executor="pexe",
                        tokens=_mon.tokens_in_feeds(feed_arrays),
                        devices=self.device_count)

                def fn(state, feeds, key, _fn=built, _amp=use_amp):
                    # lowering reads the AMP flag at TRACE time; pin it
                    # for the trace and restore the ambient value (no
                    # global leak)
                    prev = amp_enabled()
                    enable_amp(_amp)
                    try:
                        return _fn(state, feeds, key)
                    finally:
                        enable_amp(prev)

                # Shardings are established by COMMITTING the inputs
                # (the device_put/make_array calls below), not by
                # in_shardings: constraining the jit would force a
                # reshard of step-2 state (whose committed sharding is
                # whatever step 1 produced), which multi-process arrays
                # cannot do. Committed-input propagation is the standard
                # JAX training-loop pattern and keeps single- and
                # multi-host behavior identical.
                entry = jax.jit(fn, donate_argnums=(0,))
                self._cache[key] = entry

        rng_key = jax.random.key(
            np.uint32(program.random_seed * 1000003
                      + self._exe._rng_counter))
        self._exe._rng_counter += 1

        # place state per its sharding once; jit keeps the placement
        # on subsequent steps (see _to_global)
        with _trc.phase("pexe.place", step=step):
            state_dev = {n: self._to_global(v, self._state_sharding(n))
                         for n, v in state.items()}
            data_sh = self._data_sharding()
            feeds_dev = {k: self._to_global(v, repl if k in lod_keys
                                            else data_sh)
                         for k, v in feed_arrays.items()}

        import time as _time
        t0 = _time.perf_counter() if mon_on else 0.0
        if mon_on:
            # windowed sync (monitor_sync_every) — shared StepTimer,
            # same windowing as core Executor.run
            timer = _mon.step_timer(self)
            do_sync = timer.begin(t0)
        with _trc.phase("pexe.build" if fresh else "pexe.dispatch",
                        step=step):
            fetches, new_state, guards, fetch_lods = entry(
                state_dev, feeds_dev, rng_key)
        if mon_on:
            fb = _mon.feed_nbytes(feed_arrays)
            tk = _mon.tokens_in_feeds(feed_arrays)
            if do_sync:
                jax.block_until_ready(fetches)   # honest step latency
                _mon.on_step(key,
                             timer.end_synced(_time.perf_counter(), t0),
                             feed_bytes=fb, tokens=tk, executor="pexe")
            else:
                _mon.on_step(key, _time.perf_counter() - t0,
                             feed_bytes=fb, tokens=tk, executor="pexe",
                             synced=False)

        with _trc.phase("pexe.pull", step=step):
            _trc.fetched(fetches)
            fetches = [self._local_value(v) for v in fetches]
            fetch_lods = {k: self._local_value(v)
                          for k, v in fetch_lods.items()}
            guards = {k: self._local_value(v) for k, v in guards.items()}
            fetches = Executor._trim_fetches(fetch_names, fetches,
                                             fetch_lods)
            if return_numpy:
                fetches = [as_numpy(v) for v in fetches]
        with _trc.phase("pexe.commit", step=step):
            for n, v in new_state.items():
                scope.set(n, v)
            if check_nan:
                Executor._check_guards(guards)
                Executor._check_nan_inf(fetch_names, fetches)
            return list(fetches)
