"""ParallelExecutor: SPMD data(+tensor)-parallel program execution.

Reference parity: python/paddle/fluid/parallel_executor.py:25-130 +
framework/parallel_executor.cc:54-203. The reference replicates the graph
per GPU, broadcasts params, splits the feed batch (SplitLoDTensor) and
inserts NCCL all-reduce per gradient. Here: ONE jitted step function with
input shardings — batch feeds sharded on the mesh's ``dp`` axis, state
replicated (or sharded by `parallel.shard` hints for TP) — and XLA GSPMD
derives every collective, overlapped with compute.

The step itself is core's: ``run`` and ``run_steps`` call the inner
Executor's ``_step`` (core/executor.py, whose head says what the skeleton
owns) with an ``_OnMesh``, which holds what is this executor's own — the
sharding hints and the accumulation in the cache key, ``use_bf16_compute``
pinned for the trace, the dp checks on the feeds, ``_to_global`` under the
phase ``pexe.place`` and ``_local_value`` under ``pexe.pull``. Core knows
nothing of meshes: the arrow points from here to there only.
"""

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import (make_mesh, default_mesh, set_default_mesh,
                   spec_to_named_sharding)
from ..core.program import default_main_program
from ..core.scope import global_scope
from ..core.executor import Executor, FeedPlanCache, _Entry
from ..core.lod import LoDTensor
from ..core.places import TPUPlace, CPUPlace


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
        # the inner executor runs every step (Executor._step); what is
        # this executor's own it is handed in an _OnMesh a call
        dev = np.ravel(self.mesh.devices)[0]
        self._exe = Executor(TPUPlace(0) if dev.platform == "tpu"
                             else CPUPlace())
        self._exe._mesh = self.mesh   # lowerings (sp/pp/ep ops) read this
        # feed-plan cache (plans only, no device commit: pexe feeds get
        # mesh shardings downstream) — repeated-shape batches skip the
        # per-call normalization derivation
        self._exe._feed_plans = FeedPlanCache(device_fn=None)
        self._cache = self._exe._cache
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

    def _data_sharding(self, batch_dim=0):
        axis = "dp" if "dp" in self.mesh.axis_names else None
        return NamedSharding(
            self.mesh, PartitionSpec(*[None] * batch_dim + [axis]))

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
        return self._exe._step(_OnMesh(self, None), self._program,
                               self._scope, fetch_list, feed or feed_dict,
                               None, return_numpy, True)

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
        feeds, k = Executor._check_run_steps_args(feeds, k)
        if self._accum_steps > 1:
            raise ValueError(
                "run_steps does not compose with gradient_"
                "accumulation_steps=%d: the megastep scan would nest "
                "the accumulation scan and change the optimizer "
                "cadence. Megastep K already amortizes dispatch; use "
                "one or the other." % self._accum_steps)
        return self._exe._step(_OnMesh(self, k), self._program,
                               self._scope, fetch_list, feeds, k,
                               return_numpy, True)


class _OnMesh(_Entry):
    """One call's entry of a ParallelExecutor into ``Executor._step``:
    the sharding hints and accumulation in the cache key, AMP pinned,
    the dp checks on the feeds, and state and feeds laid out on the
    mesh before the call and fetched values brought to the host after
    it. ``k`` is None for ``run``, a megastep's for ``run_steps``."""

    prefix = "pexe"
    host_ops = False

    def __init__(self, pexe, k):
        from ..amp import amp_enabled
        self.pexe = pexe
        self.devices = pexe.device_count
        self.accum = (pexe._accum_steps, pexe._accum_loss_norm)
        hints = tuple(sorted(
            (n, tuple(v))
            for n, v in pexe._program._sharding_hints.items()))
        self.key_extras = (hints,) + self.accum
        self.amp = pexe._force_bf16 if pexe._force_bf16 is not None \
            else amp_enabled()
        # a megastep's dim 0 is the scan dim: a step's batch is dim 1
        self.batch_dim = 0 if k is None else 1
        self.replicated = set()

    def check_feeds(self, feeds, feed_arrays):
        pexe, mesh, dim = self.pexe, self.pexe.mesh, self.batch_dim
        if pexe._accum_steps > 1:
            pexe._check_accum_weights(feed_arrays)
        # ragged LoD buffers keep a replicated layout (SplitLoDTensor
        # parity; GSPMD re-shards downstream): the derived @LOD/@ACCUM
        # vectors by suffix AND the flat token buffer itself, found by
        # its fed value being a LoDTensor — its leading dim is a
        # data-dependent token total, not a batch dim
        self.replicated.update(
            n for n in feed_arrays
            if n.endswith("@LOD") or n.endswith("@ACCUM_TOKENS"))
        for f in [feeds] if isinstance(feeds, dict) else feeds:
            self.replicated.update(n for n, v in (f or {}).items()
                                   if isinstance(v, LoDTensor))
        dp = mesh.shape["dp"] if "dp" in mesh.axis_names else 1
        for n, v in feed_arrays.items():
            if n not in self.replicated and getattr(v, "ndim", 0) > dim \
                    and v.shape[dim] % dp != 0:
                raise ValueError(
                    "%sfeed %r batch dim %d not divisible by dp=%d "
                    "(SplitLoDTensor parity requires equal chunks)"
                    % ("megastep per-step " if dim else "", n,
                       v.shape[dim], dp))

    def place(self, state, feed_arrays):
        pexe, dim = self.pexe, self.batch_dim
        repl = NamedSharding(pexe.mesh, PartitionSpec())
        data = pexe._data_sharding(dim)
        return (
            {n: pexe._to_global(v, pexe._state_sharding(n))
             for n, v in state.items()},
            {n: pexe._to_global(
                v, repl if n in self.replicated
                or getattr(v, "ndim", 0) <= dim else data)
             for n, v in feed_arrays.items()})

    def pull(self, fetches, fetch_lods, guards):
        local = self.pexe._local_value
        return ([local(v) for v in fetches],
                {n: local(v) for n, v in fetch_lods.items()},
                {n: local(v) for n, v in guards.items()})
