"""Device-prefetching data loader.

Reference parity: operators/reader/create_double_buffer_reader_op.cc:34,168
— a prefetch thread keeping a 2-slot device-side buffer so host→device
transfer overlaps compute: naive per-step feeding puts the host→device
copy on the step's critical path.

The prefetch path rides the core executor's feed-plan cache
(core/executor.FeedPlanCache): repeated same-shape batches skip the
per-batch normalization derivation, and feeds the caller froze
(``arr.flags.writeable = False`` — constant masks, position ids) are
committed to a device buffer ONCE and reused zero-copy every batch
instead of re-uploading.

Megastep staging (ISSUE 7): ``megabatches(k)`` generalizes the 2-slot
prefetch into a ``[k, ...]`` device-resident staging stack — k source
batches stacked on the worker thread into the layout
``Executor.run_steps(feeds=stack, k=k)`` indexes in-graph, so the host
feed of megastep N+1 overlaps device compute of megastep N.
"""

import queue
import threading

import numpy as np
import jax

__all__ = ["DeviceLoader"]


class DeviceLoader:
    """Wrap an iterable of feed dicts; yields dicts of device-resident
    jax.Arrays, transferring `capacity` batches ahead on a worker thread.

    ``plan_cache=None`` (default) builds a private feed-plan cache so
    repeated same-shape batches skip re-normalization; pass an existing
    core/executor FeedPlanCache to share plans (e.g. the consuming
    Executor's ``_feed_plans``), or ``plan_cache=False`` to disable.

    LoD feeds ride through HOST-SIDE, untouched: their flat/bucketed
    normalization carries trace-time static_info only the consuming
    executor's own pass can deliver, so the loader neither pre-splits
    nor uploads them (uploading ``np.asarray(lod_tensor)`` would
    silently strip the LoD — the pre-ISSUE-7 behavior). A batch mixing
    dense and LoD feeds still prefetches its dense values, and (since
    ISSUE 12) the dense subset rides the plan cache too — only the LoD
    values take the executor-side normalization fallback."""

    def __init__(self, feed_iterable, capacity=2, device=None,
                 sharding=None, plan_cache=None):
        self._src = feed_iterable
        self._capacity = max(1, capacity)
        self._device = device
        self._sharding = sharding
        if plan_cache is None:
            from ..core.executor import FeedPlanCache
            # commit only when placement is a single device the cache
            # can reproduce; sharded puts stay on the loader's path
            dev_fn = (lambda: self._resolve_device()) \
                if sharding is None else None
            plan_cache = FeedPlanCache(device_fn=dev_fn)
        self._plans = plan_cache or None

    def _resolve_device(self):
        """The device committed buffers land on — must agree with what
        a bare device_put would pick, or one batch could mix devices
        (jax_default_device is process-wide and a caller may have
        set it)."""
        if self._device is not None:
            return self._device
        return jax.config.jax_default_device or jax.local_devices()[0]

    def _put(self, value):
        # explicit placement always re-puts (device_put is a no-op for
        # a value already living there), matching the pre-plan-cache
        # contract that yielded arrays honor sharding=/device=
        if self._sharding is not None:
            return jax.device_put(value, self._sharding)
        if self._device is not None:
            return jax.device_put(value, self._device)
        if isinstance(value, jax.Array):
            return value            # committed / already resident
        return jax.device_put(value)

    def _normalize(self, feed):
        """Plan-cached dense normalization on the worker thread. LoD
        feeds pass through untouched — their flat/bucketed form carries
        trace-time static_info only the executor's own normalization
        pass can deliver, so pre-splitting them here would change what
        the compiled step sees.

        A batch MIXING dense and LoD feeds (the shape a recsys scoring
        pipeline produces: ragged sparse-ID lists next to dense
        features) previously bypassed the plan cache WHOLESALE — every
        dense value re-derived its normalization per batch. Now the
        dense subset rides its own cached plan (keyed by the subset's
        signature) and only the LoD values take the documented
        executor-side fallback."""
        if self._plans is None:
            return feed
        from ..core.lod import LoDTensor
        lod = {k: v for k, v in feed.items()
               if isinstance(v, LoDTensor)}
        if not lod:
            from ..core.executor import _normalize_feeds
            arrays, _ = _normalize_feeds(feed, plan_cache=self._plans)
            return arrays
        dense = {k: v for k, v in feed.items() if k not in lod}
        if not dense:
            return feed
        from ..core.executor import _normalize_feeds
        arrays, _ = _normalize_feeds(dense, plan_cache=self._plans)
        out = dict(arrays)
        out.update(lod)
        return out

    def _stage(self, feed):
        """One prefetched batch → device (dense values) / host
        pass-through (LoD values — see the class docstring)."""
        from ..core.lod import LoDTensor
        out = {}
        for k, v in feed.items():
            if isinstance(v, LoDTensor):
                out[k] = v
            elif isinstance(v, jax.Array):
                out[k] = self._put(v)
            else:
                out[k] = self._put(np.asarray(v))
        return out

    def __iter__(self):
        q = queue.Queue(maxsize=self._capacity)
        stop = object()
        err = []

        def worker():
            try:
                for feed in self._src:
                    q.put(self._stage(self._normalize(feed)))
            except BaseException as e:   # propagate to consumer
                err.append(e)
            finally:
                q.put(stop)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is stop:
                break
            yield item
        if err:
            raise err[0]

    def megabatches(self, k):
        """Iterate ``[k, ...]`` megastep staging stacks: k consecutive
        source batches are normalized, stacked on the WORKER thread and
        uploaded as one device-resident dict — exactly the pre-stacked
        layout ``Executor.run_steps(feeds=stack, k=k)`` (and the
        ParallelExecutor twin) index in-graph, so staging megastep N+1
        overlaps device compute of megastep N. A trailing group
        shorter than k is yielded at its true length (read k from the
        leading dim).

        LoD feeds cannot ride this path: their per-step normalization
        produces trace-time static_info (@MAXLEN, bucketing) only the
        executor's host path can derive, so a LoD batch raises a clear
        error here instead of a shape mismatch inside the scan — feed
        LoD work to ``run_steps`` as a LIST of per-step feed dicts
        instead (the documented host fallback)."""
        k = int(k)
        if k < 1:
            raise ValueError("megabatches needs k >= 1, got %d" % k)
        from ..core.lod import LoDTensor

        def stacked():
            group = []
            for feed in self._src:
                bad = sorted(n for n, v in feed.items()
                             if isinstance(v, LoDTensor))
                if bad:
                    raise ValueError(
                        "LoD feed(s) %s cannot ride the [k, ...] "
                        "megastep staging stack (their normalization "
                        "needs the executor's trace-time static_info); "
                        "pass run_steps a LIST of per-step feed dicts "
                        "instead" % bad)
                group.append(self._normalize(feed))
                if len(group) == k:
                    yield self._stack(group)
                    group = []
            if group:
                yield self._stack(group)

        for staged in DeviceLoader(stacked(), capacity=self._capacity,
                                   device=self._device,
                                   sharding=self._stack_sharding(),
                                   plan_cache=False):
            yield staged

    def _stack_sharding(self):
        """The loader's per-batch sharding spec remapped to the
        ``[k, ...]`` stack layout: dim 0 is the scan dim (never
        sharded), every batch dim shifts right by one — so a loader
        built with ``P('dp')`` stages stacks as ``P(None, 'dp')``,
        exactly what ``ParallelExecutor.run_steps`` expects. Passing
        the per-batch spec through unchanged would shard the SCAN dim
        (crashing when k is not divisible by the mesh axis, silently
        mis-laying the stack when it is)."""
        s = self._sharding
        if s is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        if isinstance(s, NamedSharding):
            return NamedSharding(s.mesh, PartitionSpec(None, *s.spec))
        raise ValueError(
            "megabatches cannot remap sharding type %s to the "
            "[k, ...] stack layout — pass a NamedSharding (its spec "
            "gains a leading None for the scan dim) or build the "
            "loader without sharding=" % type(s).__name__)

    @staticmethod
    def _stack(group):
        names = sorted(group[0])
        for i, g in enumerate(group[1:], 1):
            if sorted(g) != names:
                raise ValueError(
                    "megabatch group mixes feed names: batch %d has %s,"
                    " batch 0 has %s" % (i, sorted(g), names))
        return {n: np.stack([np.asarray(g[n]) for g in group])
                for n in names}


def repeat_feed(feed, n):
    """Iterator yielding the same feed dict n times (benchmark helper)."""
    for _ in range(n):
        yield feed
