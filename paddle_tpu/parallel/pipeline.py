"""Pipeline parallelism over the ``pp`` mesh axis (GPipe + interleaved).

Beyond the 2018 reference (SURVEY.md §2.7: PP absent; the closest legacy
analog is ParallelNeuralNetwork's static layer placement). TPU-native
design: stage parameters are STACKED on a leading [S, ...] axis sharded on
``pp`` — every device runs the same stage function on its own parameter
shard, and activations ride the ICI ring via ``ppermute``. One jitted
computation, S + M - 1 ticks for M microbatches (the classic GPipe bubble),
differentiable end-to-end (grads flow through ppermute).

Schedules:
  * ``gpipe`` — all M microbatches stream through the S stages;
    bubble fraction (S-1)/(S+M-1) per direction. Reverse-mode AD turns
    the tick loop into the mirrored backward pipeline, so the memory
    profile already matches 1F1B-with-flush (PipeDream-flush): both
    schedules have the SAME bubble; 1F1B's classic win is activation
    memory, which here is had with ``recompute`` on the stage body.
  * ``gpipe_interleaved`` — Megatron-style virtual stages: each device
    holds V non-contiguous layer CHUNKS (device d owns global chunks
    {d, d+S, ...}), a microbatch makes V laps around the ring, and the
    pipeline fill shrinks to (S-1) CHUNK times — bubble cut by V:
    time = M·t_stage + (S-1)·t_stage/V  vs  (M+S-1)·t_stage.
    This is the schedule that beats GPipe at small M (the interleaved
    1F1B regime); it requires M <= S so at most one microbatch is in
    flight per device per tick (the single-register SPMD carry).

Composition with tensor parallelism: ``param_specs`` lets the stacked
params carry extra mesh axes (e.g. Megatron col/row sharding on ``tp``);
the stage_fn then runs INSIDE shard_map over both axes and issues its own
``lax.psum`` over tp — see ops/parallel_ops._decoder_layer_apply_tp.

Output handling: only the LAST stage produces real outputs, so the result
leaves the shard_map with its leading axis sharded on ``pp`` and the
caller slices stage S-1 — a single sliced transfer sized like the output,
instead of an S-redundant psum of the whole buffer. Heterogeneous stages
(per-stage parameter SHAPES) are supported by passing a list of per-stage
param pytrees: those are replicated to every device and selected by
``lax.switch`` on the stage index — functional, at the memory cost of
holding all stages' params per device; the stacked form is the scalable
path.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def _run_ticks(apply, xs, s_idx, n_stage, axis_name, with_aux=False):
    """The GPipe tick loop for one shard. apply: x -> stage output for
    THIS stage (-> (out, aux) when with_aux). xs [M, mb, ...]
    microbatches (replicated or dp-sharded). Returns [1, M, mb, ...]
    final-stage outputs (zeros on other shards) — plus, with_aux, this
    stage's aux sum over LIVE ticks / M (bubble ticks run on garbage
    and must not pollute the aux loss). The buffer is allocated per
    shard (SPMD executes one program), but only the last stage ever
    writes it."""
    m = xs.shape[0]

    def tick(t, carry):
        state_in, outputs, aux_sum = carry
        mb_idx = jnp.clip(t, 0, m - 1)
        inject = jnp.where(t < m, xs[mb_idx], jnp.zeros_like(xs[0]))
        inp = jnp.where(s_idx == 0, inject, state_in)
        if with_aux:
            out, aux = apply(inp)
            # stage s runs microbatch t - s at tick t
            live = jnp.logical_and(t - s_idx >= 0, t - s_idx < m)
            aux_sum = aux_sum + jnp.where(live, aux, 0.0)
        else:
            out = apply(inp)
        out_mb = t - (n_stage - 1)
        write = jnp.logical_and(s_idx == n_stage - 1, out_mb >= 0)
        upd = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(write, out, outputs[jnp.clip(out_mb, 0, m - 1)]),
            jnp.clip(out_mb, 0, m - 1), 0)
        outputs = jnp.where(write, upd, outputs)
        state_next = lax.ppermute(
            out, axis_name,
            [(j, (j + 1) % n_stage) for j in range(n_stage)])
        return state_next, outputs, aux_sum

    state0 = jnp.zeros_like(xs[0])
    outputs0 = jnp.zeros_like(xs)
    # the aux accumulator carries as shape [1], NOT a scalar: jax
    # 0.4.37's shard_map partial-eval only promotes NON-forwarded scalar
    # residuals, so a scalar loop-carry tangent crossing the shard_map
    # boundary gets paired with a rank-referencing spec in the transpose
    # and raises _SpecError under value_and_grad (the pp x ep failure)
    _, outputs, aux_sum = lax.fori_loop(
        0, n_stage + m - 1, tick,
        (state0, outputs0, jnp.zeros((1,), jnp.float32)))
    # leading singleton axis: the caller's out_spec shards it on pp, so
    # the global result is [S, M, mb, ...] and slicing [-1] pulls ONLY
    # the last stage's buffer — no collective inside the loop or after
    if with_aux:
        return outputs[None], aux_sum / m
    return outputs[None]


def _run_ticks_interleaved(apply, xs, s_idx, n_stage, axis_name,
                           n_chunks, with_aux=False):
    """Virtual-stage tick loop for one shard. apply: (chunk_idx, x) ->
    chunk output for THIS device's local chunk `chunk_idx`. Microbatch i
    is injected at tick i and makes V laps: at hop h (one hop per tick)
    it sits on device h % S running global chunk h. With M <= S no two
    microbatches ever share a device, so the carry stays one state
    register. Total ticks: M - 1 + V*S."""
    m = xs.shape[0]
    total = n_chunks * n_stage

    def tick(t, carry):
        state_in, outputs, aux_sum = carry
        # the unique hop index on THIS device at tick t: the largest
        # h <= t with h ≡ s_idx (mod S); the microbatch holding it is
        # mb = t - h (live iff mb < M and h < total)
        h = t - ((t - s_idx) % n_stage)
        mb = t - h
        # h >= 0 matters: during pipeline FILL a device's congruent hop
        # is negative (idle tick) — without the bound the aux of the
        # garbage apply() would be counted (output writes were always
        # safe: they additionally require h == total-1)
        live = (h >= 0) & (h < total) & (mb < m)
        inject = jnp.where(h == 0, xs[jnp.clip(mb, 0, m - 1)], state_in)
        chunk = jnp.clip(h // n_stage, 0, n_chunks - 1)
        if with_aux:
            out, aux = apply(chunk, inject)
            aux_sum = aux_sum + jnp.where(live, aux, 0.0)
        else:
            out = apply(chunk, inject)
        write = jnp.logical_and(live, h == total - 1)
        mb_c = jnp.clip(mb, 0, m - 1)
        upd = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, out, outputs[mb_c]), mb_c, 0)
        outputs = jnp.where(write, upd, outputs)
        state_next = lax.ppermute(
            out, axis_name,
            [(j, (j + 1) % n_stage) for j in range(n_stage)])
        return state_next, outputs, aux_sum

    state0 = jnp.zeros_like(xs[0])
    outputs0 = jnp.zeros_like(xs)
    # [1]-shaped aux carry — see _run_ticks for the shard_map
    # scalar-residual rationale
    _, outputs, aux_sum = lax.fori_loop(
        0, m - 1 + total, tick,
        (state0, outputs0, jnp.zeros((1,), jnp.float32)))
    if with_aux:
        return outputs[None], aux_sum / m
    return outputs[None]


def _aux_reduce(aux, axis_name, aux_mean_axes):
    """Stage aux sums add over pp (total over layers); members along the
    token-splitting axes (dp/ep/sp) hold DIFFERENT token groups, so their
    auxes average — matching a dense fallback that means over groups.
    (tp members compute identical values; the pmean is a no-op there.)"""
    aux = lax.psum(aux, axis_name)
    for ax in aux_mean_axes or ():
        aux = lax.pmean(aux, ax)
    return aux


def _gpipe_sharded(params, xs, stage_fn, axis_name, with_aux=False,
                   aux_mean_axes=()):
    """Stacked (homogeneous) path: params leaves arrive [1, ...] — this
    shard's slice of the [S, ...] stack."""
    s_idx = lax.axis_index(axis_name)
    n_stage = lax.psum(1, axis_name)
    local_params = jax.tree_util.tree_map(lambda p: p[0], params)
    res = _run_ticks(lambda x: stage_fn(local_params, x), xs, s_idx,
                     n_stage, axis_name, with_aux=with_aux)
    if with_aux:
        out, aux = res
        return out, _aux_reduce(aux, axis_name, aux_mean_axes)
    return res


def _interleaved_sharded(params, xs, stage_fn, axis_name, n_chunks,
                         with_aux=False, aux_mean_axes=()):
    """Interleaved path: params leaves arrive [1, V, ...] — this shard's
    V chunk slices. stage_fn(chunk_params, x) runs ONE chunk."""
    s_idx = lax.axis_index(axis_name)
    n_stage = lax.psum(1, axis_name)
    local = jax.tree_util.tree_map(lambda p: p[0], params)

    def apply(chunk, x):
        cp = jax.tree_util.tree_map(
            lambda p: lax.dynamic_index_in_dim(p, chunk, 0,
                                               keepdims=False), local)
        return stage_fn(cp, x)

    res = _run_ticks_interleaved(apply, xs, s_idx, n_stage, axis_name,
                                 n_chunks, with_aux=with_aux)
    if with_aux:
        out, aux = res
        return out, _aux_reduce(aux, axis_name, aux_mean_axes)
    return res


def _gpipe_hetero(params_seq, xs, stage_fn, axis_name):
    """Heterogeneous path: params_seq is a tuple of per-stage pytrees
    (arbitrary, differing shapes), replicated; lax.switch picks this
    stage's branch."""
    s_idx = lax.axis_index(axis_name)
    n_stage = lax.psum(1, axis_name)
    branches = [functools.partial(stage_fn, p) for p in params_seq]
    return _run_ticks(lambda x: lax.switch(s_idx, branches, x), xs, s_idx,
                      n_stage, axis_name)


def gpipe(stage_fn, stacked_params, microbatches, mesh, axis_name="pp",
          batch_axis=None, param_specs=None, seq_axis=None,
          with_aux=False):
    """Run ``stage_fn(params_i, x)`` as an S-stage pipeline.

    stacked_params: EITHER a pytree whose leaves have leading dim S
                    (= mesh[axis]) — sharded on ``axis_name``, the
                    scalable form — OR a list/tuple of S per-stage
                    pytrees with arbitrary per-stage shapes (replicated
                    to every device, selected by stage index).
    microbatches:   [M, mb, T, ...] array of M microbatches.
    batch_axis:     mesh axis the mb dim is data-sharded on (e.g. "dp"),
                    None if replicated.
    param_specs:    optional pytree of PartitionSpecs for the NON-leading
                    dims of the stacked params (tensor-parallel
                    composition: Megatron col/row shards on "tp"; the
                    leading ``axis_name`` entry is prepended here). The
                    stage_fn then runs inside shard_map over both axes
                    and must psum its partial sums over the tp axis.
    seq_axis:       mesh axis the T (dim-2) activation dim is sharded on
                    (sequence-parallel composition: the stage_fn must
                    run ring/Ulysses attention over that axis).
    with_aux:       stage_fn returns (out, aux_scalar) — e.g. the MoE
                    load-balancing loss (pp x ep). Live-tick aux sums
                    psum over pp and pmean over the token-splitting
                    axes; gpipe then returns (outputs, aux). batch_axis
                    may be a TUPLE of axes (the dp x ep token split).
    Returns [M, mb, ...] outputs of the final stage (with_aux: a tuple).
    """
    s = mesh.shape[axis_name]
    xspec = P(None, batch_axis, seq_axis)
    out_spec = P(axis_name, None, batch_axis, seq_axis)
    aux_axes = tuple(a for a in jax.tree_util.tree_leaves(
        (batch_axis, seq_axis)) if a) if with_aux else ()
    out_specs = (out_spec, P()) if with_aux else out_spec

    if isinstance(stacked_params, (list, tuple)):
        if with_aux:
            raise NotImplementedError(
                "with_aux is not supported on the heterogeneous "
                "per-stage-params path")
        if len(stacked_params) != s:
            raise ValueError(
                "per-stage params list has %d entries != %d pipeline "
                "stages" % (len(stacked_params), s))
        params_seq = tuple(stacked_params)
        pspec = jax.tree_util.tree_map(lambda _: P(), params_seq)
        fn = shard_map(
            functools.partial(_gpipe_hetero, stage_fn=stage_fn,
                              axis_name=axis_name),
            mesh=mesh, in_specs=(pspec, xspec), out_specs=out_spec,
            check_vma=False)
        return fn(params_seq, microbatches)[-1]

    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != s:
            raise ValueError(
                "stacked_params leading dim %d != %d pipeline stages"
                % (leaf.shape[0], s))
    if param_specs is None:
        pspec = jax.tree_util.tree_map(lambda _: P(axis_name),
                                       stacked_params)
    else:
        pspec = jax.tree_util.tree_map(
            lambda sp: P(axis_name, *sp), param_specs,
            is_leaf=lambda x: isinstance(x, (P, tuple)))
    fn = shard_map(
        functools.partial(_gpipe_sharded, stage_fn=stage_fn,
                          axis_name=axis_name, with_aux=with_aux,
                          aux_mean_axes=aux_axes),
        mesh=mesh, in_specs=(pspec, xspec), out_specs=out_specs,
        check_vma=False)
    res = fn(stacked_params, microbatches)
    if with_aux:
        # aux crosses the shard_map as [1] (scalar-residual workaround
        # in _run_ticks); hand the caller the scalar it expects
        return res[0][-1], res[1].reshape(())
    return res[-1]


def gpipe_interleaved(stage_fn, stacked_params, microbatches, mesh,
                      n_chunks, axis_name="pp", batch_axis=None,
                      param_specs=None, seq_axis=None, with_aux=False):
    """Interleaved virtual-stage pipeline (Megatron 1F1B-interleaved
    regime): device d holds the V = n_chunks chunk param slices
    {d, d+S, ...}; bubble = (S-1)/V chunk-times instead of (S-1)
    stage-times — the schedule that beats GPipe at small M.

    stacked_params: pytree with leaves [S, V, per_chunk, ...] — leading
                    dim sharded on ``axis_name``, dim 1 the local chunk
                    index (see ops/parallel_ops for the [L,...] →
                    [S, V, ...] interleave reshape).
    microbatches:   [M, mb, ...], M <= S (single in-flight microbatch
                    per device per tick).
    stage_fn(chunk_params, x) runs ONE chunk (per_chunk layers).
    """
    s = mesh.shape[axis_name]
    m = microbatches.shape[0]
    if m > s:
        raise ValueError(
            "interleaved schedule needs microbatches M=%d <= S=%d "
            "pipeline stages (use gpipe for the large-M regime)" % (m, s))
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != s or leaf.shape[1] != n_chunks:
            raise ValueError(
                "interleaved stacked_params leaves must be "
                "[S=%d, V=%d, ...]; got %s" % (s, n_chunks, leaf.shape))
    xspec = P(None, batch_axis, seq_axis)
    out_spec = P(axis_name, None, batch_axis, seq_axis)
    aux_axes = tuple(a for a in jax.tree_util.tree_leaves(
        (batch_axis, seq_axis)) if a) if with_aux else ()
    out_specs = (out_spec, P()) if with_aux else out_spec
    if param_specs is None:
        pspec = jax.tree_util.tree_map(lambda _: P(axis_name, None),
                                       stacked_params)
    else:
        pspec = jax.tree_util.tree_map(
            lambda sp: P(axis_name, None, *sp), param_specs,
            is_leaf=lambda x: isinstance(x, (P, tuple)))
    fn = shard_map(
        functools.partial(_interleaved_sharded, stage_fn=stage_fn,
                          axis_name=axis_name, n_chunks=n_chunks,
                          with_aux=with_aux, aux_mean_axes=aux_axes),
        mesh=mesh, in_specs=(pspec, xspec), out_specs=out_specs,
        check_vma=False)
    res = fn(stacked_params, microbatches)
    if with_aux:
        return res[0][-1], res[1].reshape(())
    return res[-1]
