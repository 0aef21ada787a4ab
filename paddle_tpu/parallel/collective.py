"""Collective communication ops as Program ops.

Reference parity: operators/nccl/nccl_op.cc:24 (NCCLInit/AllReduce/Reduce/
Bcast as graph ops) and the allreduce op-handles. On TPU these lower to XLA
collectives over the ICI mesh. Outside shard_map (normal jit SPMD), sharding
propagation already inserts collectives, so these ops lower to identity /
psum-style reductions only when an explicit mesh axis context exists
(ctx.mesh set by shard_map-based runners); otherwise they are sharding
constraints or no-ops — semantically the value is already global-view.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register


def _axis(op, default="dp"):
    return op.attr("ring_id_axis", op.attr("axis_name", default))


@register("c_allreduce_sum")
def _c_allreduce_sum(ctx, op):
    x = ctx.in1(op, "X")
    if ctx.mesh is not None:
        x = lax.psum(x, _axis(op))
    ctx.set_out(op, "Out", x)


@register("c_allreduce_max")
def _c_allreduce_max(ctx, op):
    x = ctx.in1(op, "X")
    if ctx.mesh is not None:
        x = lax.pmax(x, _axis(op))
    ctx.set_out(op, "Out", x)


@register("c_allgather")
def _c_allgather(ctx, op):
    x = ctx.in1(op, "X")
    if ctx.mesh is not None:
        x = lax.all_gather(x, _axis(op), tiled=True)
    ctx.set_out(op, "Out", x)


@register("c_reducescatter")
def _c_reducescatter(ctx, op):
    x = ctx.in1(op, "X")
    if ctx.mesh is not None:
        x = lax.psum_scatter(x, _axis(op), tiled=True)
    ctx.set_out(op, "Out", x)


@register("c_broadcast")
def _c_broadcast(ctx, op):
    # root's value everywhere; in global-view SPMD the value is already
    # consistent, so this is an identity (parity with ncclBcast of params,
    # parallel_executor.cc:115)
    ctx.set_out(op, "Out", ctx.in1(op, "X"))


@register("all_to_all")
def _all_to_all(ctx, op):
    x = ctx.in1(op, "X")
    if ctx.mesh is not None:
        split_axis = int(op.attr("split_axis", 0))
        concat_axis = int(op.attr("concat_axis", 0))
        x = lax.all_to_all(x, _axis(op), split_axis, concat_axis,
                           tiled=True)
    ctx.set_out(op, "Out", x)


@register("c_sync_comm_stream")
def _c_sync(ctx, op):
    # stream sync is meaningless under XLA's single-program schedule
    for name, out in zip(op.input("X"), op.output("Out")):
        ctx.env[out] = ctx.get(name)


def allreduce(x, axis_name="dp"):
    """Functional helper for shard_map code."""
    return lax.psum(x, axis_name)


def barrier(mesh):
    """Host-side barrier: tiny psum across the mesh (send_barrier parity)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    f = shard_map(lambda x: lax.psum(x, mesh.axis_names),
                  mesh=mesh,
                  in_specs=P(*([None] * 0)), out_specs=P())
    jax.block_until_ready(f(jnp.zeros(())))
