"""Op lowering registry.

This replaces the reference's kernel registry (paddle/fluid/framework/
op_registry.h:52-129 + per-device kernels): instead of CPU/CUDA kernel
functions selected at interpreter time, each op type registers a *lowering
rule* — a pure function from jax values to jax values — that the Executor's
tracer calls while staging the whole Program into one XLA computation.

An op therefore needs no per-device variants: XLA compiles the same lowering
for TPU and CPU. Pallas kernels slot in as lowering bodies for ops where XLA
fusion is insufficient (attention etc.).
"""

import jax.numpy as jnp


class OpInfo:
    def __init__(self, type, lower, infer_shape=None, stateful_rng=False,
                 host=False):
        self.type = type
        self.lower = lower            # fn(ctx, op) -> None (writes ctx env)
        self.infer_shape = infer_shape
        self.stateful_rng = stateful_rng  # consumes a PRNG key at trace time
        self.host = host  # does IO → program runs in eager-interpreter mode


_REGISTRY = {}


def register(type, lower=None, infer_shape=None, stateful_rng=False,
             host=False):
    """Register an op lowering. Usable as decorator or direct call."""
    def deco(fn):
        _REGISTRY[type] = OpInfo(type, fn, infer_shape, stateful_rng, host)
        return fn
    if lower is not None:
        return deco(lower)
    return deco


def is_host_op(type):
    info = _REGISTRY.get(type)
    return bool(info and info.host)


def lookup(type):
    return _REGISTRY.get(type)


def registered_ops():
    return sorted(_REGISTRY)


class LowerContext:
    """Environment handed to lowering rules during tracing.

    env maps var name -> jax value. Replaces the reference's ExecutionContext
    (scope lookup + device context); there is no device context because
    placement is XLA's job.
    """

    def __init__(self, env, rng_fn, is_test=False, executor=None, block=None,
                 mesh=None, static_info=None, fetch_names=()):
        self.env = env
        self._rng_fn = rng_fn      # () -> fresh jax PRNG key
        self.is_test = is_test
        self.executor = executor
        self.block = block
        self.mesh = mesh
        # trace-time constants derived from the feed (e.g. "<name>@MAXLEN"
        # bucketed max sequence length); part of the compile-cache key
        self.static_info = static_info or {}
        # what the caller will fetch — rematerialization regions consult
        # this so a fetched region output is exported instead of dropped
        self.fetch_names = tuple(fetch_names or ())
        # the op ledger (paddle_tpu.trace.ops): what a build's lowering
        # writes its rows with (core/executor.py _OpLog; None where no
        # table is being written: the eager interpreter, a loop's body),
        # the row of the op being lowered and, in a context that lowers
        # a recompute region's ops, the region's index
        self._op_log = None
        self._op_row = None
        self._op_region = None
        # in a context that lowers a recompute region's ops: what the
        # block's plan keeps of them, {id(op): the name its result is
        # kept by} (ops/control_flow.py _plan_kept); an op whose
        # lowering names its own values reads it (gated_delta_rule)
        self.kept_ops = {}

    # -- value access --------------------------------------------------------
    def get(self, name):
        if name not in self.env:
            raise KeyError("var %r not materialized at lowering time" % name)
        return self.env[name]

    def maybe_get(self, name, default=None):
        return self.env.get(name, default)

    def set(self, name, value):
        self.env[name] = value

    def in1(self, op, slot, default=None):
        names = op.input(slot)
        if not names:
            return default
        return self.get(names[0])

    def in_list(self, op, slot):
        return [self.get(n) for n in op.input(slot)]

    def out_name(self, op, slot):
        names = op.output(slot)
        return names[0] if names else None

    def set_out(self, op, slot, value):
        name = self.out_name(op, slot)
        if name is not None:
            self.env[name] = value

    def rng(self):
        return self._rng_fn()

    def note(self, **fields):
        """A lowering's own words on its op's row of the op ledger (the
        shape a product runs at, what a plan decided): numbers, strings
        and tuples, never an array or a tracer. Nothing where no table
        is being written."""
        if self._op_row is not None:
            self._op_row.update(fields)

    # -- helpers -------------------------------------------------------------
    @staticmethod
    def cast_like(x, ref):
        return jnp.asarray(x, dtype=ref.dtype)
