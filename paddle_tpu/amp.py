"""Automatic mixed precision (bf16 compute, fp32 accumulate/state).

The reference era used fp16 kernels selected by OpKernelType
(data_type_transform.cc fp16↔fp32); the TPU-native equivalent is bf16 on
the MXU: matmul/conv INPUTS are cast to bfloat16 while accumulation stays
fp32 (preferred_element_type) and all state (params, optimizer moments,
batch-norm stats) remains fp32. Enable per-process with ``enable_amp()`` or
scoped with ``amp_guard()``; the matmul/conv lowerings consult this flag.
"""

import contextlib

_AMP = {"enabled": False}


def enable_amp(flag=True):
    _AMP["enabled"] = bool(flag)


def amp_enabled():
    return _AMP["enabled"]


@contextlib.contextmanager
def amp_guard(enable=True):
    old = _AMP["enabled"]
    _AMP["enabled"] = bool(enable)
    try:
        yield
    finally:
        _AMP["enabled"] = old


@contextlib.contextmanager
def float32():
    """``with amp.float32(): ...``: every op built inside carries the
    attr ``float32``, and a `mul` that carries it reads both operands
    as float32, multiplies at the highest precision and hands on
    float32, whatever AMP says: an exit gate's logit, whose ``log(1 -
    sigmoid)`` a bfloat16 result would flatten and whose gradient it
    would lose; a `scale` that carries it widens what it reads before
    it multiplies (a published multiplier that bfloat16 does not hold,
    ``models/granite_hybrid.py``). The elementwise ops never narrow
    what they read, so a float32 value stays one through them (the
    survival products, the entropy); ``exit_distribution`` widens by
    itself."""
    from .core.program import default_main_program
    with default_main_program().op_attrs(float32=True):
        yield


def maybe_bf16(*arrays):
    """Cast fp32 arrays to bf16 when AMP is on (inputs to MXU ops)."""
    import jax.numpy as jnp
    if not _AMP["enabled"]:
        return arrays if len(arrays) > 1 else arrays[0]
    out = tuple(a.astype(jnp.bfloat16)
                if a is not None and a.dtype == jnp.float32 else a
                for a in arrays)
    return out if len(out) > 1 else out[0]


def result_dtype(orig_dtype):
    """The dtype amp_out gives a result whose op read `orig_dtype`."""
    import jax.numpy as jnp
    if _AMP["enabled"] and jnp.dtype(orig_dtype) == jnp.float32:
        return jnp.bfloat16
    return orig_dtype


def amp_out(out, orig_dtype):
    """Result-dtype policy for MXU ops (conv/mul/matmul).

    Without AMP: cast back to the op's input dtype. With AMP: KEEP the
    activation in bf16 instead of round-tripping to fp32 — the profiler
    showed the ResNet-50 step 82% HBM-bound with fp32 materialization of
    every conv output doubling the traffic. Params stay fp32 (master
    weights); the cast's vjp upcasts their grads back to fp32."""
    return out.astype(result_dtype(orig_dtype))
