"""Static per-eqn cost model: FLOPs + bytes from avals alone.

The roll-up the reference framework never had at the IR level (its cost
model lived in per-op C++ GetExpectedKernelType heuristics); here every
jaxpr eqn gets a (flops, bytes) estimate so rules can rank diagnostics
by how much compute sits behind them.
"""

import numpy as np

from .engine import sub_jaxprs, aval_nbytes as _aval_bytes


def matmul_flops(m, k, n):
    """FLOPs of an [M,K] @ [K,N] matmul (multiply-accumulate = 2 ops)."""
    return 2.0 * float(m) * float(k) * float(n)


def dot_general_flops(lhs_shape, rhs_shape, dimension_numbers):
    """FLOPs of a lax.dot_general from its shapes + dimension_numbers —
    the per-eqn cost the jaxpr analyzer rolls up. Batch dims multiply,
    contracting dims form K, the rest form M / N."""
    (lc, rc), (lb, rb) = dimension_numbers
    batch = 1.0
    for i in lb:
        batch *= lhs_shape[i]
    k = 1.0
    for i in lc:
        k *= lhs_shape[i]
    m = 1.0
    for i in range(len(lhs_shape)):
        if i not in lb and i not in lc:
            m *= lhs_shape[i]
    n = 1.0
    for i in range(len(rhs_shape)):
        if i not in rb and i not in rc:
            n *= rhs_shape[i]
    return batch * matmul_flops(m, k, n)

# eqns that are pure data movement / metadata: zero FLOPs, bytes only
_MOVEMENT = {
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev",
    "expand_dims", "slice", "concatenate", "pad", "copy",
    "convert_element_type", "bitcast_convert_type", "stop_gradient",
    "gather", "scatter", "dynamic_slice", "dynamic_update_slice",
    "device_put", "iota", "select_n",
}

# expensive transcendentals: count a few FLOPs per element
_TRANSCENDENTAL = {"exp", "log", "log1p", "tanh", "logistic", "erf",
                   "rsqrt", "sqrt", "pow", "sin", "cos", "cbrt",
                   "exp2", "expm1"}


def _aval_size(aval):
    try:
        return float(np.prod(aval.shape, dtype=np.float64))
    except Exception:
        return 0.0


def has_subjaxpr(eqn):
    """True for call-like eqns (scan/while/cond/jit/shard_map...) whose
    cost lives in their inner jaxpr — counted there, not on the eqn."""
    for _ in sub_jaxprs(eqn):
        return True
    return False


def eqn_cost(eqn):
    """(flops, bytes) estimate for one eqn. Bytes = operands + outputs
    touched once (the bandwidth floor); FLOPs from shapes."""
    prim = eqn.primitive.name
    if has_subjaxpr(eqn):
        return 0.0, 0.0
    nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                 if hasattr(v, "aval"))
    nbytes += sum(_aval_bytes(v.aval) for v in eqn.outvars
                  if hasattr(v, "aval"))
    if prim == "dot_general":
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        flops = dot_general_flops(lhs.shape, rhs.shape,
                                  eqn.params["dimension_numbers"])
        return flops, nbytes
    if prim == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        out = eqn.outvars[0].aval
        # out elements x (2 * K_spatial * Cin/groups) MACs each
        dn = eqn.params["dimension_numbers"]
        k_spatial = 1.0
        for i in dn.rhs_spec[2:]:
            k_spatial *= rhs.shape[i]
        cin = rhs.shape[dn.rhs_spec[1]]  # already Cin/groups
        flops = 2.0 * _aval_size(out) * k_spatial * cin
        return flops, nbytes
    if prim in _MOVEMENT:
        return 0.0, nbytes
    out_sz = max([_aval_size(v.aval) for v in eqn.outvars
                  if hasattr(v, "aval")] or [0.0])
    in_sz = max([_aval_size(v.aval) for v in eqn.invars
                 if hasattr(v, "aval")] or [0.0])
    if prim.startswith("reduce_") or prim in ("argmax", "argmin",
                                              "cumsum", "cumlogsumexp",
                                              "cummax", "cumprod"):
        return in_sz, nbytes
    if prim in _TRANSCENDENTAL:
        return 8.0 * out_sz, nbytes
    if prim == "sort":
        n = max(in_sz, 1.0)
        return n * np.log2(max(n, 2.0)), nbytes
    # default: one FLOP per output element (elementwise / compare / etc.)
    return out_sz, nbytes


class CostTable:
    """Per-eqn costs over an Analysis, weighted by loop trip counts
    (a scan body's cost counts ``length`` times)."""

    def __init__(self, analysis):
        self.per_eqn = {}     # id(eqn) -> (flops, bytes, weight)
        self.total_flops = 0.0
        self.total_bytes = 0.0
        for view, eqn in analysis.iter_eqns():
            f, b = eqn_cost(eqn)
            w = view.weight
            self.per_eqn[id(eqn)] = (f, b, w)
            self.total_flops += f * w
            self.total_bytes += b * w

    def flops(self, eqn):
        f, _, w = self.per_eqn.get(id(eqn), (0.0, 0.0, 1.0))
        return f * w

    def bytes(self, eqn):
        _, b, w = self.per_eqn.get(id(eqn), (0.0, 0.0, 1.0))
        return b * w


def step_costs(fn, example_args):
    """(total_flops, total_bytes) of one call of ``fn(*example_args)``
    from the static cost model — abstract trace only, nothing executes.
    This is the bridge paddle_tpu.monitor uses to price a compiled step
    once per compile and derive per-step MFU from wall time."""
    from .engine import Analysis
    table = CostTable(Analysis(fn, example_args, name="step"))
    return table.total_flops, table.total_bytes


def fmt_flops(f):
    for unit, scale in (("TFLOP", 1e12), ("GFLOP", 1e9), ("MFLOP", 1e6),
                        ("kFLOP", 1e3)):
        if f >= scale:
            return "%.2f %s" % (f / scale, unit)
    return "%.0f FLOP" % f


def fmt_bytes(b):
    for unit, scale in (("GiB", 2 ** 30), ("MiB", 2 ** 20),
                        ("KiB", 2 ** 10)):
        if b >= scale:
            return "%.2f %s" % (b / scale, unit)
    return "%.0f B" % b
