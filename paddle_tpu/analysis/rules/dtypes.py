"""R001 dtype-promotion audit.

Verifies the bf16 serving invariants statically (the contract the bf16
KV-cache work established at runtime: weights/caches bf16, softmax
normalizers + LN statistics f32) and rejects fp16, which the serving
path hand-rejects per model (TransformerInfer._cast_params) — here the
rejection happens before any model-specific code runs.
"""

import numpy as np
import jax.numpy as jnp

from ..diagnostics import Diagnostic, ERROR, WARNING
from ..engine import Rule, register_rule

_F16 = np.dtype(np.float16)
_BF16 = jnp.bfloat16
_F32 = np.dtype(np.float32)

# eqns after which an upcast result plausibly needs f32 (accumulation /
# contraction); upcasts feeding ONLY these stay un-flagged
_ACCUMULATING = {
    "dot_general", "conv_general_dilated", "reduce_sum", "reduce_max",
    "reduce_min", "reduce_prod", "cumsum", "scan", "while", "cond",
    "jit", "custom_vjp_call", "custom_jvp_call", "shard_map", "sort",
    "reduce_precision", "argmax", "argmin",
}


def _is_dtype(aval, dt):
    try:
        return np.dtype(aval.dtype) == np.dtype(dt)
    except TypeError:
        return False   # extended dtypes (PRNG keys)


@register_rule
class DtypePromotionRule(Rule):
    name = "dtype-promotion"
    id = "R001"
    doc = ("fp16 creep (error), bf16 softmax/reduction accumulators "
           "(error/warning), and bf16->f32 upcasts that feed no "
           "accumulation (warning)")

    def __init__(self, upcast_min_elems=4096):
        self.upcast_min_elems = upcast_min_elems

    def check(self, a):
        for var in a.closed_jaxpr.jaxpr.invars:
            if hasattr(var, "aval") and _is_dtype(var.aval, _F16):
                yield Diagnostic(
                    self.name, ERROR,
                    "float16 input %s: fp16 is rejected on the serving "
                    "path (5-bit exponent degrades LN/softmax stats)"
                    % a.label(var),
                    hint="cast parameters to bfloat16 or float32")
        for view, eqn in a.iter_eqns():
            prim = eqn.primitive.name
            out_avals = [v.aval for v in eqn.outvars
                         if hasattr(v, "aval")]
            if any(_is_dtype(av, _F16) for av in out_avals):
                yield Diagnostic(
                    self.name, ERROR,
                    "float16 value produced by %r" % prim,
                    path=view.eqn_path(eqn),
                    hint="use bfloat16 (same exponent range as f32) "
                         "for reduced-precision compute on TPU")
                continue
            in_avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
            if prim == "exp" and in_avals \
                    and _is_dtype(in_avals[0], _BF16):
                # a bf16 exp is (in every graph we ship) a softmax /
                # logsumexp numerator about to be sum-reduced: its
                # normalizer then accumulates in bf16 (8-bit mantissa)
                yield Diagnostic(
                    self.name, ERROR,
                    "exp over bfloat16 — softmax/logsumexp normalizer "
                    "accumulates in bf16",
                    path=view.eqn_path(eqn),
                    hint="cast scores to float32 before exp (the bf16 "
                         "KV-cache serving contract keeps softmax "
                         "stats f32)")
                continue
            if prim == "reduce_sum" and in_avals and out_avals \
                    and _is_dtype(in_avals[0], _BF16) \
                    and _is_dtype(out_avals[0], _BF16):
                yield Diagnostic(
                    self.name, WARNING,
                    "bf16 reduce_sum accumulates in bf16 over %s "
                    "elements" % int(np.prod(in_avals[0].shape)),
                    path=view.eqn_path(eqn),
                    hint="upcast to f32 before the reduction (LN/"
                         "softmax statistics must be f32 in bf16 "
                         "serving mode)")
                continue
            if prim == "convert_element_type" and in_avals:
                src, dst = in_avals[0], eqn.outvars[0].aval
                if _is_dtype(src, _BF16) and _is_dtype(dst, _F32) \
                        and np.prod(src.shape) >= self.upcast_min_elems:
                    users = view.consumers.get(eqn.outvars[0], [])
                    if users and all(
                            u.primitive.name not in _ACCUMULATING
                            for u in users):
                        yield Diagnostic(
                            self.name, WARNING,
                            "bf16->f32 upcast of %s elements feeds "
                            "only non-accumulating ops (%s) — compute "
                            "could stay bf16"
                            % (int(np.prod(src.shape)),
                               ",".join(sorted({u.primitive.name
                                                for u in users}))),
                            path=view.eqn_path(eqn),
                            hint="drop the upcast or move it after "
                                 "the elementwise chain")
