"""The gated short convolution of a convolution-only token mixer (ISSUE
49), and the causal taps it shares with the convolution in front of a
selective scan (``ops/selective_scan.py`` ``causal_conv_silu``, the
Program op ``ssm_conv``: its path wherever there is no TPU; on a TPU
that op is the kernel pair of ``ops/ssm_conv.py`` since ISSUE 65,
and this file's op keeps the taps).

The operator, for the rows h ``[T, d]`` of one sequence: ``[B, C, X] =
h W_in`` (three parts of C channels side by side, as ONE ``mul`` leaves
them), ``u = B * X``, ``v_t = sum_i w[i] * u_(t - K + 1 + i)`` over K
taps (3), each channel by itself, zeros before the sequence, no bias
and NO activation, ``y = C * v``, then ``y W_out``. The Program op
``gated_short_conv`` is what lies between the two ``mul``s: X ``[B, T,
3C]`` and Filter ``[K, C]`` -> Out ``[B, T, C]``, float32 inside and
X's dtype out.

``jax.numpy``: the taps are K shifted slices of a padded array added
up, which XLA fuses with the two gates into a pass over X forward, and
autodiff's transpose of them into the passes backward; no kernel of
``gated_short_conv``'s own yet (the cell ``lfm2_train_T32k`` reads what
that costs: ``short_conv_dev_share_pct``). Each lowering counts itself
at trace time in ``ptpu_short_conv_lowerings_total{taps, channels}``,
and its device rows carry the Program op's scope. A ``layers.recompute``
region may keep the op's result from its forward to its backward under
the name `CONV_OUT` (``ops/control_flow.py``: the block's plan names it
where the op is lowered, as it names a `mul` result).
"""

import jax.numpy as jnp

from ..core.registry import register
from ..monitor import metrics as _metrics

_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_short_conv_lowerings_total",
    "gated short convolution lowerings at trace time (one a lowering of "
    "the op, none a step): the taps and the channels of a part",
    ("taps", "channels"))
# the name of the op's result where a recompute region keeps it
CONV_OUT = "short_conv_out"


def causal_taps(x32, w, start=None):
    """``start + sum_i w[i] * x_(t - K + 1 + i)`` over time, each
    channel by itself, zeros before the sequence: x32 [B, T, C]
    float32, w [K, C], `start` what the sum begins from (a bias [C];
    None: the first tap's term). K shifted slices added up in the
    taps' order, float32."""
    k, t = w.shape[0], x32.shape[1]
    padded = jnp.pad(x32, [(0, 0), (k - 1, 0), (0, 0)])
    out = start
    for i in range(k):
        term = w[i].astype(jnp.float32) * padded[:, i:i + t]
        out = term if out is None else out + term
    return out


def gated_short_conv(x, w):
    """x [B, T, 3C] holding B, C and X side by side, w [K, C] -> ``C *
    taps(B * X)`` [B, T, C], float32 inside, x's dtype out."""
    c = x.shape[-1] // 3
    if x.shape[-1] != 3 * c or w.shape[1] != c:
        raise ValueError(
            "gated_short_conv: X holds three parts of the filter's %d "
            "channels side by side, got %s" % (w.shape[1], x.shape))
    _LOWERINGS.inc(taps=str(w.shape[0]), channels=str(c))
    gate_in, gate_out, value = (
        x[..., i * c:(i + 1) * c].astype(jnp.float32) for i in range(3))
    return (gate_out * causal_taps(gate_in * value, w)).astype(x.dtype)


@register("gated_short_conv")
def _gated_short_conv(ctx, op):
    """X [B, T, 3C] (the parts B, C, X of one projection), Filter [K,
    C] -> Out [B, T, C]."""
    ctx.set_out(op, "Out", gated_short_conv(ctx.in1(op, "X"),
                                            ctx.in1(op, "Filter")))
