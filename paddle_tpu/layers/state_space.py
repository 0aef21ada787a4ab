"""Layers of a hybrid state-space / attention decoder (ISSUE 40): the
selective scan and the ops round it (``ops/selective_scan.py``),
differential attention (``ops/diff_attention.py``), a head tied to
the embedding, and the gated short convolution of a convolution-only
mixer (ISSUE 49, ``ops/short_conv.py``), and the gated delta rule of a
linear-attention layer with the ops round it (ISSUE 53,
``ops/delta_rule.py``), and the state-space-dual scan of a Mamba-2
mixer with the gate-then-norm behind it (ISSUE 62,
``ops/ssd_scan.py``). Each is one Program op under its own type, so
that a device trace gives each its scope."""

import math

import numpy as np

from ..initializer import (ConstantInitializer, NormalInitializer,
                           NumpyArrayInitializer, UniformInitializer)
from ..param_attr import ParamAttr
from .layer_helper import LayerHelper

__all__ = ["ssm_conv", "ssm_dt", "selective_scan", "ssm_gate", "gmu_gate",
           "diff_attention", "diff_attn", "tied_head", "gated_short_conv",
           "l2_norm_scale", "delta_gates", "gated_delta_rule",
           "gated_rms_norm", "ssd_scan", "gated_group_norm"]


def _param(helper, name, shape, initializer):
    return helper.create_parameter(
        ParamAttr(name=name, initializer=initializer), shape=shape,
        dtype="float32")


def _same(helper, x, shape=None):
    return helper.create_variable_for_type_inference(
        x.dtype, shape=x.shape if shape is None else shape)


def ssm_conv(x, width=4, bias=True, name=None):
    """``silu(bias + causal depthwise conv over time)`` of x [B, T, C]:
    parameters ``<name>_w`` [width, C] and, where `bias`, ``<name>_b``
    [C], both U(-width^-0.5, width^-0.5) (a depthwise Conv1d's
    default)."""
    helper = LayerHelper("ssm_conv", name=name)
    c, bound = int(x.shape[-1]), width ** -0.5
    inputs = {"X": [x], "Filter": [_param(
        helper, helper.name + "_w", [width, c],
        UniformInitializer(-bound, bound))]}
    if bias:
        inputs["Bias"] = [_param(helper, helper.name + "_b", [c],
                                 UniformInitializer(-bound, bound))]
    out = _same(helper, x)
    helper.append_op(type="ssm_conv", inputs=inputs,
                     outputs={"Out": [out]})
    return out


def gated_short_conv(x, width=3, name=None):
    """``C * conv(B * X)`` of x [B, T, 3C], the parts B, C and X of one
    projection side by side (``ops/short_conv.py``): a causal depthwise
    convolution of `width` taps over time between two gates, no bias
    and no activation. Parameter ``<name>_w`` [width, C], U(-width^-0.5,
    width^-0.5) (a depthwise Conv1d's default). Returns [B, T, C]."""
    helper = LayerHelper("gated_short_conv", name=name)
    c, bound = int(x.shape[-1]) // 3, width ** -0.5
    w = _param(helper, helper.name + "_w", [width, c],
               UniformInitializer(-bound, bound))
    out = _same(helper, x, tuple(x.shape[:-1]) + (c,))
    helper.append_op(type="gated_short_conv",
                     inputs={"X": [x], "Filter": [w]},
                     outputs={"Out": [out]})
    return out


def _step_bias(c, dt_min, dt_max):
    """[c] biases whose softplus is spread log-uniformly over [dt_min,
    dt_max] (Mamba's)."""
    dt = np.exp(np.linspace(math.log(dt_min), math.log(dt_max), c))
    return NumpyArrayInitializer(
        (dt + np.log(-np.expm1(-dt))).astype("float32"))


def ssm_dt(x, dt_min=1e-3, dt_max=1e-1, name=None):
    """``softplus(x + bias)``, the scan's step size: parameter
    ``<name>`` [C], initialised so that softplus(bias) is spread
    log-uniformly over [dt_min, dt_max] (Mamba's)."""
    helper = LayerHelper("ssm_dt", name=name)
    c = int(x.shape[-1])
    bias = _param(helper, helper.name, [c], _step_bias(c, dt_min, dt_max))
    out = _same(helper, x)
    helper.append_op(type="ssm_dt", inputs={"X": [x], "Bias": [bias]},
                     outputs={"Out": [out]})
    return out


def selective_scan(x, dt, b, c, d_state=16, chunk=0, force="", name=None):
    """The selective scan of x [B, T, C] with steps dt [B, T, C], inputs
    b and outputs c [B, T, N]: parameters ``<name>_a_log`` [C, N] (A =
    -exp(.), initialised log(1 .. N) in every channel) and ``<name>_d``
    [C] (ones). The state is float32 whatever x is. `chunk` and `force`
    are ``ops/selective_scan.selective_scan``'s (0 and "": its own
    choice; a CPU rehearsal pins ``"interpret"`` and a short chunk)."""
    helper = LayerHelper("selective_scan", name=name)
    ch = int(x.shape[-1])
    a_log = _param(helper, helper.name + "_a_log", [ch, d_state],
                   NumpyArrayInitializer(np.tile(np.log(np.arange(
                       1, d_state + 1, dtype="float32")), (ch, 1))))
    d = _param(helper, helper.name + "_d", [ch], ConstantInitializer(1.0))
    out = _same(helper, x)
    helper.append_op(type="selective_scan",
                     inputs={"X": [x], "Dt": [dt], "ALog": [a_log],
                             "B": [b], "C": [c], "D": [d]},
                     outputs={"Out": [out]},
                     attrs={"chunk": int(chunk), "force": str(force)})
    return out


def ssd_scan(x, dt, b, c, n_head, n_group, a_max=16.0, chunk=0, name=None):
    """The state-space-dual scan (``ops/ssd_scan.py``) of x [B, T, H *
    P] with steps dt [B, T, H] (past their softplus), inputs b and
    outputs c [B, T, G * N], `n_head` H heads in `n_group` G groups that
    share b and c: parameters ``<name>_a_log`` [H] (A = -exp(.), ONE
    number a head, ``exp(.)`` at the H quantiles of U(1, `a_max`):
    Mamba-2's range) and ``<name>_d`` [H] (ones). The state a head is
    ``[P, N]`` float32 whatever x is. `chunk` is
    ``ops/ssd_scan.ssd_scan``'s (0: its own). Returns [B, T, H * P]."""
    helper = LayerHelper("ssd_scan", name=name)
    a_log = _param(helper, helper.name + "_a_log", [n_head],
                   NumpyArrayInitializer(np.log(
                       1.0 + (a_max - 1.0) * (np.arange(n_head) + 0.5)
                       / n_head).astype("float32")))
    d = _param(helper, helper.name + "_d", [n_head],
               ConstantInitializer(1.0))
    out = _same(helper, x)
    helper.append_op(type="ssd_scan",
                     inputs={"X": [x], "Dt": [dt], "ALog": [a_log],
                             "B": [b], "C": [c], "D": [d]},
                     outputs={"Out": [out]},
                     attrs={"n_head": int(n_head), "n_group": int(n_group),
                            "chunk": int(chunk)})
    return out


def gated_group_norm(x, gate, groups, epsilon=1e-5, name=None):
    """``RMSNorm(x * silu(gate)) * w`` over each of `groups` equal
    groups of the channels of x [B, T, C] by itself, the gate BEFORE
    the norm (``gated_rms_norm`` norms first): parameter ``<name>`` [C]
    (ones)."""
    helper = LayerHelper("gated_group_norm", name=name)
    scale = _param(helper, helper.name, [int(x.shape[-1])],
                   ConstantInitializer(1.0))
    out = _same(helper, x)
    helper.append_op(type="gated_group_norm",
                     inputs={"X": [x], "Gate": [gate], "Scale": [scale]},
                     outputs={"Out": [out]},
                     attrs={"groups": int(groups),
                            "epsilon": float(epsilon)})
    return out


def _gated(op_type, x, gate, name):
    helper = LayerHelper(op_type, name=name)
    out = _same(helper, gate)
    helper.append_op(type=op_type, inputs={"X": [x], "Gate": [gate]},
                     outputs={"Out": [out]})
    return out


def ssm_gate(y, z, name=None):
    """``y * silu(z)``: a Mamba mixer's output gate."""
    return _gated("ssm_gate", y, z, name)


def gmu_gate(memory, g, name=None):
    """``memory * silu(g)``: a gated memory unit, the memory another
    layer's scan output at the same positions."""
    return _gated("gmu_gate", memory, g, name)


def diff_attention(q, k, v, n_head, n_kv_head, window=0, kind="full",
                   name=None):
    """The two softmaxes of differential attention
    (``ops/diff_attention.py``): q [B, T, H*D], k and v [B, T, Hkv*D];
    returns ``(a1, a2)``, [B, T, (H/2)*2D] each."""
    helper = LayerHelper("diff_attention", name=name)
    a1, a2 = _same(helper, q), _same(helper, q)
    helper.append_op(
        type="diff_attention",
        inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"A1": [a1], "A2": [a2]},
        attrs={"n_head": int(n_head), "n_kv_head": int(n_kv_head),
               "window": int(window), "kind": str(kind)})
    return a1, a2


def diff_attn(a1, a2, head_dim, lambda_init, epsilon=1e-5, lambda_std=0.1,
              name=None):
    """``RMSNorm(a1 - lam a2) * (1 - lambda_init)`` over a1, a2 [B, T,
    P * 2D]: parameters ``<name>_lq1``, ``_lk1``, ``_lq2``, ``_lk2``
    [D] (N(0, lambda_std)) and ``<name>_subln`` [2D] (ones)."""
    helper = LayerHelper("diff_attn", name=name)
    lam = {part: _param(helper, "%s_%s" % (helper.name, part), [head_dim],
                        NormalInitializer(0.0, lambda_std))
           for part in ("lq1", "lk1", "lq2", "lk2")}
    scale = _param(helper, helper.name + "_subln", [2 * head_dim],
                   ConstantInitializer(1.0))
    out = _same(helper, a1)
    helper.append_op(
        type="diff_attn",
        inputs={"A1": [a1], "A2": [a2], "LambdaQ1": [lam["lq1"]],
                "LambdaK1": [lam["lk1"]], "LambdaQ2": [lam["lq2"]],
                "LambdaK2": [lam["lk2"]], "Scale": [scale]},
        outputs={"Out": [out]},
        attrs={"lambda_init": float(lambda_init), "epsilon": float(epsilon)})
    return out


def l2_norm_scale(x, n_head, scale=1.0, epsilon=1e-6, name=None):
    """Each head's rows of x [B, T, H * D] over their l2 norm, times
    `scale`: ``x / sqrt(sum(x^2) + epsilon) * scale``; no parameter."""
    helper = LayerHelper("l2_norm_scale", name=name)
    out = _same(helper, x)
    helper.append_op(type="l2_norm_scale", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"n_head": int(n_head), "scale": float(scale),
                            "epsilon": float(epsilon)})
    return out


def delta_gates(x_a, x_b, beta_scale=1.0, a_max=16.0, dt_min=1e-3,
                dt_max=1e-1, name=None):
    """The two gates of a gated delta rule from x_a and x_b [B, T, H]:
    ``g = -exp(a_log) * softplus(x_a + dt_bias)`` (the log of a row's
    decay) and ``beta = beta_scale * sigmoid(x_b)``, both float32.
    Parameters ``<name>_a_log`` [H] (``exp(.)`` at the H quantiles of
    U(0, `a_max`)) and ``<name>_dt_bias`` [H] (``ssm_dt``'s spread)."""
    helper = LayerHelper("delta_gates", name=name)
    h = int(x_a.shape[-1])
    a_log = _param(helper, helper.name + "_a_log", [h],
                   NumpyArrayInitializer(np.log(
                       a_max * (np.arange(h) + 0.5) / h).astype("float32")))
    dt_bias = _param(helper, helper.name + "_dt_bias", [h],
                     _step_bias(h, dt_min, dt_max))
    g, beta = _same(helper, x_a), _same(helper, x_a)
    helper.append_op(type="delta_gates",
                     inputs={"XA": [x_a], "XB": [x_b], "ALog": [a_log],
                             "DtBias": [dt_bias]},
                     outputs={"G": [g], "Beta": [beta]},
                     attrs={"beta_scale": float(beta_scale)})
    return g, beta


def gated_delta_rule(q, k, v, g, beta, n_head, chunk=0, name=None):
    """The gated delta rule (``ops/delta_rule.py``) over `n_head` heads:
    q and k [B, T, H * d_k] (normed, the query scaled), v [B, T, H *
    d_v], g and beta [B, T, H]; the state a head ``[d_k, d_v]`` float32
    whatever the operands are. `chunk` is
    ``ops/delta_rule.gated_delta_rule``'s (0: its own choice).
    Returns [B, T, H * d_v]; no parameter."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = _same(helper, v)
    helper.append_op(type="gated_delta_rule",
                     inputs={"Q": [q], "K": [k], "V": [v], "G": [g],
                             "Beta": [beta]},
                     outputs={"Out": [out]},
                     attrs={"n_head": int(n_head), "chunk": int(chunk)})
    return out


def gated_rms_norm(x, gate, head_dim, epsilon=1e-6, name=None):
    """``RMSNorm(x) * silu(gate)`` over each head of `head_dim` values
    of x [B, T, H * D], the norm before the gate: parameter ``<name>``
    [D] (ones), one weight for every head."""
    helper = LayerHelper("gated_rms_norm", name=name)
    scale = _param(helper, helper.name, [head_dim],
                   ConstantInitializer(1.0))
    out = _same(helper, gate)
    helper.append_op(type="gated_rms_norm",
                     inputs={"X": [x], "Gate": [gate], "Scale": [scale]},
                     outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def tied_head(x, table, name=None):
    """Logits of x [B, T, d] against the embedding's OWN parameter
    `table` [V, d]: a ``mul`` that contracts the table's second
    dimension (``transpose_Y``), no copy and no second parameter, so
    the table's gradient is the sum of its two uses."""
    helper = LayerHelper("tied_head", name=name)
    out = _same(helper, x, tuple(x.shape[:-1]) + (int(table.shape[0]),))
    helper.append_op(type="mul", inputs={"X": [x], "Y": [table]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": len(x.shape) - 1,
                            "y_num_col_dims": 1, "transpose_Y": True})
    return out
