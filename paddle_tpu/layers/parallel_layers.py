"""Layer DSL for SP / PP / EP (ops/parallel_ops.py lowerings).

Makes the distributed subsystem reachable from fluid-style model code:

    attn = layers.sequence_parallel_attention(q, k, v, causal=True,
                                              n_head=H)   # q/k/v [B, T, H*dk]
    out, aux = layers.sparse_moe(x, num_experts=8, d_inner=2048)
    y = layers.pipelined_decoder_stack(x, n_layer=8, n_head=8, d_inner=2048)

Each runs the distributed path when ParallelExecutor's mesh has the
matching axis (sp / ep / pp) and an identical-math dense fallback
otherwise, so programs stay testable single-device.
"""

import numpy as np

from .layer_helper import LayerHelper
from ..initializer import Normal, Constant
from ..param_attr import ParamAttr

__all__ = ["sequence_parallel_attention", "sparse_moe", "routed_experts",
           "pipelined_decoder_stack"]


def sequence_parallel_attention(q, k, v, causal=False, variant="ring",
                                scale=0.0, n_head=None, name=None):
    """q/k/v: [B, T, H*dk] variables with `n_head` = H, the layout a
    projection (`fc`) leaves them in, which the flash kernels read and
    write as it is: no reshape or transpose on either side. Without
    `n_head`, [B, H, T, dk] variables (the lowering then transposes
    into the kernels' layout and back). T is sharded on the sp mesh axis
    under ParallelExecutor. Returns a variable of q's shape."""
    if (n_head is None) != (len(q.shape) == 4):
        raise ValueError(
            "sequence_parallel_attention: q of shape %s wants %s"
            % (tuple(q.shape), "no n_head" if len(q.shape) == 4
               else "n_head (q, k, v [B, T, H*dk])"))
    helper = LayerHelper("sp_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    attrs = {"causal": causal, "variant": variant, "scale": scale}
    if n_head is not None:
        attrs["n_head"] = int(n_head)
    helper.append_op(
        type="sp_attention", inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"Out": [out]}, attrs=attrs)
    return out


def sparse_moe(x, num_experts, d_inner, capacity_factor=1.25,
               top_k=1, return_overflow=False, param_attr=None, name=None):
    """MoE FFN over [B, T, D] (or [T, D]) input: Switch top-1 (top_k=1)
    or GShard top-2 with normalized combine weights (top_k=2). Expert
    weights are stacked [E, ...] and sharded on the ep mesh axis. Returns
    (out, aux_loss) — add aux_loss (scaled) to the training cost — plus
    the scalar capacity-overflow fraction (the routing-health metric to
    monitor) when return_overflow=True."""
    helper = LayerHelper("moe_ffn", param_attr=param_attr, name=name)
    d = int(x.shape[-1])
    gate = helper.create_parameter(helper.param_attr, shape=[d, num_experts],
                                   dtype=x.dtype,
                                   default_initializer=Normal(0., 0.02))
    w_up = helper.create_parameter(
        ParamAttr(name=helper.name + ".w_up"),
        shape=[num_experts, d, d_inner], dtype=x.dtype,
        default_initializer=Normal(0., d ** -0.5))
    w_down = helper.create_parameter(
        ParamAttr(name=helper.name + ".w_down"),
        shape=[num_experts, d_inner, d], dtype=x.dtype,
        default_initializer=Normal(0., d_inner ** -0.5))
    # expert dim rides the ep axis
    prog = helper.main_program
    prog._sharding_hints[w_up.name] = ("ep", None, None)
    prog._sharding_hints[w_down.name] = ("ep", None, None)

    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    aux = helper.create_variable_for_type_inference("float32", shape=())
    outputs = {"Out": [out], "AuxLoss": [aux]}
    overflow = None
    if return_overflow:
        overflow = helper.create_variable_for_type_inference(
            "float32", shape=())
        overflow.stop_gradient = True
        outputs["Overflow"] = [overflow]
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [x], "GateW": [gate], "WUp": [w_up],
                "WDown": [w_down]},
        outputs=outputs,
        attrs={"capacity_factor": capacity_factor, "top_k": int(top_k)})
    if return_overflow:
        return out, aux, overflow
    return out, aux


def routed_experts(x, num_experts, experts_held, first_expert, top_k,
                   d_inner, norm_topk=True, name=None, score_func="softmax",
                   routed_scaling_factor=1.0, bias_update_rate=None,
                   shared_expert=False, router_std=0.02, router_input=None,
                   activation="silu", norm_topk_eps=0.0):
    """One chip's share of a mixture of gated experts over
    ``[B, T, D]`` input, dropless (``parallel/moe.routed_experts``): a
    float32 router over all `num_experts`, the `top_k` largest a row
    (their weights divided by their sum where `norm_topk`), and the
    `experts_held` experts with ids from `first_expert` computed here,
    ``w_down(act(w_gate x) * (w_up x))`` of width `d_inner`, act
    `activation` ("silu", or "relu"; under "relu2" an expert has no gate
    matrix and is ``w_down relu(w_up x)^2``, no ``.w_gate``); what the
    other experts would add is left out, as an expert-parallel group
    leaves it to its other members. Returns ``(out, aux_loss, choices,
    load)``: `aux_loss` is ``E * sum_e f_e P_e`` (scale it and add it to
    the cost), `choices` the router's ``[B, T, top_k]`` indices, `load`
    a persistable ``[num_experts]`` int32 count of the rows that chose
    each expert, summed over every TRAIN run of the program. Parameters
    are named ``<name>.router``, ``.w_gate``, ``.w_up``, ``.w_down``.

    `score_func` "sigmoid" scores each expert by itself, and the chosen
    weights are multiplied by `routed_scaling_factor`; `norm_topk_eps`
    is added to their sum before `norm_topk` divides by it. A
    `bias_update_rate` u (not None) routes without an auxiliary loss: a
    persistable ``<name>.bias`` [num_experts], zero at first and never
    differentiated, is added to the scores for the CHOICE alone, and
    every train run moves it by u towards an even load
    (``moe.bias_step``) and adds one to the persistable
    ``<name>.steps`` [1] int32. `shared_expert` labels the lowering's count:
    the caller adds a shared expert's output to `out`. `router_std` is
    the router's initialisation, N(0, `router_std`).

    `router_input` (None: `x`) is what the router reads, a variable of
    x's shape: a router that stands before the sublayers that make the
    experts' input. The router's weights send their gradient to it, the
    experts theirs to `x`. Under a "relu" gate (and behind "relu2") the
    zeros are exact, and
    every train run adds to the persistable ``<name>.gate_on`` [2]
    float32 the hidden units the gate left on and the hidden units
    there were, over the step's pairs on held experts, and one to
    ``<name>.steps``."""
    helper = LayerHelper("routed_experts", name=name)
    d = int(x.shape[-1])
    param = lambda suffix, shape, std: helper.create_parameter(
        ParamAttr(name="%s.%s" % (helper.name, suffix)), shape=shape,
        dtype="float32", default_initializer=Normal(0., std))
    router = param("router", [d, num_experts], router_std)
    # (an expert of two matrices has no gate's)
    w_gate = None if activation == "relu2" else param(
        "w_gate", [experts_held, d, d_inner], d ** -0.5)
    w_up = param("w_up", [experts_held, d, d_inner], d ** -0.5)
    w_down = param("w_down", [experts_held, d_inner, d], d_inner ** -0.5)
    from .tensor import create_global_var
    load = create_global_var([num_experts], 0, "int32", persistable=True,
                             name=helper.name + ".load")
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    aux = helper.create_variable_for_type_inference("float32", shape=())
    choices = helper.create_variable_for_type_inference(
        "int32", shape=tuple(x.shape[:-1]) + (top_k,), stop_gradient=True)
    inputs = {"X": [x], "RouterW": [router], "WGate": [w_gate],
              "WUp": [w_up], "WDown": [w_down], "Load": [load]}
    if w_gate is None:
        del inputs["WGate"]
    outputs = {"Out": [out], "AuxLoss": [aux], "Indices": [choices],
               "LoadOut": [load]}
    attrs = {"first_expert": int(first_expert), "top_k": int(top_k),
             "norm_topk": bool(norm_topk)}
    if router_input is not None:
        inputs["RouterX"] = [router_input]
    if activation != "silu":
        attrs["activation"] = str(activation)
    if norm_topk_eps:
        attrs["norm_topk_eps"] = float(norm_topk_eps)
    counts_gate = activation in ("relu", "relu2")    # exact zeros behind it
    if counts_gate:
        gate_on = create_global_var([2], 0.0, "float32", persistable=True,
                                    name=helper.name + ".gate_on")
        gate_on.stop_gradient = True
        inputs["GateOn"], outputs["GateOnOut"] = [gate_on], [gate_on]
    if (score_func, routed_scaling_factor, shared_expert) != (
            "softmax", 1.0, False):
        attrs.update(score_func=str(score_func),
                     routed_scaling_factor=float(routed_scaling_factor),
                     shared_expert=bool(shared_expert))
    if bias_update_rate is not None:
        bias = create_global_var([num_experts], 0.0, "float32",
                                 persistable=True, name=helper.name + ".bias")
        bias.stop_gradient = True
        inputs["Bias"], outputs["BiasOut"] = [bias], [bias]
        attrs["bias_update_rate"] = float(bias_update_rate)
    if bias_update_rate is not None or counts_gate:
        steps = create_global_var([1], 0, "int32", persistable=True,
                                  name=helper.name + ".steps")
        inputs["Steps"], outputs["StepsOut"] = [steps], [steps]
    helper.append_op(type="routed_experts", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out, aux, choices, load


def pipelined_decoder_stack(x, n_layer, n_head, d_inner,
                            num_microbatches=0, recompute=False,
                            schedule="gpipe", virtual_stages=0,
                            tp_shard=False, num_experts=0, moe_top_k=1,
                            moe_capacity_factor=1.25, moe_gate_groups=1,
                            name=None):
    """L identical causal decoder layers with layer-stacked parameters
    ([L, ...], leading dim sharded on the pp mesh axis → pipeline
    schedule under ParallelExecutor; lax.scan over layers otherwise).
    recompute=True rematerializes each layer's activations in the
    backward pass (jax.checkpoint on the scan body).
    schedule: "gpipe" (M >= S regime) or "interleaved" (Megatron
    virtual stages — bubble cut by `virtual_stages` chunks per device;
    requires M <= S). tp_shard=True adds Megatron col/row sharding
    hints for a tp mesh axis (the pp x tp composition — the stage body
    then psums per sublayer; ops/parallel_ops._decoder_layer_apply_tp).

    num_experts > 0 replaces every layer's dense FFN with a routed MoE
    layer (experts' hidden dim = d_inner) — the pp x ep composition:
    expert stacks shard on the ep mesh axis and the dispatch
    all-to-alls inside the stage body. Requires an explicit
    num_microbatches and moe_gate_groups = dp*ep of the target mesh
    (routing is per-microbatch per token-group; the static attrs let
    the dense fallback reproduce it exactly), and the call then
    returns (out, aux_loss) instead of out.

    x: [B, T, D]. Returns [B, T, D]."""
    helper = LayerHelper("pipeline_stack", name=name)
    d = int(x.shape[-1])
    L = int(n_layer)
    # storage-placement hints for the GLOBAL [L, ...] params (the op
    # re-blocks them per schedule inside the jit); col/row tp tails
    # mirror ops/parallel_ops._TP_SPEC_TAILS
    tp_tails = {
        ".wq": (None, "tp"), ".wk": (None, "tp"), ".wv": (None, "tp"),
        ".wo": ("tp", None), ".w1": (None, "tp"), ".b1": ("tp",),
        ".w2": ("tp", None),
    }

    def p(suffix, shape, init):
        w = helper.create_parameter(ParamAttr(name=helper.name + suffix),
                                    shape=list(shape), dtype=x.dtype,
                                    default_initializer=init)
        tail = tp_tails.get(suffix) if tp_shard else None
        helper.main_program._sharding_hints[w.name] = \
            ("pp",) + (tail or (None,) * (len(shape) - 1))
        return w

    std = d ** -0.5
    params = {
        "WQ": p(".wq", (L, d, d), Normal(0., std)),
        "WK": p(".wk", (L, d, d), Normal(0., std)),
        "WV": p(".wv", (L, d, d), Normal(0., std)),
        "WO": p(".wo", (L, d, d), Normal(0., std)),
        "LN1S": p(".ln1_s", (L, d), Constant(1.0)),
        "LN1B": p(".ln1_b", (L, d), Constant(0.0)),
        "LN2S": p(".ln2_s", (L, d), Constant(1.0)),
        "LN2B": p(".ln2_b", (L, d), Constant(0.0)),
    }
    moe = int(num_experts) > 0
    if moe:
        e = int(num_experts)
        # gate replicated (routing needs every logit); expert stacks
        # shard on ep (storage hints for the GLOBAL [L, E, ...] params)
        gate = helper.create_parameter(
            ParamAttr(name=helper.name + ".gate_w"),
            shape=[L, d, e], dtype=x.dtype,
            default_initializer=Normal(0., 0.02))
        w_up = helper.create_parameter(
            ParamAttr(name=helper.name + ".w_up"),
            shape=[L, e, d, d_inner], dtype=x.dtype,
            default_initializer=Normal(0., std))
        w_down = helper.create_parameter(
            ParamAttr(name=helper.name + ".w_down"),
            shape=[L, e, d_inner, d], dtype=x.dtype,
            default_initializer=Normal(0., d_inner ** -0.5))
        hints = helper.main_program._sharding_hints
        hints[gate.name] = ("pp", None, None)
        hints[w_up.name] = ("pp", "ep", None, None)
        hints[w_down.name] = ("pp", "ep", None, None)
        params.update({"GateW": gate, "WUp": w_up, "WDown": w_down})
    else:
        params.update({
            "W1": p(".w1", (L, d, d_inner), Normal(0., std)),
            "B1": p(".b1", (L, d_inner), Constant(0.0)),
            "W2": p(".w2", (L, d_inner, d), Normal(0., d_inner ** -0.5)),
            "B2": p(".b2", (L, d), Constant(0.0)),
        })
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    outputs = {"Out": [out]}
    aux = None
    if moe:
        aux = helper.create_variable_for_type_inference(
            "float32", shape=())
        outputs["AuxLoss"] = [aux]
    helper.append_op(
        type="pipeline_stack",
        inputs=dict({"X": [x]}, **{s: [w] for s, w in params.items()}),
        outputs=outputs,
        attrs={"n_head": n_head, "num_microbatches": num_microbatches,
               "recompute": bool(recompute), "schedule": str(schedule),
               "virtual_stages": int(virtual_stages),
               "moe_top_k": int(moe_top_k),
               "moe_capacity_factor": float(moe_capacity_factor),
               "moe_gate_groups": int(moe_gate_groups)})
    if moe:
        return out, aux
    return out
