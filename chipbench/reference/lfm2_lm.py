"""Plain float32 reference of LFM2-8B-A1B's training step on ONE chip's
share of a 4-way expert-parallel deployment
(``configs/lfm2-8b-a1b-train-ep4.json``; source
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json,
``model_type`` ``lfm2_moe``).

Straightforward ``jax.numpy``: no kernel, no sort, no grouped matmul;
the convolution a loop over its taps on an array padded with zeros in
front; attention a head and a block of ``ROW_BLOCK`` query rows at a
time against all keys with the mask written out, so that 32,768 rows
fit. Every matmul runs at ``highest``. It imports nothing of the
program. What it computes (the equations of ISSUE 49):

* ``x = Embed(ids)``, not scaled; every layer ``x = x + Op(RMSNorm(x))``
  then ``x = x + FFN(RMSNorm(x))``; after the last layer RMSNorm, the
  head the embedding's own table, ``logits = x E^T``, next-token
  cross-entropy against ``label``, mean over ``mask``;
* ``Op`` of a ``conv`` layer, h [T, d]: ``[B, C, X] = h W_in`` (the
  three parts in this order), ``u = B * X``, ``v_t = sum_i w[i]
  u_(t - K + 1 + i)`` (K = ``conv_L_cache`` taps, each channel by
  itself, u zero before the sequence, no bias, no activation), ``y = C
  * v``, ``Op = y W_out``;
* ``Op`` of a ``full_attention`` layer: ``q, k, v = h Wq, h Wk, h Wv``
  (H heads of D; Hkv; Hkv); q and k RMSNorm'd over each head under one
  weight [D] each, then turned by their rows' positions (RoPE,
  rotate-half, theta ``rope_theta``); ``s_ij = q_i . k_j / sqrt(D)``
  kept where ``j <= i``; head h reads key/value head ``h // (H /
  Hkv)``; ``Op = softmax(s) v Wo``;
* ``FFN`` of a layer below ``num_dense_layers``: ``Wdown(silu(Wgate h)
  * (Wup h))``; of the others: ``s = sigmoid(h Wr)`` over ALL experts,
  the k largest of ``s + bias``, weights ``s`` at the chosen over
  (their sum + 1e-6) (``norm_topk_prob``) times
  ``routed_scaling_factor``, ``FFN = sum_e w_e Expert_e(h)`` over the
  chosen experts THIS CHIP HOLDS (ids ``first_expert`` .. + the number
  held), every held expert evaluated on every row; what the other
  experts would add is left out; no shared expert.

``choices`` (``[routed layers, T, k]``, the program's routing) and
``near_tie``: a row's proposed experts stand in for the reference's own
top-k only where every one of them scores (with the bias) within
``near_tie`` of the reference's own k-th largest; everywhere else the
reference routes by itself (``sdar_lm.routed``). ``operands`` is for
the CONTROL alone (as in ``opt_lm.py``): every matmul's operands held
in that dtype, per-tensor scaled; the router, the gates and the taps
stay float32, as the program keeps them.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "layers": [{"ln1",
"ln2" [d]; a conv layer "w_in" [d, 3d], "conv_w" [K, d], "w_out" [d,
d]; an attention layer "wq" [d, H D], "wk", "wv" [d, Hkv D], "q_norm",
"k_norm" [D], "wo" [H D, d]; and either "ffn": (gate, up, down) or
"router" [d, E], "bias" [E], "w_gate", "w_up" [Eh, d, f], "w_down" [Eh,
f, d]}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import ROW_BLOCK, _p32, attention
from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, _rope, routed

CONV = "conv"
NORM_TOPK_EPS = 1e-6


def head_dim(cfg):
    return cfg.get("head_dim",
                   cfg["hidden_size"] // cfg["num_attention_heads"])


def short_conv(u, w):
    """u [T, C], w [K, C] -> v [T, C]: ``v_t = sum_i w[i] * u_(t - K +
    1 + i)``, each channel by itself, zeros before the sequence."""
    taps, t = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    v = jnp.zeros_like(u)
    for i in range(taps):
        v = v + w[i] * padded[i:i + t]
    return v


def conv_operator(p, h, mm):
    """``(C * conv(B * X)) W_out`` of h [T, d]."""
    gate_in, gate_out, value = jnp.split(mm(h, p["w_in"]), 3, axis=-1)
    return mm(gate_out * short_conv(gate_in * value, p["conv_w"]),
              p["w_out"])


def router_weights(p, h, cfg, proposed=None, near_tie=0.0):
    """h [T, d] -> weights [T, E] over ALL experts, 0 at the ones not
    chosen: float32."""
    k, n_exp = cfg["num_experts_per_tok"], cfg["published"]["num_experts"]
    score = jax.nn.sigmoid(h @ p["router"])
    top_i = routed(score + p["bias"], k, proposed, near_tie)
    chosen = jnp.any(top_i[:, :, None] == jnp.arange(n_exp), axis=1)
    weight = jnp.where(chosen, score, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True)
                           + NORM_TOPK_EPS)
    return weight * cfg["routed_scaling_factor"]


def expert_layer(p, h, cfg, first, held, mm, proposed=None, near_tie=0.0):
    """What the `held` experts with ids from `first` (``p["w_gate"]`` ..
    hold those alone) give on rows h [T, d]."""
    weight = router_weights(p, h, cfg, proposed, near_tie)

    def one_expert(y, e):                # every held expert, every row
        w_gate, w_up, w_down, w_e = e
        hidden_ = jax.nn.silu(mm(h, w_gate)) * mm(h, w_up)
        return y + w_e[:, None] * mm(hidden_, w_down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["w_gate"], p["w_up"], p["w_down"],
        weight[:, first:first + held].T))
    return y


def hidden(params, tokens, cfg, choices=None, near_tie=0.0, operands=None):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head, eps = head_dim(cfg), cfg["norm_eps"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["word_emb"][tokens]
    at = 0
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        h = _rms(x, p["ln1"], eps)
        if kind == CONV:
            x = x + conv_operator(p, h, mm)
        else:
            theta = float(cfg["rope_theta"])
            q = _rope(_rms(mm(h, p["wq"]).reshape(t, heads, d_head),
                           p["q_norm"], eps), pos, theta)
            k = _rope(_rms(mm(h, p["wk"]).reshape(t, kv_heads, d_head),
                           p["k_norm"], eps), pos, theta)
            v = mm(h, p["wv"]).reshape(t, kv_heads, d_head)
            x = x + mm(attention(q, k, v, None, mm), p["wo"])
        h = _rms(x, p["ln2"], eps)
        if "ffn" in p:
            w = p["ffn"]
            x = x + mm(jax.nn.silu(mm(h, w[0])) * mm(h, w[1]), w[2])
        else:
            x = x + expert_layer(p, h, cfg, cfg["first_expert"],
                                 cfg["num_experts"], mm,
                                 None if choices is None else choices[at],
                                 near_tie)
            at += 1
    return x


def lm_loss(params, src, label, mask, cfg):
    """Mean next-token cross-entropy of batch ``src`` [B, T] against
    ``label``, weighted by ``mask``: the train step's cost. The tied
    head runs on ``ROW_BLOCK`` rows at a time."""
    p = _p32(params)
    with jax.default_matmul_precision("highest"):
        def one(args):
            tokens, target, weight = args
            x = _rms(hidden(p, tokens, cfg), p["final_norm"],
                     cfg["norm_eps"])
            block = min(ROW_BLOCK, x.shape[0])

            def rows(args):
                xb, tb, wb = args
                logp = jax.nn.log_softmax(xb @ p["word_emb"].T)
                return -jnp.sum(jnp.take_along_axis(
                    logp, tb[:, None], -1)[:, 0] * wb)

            cut = lambda a: a.reshape((-1, block) + a.shape[1:])
            return jnp.sum(jax.lax.map(rows, (cut(x), cut(target),
                                              cut(weight))))
        return jnp.sum(jax.lax.map(one, (src, label, mask))) / jnp.sum(mask)


def logits_at(params, tokens, first, count, cfg, choices=None,
              near_tie=0.0, operands=None):
    """Next-token logits ``[count, V]`` after positions ``first`` ..
    ``first + count - 1`` of the one sequence ``tokens`` [T]."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        x = hidden(p, tokens, cfg, choices, near_tie, operands)
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        return r(_rms(rows, p["final_norm"], cfg["norm_eps"])) \
            @ r(p["word_emb"]).T
