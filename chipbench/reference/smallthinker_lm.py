"""Plain float32 reference of SmallThinker-21BA3B-Instruct's training
step on ONE chip's share of a 4-way expert-parallel deployment
(``configs/smallthinker-21b-a3b-train-ep4.json``; source
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json,
``model_name`` ``smallthinker_21b_instruct``).

Straightforward ``jax.numpy``: no kernel, no sort, no grouped matmul;
attention a head and a block of ``ROW_BLOCK`` query rows at a time
against all keys with the mask written out, so that 16,384 rows fit.
Every matmul runs at ``highest``. It imports nothing of the program.
What it computes (the equations of ISSUE 46), x the stream ``[T, d]``
entering layer l:

1. ``r = x W_r`` (``W_r [d, E]``, float32): the router's logits from
   the layer's INPUT as it arrives, before the layer's first norm and
   before attention;
2. ``idx = top-k(r)``, ``w = softmax(r[idx])`` over those k alone
   (``moe_primary_router_apply_softmax``), float32;
3. ``h = RMSNorm_1(x)``; ``q, k, v = h W_q, h W_k, h W_v`` (H heads of
   D; Hkv; Hkv), no bias, no norm on q or k; where ``rope_layout[l]``
   is 1, q and k turned by their rows' positions (rotate-half, theta
   ``rope_theta``), where 0 NOT; ``s_ij = q_i . k_j / sqrt(D)`` kept
   where ``j <= i`` and, where ``sliding_window_layout[l]`` is 1,
   ``i - j < sliding_window_size``; head j reads key/value head ``j //
   (H / Hkv)``; ``x1 = x + softmax(s) v W_o``;
4. ``h2 = RMSNorm_2(x1)``; ``y = sum_j w_j W_down[idx_j](relu(h2
   W_gate[idx_j]) * (h2 W_up[idx_j]))`` over the chosen experts THIS
   CHIP HOLDS (ids ``first_expert`` .. + the number held), every held
   expert evaluated on every row; what the other experts would add is
   left out; ``x_out = x1 + y``;
5. after the last layer RMSNorm, the untied head, next-token
   cross-entropy against ``label``, mean over ``mask``. No auxiliary
   loss, no selection bias.

``choices`` (``[layers, T, k]``, the program's routing) and
``near_tie``: a row's proposed experts stand in for the reference's own
top-k only where every one of them has a float32 probability
(``softmax(r)`` over all experts) within ``near_tie`` of the
reference's own k-th largest; everywhere else the reference routes by
itself (``sdar_lm.routed``). ``operands`` is for the CONTROL alone (as
in ``opt_lm.py``): every matmul's operands held in that dtype,
per-tensor scaled; the router stays float32, as the program keeps it.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "w_out" [d, V],
"layers": [{"ln1", "ln2" [d], "wq" [d, H D], "wk", "wv" [d, Hkv D], "wo"
[H D, d], "router" [d, E], "w_gate", "w_up" [Eh, d, f], "w_down" [Eh, f,
d]}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import ROW_BLOCK, attention
from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, _rope, routed


def router_weights(r, k, proposed=None, near_tie=0.0):
    """Router logits r [T, E] -> weights [T, E]: the softmax over the k
    chosen logits at their experts, 0 elsewhere."""
    top_i = routed(jax.nn.softmax(r, -1), k, proposed, near_tie)
    w = jax.nn.softmax(jnp.take_along_axis(r, top_i, axis=1), -1)
    at = jnp.arange(r.shape[0])[:, None]
    return jnp.zeros_like(r).at[at, top_i].set(w)


def expert_layer(p, router_x, h, cfg, first, held, mm, proposed=None,
                 near_tie=0.0):
    """What the `held` experts with ids from `first` (``p["w_gate"]`` ..
    hold those alone) give on rows h [T, d], routed by `router_x`."""
    weight = router_weights(router_x @ p["router"],        # float32
                            cfg["moe_num_active_primary_experts"],
                            proposed, near_tie)

    def one_expert(y, e):                # every held expert, every row
        w_gate, w_up, w_down, w_e = e
        hidden = jax.nn.relu(mm(h, w_gate)) * mm(h, w_up)
        return y + w_e[:, None] * mm(hidden, w_down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["w_gate"], p["w_up"], p["w_down"],
        weight[:, first:first + held].T))
    return y


def hidden(params, tokens, cfg, choices=None, near_tie=0.0, operands=None):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["word_emb"][tokens]
    for l, p in enumerate(params["layers"]):
        h = _rms(x, p["ln1"], eps)
        q = mm(h, p["wq"]).reshape(t, heads, d_head)
        k = mm(h, p["wk"]).reshape(t, kv_heads, d_head)
        v = mm(h, p["wv"]).reshape(t, kv_heads, d_head)
        if cfg["rope_layout"][l]:
            q = _rope(q, pos, float(cfg["rope_theta"]))
            k = _rope(k, pos, float(cfg["rope_theta"]))
        a = attention(q, k, v, cfg["sliding_window_size"]
                      if cfg["sliding_window_layout"][l] else None, mm)
        x1 = x + mm(a, p["wo"])
        x = x1 + expert_layer(
            p, x, _rms(x1, p["ln2"], eps), cfg, cfg["first_expert"],
            cfg["num_experts"], mm,
            None if choices is None else choices[l], near_tie)
    return x


def _p32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)


def lm_loss(params, src, label, mask, cfg):
    """Mean next-token cross-entropy of batch ``src`` [B, T] against
    ``label``, weighted by ``mask``: the train step's cost. The head
    runs on ``ROW_BLOCK`` rows at a time."""
    p = _p32(params)
    with jax.default_matmul_precision("highest"):
        def one(args):
            tokens, target, weight = args
            x = _rms(hidden(p, tokens, cfg), p["final_norm"],
                     cfg["rms_norm_eps"])
            block = min(ROW_BLOCK, x.shape[0])

            def rows(args):
                xb, tb, wb = args
                logp = jax.nn.log_softmax(xb @ p["w_out"])
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
        return r(_rms(rows, p["final_norm"], cfg["rms_norm_eps"])) \
            @ r(p["w_out"])
