"""Plain float32 reference of JoyAI-LLM-Flash's training step on ONE
chip's share of a 16-way expert-parallel deployment
(``configs/joyai-llm-flash-train-ep16.json``; source
https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json,
``model_type`` ``joyai_llm_flash``): the DeepSeek-V3 family's block on
a plain residual stream, and its multi-token-prediction module in the
loss.

Straightforward ``jax.numpy``: no kernel, no sort, no grouped matmul.
Every matmul runs at ``highest``. Written from the published
description (``transformers``' ``modeling_deepseek_v3.py`` for the
block, section 2.2 of arXiv:2412.19437 for the module, the released
checkpoint's names), not from the program:

* stream: ``x = E[tokens]``; a layer is ``x += Attn(RMSNorm_1(x))``,
  ``x += FFN(RMSNorm_2(x))``; ``h`` is the stream after the last layer;
  main logits ``RMSNorm_f(h) W_head``;
* MLA: ``c_q = RMSNorm(x W_qa)``, ``q_nope``, ``q_pe`` from it; ``c_kv``
  and the ONE ``k_pe`` from x; ``k_nope``, ``v`` from ``RMSNorm(c_kv)``;
  RoPE on q_pe and k_pe as PUBLISHED (``rope_interleave``): columns
  ``(2i, 2i + 1)`` are a pair and turn at ``theta^(-2i/Dr)``, no
  scaling; ``s = (q_nope k_nope^T + q_pe k_pe^T) (D + Dr)^-0.5``, causal
  softmax, ``o = p v``, ``W_o``; a head at a time;
* experts: ``s = sigmoid(x W_r)`` over ALL 256, the k largest of ``s +
  b``, weights ``s`` at the chosen over their sum times
  ``routed_scaling_factor``; ``y = shared(x) + sum_e w_e E_e(x)`` over
  the chosen experts THIS CHIP HOLDS (ids ``first_expert`` .. + the
  number held), every held expert evaluated on every row; what the
  other experts would add is left out. The first
  ``first_k_dense_replace`` layers run the dense FFN instead;
* the module: with ``x_{t+1}`` the next token, ``u_t =
  [RMSNorm_e(E[x_{t+1}]) ; RMSNorm_h(h_t)] W_eh`` (the embedding's half
  first, as the released inference code has it), ``g = Block(u)`` (one
  more routed layer: the same mask and positions, its own router and
  experts), module logits ``RMSNorm_s(g) W_head`` with the main model's
  table ``E`` and head;
* loss: ``L_main`` is the mean over ``mask`` of the cross-entropy of the
  main logits against ``label``; ``L_mtp`` that of the module's logits
  against ``x_{t+2}`` (``label`` shifted by one, 0 last), row t weighed
  by ``mask_t mask_{t+1}`` and the last row by 0, over the sum of those
  weights; the cost is ``L_main + mtp_loss_weight L_mtp``.

``lm_loss`` takes the next tokens from ``label``, as the train step
does; ``logits_at`` is handed ``src`` alone and makes them by the
traffic's own rule (``traffic.lm_batches``: ``src`` rolled by one, 0
last). It returns the main logits and the module's side by side,
``[rows, 2 V]``, as the program's ``logits`` are.

``choices`` (``[routed layers + 1, T, k]``, the program's routing, the
module's router last) and ``near_tie``: a row's proposed experts stand
in for the reference's own top-k only where every one of them scores
(with the bias) within ``near_tie`` of the reference's own k-th
largest; everywhere else the reference routes by itself. ``operands``
is for the CONTROL alone (as in ``opt_lm.py``): every matmul's operands
held in that dtype, per-tensor scaled; the router stays float32, as the
program keeps it.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "w_out" [d, V],
"layers": [layer...], "mtp": {"enorm", "hnorm" [d], "eh_proj" [2d, d],
"shared_head_norm" [d], and a routed layer's keys}}``; a layer is
``{"ln1", "ln2" [d], "q_a" [d, rq], "q_norm" [rq], "q_b_nope" [rq, H D],
"q_b_pe" [rq, H Dr], "kv_a_c" [d, rkv], "kv_a_pe" [d, Dr], "kv_norm"
[rkv], "kv_b_k", "kv_b_v" [rkv, H D], "o" [H D, d], and either "ffn":
(gate, up, down) or "shared": (gate, up, down), "router" [d, E], "bias"
[E], "w_gate", "w_up" [Eh, d, f], "w_down" [Eh, f, d]}``, the rotary
columns of ``q_b_pe`` (a head's 64) and ``kv_a_pe`` in the PUBLISHED
order.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, routed


def frequencies(cfg):
    """The Dr / 2 frequencies of the rotary part: plain, no scaling."""
    dim = cfg["qk_rope_head_dim"]
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    return float(cfg["rope_theta"]) ** (-2.0 * pair / dim)


def _rope(x, freqs):
    """x [T, ..., Dr] at positions 0..T-1, columns (2i, 2i + 1) a pair."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      odd * jnp.cos(ang) + even * jnp.sin(ang)],
                     -1).reshape(x.shape)


def next_tokens(tokens):
    """[T] -> the tokens one place on, 0 in the last place."""
    return jnp.roll(tokens, -1, axis=-1).at[..., -1].set(0)


def _gated(mm, h, w):
    """``W_down(silu(W_gate h) * (W_up h))``, w (gate, up, down)."""
    return mm(jax.nn.silu(mm(h, w[0])) * mm(h, w[1]), w[2])


def expert_layer(p, x, cfg, proposed=None, near_tie=0.0, operands=None):
    """x [T, d] (the stream) -> what a routed layer adds to it: the
    shared expert and this chip's held experts on ``RMSNorm_2(x)``."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    k, n_exp = cfg["num_experts_per_tok"], cfg["published"]["n_routed_experts"]
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    h = _rms(x, p["ln2"], cfg["rms_norm_eps"])
    score = jax.nn.sigmoid(h @ p["router"])                   # float32
    top_i = routed(score + p["bias"], k, proposed, near_tie)
    chosen = jnp.any(top_i[:, :, None] == jnp.arange(n_exp), axis=1)
    weight = jnp.where(chosen, score, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    weight = weight * cfg["routed_scaling_factor"]

    def one_expert(y, e):                # every held expert, every row
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * _gated(mm, h, (w_gate, w_up, w_down)), None

    y, _ = jax.lax.scan(one_expert, _gated(mm, h, p["shared"]), (
        p["w_gate"], p["w_up"], p["w_down"],
        weight[:, first:first + held].T))
    return y


def hidden(params, tokens, ahead, cfg, choices=None, near_tie=0.0,
           operands=None):
    """tokens, ahead (the next tokens) [T] -> (the stream after the last
    layer, the module's block's output), [T, d] each."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    d_nope, d_rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v = cfg["v_head_dim"]
    freqs, scale = frequencies(cfg), (d_nope + d_rope) ** -0.5
    t = tokens.shape[0]
    future = jnp.arange(t)[None, :] > jnp.arange(t)[:, None]

    def attention(p, x):
        h = _rms(x, p["ln1"], eps)
        c_q = _rms(mm(h, p["q_a"]), p["q_norm"], eps)
        c_kv = _rms(mm(h, p["kv_a_c"]), p["kv_norm"], eps)
        q_nope = mm(c_q, p["q_b_nope"]).reshape(t, heads, d_nope)
        q_pe = _rope(mm(c_q, p["q_b_pe"]).reshape(t, heads, d_rope), freqs)
        k_pe = _rope(mm(h, p["kv_a_pe"]), freqs)               # [T, Dr]
        k_nope = mm(c_kv, p["kv_b_k"]).reshape(t, heads, d_nope)
        v = mm(c_kv, p["kv_b_v"]).reshape(t, heads, d_v)

        def one_head(args):
            qn, qp, kn, vh = args
            s = (mm(qn, kn.T) + mm(qp, k_pe.T)) * scale
            return mm(jax.nn.softmax(jnp.where(future, -jnp.inf, s), -1), vh)

        o = jax.lax.map(one_head, tuple(
            a.transpose(1, 0, 2) for a in (q_nope, q_pe, k_nope, v)))
        return mm(o.transpose(1, 0, 2).reshape(t, heads * d_v), p["o"])

    def block(p, x, proposed):
        x = x + attention(p, x)
        if "ffn" in p:
            return x + _gated(mm, _rms(x, p["ln2"], eps), p["ffn"])
        return x + expert_layer(p, x, cfg, proposed, near_tie, operands)

    proposals = iter([None] * (len(params["layers"]) + 1)
                     if choices is None else choices)
    x = params["word_emb"][tokens]
    for p in params["layers"]:
        x = block(p, x, None if "ffn" in p else next(proposals))
    p = params["mtp"]
    u = mm(jnp.concatenate([_rms(params["word_emb"][ahead], p["enorm"], eps),
                            _rms(x, p["hnorm"], eps)], -1), p["eh_proj"])
    return x, block(p, u, next(proposals))


def _p32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)


def loss_terms(params, src, label, mask, cfg):
    """(L_main, L_mtp) of batch ``src`` [B, T]: the two masked mean
    cross-entropies before the module's is weighed."""
    p = _p32(params)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        def one(args):
            tokens, target, weight = args
            h, g = hidden(p, tokens, target, cfg)
            xent = lambda rows, norm, want: -jnp.take_along_axis(
                jax.nn.log_softmax(_rms(rows, norm, eps) @ p["w_out"]),
                want[:, None], -1)[:, 0]
            both = weight * next_tokens(weight)      # 0 in the last place
            return (jnp.sum(xent(h, p["final_norm"], target) * weight),
                    jnp.sum(xent(g, p["mtp"]["shared_head_norm"],
                                 next_tokens(target)) * both),
                    jnp.sum(both))
        main, ahead, rows_ahead = jax.lax.map(one, (src, label, mask))
        return jnp.sum(main) / jnp.sum(mask), \
            jnp.sum(ahead) / jnp.sum(rows_ahead)


def lm_loss(params, src, label, mask, cfg):
    """``L_main + mtp_loss_weight L_mtp``: the train step's cost."""
    main, ahead = loss_terms(params, src, label, mask, cfg)
    return main + cfg["mtp_loss_weight"] * ahead


def logits_at(params, tokens, first, count, cfg, choices=None,
              near_tie=0.0, operands=None):
    """``[count, 2 V]``: the next-token logits after positions ``first``
    .. ``first + count - 1`` of the one sequence ``tokens`` [T], and
    beside them the module's logits of those rows (for the token after
    next)."""
    p = _p32(params)
    r = _held_in(operands)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h, g = hidden(p, tokens, next_tokens(tokens), cfg, choices,
                      near_tie, operands)
        head = lambda x, norm: r(_rms(
            jax.lax.dynamic_slice_in_dim(x, first, count), norm, eps)) \
            @ r(p["w_out"])
        return jnp.concatenate([
            head(h, p["final_norm"]),
            head(g, p["mtp"]["shared_head_norm"])], -1)
