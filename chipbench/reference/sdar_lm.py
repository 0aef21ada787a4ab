"""Plain float32 reference of SDAR-30B-A3B-Chat's block-diffusion
training step on ONE chip's share of an 8-way expert-parallel
deployment (``configs/sdar-30b-a3b-train-ep8.json``; source
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
``model_type`` ``sdar_moe``).

Straightforward ``jax.numpy``: no kernel, no sort, no grouped matmul.
Every matmul runs at ``highest``. What it computes, and the program
(``paddle_tpu/models/block_diffusion.py``) with it:

* layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
  RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; after the last layer a
  final RMSNorm and the untied head;
* Attn: ``q = x Wq`` (H heads of D), ``k = x Wk``, ``v = x Wv`` (Hkv
  heads), no biases; RMSNorm over each head of q and k (QK-norm); RoPE
  (rotate-half) by the row's position; query head h reads key/value
  head ``h // (H / Hkv)``; softmax(q k^T / sqrt(D) + mask) v; ``Wo``.
  The 2L x 2L mask is written out densely (``bd_mask``) and applied a
  query head at a time;
* MoE: ``p = softmax(x Wr)`` over ALL experts, the k largest, their
  weights divided by their sum, and ``sum_e w_e Wd_e(silu(Wg_e x) *
  (Wu_e x))`` over the chosen experts THIS CHIP HOLDS (ids
  ``first_expert`` .. + the number held), every held expert evaluated
  on every row; what the other experts would add is left out, and that
  partial result goes on to the next layer. The load-balancing loss
  ``E * sum_e f_e P_e`` over all E (f_e: the rows that chose e over the
  rows; P_e: the mean probability) times its coefficient, summed over
  the layers, is added to the cost;
* the objective: ``noise`` remakes the program's draw from the same
  integers (salt, step, batch row); the layers run on ``[x_t; x_0]``,
  both halves at positions 0..L-1; head and loss on the noised half,
  unshifted, ``sum(mask * [masked] / t * CE) / sum(mask)``.

``choices`` (``[layers, 2L, k]``, the program's routing) and
``near_tie``: a row's proposed experts stand in for the reference's own
top-k only where every one of them has a float32 probability within
``near_tie`` of the reference's own k-th largest; everywhere else the
reference routes by itself. ``operands`` is for the CONTROL alone (as in
``opt_lm.py``): every matmul's operands held in that dtype, per-tensor
scaled; the router stays float32, so a control routes as the reference.

``params``: ``{"salt", "step" (int32 scalars), "word_emb" [V, d],
"final_norm" [d], "w_out" [d, V], "layers": [{"ln1", "wq" [d, H D],
"wk", "wv" [d, Hkv D], "q_norm", "k_norm" [D], "wo" [H D, d], "ln2",
"router" [d, E], "w_gate", "w_up" [Eh, d, f], "w_down" [Eh, f, d]}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.opt_lm import _held_in

T_MIN = 1e-3


def noise(salt, step, rows, seq_len, block):
    """(masked [rows, L] bool, t [rows, L]) for batch rows 0..rows-1:
    the draw of ``paddle_tpu.ops.block_diffusion.draw_noise``, made
    again from the same integers."""
    def one(b):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(salt), step), b)
        k_t, k_u = jax.random.split(key)
        t = jnp.repeat(jax.random.uniform(
            k_t, (seq_len // block,), jnp.float32, T_MIN, 1.0), block)
        return jax.random.uniform(k_u, (seq_len,), jnp.float32) < t, t
    return jax.vmap(one)(jnp.arange(rows))


def bd_mask(seq_len, block):
    """[2L, 2L] bool, rows and keys ``[noised; clean]``: whether query
    i sees key j."""
    blk = jnp.arange(seq_len) // block
    same, before = blk[:, None] == blk[None, :], blk[None, :] < blk[:, None]
    none = jnp.zeros_like(same)
    return jnp.block([[same, before], [none, same | before]])


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, pos, theta):
    """x [T, H, D] by positions pos [T], rotate-half."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def routed(probs, k, proposed, near_tie):
    top_p, top_i = jax.lax.top_k(probs, k)
    if proposed is None:
        return top_i
    p_of = jnp.take_along_axis(probs, proposed, axis=1)
    near = jnp.all(p_of >= (1.0 - near_tie) * top_p[:, -1:], axis=1)
    return jnp.where(near[:, None], proposed, top_i)


def hidden(params, tokens, cfg, choices=None, near_tie=0.0, operands=None):
    """tokens [2L] ``[x_t; x_0]`` -> (the last layer's output [2L, d],
    (f [layers, E], P [layers, E]): the share of these rows that chose
    each expert and its mean probability, a layer each, for the
    load-balancing loss). The layers and a layer's held experts are
    ``lax.scan``s over their stacked parameters: the same arithmetic as
    Python loops, a program a twentieth the size to compile."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    k, n_exp = cfg["num_experts_per_tok"], cfg["published"]["num_experts"]
    first, held = cfg["first_expert"], cfg["num_experts"]
    seq = tokens.shape[0] // 2
    pos = jnp.tile(jnp.arange(seq), 2)
    mask = bd_mask(seq, cfg["block_length"])

    def layer(x, p, proposed):
        h = _rms(x, p["ln1"], eps)
        q = mm(h, p["wq"]).reshape(-1, heads, d_head)
        kk = mm(h, p["wk"]).reshape(-1, kv_heads, d_head)
        v = mm(h, p["wv"]).reshape(-1, kv_heads, d_head)
        q = _rope(_rms(q, p["q_norm"], eps), pos, cfg["rope_theta"])
        kk = _rope(_rms(kk, p["k_norm"], eps), pos, cfg["rope_theta"])
        kk, v = kk.transpose(1, 0, 2), v.transpose(1, 0, 2)

        def one_head(args):
            qh, at = args                # [2L, D], the head's number
            kh, vh = kk[at // (heads // kv_heads)], v[at // (heads // kv_heads)]
            s = mm(qh, kh.T) * d_head ** -0.5
            return mm(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), vh)

        attn = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                      jnp.arange(heads)))   # [H, 2L, D]
        x = x + mm(attn.transpose(1, 0, 2).reshape(-1, heads * d_head),
                   p["wo"])

        h = _rms(x, p["ln2"], eps)
        probs = jax.nn.softmax(h @ p["router"], -1)        # float32
        top_i = routed(probs, k, proposed, near_tie)
        chosen = jnp.any(top_i[:, :, None] == jnp.arange(n_exp), axis=1)
        weight = jnp.where(chosen, probs, 0.0)
        if cfg["norm_topk_prob"]:
            weight = weight / jnp.sum(weight, -1, keepdims=True)

        def one_expert(y, e):            # every held expert, every row
            w_gate, w_up, w_down, w_e = e
            up = jax.nn.silu(mm(h, w_gate)) * mm(h, w_up)
            return y + w_e[:, None] * mm(up, w_down), None

        x, _ = jax.lax.scan(one_expert, x, (
            p["w_gate"], p["w_up"], p["w_down"],
            weight[:, first:first + held].T))
        return x, (jnp.mean(chosen.astype(jnp.float32), 0),
                   jnp.mean(probs, 0))

    stacked = jax.tree.map(lambda *a: jnp.stack(a), *params["layers"])
    x = params["word_emb"][tokens]
    if choices is None:
        return jax.lax.scan(lambda x, p: layer(x, p, None), x, stacked)
    return jax.lax.scan(lambda x, pc: layer(x, *pc), x, (stacked, choices))


def _p32(params):
    return jax.tree.map(
        lambda a: a if jnp.issubdtype(jnp.asarray(a).dtype, jnp.integer)
        else jnp.asarray(a, jnp.float32), params)


def _noised(params, src, cfg):
    """src [B, L] -> (x_t [B, L], the loss's weight [B, L])."""
    masked, t = noise(params["salt"], params["step"], src.shape[0],
                      src.shape[1], cfg["block_length"])
    return (jnp.where(masked, cfg["mask_token_id"], src),
            jnp.where(masked, 1.0 / t, 0.0))


def lm_loss(params, src, label, mask, cfg):
    """The first train step's cost on batch ``src`` [B, L] (``label`` is
    not read: the targets are ``src``, unshifted)."""
    del label
    p = _p32(params)
    with jax.default_matmul_precision("highest"):
        x_t, weight = _noised(p, src, cfg)
        seq = src.shape[1]

        def one(args):
            tokens, target = args
            x, balance = hidden(p, tokens, cfg)
            logp = jax.nn.log_softmax(
                _rms(x[:seq], p["final_norm"], cfg["rms_norm_eps"])
                @ p["w_out"])
            return (-jnp.take_along_axis(logp, target[:, None], -1)[:, 0],
                    balance)

        ce, balance = jax.lax.map(one, (jnp.concatenate([x_t, src], 1), src))
        # a router's statistics are over the whole batch's rows
        f, prob = (jnp.mean(a, 0) for a in balance)      # [layers, E]
        aux = jnp.sum(f * prob) * cfg["published"]["num_experts"]
        return jnp.sum(ce * weight * mask) / jnp.sum(mask) \
            + cfg["router_aux_loss_coef"] * aux


def logits_at(params, tokens, first, count, cfg, choices=None,
              near_tie=0.0, operands=None):
    """Logits ``[count, V]`` of the NOISED half's rows ``first`` ..
    ``first + count - 1`` of the one sequence ``tokens`` [L], batch row
    0 at the parameters' step."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        x_t, _ = _noised(p, tokens[None], cfg)
        x, _ = hidden(p, jnp.concatenate([x_t[0], tokens]), cfg, choices,
                      near_tie, operands)
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        return r(_rms(rows, p["final_norm"], cfg["rms_norm_eps"])) \
            @ r(p["w_out"])
