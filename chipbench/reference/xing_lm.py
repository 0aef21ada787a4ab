"""Plain float32 reference of Xing4.0-29B-A4B's training step on ONE
chip's share of an 8-way expert-parallel deployment
(``configs/xing4.0-29b-a4b-train-ep8.json``; source
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json,
``model_type`` ``xing4_0``).

Straightforward ``jax.numpy``: no kernel, no sort, no grouped matmul,
the stream held ``[N, n, d]`` and its mixes as einsums. Every matmul
runs at ``highest``. What it computes (the equations of ISSUE 34, and
the program, ``paddle_tpu/models/latent_moe.py``, with them):

* stream: the embedding copied to n = ``hc_mult`` lanes; after the last
  layer the lanes summed, RMSNorm, the untied head, next-token
  cross-entropy against ``label``, mean over ``mask``;
* hyper-connection round a sublayer F: ``x~ = vec(X) rsqrt(mean(vec(X)^2)
  + eps)`` (no weight), ``H~ = alpha (x~ P) + bias`` cut into pre (n),
  post (n) and res (n x n, row major); ``H_pre = sigmoid``, ``H_post = 2
  sigmoid``, ``H_res = SK(exp(clamp(., lo, hi)))`` with SK
  ``hc_sinkhorn_iters`` rounds of each column over its sum +
  ``hc_eps``, then each row; ``X' = H_res X + H_post^T F(RMSNorm_F(H_pre
  X))``;
* MLA: ``c_q = RMSNorm(x W_qa)``, ``q_nope``, ``q_pe`` from it; ``c_kv``
  and the ONE ``k_pe`` from x; ``k_nope``, ``v`` from ``RMSNorm(c_kv)``;
  RoPE (rotate-half) with YaRN's frequencies on q_pe and k_pe; ``s =
  (q_nope k_nope^T + q_pe k_pe^T) (D + Dr)^-0.5 mscale^2``, causal
  softmax, ``o = p v``, ``W_o``; a head at a time;
* experts: ``s = sigmoid(x W_r)`` over ALL experts, the k largest of
  ``s + b``, weights ``s`` at the chosen over their sum times
  ``routed_scaling_factor``; ``y = shared(x) + sum_e w_e E_e(x)`` over
  the chosen experts THIS CHIP HOLDS (ids ``first_expert`` .. + the
  number held), every held expert evaluated on every row; what the
  other experts would add is left out. The first
  ``first_k_dense_replace`` layers run the dense FFN instead.

``choices`` (``[routed layers, T, k]``, the program's routing) and
``near_tie``: a row's proposed experts stand in for the reference's own
top-k only where every one of them scores (with the bias) within
``near_tie`` of the reference's own k-th largest; everywhere else the
reference routes by itself. ``operands`` is for the CONTROL alone (as
in ``opt_lm.py``): every matmul's operands held in that dtype,
per-tensor scaled; the router and the hyper-connections' coefficients
stay float32, as the program keeps them.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "w_out" [d, V],
"layers": [{"hc_attn", "hc_ffn": {"proj" [n d, n (n + 2)], "alpha" [3],
"bias" [n (n + 2)]}, "ln1", "ln2" [d], "q_a" [d, rq], "q_norm" [rq],
"q_b_nope" [rq, H D], "q_b_pe" [rq, H Dr], "kv_a_c" [d, rkv], "kv_a_pe"
[d, Dr], "kv_norm" [rkv], "kv_b_k", "kv_b_v" [rkv, H D], "o" [H D, d],
and either "ffn": (gate, up, down) or "shared": (gate, up, down),
"router" [d, E], "bias" [E], "w_gate", "w_up" [Eh, d, f], "w_down"
[Eh, f, d]}]}``.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, routed


def yarn_frequencies(cfg):
    """The Dr / 2 frequencies of the rotary part, YaRN's blend."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rope = cfg["rope_scaling"]
    orig = rope["original_max_position_embeddings"]
    at = lambda turns: dim * math.log(orig / (turns * 2 * math.pi)) / (
        2 * math.log(base))
    low = max(math.floor(at(rope["beta_fast"])), 0)
    high = min(math.ceil(at(rope["beta_slow"])), dim - 1)
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = base ** (-2.0 * pair / dim)
    return freq / rope["factor"] * ramp + freq * (1.0 - ramp)


def softmax_scale(cfg):
    rope = cfg["rope_scaling"]
    mscale = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


def _rope(x, freqs):
    """x [T, ..., Dr] at positions 0..T-1, rotate-half."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def sinkhorn(m, iters, eps):
    """m [N, n, n] positive: each column over its sum + eps, then each
    row, `iters` times."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    return m


def hyper_connection(stream, p, sublayer, cfg):
    """stream [N, n, d] -> [N, n, d] round `sublayer` ([N, d] -> [N, d],
    its own pre-norm inside)."""
    n = cfg["hc_mult"]
    flat = stream.reshape(stream.shape[0], -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                                + cfg["rms_norm_eps"])
    alpha = jnp.concatenate([jnp.broadcast_to(a, (width,)) for a, width
                             in zip(p["alpha"], (n, n, n * n))])
    h = alpha * (flat @ p["proj"]) + p["bias"]              # float32
    pre = jax.nn.sigmoid(h[:, :n])
    post = 2.0 * jax.nn.sigmoid(h[:, n:2 * n])
    res = sinkhorn(jnp.exp(jnp.clip(
        h[:, 2 * n:], cfg["mhc_h_res_clamp_min"],
        cfg["mhc_h_res_clamp_max"])).reshape(-1, n, n),
        cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    out = sublayer(jnp.einsum("ni,nid->nd", pre, stream))
    return jnp.einsum("nij,njd->nid", res, stream) \
        + post[:, :, None] * out[:, None, :]


def hidden(params, tokens, cfg, choices=None, near_tie=0.0, operands=None):
    """tokens [T] -> the summed stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    d_nope, d_rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v = cfg["v_head_dim"]
    k, n_exp = cfg["num_experts_per_tok"], cfg["published"]["n_routed_experts"]
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    freqs, scale = yarn_frequencies(cfg), softmax_scale(cfg)
    t = tokens.shape[0]
    future = jnp.arange(t)[None, :] > jnp.arange(t)[:, None]
    gated = lambda h, w: mm(jax.nn.silu(mm(h, w[0])) * mm(h, w[1]), w[2])

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

    def experts(p, x, proposed):
        h = _rms(x, p["ln2"], eps)
        score = jax.nn.sigmoid(h @ p["router"])               # float32
        top_i = routed(score + p["bias"], k, proposed, near_tie)
        chosen = jnp.any(top_i[:, :, None] == jnp.arange(n_exp), axis=1)
        weight = jnp.where(chosen, score, 0.0)
        if cfg["norm_topk_prob"]:
            weight = weight / jnp.sum(weight, -1, keepdims=True)
        weight = weight * cfg["routed_scaling_factor"]

        def one_expert(y, e):            # every held expert, every row
            w_gate, w_up, w_down, w_e = e
            return y + w_e[:, None] * gated(h, (w_gate, w_up, w_down)), None

        y, _ = jax.lax.scan(one_expert, gated(h, p["shared"]), (
            p["w_gate"], p["w_up"], p["w_down"],
            weight[:, first:first + held].T))
        return y

    x = params["word_emb"][tokens]
    stream = jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)
    at = 0
    for p in params["layers"]:
        stream = hyper_connection(stream, p["hc_attn"],
                                  lambda x: attention(p, x), cfg)
        if "ffn" in p:
            ffn = lambda x: gated(_rms(x, p["ln2"], eps), p["ffn"])
        else:
            proposed = None if choices is None else choices[at]
            ffn = lambda x: experts(p, x, proposed)
            at += 1
        stream = hyper_connection(stream, p["hc_ffn"], ffn, cfg)
    return jnp.sum(stream, axis=1)


def _p32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)


def lm_loss(params, src, label, mask, cfg):
    """Mean next-token cross-entropy of batch ``src`` [B, T] against
    ``label``, weighted by ``mask``: the train step's cost."""
    p = _p32(params)
    with jax.default_matmul_precision("highest"):
        def one(args):
            tokens, target, weight = args
            logp = jax.nn.log_softmax(
                _rms(hidden(p, tokens, cfg), p["final_norm"],
                     cfg["rms_norm_eps"]) @ p["w_out"])
            return -jnp.sum(jnp.take_along_axis(
                logp, target[:, None], -1)[:, 0] * weight)
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
