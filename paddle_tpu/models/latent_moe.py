"""A latent-attention mixture-of-experts language model (ISSUE 34): the
DeepSeek-V3 family's block as Xing4.0-29B-A4B carries it.

* The residual stream is `hc_mult` lanes wide, ``[B, T, n * d]``, under
  manifold-constrained hyper-connections: every sublayer F reads
  ``H_pre X``, and the stream goes on as ``H_res X + H_post^T F(.)``
  with input-dependent coefficients, the residual mix doubly stochastic
  (``layers.hyper_connection``, ``ops/hyper_connection.py``). The
  embedding is copied to the lanes; after the last layer they are
  summed, then RMSNorm, an untied head and the next-token loss.
* Attention is multi-head latent attention: ``c_q = RMSNorm(x W_qa)``,
  ``q_nope = c_q W_qb_nope`` and ``q_pe = c_q W_qb_pe`` (H heads of D
  and of Dr), ``c_kv = x W_kva_c`` and ONE rotary key ``k_pe = x
  W_kva_pe`` (Dr), ``k_nope = RMSNorm(c_kv) W_kvb_k``, ``v =
  RMSNorm(c_kv) W_kvb_v`` (H heads of D each); RoPE with YaRN's
  frequencies on q_pe and k_pe; a score is the sum of the two products
  times ``(D + Dr)^-0.5 * mscale^2`` (``layers.mla_attention``); no
  bias anywhere. The published ``q_b``, ``kv_a`` and ``kv_b`` matrices
  are held as their column blocks, each a parameter of its own, so that
  no projection's output is sliced a head at a time.
* The first `n_dense` layers' FFN is dense and SiLU-gated, width
  `d_dense`; the others run ONE shared expert of width `d_expert` on
  every row beside the routed experts (``layers.routed_experts``:
  sigmoid scores over all `num_experts`, the `top_k` of score + bias,
  weights from the unbiased scores over their sum times
  `routed_scaling_factor`, no auxiliary loss; the selection bias moves
  by `bias_update_rate` a train step towards an even load), of which
  this chip holds `experts_held` from `first_expert`.
* Every layer is a ``layers.recompute`` region where `recompute`.
"""

import contextlib
import math

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.transformer import lm_cost
from paddle_tpu.ops.rotary import yarn_inv_freq


def _linear(x, size, name):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(name=name))


def _norm(x, name, eps):
    return layers.rms_norm(x, epsilon=eps,
                           param_attr=fluid.ParamAttr(name=name))


def gated_ffn(x, width, name):
    """``W_down(silu(W_gate x) * (W_up x))``: parameters ``<name>_gate``,
    ``_up``, ``_down``."""
    hidden = layers.silu_mul(_linear(x, width, name + "_gate"),
                             _linear(x, width, name + "_up"))
    return _linear(hidden, int(x.shape[-1]), name + "_down")


def attention_scale(d_nope, d_rope, rope):
    """``(D + Dr)^-0.5`` times YaRN's ``mscale^2`` (``0.1 mscale_all_dim
    ln(factor) + 1`` where the factor is over 1)."""
    factor, all_dim = rope.get("factor", 1.0), rope.get("mscale_all_dim", 0)
    mscale = 0.1 * all_dim * math.log(factor) + 1.0 \
        if factor > 1 and all_dim else 1.0
    return (d_nope + d_rope) ** -0.5 * mscale * mscale


def rope_frequencies(d_rope, theta, rope):
    return yarn_inv_freq(
        d_rope, theta, rope.get("factor", 1.0),
        rope.get("original_max_position_embeddings", 4096),
        rope.get("beta_fast", 32.0), rope.get("beta_slow", 1.0))


def latent_attention(x, name, n_head, q_rank, kv_rank, d_nope, d_rope, d_v,
                     inv_freq, scale, eps):
    """MLA over x ``[B, T, d]``: parameters ``<name>_q_a``, ``_q_norm``,
    ``_q_b_nope``, ``_q_b_pe``, ``_kv_a_c``, ``_kv_a_pe``, ``_kv_norm``,
    ``_kv_b_k``, ``_kv_b_v``, ``_o``."""
    c_q = _norm(_linear(x, q_rank, name + "_q_a"), name + "_q_norm", eps)
    c_kv = _norm(_linear(x, kv_rank, name + "_kv_a_c"), name + "_kv_norm",
                 eps)
    attn = layers.mla_attention(
        _linear(c_q, n_head * d_nope, name + "_q_b_nope"),
        _linear(c_q, n_head * d_rope, name + "_q_b_pe"),
        _linear(c_kv, n_head * d_nope, name + "_kv_b_k"),
        _linear(x, d_rope, name + "_kv_a_pe"),
        _linear(c_kv, n_head * d_v, name + "_kv_b_v"),
        n_head, inv_freq, scale)
    return _linear(attn, int(x.shape[-1]), name + "_o")


def latent_moe_lm(vocab_size, seq_len, n_layer, n_dense, d_model, n_head,
                  q_rank, kv_rank, d_nope, d_rope, d_v, d_dense, d_expert,
                  num_experts, experts_held, first_expert=0, top_k=4,
                  norm_topk=True, routed_scaling_factor=1.0,
                  bias_update_rate=1e-3, hc_mult=4, hc_sinkhorn_iters=20,
                  hc_eps=1e-6, hc_clamp=(-30.0, 30.0), rope_theta=10000.0,
                  rope_scaling=None, rms_eps=1e-6, embedding_std=0.02,
                  recompute=True, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). Returns ``(avg_cost, logits
    [B, T, V])``."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    rope = rope_scaling or {}
    inv_freq = rope_frequencies(d_rope, rope_theta, rope)
    scale = attention_scale(d_nope, d_rope, rope)
    hc = lambda x, stage, part=None, **kw: layers.hyper_connection(
        x, hc_mult, stage, sinkhorn_iters=hc_sinkhorn_iters,
        sinkhorn_eps=hc_eps, clamp=hc_clamp, epsilon=rms_eps,
        name=part, **kw)

    x = layers.embedding(src, size=[vocab_size, d_model],
                         param_attr=fluid.ParamAttr(
                             name=name + "_word_emb",
                             initializer=fluid.initializer.Normal(
                                 0., embedding_std)))
    stream = hc(x, "widen")
    for i in range(n_layer):
        at = "%s_l%d" % (name, i)
        with layers.recompute() if recompute else contextlib.nullcontext():
            h, mixes = hc(stream, "mix", at + "_hc_attn")
            a = latent_attention(_norm(h, at + "_ln1", rms_eps), at, n_head,
                                 q_rank, kv_rank, d_nope, d_rope, d_v,
                                 inv_freq, scale, rms_eps)
            stream = hc(stream, "merge", y=a, coefficients=mixes)
            h, mixes = hc(stream, "mix", at + "_hc_ffn")
            h = _norm(h, at + "_ln2", rms_eps)
            if i < n_dense:
                f = gated_ffn(h, d_dense, at + "_ffn")
            else:
                routed, _, _, _ = layers.routed_experts(
                    h, num_experts, experts_held, first_expert, top_k,
                    d_expert, norm_topk, name=at + "_moe",
                    score_func="sigmoid",
                    routed_scaling_factor=routed_scaling_factor,
                    bias_update_rate=bias_update_rate, shared_expert=True)
                f = layers.elementwise_add(
                    gated_ffn(h, d_expert, at + "_shared"), routed)
            stream = hc(stream, "merge", y=f, coefficients=mixes)
    x = _norm(hc(stream, "narrow"), name + "_final_norm", rms_eps)
    logits = _linear(x, vocab_size, name + "_head")
    return lm_cost(logits, label, mask, vocab_size), logits
