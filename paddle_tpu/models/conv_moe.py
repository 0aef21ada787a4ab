"""A hybrid of short convolutions and attention with a mixture of
experts (ISSUE 49): the block as LFM2-8B-A1B carries it.

* A pre-norm block with two RMSNorms: ``x = x + Op(RMSNorm(x))``, then
  ``x = x + FFN(RMSNorm(x))``. The embedding is not scaled; after the
  last layer one more RMSNorm, and the head is the embedding's own
  table (``layers.tied_head``), then the next-token loss.
* `layer_types` names each layer's ``Op``. ``conv``: ``[B, C, X] = h
  W_in`` as ONE projection ``[d, 3d]``, then ``C * conv(B * X)``, a
  causal depthwise convolution of `conv_width` taps over time between
  two gates, no bias and no activation (``layers.gated_short_conv``),
  then ``W_out``: nothing of it looks further back than `conv_width` -
  1 rows. ``full_attention``: ``q = h W_q`` (H heads of D), ``k = h
  W_k``, ``v = h W_v`` (Hkv heads), no bias; RMSNorm over each head of q
  and of k under one weight [D] each, then RoPE (rotate-half) on both
  (``layers.qk_norm_rope``); causal attention, query head j reading
  key/value head ``j // (H / Hkv)`` (``layers.causal_attention``: the
  flash kernels, grouped heads of 64 two to a block); then ``W_o``.
* The first `n_dense` layers' FFN is dense and SiLU-gated, width
  `d_dense`; the others route (``layers.routed_experts``): sigmoid
  scores over all `num_experts`, the `top_k` of score + bias (the
  selection bias moves by `bias_update_rate` a train step towards an
  even load and is never differentiated), weights from the unbiased
  scores over their sum plus `norm_topk_eps`, times
  `routed_scaling_factor`; no shared expert, no auxiliary loss. This
  chip holds `experts_held` experts from `first_expert`.
* Every layer is a ``layers.recompute`` region where `recompute`.
"""

import contextlib

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, _norm, gated_ffn
from paddle_tpu.models.transformer import lm_cost

CONV, FULL = "conv", "full_attention"


def conv_mixer(x, name, conv_width):
    """The convolution operator over the normed stream x ``[B, T, d]``:
    parameters ``<name>_in`` [d, 3d], ``<name>_conv_w`` [K, d],
    ``<name>_out`` [d, d]."""
    d = int(x.shape[-1])
    y = layers.gated_short_conv(_linear(x, 3 * d, name + "_in"), conv_width,
                                name=name + "_conv")
    return _linear(y, d, name + "_out")


def attention_mixer(x, name, n_head, n_kv_head, head_dim, rope_theta, eps):
    """Grouped-query attention over the normed stream x ``[B, T, d]``:
    parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_q_norm``, ``_k_norm``,
    ``_wo``."""
    qk = lambda v, part, heads: layers.qk_norm_rope(
        v, heads, rope_theta, epsilon=eps,
        param_attr=fluid.ParamAttr(name="%s_%s" % (name, part)))
    q = qk(_linear(x, n_head * head_dim, name + "_wq"), "q_norm", n_head)
    k = qk(_linear(x, n_kv_head * head_dim, name + "_wk"), "k_norm",
           n_kv_head)
    v = _linear(x, n_kv_head * head_dim, name + "_wv")
    attn = layers.causal_attention(q, k, v, n_head, n_kv_head)
    return _linear(attn, int(x.shape[-1]), name + "_wo")


def conv_moe_lm(vocab_size, seq_len, layer_types, n_dense, d_model, n_head,
                n_kv_head, head_dim, conv_width, d_dense, d_expert,
                num_experts, experts_held, first_expert=0, top_k=4,
                norm_topk=True, norm_topk_eps=1e-6,
                routed_scaling_factor=1.0, bias_update_rate=1e-3,
                rope_theta=1e6, rms_eps=1e-5, embedding_std=0.02,
                router_std=0.02, recompute=True, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `layer_types` names each
    layer's mixer, the first `n_dense` of them with a dense FFN; the
    embedding is initialised N(0, `embedding_std`), a router N(0,
    `router_std`). Returns ``(avg_cost, logits [B, T, V])``."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    table = fluid.ParamAttr(name=name + "_word_emb",
                            initializer=fluid.initializer.Normal(
                                0., embedding_std))
    x = layers.embedding(src, size=[vocab_size, d_model], param_attr=table)
    for i, kind in enumerate(layer_types):
        at = "%s_l%d" % (name, i)
        if kind not in (CONV, FULL):
            raise ValueError("conv_moe: a layer is %r or %r, got %r"
                             % (CONV, FULL, kind))
        with layers.recompute() if recompute else contextlib.nullcontext():
            h = _norm(x, at + "_ln1", rms_eps)
            if kind == CONV:
                mixed = conv_mixer(h, at, conv_width)
            else:
                mixed = attention_mixer(h, at, n_head, n_kv_head, head_dim,
                                        rope_theta, rms_eps)
            x = layers.elementwise_add(x, mixed)
            h = _norm(x, at + "_ln2", rms_eps)
            if i < n_dense:
                f = gated_ffn(h, d_dense, at + "_ffn")
            else:
                f, _, _, _ = layers.routed_experts(
                    h, num_experts, experts_held, first_expert, top_k,
                    d_expert, norm_topk, name=at + "_moe",
                    score_func="sigmoid",
                    routed_scaling_factor=routed_scaling_factor,
                    bias_update_rate=bias_update_rate,
                    router_std=router_std, norm_topk_eps=norm_topk_eps)
            x = layers.elementwise_add(x, f)
    x = _norm(x, name + "_final_norm", rms_eps)
    logits = layers.tied_head(
        x, fluid.default_main_program().global_block().var(table.name))
    return lm_cost(logits, label, mask, vocab_size), logits
