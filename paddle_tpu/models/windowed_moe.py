"""A mixture-of-experts language model whose stack mixes sliding-window
and full attention (ISSUE 38): the `afmoe` block as Trinity-Mini
carries it.

* The embedding is scaled by ``sqrt(d)`` (muP); after the last layer a
  final RMSNorm, an untied head and the next-token loss.
* A layer is two sublayers under SANDWICH norms, four RMSNorms a layer:
  ``x = x + RMSNorm(Attn(RMSNorm(x)))``, ``x = x + RMSNorm(FFN(
  RMSNorm(x)))``.
* Attn: ``q = h Wq`` (H heads of D), ``k = h Wk``, ``v = h Wv`` (Hkv
  heads), a gate ``g = h Wg`` (H heads of D), no bias anywhere; RMSNorm
  over each head of q and of k under one weight ``[D]`` each
  (``layers.qk_norm_rope``); the layer's KIND, ``layer_types[l]``,
  decides the rest: a ``sliding_attention`` layer turns q and k by
  their rows' positions (RoPE, rotate-half) and sees the `window` keys
  up to its own; a ``full_attention`` layer carries NO position signal
  (QK-norm alone) and sees every earlier key
  (``layers.causal_attention``: the flash kernels, which do not walk the
  key blocks under a window's band). The output is gated, ``attn *
  sigmoid(g)`` (``layers.sigmoid_mul``), before ``Wo``.
* FFN: the first `n_dense` layers' is dense and SiLU-gated, width
  `d_dense`; the others run ONE shared expert of width `d_expert` on
  every row beside the routed experts (``layers.routed_experts``:
  sigmoid scores over all `num_experts`, the `top_k` of score + bias,
  weights from the unbiased scores over their sum times `route_scale`,
  no auxiliary loss; the selection bias moves by `bias_update_rate` a
  train step towards an even load), of which this chip holds
  `experts_held` from `first_expert`.
* Every layer is a ``layers.recompute`` region where `recompute`.
"""

import contextlib
import math

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, _norm, gated_ffn
from paddle_tpu.models.transformer import lm_cost

SLIDING, FULL = "sliding_attention", "full_attention"


def gated_attention(x, name, kind, n_head, n_kv_head, head_dim, window,
                    rope_theta, eps):
    """One layer's attention over the normed stream x ``[B, T, d]``:
    parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_wg``, ``_q_norm``,
    ``_k_norm``, ``_wo``."""
    if kind not in (SLIDING, FULL):
        raise ValueError("windowed_moe: a layer is %r or %r, got %r"
                         % (SLIDING, FULL, kind))
    sliding = kind == SLIDING
    qk = lambda v, part, heads: layers.qk_norm_rope(
        v, heads, rope_theta, epsilon=eps, rotate=sliding,
        param_attr=fluid.ParamAttr(name="%s_%s" % (name, part)))
    q = qk(_linear(x, n_head * head_dim, name + "_wq"), "q_norm", n_head)
    k = qk(_linear(x, n_kv_head * head_dim, name + "_wk"), "k_norm",
           n_kv_head)
    v = _linear(x, n_kv_head * head_dim, name + "_wv")
    gate = _linear(x, n_head * head_dim, name + "_wg")
    attn = layers.causal_attention(q, k, v, n_head, n_kv_head,
                                   window if sliding else 0)
    return _linear(layers.sigmoid_mul(attn, gate), int(x.shape[-1]),
                   name + "_wo")


def windowed_moe_lm(vocab_size, seq_len, layer_types, n_dense, d_model,
                    n_head, n_kv_head, head_dim, window, d_dense, d_expert,
                    num_experts, experts_held, first_expert=0, top_k=8,
                    norm_topk=True, route_scale=1.0, bias_update_rate=1e-3,
                    rope_theta=10000.0, rms_eps=1e-5, embedding_std=0.02,
                    router_std=0.02, recompute=True, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `layer_types` names each
    layer's kind, the first `n_dense` of them dense; the embedding is
    initialised N(0, `embedding_std`), a router N(0, `router_std`). Returns
    ``(avg_cost, logits [B, T, V])``."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    x = layers.embedding(src, size=[vocab_size, d_model],
                         param_attr=fluid.ParamAttr(
                             name=name + "_word_emb",
                             initializer=fluid.initializer.Normal(
                                 0., embedding_std)))
    x = layers.scale(x, math.sqrt(d_model))
    for i, kind in enumerate(layer_types):
        at = "%s_l%d" % (name, i)
        with layers.recompute() if recompute else contextlib.nullcontext():
            a = gated_attention(_norm(x, at + "_ln1", rms_eps), at, kind,
                                n_head, n_kv_head, head_dim, window,
                                rope_theta, rms_eps)
            x = layers.elementwise_add(x, _norm(a, at + "_ln1_post",
                                                rms_eps))
            h = _norm(x, at + "_ln2", rms_eps)
            if i < n_dense:
                f = gated_ffn(h, d_dense, at + "_ffn")
            else:
                routed, _, _, _ = layers.routed_experts(
                    h, num_experts, experts_held, first_expert, top_k,
                    d_expert, norm_topk, name=at + "_moe",
                    score_func="sigmoid", routed_scaling_factor=route_scale,
                    bias_update_rate=bias_update_rate, shared_expert=True,
                    router_std=router_std)
                f = layers.elementwise_add(
                    gated_ffn(h, d_expert, at + "_shared"), routed)
            x = layers.elementwise_add(x, _norm(f, at + "_ln2_post",
                                                rms_eps))
    x = _norm(x, name + "_final_norm", rms_eps)
    logits = _linear(x, vocab_size, name + "_head")
    return lm_cost(logits, label, mask, vocab_size), logits
