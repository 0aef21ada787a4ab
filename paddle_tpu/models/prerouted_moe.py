"""A mixture-of-experts language model whose router reads the layer's
INPUT, before attention (ISSUE 46): the block as
SmallThinker-21BA3B-Instruct carries it.

* Every layer is an expert layer; a pre-norm block with two RMSNorms:
  ``x1 = x + Attn(RMSNorm_1(x))``, ``x_out = x1 + MoE(RMSNorm_2(x1);
  routed by x)``. After the last layer a final RMSNorm, an untied head
  and the next-token loss; no auxiliary loss, no selection bias.
* The router: ``r = x W_r`` in float32 from the stream AS IT ENTERS the
  layer, before the first norm (``layers.routed_experts``' `router_input`):
  softmax over all `num_experts`, the `top_k` largest, their weights
  over their sum, which is the softmax over the chosen logits alone.
  Nothing of it waits on attention.
* Attn: ``q = h W_q`` (H heads of D; H D may be wider than the
  stream), ``k = h W_k``, ``v = h W_v`` (Hkv heads), no bias, no norm on
  q or k. Two lists, an entry a layer, say the rest: where
  `rope_layout` is 1, q and k are turned by their rows' positions
  (``layers.rope``, rotate-half); where `window_layout` is 1, a query
  sees its own key and the `window` - 1 before it, elsewhere every
  earlier key (``layers.causal_attention``: the flash kernels, which do
  not walk the key blocks under a window's band). Query head j reads
  key/value head ``j // (H / Hkv)``.
* MoE: ``sum_j w_j W_down[e_j](relu(h2 W_gate[e_j]) * (h2 W_up[e_j]))``
  over the chosen experts this chip holds, `experts_held` from
  `first_expert` (a ReLU gate: ``activation="relu"``).
* Every layer is a ``layers.recompute`` region where `recompute`; the
  router's input is the region's own input.
"""

import contextlib

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, _norm
from paddle_tpu.models.transformer import lm_cost


def grouped_attention(x, name, n_head, n_kv_head, head_dim, window, rotate,
                      rope_theta):
    """One layer's attention over the normed stream x ``[B, T, d]``:
    parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_wo``. `window` 0 is
    full attention; `rotate` turns q and k by their positions."""
    q = _linear(x, n_head * head_dim, name + "_wq")
    k = _linear(x, n_kv_head * head_dim, name + "_wk")
    v = _linear(x, n_kv_head * head_dim, name + "_wv")
    if rotate:
        q = layers.rope(q, n_head, rope_theta)
        k = layers.rope(k, n_kv_head, rope_theta)
    attn = layers.causal_attention(q, k, v, n_head, n_kv_head, window)
    return _linear(attn, int(x.shape[-1]), name + "_wo")


def prerouted_moe_lm(vocab_size, seq_len, window_layout, rope_layout,
                     d_model, n_head, n_kv_head, head_dim, window, d_expert,
                     num_experts, experts_held, first_expert=0, top_k=6,
                     norm_topk=True, rope_theta=1.5e6, rms_eps=1e-6,
                     embedding_std=0.02, router_std=0.02, recompute=True,
                     name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `window_layout` and
    `rope_layout` hold a 0 or a 1 a layer; the embedding is initialised
    N(0, `embedding_std`), a router N(0, `router_std`). Returns
    ``(avg_cost, logits [B, T, V])``."""
    if len(window_layout) != len(rope_layout):
        raise ValueError("prerouted_moe: %d layers by window_layout and %d "
                         "by rope_layout" % (len(window_layout),
                                             len(rope_layout)))
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    x = layers.embedding(src, size=[vocab_size, d_model],
                         param_attr=fluid.ParamAttr(
                             name=name + "_word_emb",
                             initializer=fluid.initializer.Normal(
                                 0., embedding_std)))
    for i, (windowed, rotate) in enumerate(zip(window_layout, rope_layout)):
        at = "%s_l%d" % (name, i)
        with layers.recompute() if recompute else contextlib.nullcontext():
            a = grouped_attention(_norm(x, at + "_ln1", rms_eps), at, n_head,
                                  n_kv_head, head_dim,
                                  window if windowed else 0, bool(rotate),
                                  rope_theta)
            x1 = layers.elementwise_add(x, a)
            f, _, _, _ = layers.routed_experts(
                _norm(x1, at + "_ln2", rms_eps), num_experts, experts_held,
                first_expert, top_k, d_expert, norm_topk, name=at + "_moe",
                router_std=router_std, router_input=x, activation="relu")
            x = layers.elementwise_add(x1, f)
    x = _norm(x, name + "_final_norm", rms_eps)
    logits = _linear(x, vocab_size, name + "_head")
    return lm_cost(logits, label, mask, vocab_size), logits
