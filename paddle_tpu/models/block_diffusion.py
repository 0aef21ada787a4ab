"""A block-diffusion language model (SDAR / BD3-LM) on the pre-norm
block: RMSNorm, grouped-query attention with QK-norm and rotary
positions, and a routed SiLU-gated expert layer of which this chip
holds a share (ISSUE 32).

The layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
after the last layer a final RMSNorm and an untied head. Attn:
``q = x Wq`` (H heads of D), ``k = x Wk``, ``v = x Wv`` (Hkv heads), no
biases; RMSNorm over each head of q and of k; RoPE on q and k by the
row's position; query head h reads key/value head ``h // (H / Hkv)``.
MoE: ``layers.routed_experts``.

The objective: a step noises its sequences block by block
(``layers.block_diffusion_noise``), runs the layers on the 2L rows
``[x_t; x_0]`` a sequence, both halves at positions 0..L-1, under the
block-diffusion mask (``ops/block_diffusion.py``), and puts head and
loss on the noised half alone, unshifted: the masked tokens' cross
entropy against ``x_0`` weighed by 1/t, over the sum of ``mask``, plus
the routers' load-balancing loss.
"""

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.transformer import lm_cost


def _linear(x, size, name):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(name=name))


def pre_norm_block(x, name, seq_len, n_head, n_kv_head, head_dim,
                   d_inner, num_experts, experts_held, first_expert, top_k,
                   norm_topk, block_length, rope_theta, rms_eps):
    """One layer over ``[B, 2 * seq_len, d]`` rows ``[noised; clean]``:
    ``(y, aux_loss)``. Parameters are named from
    `name`: ``_ln1``, ``_wq``, ``_wk``, ``_wv``, ``_q_norm``,
    ``_k_norm``, ``_wo``, ``_ln2`` and the expert layer's ``_moe.*``."""
    named = lambda part: fluid.ParamAttr(name="%s_%s" % (name, part))
    norm = lambda v, part: layers.rms_norm(v, epsilon=rms_eps,
                                           param_attr=named(part))
    qk = lambda v, part, heads: layers.qk_norm_rope(
        v, heads, rope_theta, seq_len, rms_eps, param_attr=named(part))
    h = norm(x, "ln1")
    q = _linear(h, n_head * head_dim, name + "_wq")
    k = _linear(h, n_kv_head * head_dim, name + "_wk")
    v = _linear(h, n_kv_head * head_dim, name + "_wv")
    q, k = qk(q, "q_norm", n_head), qk(k, "k_norm", n_kv_head)
    attn = layers.block_diffusion_attention(q, k, v, n_head, n_kv_head,
                                            block_length)
    x = layers.elementwise_add(x, _linear(attn, int(x.shape[-1]),
                                          name + "_wo"))
    f, aux, _, _ = layers.routed_experts(
        norm(x, "ln2"), num_experts, experts_held, first_expert, top_k,
        d_inner, norm_topk, name=name + "_moe")
    return layers.elementwise_add(x, f), aux


def block_diffusion_lm(vocab_size, seq_len, n_layer, d_model, n_head,
                       n_kv_head, head_dim, d_inner, num_experts,
                       experts_held, first_expert=0, top_k=8, norm_topk=True,
                       block_length=4, mask_id=0, rope_theta=1e6,
                       rms_eps=1e-6, aux_weight=1e-3, embedding_std=0.02,
                       name="bd"):
    """Feeds: src [B, L] int64 (the clean tokens ``x_0``), mask [B, L]
    float32 (weighs the loss) and label [B, L] int64, which is declared
    for the trainers that feed next-token labels and NOT read: the
    targets are ``src`` itself, unshifted. Returns ``(avg_cost, logits
    [B, L, V])``, the logits those of the noised half."""
    src = layers.data("src", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    layers.data("label", [seq_len], dtype="int64")

    noised, weight, _ = layers.block_diffusion_noise(
        src, block_length, mask_id, name=name + "_noise")
    x = layers.embedding(
        layers.concat([noised, src], axis=1), size=[vocab_size, d_model],
        param_attr=fluid.ParamAttr(
            name=name + "_word_emb",
            initializer=fluid.initializer.Normal(0., embedding_std)))
    aux_losses = []
    for i in range(n_layer):
        x, aux = pre_norm_block(
            x, "%s_l%d" % (name, i), seq_len, n_head, n_kv_head, head_dim,
            d_inner, num_experts, experts_held, first_expert, top_k,
            norm_topk, block_length, rope_theta, rms_eps)
        aux_losses.append(aux)
    x = layers.slice(x, axes=[1], starts=[0], ends=[seq_len])
    x = layers.rms_norm(x, epsilon=rms_eps, param_attr=fluid.ParamAttr(
        name=name + "_final_norm"))
    logits = _linear(x, vocab_size, name + "_head")
    avg_cost = lm_cost(logits, src, mask, vocab_size, weight=weight)
    for aux in aux_losses:
        avg_cost = layers.elementwise_add(
            avg_cost, layers.scale(aux, aux_weight))
    return avg_cost, logits
