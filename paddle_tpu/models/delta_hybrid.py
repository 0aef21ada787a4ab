"""A dense hybrid of linear attention under a gated delta rule and full
attention (ISSUE 53): the block as Olmo-Hybrid-7B carries it.

* A POST-norm block with two RMSNorms, each on a sublayer's OUTPUT and
  none on its input: ``x = x + RMSNorm(Mixer(x))``, then ``x = x +
  RMSNorm(MLP(x))``, ``MLP(x) = W_down(silu(x W_gate) * (x W_up))``.
  The embedding is not scaled; after the last layer one more RMSNorm,
  an untied head, then the next-token loss. No bias anywhere.
* `layer_types` names each layer's ``Mixer``. ``linear_attention``:
  ``q, k = x W_q, x W_k`` (H heads of `d_k`), ``v = x W_v`` (H heads of
  `d_v`), each through a causal depthwise convolution of `conv_width`
  taps and a SiLU, no bias (``layers.ssm_conv``); q and k over their
  head's l2 norm, the query times ``d_k^-0.5``
  (``layers.l2_norm_scale``); ``beta = beta_scale * sigmoid(x W_b)``
  and ``g = -exp(A_log) * softplus(x W_a + dt_bias)`` a head and row
  (``layers.delta_gates``); the rule, its state ``[d_k, d_v]`` a head
  in float32 (``layers.gated_delta_rule``); ``RMSNorm(o) * silu(x
  W_g)`` over each head of `d_v` under one weight
  (``layers.gated_rms_norm``); then ``W_o``. ``full_attention``: ``q =
  RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)``, each norm over the WHOLE
  projection under a weight as long as it, ``v = x W_v``, `n_head`
  heads of `head_dim` with no groups and NO position signal (the
  linear layers carry order), causal softmax at ``head_dim^-0.5``
  (``layers.causal_attention``: the flash kernels); then ``W_o``.
* Every size of a head and every count of heads is an argument: a chip
  that holds some of a layer's heads states them, and nothing here
  divides the hidden size by a count.
* Every layer is a ``layers.recompute`` region where `recompute`.
"""

import contextlib

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, _norm, gated_ffn
from paddle_tpu.models.transformer import lm_cost

LINEAR, FULL = "linear_attention", "full_attention"


def delta_mixer(x, name, n_head, d_k, d_v, conv_width, beta_scale, eps,
                chunk=0):
    """Linear attention under the gated delta rule over the stream x
    ``[B, T, d]``: parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_wg``,
    ``_wa``, ``_wb``, ``_conv_q_w``, ``_conv_k_w``, ``_conv_v_w``,
    ``_gates_a_log``, ``_gates_dt_bias``, ``_o_norm``, ``_wo``."""
    conv = lambda part, width: layers.ssm_conv(
        _linear(x, n_head * width, "%s_w%s" % (name, part)), conv_width,
        bias=False, name="%s_conv_%s" % (name, part))
    q = layers.l2_norm_scale(conv("q", d_k), n_head, scale=d_k ** -0.5)
    k = layers.l2_norm_scale(conv("k", d_k), n_head)
    g, beta = layers.delta_gates(
        _linear(x, n_head, name + "_wa"), _linear(x, n_head, name + "_wb"),
        beta_scale=beta_scale, name=name + "_gates")
    o = layers.gated_delta_rule(q, k, conv("v", d_v), g, beta, n_head,
                                chunk=chunk)
    y = layers.gated_rms_norm(o, _linear(x, n_head * d_v, name + "_wg"),
                              d_v, epsilon=eps, name=name + "_o_norm")
    return _linear(y, int(x.shape[-1]), name + "_wo")


def attention_mixer(x, name, n_head, head_dim, eps):
    """Full attention with no position signal over the stream x ``[B,
    T, d]``: parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_q_norm``,
    ``_k_norm`` (each as long as its projection), ``_wo``."""
    width = n_head * head_dim
    q = _norm(_linear(x, width, name + "_wq"), name + "_q_norm", eps)
    k = _norm(_linear(x, width, name + "_wk"), name + "_k_norm", eps)
    attn = layers.causal_attention(q, k, _linear(x, width, name + "_wv"),
                                   n_head, n_head)
    return _linear(attn, int(x.shape[-1]), name + "_wo")


def delta_hybrid_lm(vocab_size, seq_len, layer_types, d_model, d_ffn,
                    n_head, head_dim, n_linear_head, linear_key_head_dim,
                    linear_value_head_dim, conv_width=4, beta_scale=2.0,
                    rms_eps=1e-6, embedding_std=0.02, recompute=True,
                    delta_chunk=0, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `layer_types` names each
    layer's mixer; a full layer runs `n_head` heads of `head_dim`, a
    linear layer `n_linear_head` heads with keys of
    `linear_key_head_dim` and values of `linear_value_head_dim`;
    `beta_scale` 2.0 lets the rule's transition have negative
    eigenvalues. The embedding is initialised N(0, `embedding_std`).
    Returns ``(avg_cost, logits [B, T, V])``."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    x = layers.embedding(src, size=[vocab_size, d_model],
                         param_attr=fluid.ParamAttr(
                             name=name + "_word_emb",
                             initializer=fluid.initializer.Normal(
                                 0., embedding_std)))
    for i, kind in enumerate(layer_types):
        at = "%s_l%d" % (name, i)
        if kind not in (LINEAR, FULL):
            raise ValueError("delta_hybrid: a layer is %r or %r, got %r"
                             % (LINEAR, FULL, kind))
        with layers.recompute() if recompute else contextlib.nullcontext():
            if kind == LINEAR:
                mixed = delta_mixer(x, at, n_linear_head,
                                    linear_key_head_dim,
                                    linear_value_head_dim, conv_width,
                                    beta_scale, rms_eps, delta_chunk)
            else:
                mixed = attention_mixer(x, at, n_head, head_dim, rms_eps)
            x = layers.elementwise_add(x, _norm(mixed, at + "_ln1", rms_eps))
            x = layers.elementwise_add(
                x, _norm(gated_ffn(x, d_ffn, at + "_ffn"), at + "_ln2",
                         rms_eps))
    x = _norm(x, name + "_final_norm", rms_eps)
    logits = _linear(x, vocab_size, name + "_head")
    return lm_cost(logits, label, mask, vocab_size), logits
