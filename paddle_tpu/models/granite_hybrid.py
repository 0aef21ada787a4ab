"""A hybrid of Mamba-2 mixers and attention in which a LAYER IS TWO
SUBLAYERS, a mixer and a gated MLP, and four multipliers scale the
stream, the scores and the logits (ISSUE 64): the stack as IBM's
Granite 4.0-H carries it (``model_type`` ``granitemoehybrid``).

* ``x = embedding_multiplier * Embed(ids)``. Layer l is ``x = x +
  residual_multiplier * Mixer_l(RMSNorm(x))``, then ``x = x +
  residual_multiplier * MLP_l(RMSNorm(x))``: two norms a layer, its
  mixer named by `layer_types`. After the last layer one RMSNorm;
  ``logits = (x E^T) / logits_scaling`` against the embedding's OWN
  table (``layers.tied_head``) and the next-token loss. No position
  signal anywhere: the Mamba-2 layers carry order.
* The multipliers scale ACTIVATIONS, each a Program op ``scale`` (the
  embedding's rows, each sublayer's result before it joins the stream,
  the logits): folded into a weight they would train differently under
  Adam. A device trace gives them the scope ``scale``; no other op of
  the program carries it. `residual_multiplier` is applied in float32
  (``amp.float32()``: under AMP a sublayer's result is bfloat16, where
  0.22 is 0.2197, and a stream that carries 0.12% less of every
  sublayer is another model); the two others are exact in bfloat16 as
  published (12, 1/8) or meet float32 values.
* ``mamba``: ``models/nemotron_h.py`` ``mamba2_mixer`` (the published
  in_proj as its five column blocks, the convolution with bias and
  SiLU, ``layers.ssd_scan``, the gate before a norm over each of the
  `n_group` groups of channels: at ONE group, over all of them).
* ``attention``: grouped-query attention with no rotation and no bias
  (``attention_mixer``), the scores times `attention_multiplier`, which
  is NOT ``head_dim^-0.5`` as published.
* The MLP: ``W_down (silu(h W_gate) * (h W_up))`` (``latent_moe.py``
  ``gated_ffn``: the published ``[d, 2 f]`` input matrix as its two
  halves), no bias.
* Every layer, both sublayers, is ONE ``layers.recompute`` region where
  `recompute`.
"""

import contextlib

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _norm, gated_ffn
from paddle_tpu.models.nemotron_h import attention_mixer, mamba2_mixer
from paddle_tpu.models.transformer import lm_cost

MAMBA, ATTENTION = "mamba", "attention"


def granite_hybrid_lm(vocab_size, seq_len, layer_types, d_model, d_ffn,
                      n_head, n_kv_head, head_dim, n_ssm_head, ssm_head_dim,
                      n_group, d_state, d_conv, embedding_multiplier=1.0,
                      residual_multiplier=1.0, attention_multiplier=0.0,
                      logits_scaling=1.0, rms_eps=1e-5, dt_min=1e-3,
                      dt_max=1e-1, a_max=16.0, embedding_std=0.02,
                      projection_std=0.0, recompute=True, scan_chunk=0,
                      name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `layer_types` names each
    layer's mixer (``"mamba"`` or ``"attention"``); the embedding is
    initialised N(0, `embedding_std`) and every projection of the
    mixers and the MLPs N(0, `projection_std`) (0: the repo's default,
    Xavier); `a_max` and `scan_chunk` are
    ``layers.ssd_scan``'s; `attention_multiplier` 0 is
    ``head_dim^-0.5``. Returns ``(avg_cost, logits [B, T, V])``, the
    logits past their scaling."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    table = fluid.ParamAttr(name=name + "_word_emb",
                            initializer=fluid.initializer.Normal(
                                0., embedding_std))
    x = layers.scale(layers.embedding(src, size=[vocab_size, d_model],
                                      param_attr=table),
                     float(embedding_multiplier))

    def joins(x, out):
        with fluid.amp.float32():
            out = layers.scale(out, float(residual_multiplier))
        return layers.elementwise_add(x, out)

    for i, kind in enumerate(layer_types):
        at = "%s_l%d" % (name, i)
        if kind not in (MAMBA, ATTENTION):
            raise ValueError("granite_hybrid: a layer is %r or %r, got %r"
                             % (MAMBA, ATTENTION, kind))
        with layers.recompute() if recompute else contextlib.nullcontext():
            h = _norm(x, at + "_norm", rms_eps)
            if kind == MAMBA:
                mixed = mamba2_mixer(h, at, n_ssm_head, ssm_head_dim,
                                     n_group, d_state, d_conv, rms_eps,
                                     dt_min, dt_max, scan_chunk, a_max,
                                     projection_std)
            else:
                mixed = attention_mixer(h, at, n_head, n_kv_head, head_dim,
                                        float(attention_multiplier),
                                        projection_std)
            x = joins(x, mixed)
            x = joins(x, gated_ffn(_norm(x, at + "_ffn_norm", rms_eps),
                                   d_ffn, at + "_ffn", projection_std))
    logits = layers.scale(layers.tied_head(
        _norm(x, name + "_final_norm", rms_eps),
        fluid.default_main_program().global_block().var(table.name)),
        1.0 / float(logits_scaling))
    return lm_cost(logits, label, mask, vocab_size), logits
