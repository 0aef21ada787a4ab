"""A decoder-hybrid-decoder language model of state-space, attention
and gated-memory layers (ISSUE 40): the SambaY stack (arXiv:2507.06607)
with differential attention (arXiv:2410.05258), as
Phi-4-mini-flash-reasoning carries it.

* ``x = Embed(ids)``, no scale and no position signal anywhere; after
  the last layer a LayerNorm, the head ``Embed^T`` (the embedding's own
  parameter, ``layers.tied_head``) and the next-token loss.
* Every layer is ``x = x + Mixer(LN(x)); x = x + MLP(LN(x))``,
  LayerNorms with weight and bias, the MLP SiLU-gated with no bias
  (``latent_moe.gated_ffn``). The mixer is the layer's KIND:

  ``mamba``         ``[s; z] = W_in h``; ``s = silu(conv4(s))`` causal and
                    depthwise; ``[d; B; C] = W_x s``; ``dt = softplus(W_dt
                    d + b)``; ``y = scan(s, dt, A, B, C, D)`` with a float32
                    state of ``d_state`` a channel; ``out = W_out(y *
                    silu(z))``.
  ``mamba_memory``  the same, and its ``y`` (before the gate) is the
                    MEMORY every ``gmu`` layer after it reads.
  ``sliding``       differential attention under a window of `window`
                    keys.
  ``full``          differential attention over every earlier key; its
                    ``k`` and ``v`` are what every ``cross`` layer after
                    it reads.
  ``gmu``           ``out = W_out(memory * silu(W_in h))``: no scan.
  ``cross``         differential attention of its own queries onto the
                    ``full`` layer's keys and values; it has a query and
                    an output projection alone.

* Differential attention: ``[q; k; v] = W h + b``, H query and Hkv
  key/value heads of D; the heads in pairs, two softmaxes against one
  value of 2D (``layers.diff_attention``, through the streamed flash
  kernels), joined by ``layers.diff_attn``: ``RMSNorm(a1 - lam a2) *
  (1 - lam0)``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)`` for the layer's index
  l in THIS stack; then ``W_o`` with its bias.
* Every layer is a ``layers.recompute`` region where `recompute`. The
  memory and the full layer's k and v LEAVE their regions (a later
  region reads them, so ``recompute_block`` exports them) and their
  gradients are summed back from every reader.
"""

import contextlib
import math

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, gated_ffn
from paddle_tpu.models.transformer import lm_cost

MAMBA, MEMORY, SLIDING, FULL, GMU, CROSS = (
    "mamba", "mamba_memory", "sliding", "full", "gmu", "cross")
KINDS = (MAMBA, MEMORY, SLIDING, FULL, GMU, CROSS)


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _ln(x, name, eps):
    return layers.layer_norm(
        x, begin_norm_axis=len(x.shape) - 1, epsilon=eps,
        param_attr=fluid.ParamAttr(name=name + "_w"),
        bias_attr=fluid.ParamAttr(name=name + "_b"))


def _biased(x, size, name):
    """``x W + b``: parameters ``<name>`` and ``<name>_b``."""
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=fluid.ParamAttr(name=name),
                     bias_attr=fluid.ParamAttr(name=name + "_b"))


def mamba_mixer(h, name, d_inner, d_state, d_conv, dt_rank, scan_chunk=0,
                scan_force=""):
    """(the mixer's output [B, T, d], its scan output y [B, T, d_inner])
    over the normed stream h: parameters ``<name>_in_s``, ``_in_z``
    (the two halves of the published in_proj, so that neither is
    sliced out of a [T, 2 d_inner] value), ``_conv_w``, ``_conv_b``,
    ``_x_dt``, ``_x_b``, ``_x_c`` (x_proj's three column blocks),
    ``_dt``, ``_dt_b``, ``_scan_a_log``, ``_scan_d``, ``_out``."""
    s = layers.ssm_conv(_linear(h, d_inner, name + "_in_s"), d_conv,
                        name=name + "_conv")
    z = _linear(h, d_inner, name + "_in_z")
    dt = layers.ssm_dt(_linear(_linear(s, dt_rank, name + "_x_dt"),
                               d_inner, name + "_dt"), name=name + "_dt_b")
    y = layers.selective_scan(s, dt, _linear(s, d_state, name + "_x_b"),
                              _linear(s, d_state, name + "_x_c"), d_state,
                              scan_chunk, scan_force, name=name + "_scan")
    return _linear(layers.ssm_gate(y, z), int(h.shape[-1]),
                   name + "_out"), y


def gmu_mixer(h, memory, name):
    """``W_out(memory * silu(W_in h))``: parameters ``<name>_in``,
    ``_out``."""
    g = _linear(h, int(memory.shape[-1]), name + "_in")
    return _linear(layers.gmu_gate(memory, g), int(h.shape[-1]),
                   name + "_out")


def attention_mixer(h, name, kind, layer, n_head, n_kv_head, head_dim,
                    window, eps, kv=None):
    """(the mixer's output, (k, v)): differential attention over the
    normed stream h. A ``cross`` layer is handed `kv`, another layer's,
    and projects a query alone. Parameters ``<name>_wq`` / ``_wk`` /
    ``_wv`` / ``_wo`` with ``_b`` each, ``<name>_diff_lq1`` .. ``_lk2``,
    ``<name>_diff_subln``."""
    q = _biased(h, n_head * head_dim, name + "_wq")
    if kind != CROSS:
        kv = (_biased(h, n_kv_head * head_dim, name + "_wk"),
              _biased(h, n_kv_head * head_dim, name + "_wv"))
    a1, a2 = layers.diff_attention(q, kv[0], kv[1], n_head, n_kv_head,
                                   window if kind == SLIDING else 0, kind={
                                       SLIDING: "window"}.get(kind, kind))
    a = layers.diff_attn(a1, a2, head_dim, lambda_init(layer), eps,
                         name=name + "_diff")
    return _biased(a, int(h.shape[-1]), name + "_wo"), kv


def hybrid_ssm_lm(vocab_size, seq_len, layer_kinds, d_model, n_head,
                  n_kv_head, head_dim, window, d_ffn, d_inner, d_state=16,
                  d_conv=4, dt_rank=None, ln_eps=1e-5, embedding_std=0.02,
                  recompute=True, scan_chunk=0, scan_force="", name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `layer_kinds` names each
    layer's mixer (the module's docstring); a ``gmu`` wants a
    ``mamba_memory`` before it and a ``cross`` a ``full``. `scan_chunk`
    and `scan_force` are ``layers.selective_scan``'s. Returns
    ``(avg_cost, logits [B, T, V])``."""
    dt_rank = dt_rank or -(-d_model // 16)
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    table = fluid.ParamAttr(name=name + "_word_emb",
                            initializer=fluid.initializer.Normal(
                                0., embedding_std))
    x = layers.embedding(src, size=[vocab_size, d_model], param_attr=table)
    memory = kv = None
    for i, kind in enumerate(layer_kinds):
        at = "%s_l%d" % (name, i)
        if kind not in KINDS:
            raise ValueError("hybrid_ssm: a layer is one of %s, got %r"
                             % (", ".join(KINDS), kind))
        if (kind == GMU and memory is None) or (kind == CROSS and kv is None):
            raise ValueError(
                "hybrid_ssm: layer %d is a %s and no layer before it "
                "made what it reads" % (i, kind))
        with layers.recompute() if recompute else contextlib.nullcontext():
            h = _ln(x, at + "_ln1", ln_eps)
            if kind in (MAMBA, MEMORY):
                out, y = mamba_mixer(h, at, d_inner, d_state, d_conv,
                                     dt_rank, scan_chunk, scan_force)
                if kind == MEMORY:
                    memory = y
            elif kind == GMU:
                out = gmu_mixer(h, memory, at)
            else:
                out, made = attention_mixer(
                    h, at, kind, i, n_head, n_kv_head, head_dim, window,
                    ln_eps, kv)
                if kind == FULL:
                    kv = made
            x = layers.elementwise_add(x, out)
            x = layers.elementwise_add(
                x, gated_ffn(_ln(x, at + "_ln2", ln_eps), d_ffn,
                             at + "_ffn"))
    x = _ln(x, name + "_final_norm", ln_eps)
    logits = layers.tied_head(
        x, fluid.default_main_program().global_block().var(table.name))
    return lm_cost(logits, label, mask, vocab_size), logits
