"""A hybrid of Mamba-2 mixers, routed experts and attention in which a
LAYER IS ONE SUBLAYER (ISSUE 62): the stack as NVIDIA-Nemotron-3-Nano
carries it (the family's report: Nemotron-H, arXiv:2504.03624).

* ``x = Embed(ids)``, not scaled. Layer l is ``x = x + Mixer_l(
  RMSNorm_l(x))`` and nothing else, its mixer named by one character of
  `pattern`; after the last layer one RMSNorm, a head of its own
  (``<name>_head`` ``[d, V]``, not the embedding's table) and the
  next-token loss. No position signal anywhere: the Mamba-2 layers
  carry order.
* ``M``, Mamba-2. ``[z | x B C | dt] = h W_in``, the published
  in_proj held as its five column blocks, each a parameter of its own
  (``_in_z``, ``_in_x``, ``_in_b``, ``_in_c``, ``_in_dt``), so that no
  part is sliced out of a ``[T, 10304]`` value; a causal depthwise
  convolution of `d_conv` taps with bias and SiLU over x, B and C, its
  published ``[taps, 6144]`` filter held as the three blocks
  (``layers.ssm_conv``: depthwise, so the blocks are the whole);
  ``dt = softplus(dt + dt_bias)`` (``layers.ssm_dt``); the
  state-space-dual scan (``layers.ssd_scan``, ``ops/ssd_scan.py``:
  `n_ssm_head` heads of `ssm_head_dim` in `n_group` groups that share
  ``B_t`` and ``C_t`` of `d_state`, one decay a head, a float32 state
  ``[ssm_head_dim, d_state]`` a head, ``+ D x``); ``RMSNorm(y *
  silu(z)) * w``, the gate first and the norm over each of the
  `n_group` groups of channels (``layers.gated_group_norm``); ``W_out``.
* ``E``, experts. Sigmoid scores over all `num_experts`, the `top_k` of
  score + bias, weights from the unbiased scores over their sum plus
  `norm_topk_eps`, times `routed_scaling_factor`
  (``layers.routed_experts``, ``activation="relu2"``: an expert is TWO
  matrices, ``W_down relu(W_up h)^2``); one shared expert of the same
  form at `d_shared` on every row beside them. This chip holds
  `experts_held` experts from `first_expert`.
* ``*``, attention. ``q = h W_q`` (`n_head` heads of `head_dim`), k and
  v (`n_kv_head` heads), no bias, no rotation; causal softmax at
  ``head_dim^-0.5``, query head j on key/value head ``j // (n_head /
  n_kv_head)`` (``layers.causal_attention``); ``W_o``.
* Every layer is a ``layers.recompute`` region where `recompute`.
"""

import contextlib

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, _norm
from paddle_tpu.models.transformer import lm_cost

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def mamba2_mixer(h, name, n_head, head_dim, n_group, d_state, d_conv, eps,
                 dt_min=1e-3, dt_max=1e-1, scan_chunk=0, a_max=16.0,
                 std=None):
    """The Mamba-2 mixer over the normed stream h ``[B, T, d]``:
    parameters ``<name>_in_z`` / ``_in_x`` / ``_in_b`` / ``_in_c`` /
    ``_in_dt``, ``<name>_conv_x_w`` and ``_b`` (and ``conv_b``,
    ``conv_c``), ``<name>_dt_bias``, ``<name>_scan_a_log``,
    ``_scan_d``, ``<name>_gnorm``, ``<name>_out``. `a_max` is
    ``layers.ssd_scan``'s, `std` ``_linear``'s for the six
    projections."""
    d_inner, d_bc = n_head * head_dim, n_group * d_state
    conv = lambda part, width: layers.ssm_conv(
        _linear(h, width, "%s_in_%s" % (name, part), std), d_conv,
        name="%s_conv_%s" % (name, part))
    z = _linear(h, d_inner, name + "_in_z", std)
    dt = layers.ssm_dt(_linear(h, n_head, name + "_in_dt", std), dt_min,
                       dt_max, name=name + "_dt_bias")
    y = layers.ssd_scan(conv("x", d_inner), dt, conv("b", d_bc),
                        conv("c", d_bc), n_head, n_group, a_max=a_max,
                        chunk=scan_chunk, name=name + "_scan")
    y = layers.gated_group_norm(y, z, n_group, eps, name=name + "_gnorm")
    return _linear(y, int(h.shape[-1]), name + "_out", std)


def relu2_ffn(x, width, name):
    """``W_down relu(W_up x)^2``: parameters ``<name>_up``,
    ``<name>_down``."""
    hidden = layers.square(layers.relu(_linear(x, width, name + "_up")))
    return _linear(hidden, int(x.shape[-1]), name + "_down")


def attention_mixer(h, name, n_head, n_kv_head, head_dim, scale=0.0,
                    std=None):
    """Grouped-query attention with no position signal over the normed
    stream h: parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_wo``.
    `scale` multiplies the scores (0: ``head_dim^-0.5``); `std` is
    ``_linear``'s for the four."""
    q = _linear(h, n_head * head_dim, name + "_wq", std)
    k = _linear(h, n_kv_head * head_dim, name + "_wk", std)
    v = _linear(h, n_kv_head * head_dim, name + "_wv", std)
    return _linear(layers.causal_attention(q, k, v, n_head, n_kv_head,
                                           scale=scale),
                   int(h.shape[-1]), name + "_wo", std)


def nemotron_h_lm(vocab_size, seq_len, pattern, d_model, n_head, n_kv_head,
                  head_dim, n_ssm_head, ssm_head_dim, n_group, d_state,
                  d_conv, d_expert, d_shared, num_experts, experts_held,
                  first_expert=0, top_k=6, norm_topk=True,
                  norm_topk_eps=1e-20, routed_scaling_factor=2.5,
                  bias_update_rate=1e-3, rms_eps=1e-5, dt_min=1e-3,
                  dt_max=1e-1, embedding_std=0.02, router_std=0.02,
                  recompute=True, scan_chunk=0, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). `pattern` names each layer's
    mixer, a character a layer (``M``, ``E``, ``*``); the embedding is
    initialised N(0, `embedding_std`), a router N(0, `router_std`);
    `scan_chunk` is ``layers.ssd_scan``'s. Returns ``(avg_cost, logits
    [B, T, V])``."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    x = layers.embedding(
        src, size=[vocab_size, d_model], param_attr=fluid.ParamAttr(
            name=name + "_word_emb",
            initializer=fluid.initializer.Normal(0., embedding_std)))
    for i, kind in enumerate(pattern):
        at = "%s_l%d" % (name, i)
        if kind not in (MAMBA, EXPERTS, ATTENTION):
            raise ValueError("nemotron_h: a layer is %r, %r or %r, got %r"
                             % (MAMBA, EXPERTS, ATTENTION, kind))
        with layers.recompute() if recompute else contextlib.nullcontext():
            h = _norm(x, at + "_norm", rms_eps)
            if kind == MAMBA:
                mixed = mamba2_mixer(h, at, n_ssm_head, ssm_head_dim,
                                     n_group, d_state, d_conv, rms_eps,
                                     dt_min, dt_max, scan_chunk)
            elif kind == ATTENTION:
                mixed = attention_mixer(h, at, n_head, n_kv_head, head_dim)
            else:
                routed, _, _, _ = layers.routed_experts(
                    h, num_experts, experts_held, first_expert, top_k,
                    d_expert, norm_topk, name=at + "_moe",
                    score_func="sigmoid",
                    routed_scaling_factor=routed_scaling_factor,
                    bias_update_rate=bias_update_rate, shared_expert=True,
                    router_std=router_std, activation="relu2",
                    norm_topk_eps=norm_topk_eps)
                mixed = layers.elementwise_add(
                    relu2_ffn(h, d_shared, at + "_shared"), routed)
            x = layers.elementwise_add(x, mixed)
    logits = _linear(_norm(x, name + "_final_norm", rms_eps), vocab_size,
                     name + "_head")
    return lm_cost(logits, label, mask, vocab_size), logits
