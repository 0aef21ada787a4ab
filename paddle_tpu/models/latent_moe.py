"""A latent-attention mixture-of-experts language model: the DeepSeek-V3
family's block, as two of the benchmark's configurations carry it.
Xing4.0-29B-A4B (ISSUE 34, ``chipbench/archs/xing.py``) runs it on a
stream of four lanes and without the multi-token-prediction module;
JoyAI-LLM-Flash (ISSUE 55, ``chipbench/archs/joyai.py``) on a plain
stream and with it. ONE file builds both: what a configuration does not
ask for builds no op, and Xing's program is op for op what it was
before the file learnt the second (``tests/chipbench/
test_chipbench_joyai.py`` pins its op types and parameter names).

* The residual stream, two kinds. `hc_mult` lanes (over 1), ``[B, T,
  n * d]``, under manifold-constrained hyper-connections: every
  sublayer F reads ``H_pre X``, and the stream goes on as ``H_res X +
  H_post^T F(.)`` with input-dependent coefficients, the residual mix
  doubly stochastic (``layers.hyper_connection``,
  ``ops/hyper_connection.py``); the embedding is copied to the lanes
  and after the last layer they are summed. Or PLAIN (`hc_mult` None or
  1): ``x + F(RMSNorm(x))``, no ``hyper_connection`` op at all (at one
  lane that op still mixes by sigmoid coefficients, which is another
  model). Then RMSNorm, an untied head and the next-token loss.
* Attention is multi-head latent attention: ``c_q = RMSNorm(x W_qa)``,
  ``q_nope = c_q W_qb_nope`` and ``q_pe = c_q W_qb_pe`` (H heads of D
  and of Dr), ``c_kv = x W_kva_c`` and ONE rotary key ``k_pe = x
  W_kva_pe`` (Dr), ``k_nope = RMSNorm(c_kv) W_kvb_k``, ``v =
  RMSNorm(c_kv) W_kvb_v`` (H heads of D each); RoPE on q_pe and k_pe,
  with YaRN's frequencies where `rope_scaling` gives a factor and the
  plain ``theta^(-2i/Dr)`` where it is None; a score is the sum of the
  two products times ``(D + Dr)^-0.5 * mscale^2``
  (``layers.mla_attention``); no bias anywhere. The published ``q_b``,
  ``kv_a`` and ``kv_b`` matrices are held as their column blocks, each a
  parameter of its own, so that no projection's output is sliced a head
  at a time. The kernels rotate HALVES: columns i and i + Dr/2 of
  ``q_b_pe`` (a head's) and of ``kv_a_pe`` turn together at frequency
  i. A source that pairs columns ``(2i, 2i + 1)`` (``rope_interleave``)
  is held with those columns de-interleaved, evens then odds, once, by
  whoever loads it: the score is the same sum, and no activation is
  permuted in the step (``archs/joyai.py`` hands the reference the
  columns back in the published order).
* The first `n_dense` layers' FFN is dense and SiLU-gated, width
  `d_dense`; the others run ONE shared expert of width `d_expert` on
  every row beside the routed experts (``layers.routed_experts``:
  sigmoid scores over all `num_experts`, the `top_k` of score + bias,
  weights from the unbiased scores over their sum times
  `routed_scaling_factor`, no auxiliary loss; the selection bias moves
  by `bias_update_rate` a train step towards an even load), of which
  this chip holds `experts_held` from `first_expert`.
* `n_nextn` 1: a multi-token-prediction module behind the last layer
  (DeepSeek-V3's technical report, section 2.2, under the released
  checkpoint's names), built inside ``layers.module("mtp")``. With
  ``h_t`` the layer stack's output at row t BEFORE the final norm and
  ``x_{t+1}`` the fed ``label``: ``u_t = [RMSNorm_e(E[x_{t+1}]) ;
  RMSNorm_h(h_t)] W_eh`` (the SAME embedding table E, ``W_eh`` ``[2d,
  d]``, the embedding's half first), one more routed block over u
  (attention under the same mask and positions, a shared expert, a
  router, bias and held experts of its own), ``RMSNorm_s``, and the
  SAME head; its loss is the cross-entropy against ``x_{t+2}``
  (``label`` shifted by one more inside the program, 0 last), row t
  weighed by ``mask_t mask_{t+1}`` and the last row by 0, over the sum
  of those weights. The cost is ``L_main + nextn_weight L_mtp``; the
  table's and the head's gradients are the sums of their two uses
  (one parameter, read twice: the step differentiates one function).
  The two terms are summed on the device in every train step
  (``layers.step_sum``: ``<name>_main_loss_sum``,
  ``<name>_mtp_loss_sum``), and the logits returned are the main
  model's and the module's side by side, ``[B, T, 2 V]``.
* Every layer, and the module's block, is a ``layers.recompute`` region
  where `recompute`.
"""

import contextlib
import math

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.transformer import lm_cost
from paddle_tpu.ops.rotary import yarn_inv_freq


def _linear(x, size, name, std=None):
    """``x W``, no bias; W drawn N(0, `std`) where one is given (the
    repo's default, Xavier, otherwise)."""
    init = fluid.initializer.Normal(0., std) if std else None
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                     param_attr=fluid.ParamAttr(name=name, initializer=init))


def _norm(x, name, eps):
    return layers.rms_norm(x, epsilon=eps,
                           param_attr=fluid.ParamAttr(name=name))


def gated_ffn(x, width, name, std=None):
    """``W_down(silu(W_gate x) * (W_up x))``: parameters ``<name>_gate``,
    ``_up``, ``_down``, each as `_linear` draws it at `std`."""
    hidden = layers.silu_mul(_linear(x, width, name + "_gate", std),
                             _linear(x, width, name + "_up", std))
    return _linear(hidden, int(x.shape[-1]), name + "_down", std)


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
                  n_nextn=0, nextn_weight=0.3, recompute=True, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). Returns ``(avg_cost, logits
    [B, T, V])``; with the module (`n_nextn` 1) the cost is ``L_main +
    nextn_weight L_mtp`` and the logits ``[B, T, 2 V]``, the module's
    behind the main model's."""
    lanes = hc_mult or 1
    if n_nextn not in (0, 1) or (n_nextn and lanes > 1):
        raise ValueError(
            "latent_moe_lm builds one multi-token-prediction module, on a "
            "plain stream (n_nextn %r, hc_mult %r): no source says how "
            "modules chain here, or what lanes a module's block reads"
            % (n_nextn, hc_mult))
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    rope = rope_scaling or {}
    inv_freq = rope_frequencies(d_rope, rope_theta, rope)
    scale = attention_scale(d_nope, d_rope, rope)
    region = layers.recompute if recompute else contextlib.nullcontext
    table = fluid.ParamAttr(name=name + "_word_emb",
                            initializer=fluid.initializer.Normal(
                                0., embedding_std))
    if lanes > 1:
        hc = lambda x, stage, part=None, **kw: layers.hyper_connection(
            x, lanes, stage, sinkhorn_iters=hc_sinkhorn_iters,
            sinkhorn_eps=hc_eps, clamp=hc_clamp, epsilon=rms_eps,
            name=part, **kw)

        def around(stream, part, sublayer):
            h, mixes = hc(stream, "mix", part)
            return hc(stream, "merge", y=sublayer(h), coefficients=mixes)
    else:
        hc = lambda x, stage: x          # one lane: widen, narrow
        around = lambda stream, part, sublayer: layers.elementwise_add(
            stream, sublayer(stream))

    def block(stream, at, dense):
        """One layer round the stream: latent attention, then the dense
        FFN or the shared expert beside the routed ones, each behind its
        own RMSNorm."""
        def ffn(h):
            h = _norm(h, at + "_ln2", rms_eps)
            if dense:
                return gated_ffn(h, d_dense, at + "_ffn")
            routed, _, _, _ = layers.routed_experts(
                h, num_experts, experts_held, first_expert, top_k,
                d_expert, norm_topk, name=at + "_moe", score_func="sigmoid",
                routed_scaling_factor=routed_scaling_factor,
                bias_update_rate=bias_update_rate, shared_expert=True)
            return layers.elementwise_add(
                gated_ffn(h, d_expert, at + "_shared"), routed)

        stream = around(stream, at + "_hc_attn", lambda h: latent_attention(
            _norm(h, at + "_ln1", rms_eps), at, n_head, q_rank, kv_rank,
            d_nope, d_rope, d_v, inv_freq, scale, rms_eps))
        return around(stream, at + "_hc_ffn", ffn)

    stream = hc(layers.embedding(src, size=[vocab_size, d_model],
                                 param_attr=table), "widen")
    for i in range(n_layer):
        with region():
            stream = block(stream, "%s_l%d" % (name, i), i < n_dense)
    hidden = hc(stream, "narrow")
    logits = _linear(_norm(hidden, name + "_final_norm", rms_eps),
                     vocab_size, name + "_head")
    if not n_nextn:
        return lm_cost(logits, label, mask, vocab_size), logits

    with layers.module("mtp"):
        at = name + "_mtp"
        ahead = layers.embedding(label, size=[vocab_size, d_model],
                                 param_attr=table)
        u = _linear(layers.concat([_norm(ahead, at + "_enorm", rms_eps),
                                   _norm(hidden, at + "_hnorm", rms_eps)],
                                  axis=2), d_model, at + "_eh_proj")
        with region():
            u = block(u, at, False)
        logits_ahead = _linear(_norm(u, at + "_shared_head_norm", rms_eps),
                               vocab_size, name + "_head")
    # both losses behind both heads: what follows the first loss op is
    # then the loss and nothing else, as a trace's reader takes it to be
    cost = lm_cost(logits, label, mask, vocab_size)
    layers.step_sum(cost, name + "_main_loss_sum")
    with layers.module("mtp"):
        # label is src rolled by one, so the token after next is label
        # rolled by one more; a row counts where both of its tokens do
        end = lambda x: layers.fill_constant_batch_size_like(
            x, [-1, 1], x.dtype, 0)
        rolled = lambda x: layers.concat(
            [layers.slice(x, axes=[1], starts=[1], ends=[seq_len]), end(x)],
            axis=1)
        cost_ahead = lm_cost(logits_ahead, rolled(label),
                             layers.elementwise_mul(mask, rolled(mask)),
                             vocab_size)
        layers.step_sum(cost_ahead, name + "_mtp_loss_sum")
        return (layers.elementwise_add(
                    cost, layers.scale(cost_ahead, nextn_weight)),
                layers.concat([logits, logits_ahead], axis=2))
