"""A looped language model: ONE stack of layers visited several times
under the same parameters, a head, a loss and an exit gate behind every
visit (ISSUE 59): the LoopLM of "Scaling Latent Reasoning via Looped
Language Models" (arXiv:2510.25741) as Ouro-2.6B carries it, its
training objective's first stage.

* A layer is two sublayers under SANDWICH norms, four RMSNorms a layer
  (``models/windowed_moe.py``'s layer without its gate, its QK-norm and
  its experts): ``s = s + RMSNorm(Attn(RMSNorm(s)))``, ``s = s +
  RMSNorm(FFN(RMSNorm(s)))``. Attn: ``q, k, v = h Wq, h Wk, h Wv`` (H
  heads of D; Hkv; Hkv), no bias, no QK-norm; q and k turned by their
  rows' positions (``layers.rope``, rotate-half), causal softmax
  attention at scale ``D^-0.5`` (``layers.causal_attention``: the flash
  kernels), ``Wo``. FFN: SiLU-gated, width `d_inner`.
* The loop (``layers.repeat``, so the Program holds the stack once):
  ``s^(0) = E[x]``; for t = 1..R: ``s^(t) = N_f(Stack(s^(t-1)))``, N_f
  the ONE final RMSNorm, applied at the end of every visit, its output
  both read by the head and carried into the next visit.
* Behind every visit, inside the loop: the ONE untied head and the
  tokens' next-token cross-entropy ``ell^(t)`` ``[B, T]`` float32
  (``layers.module("loop_head")``, a recompute region of their own, which
  is lowered in row blocks, ``ops/control_flow.py`` _loss_in_row_blocks:
  the logits of a visit and their softmax are never whole values, in the
  forward or in the backward, but a block of rows at a time),
  and the exit gate's logit ``g^(t) = s^(t) w_g + b_g`` ``[B, T]``, a
  ``Linear(d, 1)`` with bias in float32 whatever AMP says
  (``layers.module("exit")``, ``amp.float32``).
* After the loop (``layers.module("exit")``, all float32): the exit
  distribution of a token in log space (``layers.exit_distribution``:
  ``p_t = sigmoid(g_t) prod_{j<t} (1 - sigmoid(g_j))`` for t < R, the
  remainder at R), and the cost ``sum_tokens m [sum_t p_t ell^(t) -
  entropy_weight H(p)] / sum_tokens m`` with ``H(p) = -sum_t p_t log
  p_t``: the paper's entropy-regularised objective under a uniform
  prior. The gate's gradient flows through p.
* Summed on the device in every train step (``layers.step_sum``): each
  visit's masked mean loss (``<name>_loss_sum_<t>``, t from 1), the mean
  expected exit step ``sum_t t p_t`` (``<name>_exit_step_sum``), the
  mean entropy (``<name>_entropy_sum``) and the steps themselves
  (``<name>_steps_sum``: the mean of ``sum_t p_t``, 1 a step).
* `ut_steps` 1 and `entropy_weight` 0: a plain sandwich-norm LM with
  one head (p is 1; the gate is built and reads nothing into the cost).

Not here: the objective's second stage (the model frozen, the gate
trained alone on the loss's improvement) and early exit at inference.
"""

import contextlib

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models.latent_moe import _linear, _norm, gated_ffn


def attention(x, name, n_head, n_kv_head, head_dim, rope_theta):
    """One layer's attention over the normed stream x ``[B, T, d]``:
    parameters ``<name>_wq``, ``_wk``, ``_wv``, ``_wo``."""
    q = layers.rope(_linear(x, n_head * head_dim, name + "_wq"), n_head,
                    rope_theta)
    k = layers.rope(_linear(x, n_kv_head * head_dim, name + "_wk"),
                    n_kv_head, rope_theta)
    v = _linear(x, n_kv_head * head_dim, name + "_wv")
    return _linear(layers.causal_attention(q, k, v, n_head, n_kv_head),
                   int(x.shape[-1]), name + "_wo")


def sandwich_layer(s, at, n_head, n_kv_head, head_dim, d_inner, rope_theta,
                   eps):
    """One layer round the stream s: parameters ``<at>_ln1``,
    ``_ln1_post``, ``_ln2``, ``_ln2_post``, the attention's and
    ``<at>_ffn_gate``, ``_up``, ``_down``."""
    a = attention(_norm(s, at + "_ln1", eps), at, n_head, n_kv_head,
                  head_dim, rope_theta)
    s = layers.elementwise_add(s, _norm(a, at + "_ln1_post", eps))
    f = gated_ffn(_norm(s, at + "_ln2", eps), d_inner, at + "_ffn")
    return layers.elementwise_add(s, _norm(f, at + "_ln2_post", eps))


def looped_lm(vocab_size, seq_len, n_layer, d_model, n_head, n_kv_head,
              head_dim, d_inner, ut_steps=4, rope_theta=1e6, rms_eps=1e-6,
              entropy_weight=0.1, recompute=True, name="lm"):
    """Feeds: src [B, T] int64, label [B, T] int64 (the next tokens),
    mask [B, T] float32 (weighs the loss). Returns ``(avg_cost, logits
    [B, T, V + R])``: the LAST visit's logits and, beside them, the R
    ``log p_t`` of the row's exit distribution."""
    src = layers.data("src", [seq_len], dtype="int64")
    label = layers.data("label", [seq_len], dtype="int64")
    mask = layers.data("mask", [seq_len], dtype="float32")
    region = layers.recompute if recompute else contextlib.nullcontext
    normal = lambda part: fluid.ParamAttr(
        name="%s_%s" % (name, part),
        initializer=fluid.initializer.Normal(0., 0.02))
    head = lambda s: layers.fc(s, vocab_size, num_flatten_dims=2,
                               bias_attr=False, param_attr=normal("head"))
    x = layers.embedding(src, size=[vocab_size, d_model],
                         param_attr=normal("word_emb"))

    loop = layers.repeat(ut_steps)
    with loop.block():
        carried = s = loop.carry(x)
        for i in range(n_layer):
            with region():
                s = sandwich_layer(s, "%s_l%d" % (name, i), n_head,
                                   n_kv_head, head_dim, d_inner, rope_theta,
                                   rms_eps)
        s = _norm(s, name + "_final_norm", rms_eps)
        with layers.module("loop_head"), region():
            ell = layers.reshape(layers.softmax_with_cross_entropy(
                layers.reshape(head(s), [-1, vocab_size]),
                layers.reshape(label, [-1, 1])), [-1, seq_len])
        with layers.module("exit"), fluid.amp.float32():
            gate = layers.reshape(layers.fc(
                s, 1, num_flatten_dims=2, param_attr=normal("gate_w"),
                bias_attr=fluid.ParamAttr(
                    name=name + "_gate_b",
                    initializer=fluid.initializer.Constant(0.))),
                [-1, seq_len])
        loop.update(carried, s)
        loop.output(ell, gate)
    ells, gates = loop()                       # [R, B, T] float32 each

    with layers.module("exit"):
        log_p = layers.exit_distribution(gates)
        p = layers.exp(log_p)
        entropy = layers.scale(layers.reduce_sum(
            layers.elementwise_mul(p, log_p), dim=0), -1.0)
        per_token = layers.elementwise_sub(
            layers.reduce_sum(layers.elementwise_mul(p, ells), dim=0),
            layers.scale(entropy, float(entropy_weight)))
        tokens = layers.reduce_sum(mask)
        mean = lambda v: layers.elementwise_div(
            layers.reduce_sum(layers.elementwise_mul(v, mask)), tokens)
        cost = mean(per_token)
        # the counters: nothing of them is differentiated
        by_visit = lambda v: layers.elementwise_div(layers.reduce_sum(
            layers.elementwise_mul(v, mask), dim=[1, 2]), tokens)
        visit_loss, visit_p = by_visit(ells), by_visit(p)
        for t in range(ut_steps):
            layers.step_sum(
                layers.slice(visit_loss, axes=[0], starts=[t], ends=[t + 1]),
                "%s_loss_sum_%d" % (name, t + 1))
        layers.step_sum(layers.reduce_sum(layers.elementwise_mul(
            visit_p, layers.assign(np.arange(1, ut_steps + 1,
                                             dtype=np.float32)))),
            name + "_exit_step_sum")
        layers.step_sum(mean(entropy), name + "_entropy_sum")
        # (the p of a token sum to 1: the steps the sums were taken over)
        layers.step_sum(layers.reduce_sum(visit_p), name + "_steps_sum")
    with layers.module("loop_head"), layers.forward_only():
        logits = layers.concat(
            [layers.cast(head(loop.final(carried)), "float32"),
             layers.transpose(log_p, perm=[1, 2, 0])], axis=2)
    return cost, logits
