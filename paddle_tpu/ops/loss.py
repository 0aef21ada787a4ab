"""Loss ops.

Reference parity: operators/{cross_entropy,softmax_with_cross_entropy,
sigmoid_cross_entropy_with_logits,hinge_loss,huber_loss,log_loss,
smooth_l1_loss,rank_loss,margin_rank_loss,modified_huber_loss,mean_iou,
nce}_op.cc. All lower to numerically-stable jnp expressions (logsumexp-based
softmax losses) that XLA fuses with the producing matmul.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register


def _gather_label_prob(x, label):
    """x: [..., C] probabilities or logits; label: [..., 1] or [...] int."""
    if label.ndim == x.ndim and label.shape[-1] == 1:
        label = label.reshape(label.shape[:-1])
    return jnp.take_along_axis(
        x, label.astype(jnp.int32)[..., None], axis=-1), label


@register("cross_entropy")
def _cross_entropy(ctx, op):
    x = ctx.in1(op, "X")          # probabilities [N, C]
    if x.dtype == jnp.bfloat16:   # AMP: loss math in fp32 (loss-scale-free)
        x = x.astype(jnp.float32)
    label = ctx.in1(op, "Label")
    if x.shape[0] != label.shape[0]:
        raise ValueError(
            "cross_entropy batch mismatch: X has %d rows, Label has %d "
            "(a silent broadcast here would train the class prior)"
            % (x.shape[0], label.shape[0]))
    if op.attr("soft_label", False):
        if label.ndim == x.ndim - 1:
            label = label[..., None]
        loss = -jnp.sum(label * jnp.log(jnp.clip(x, 1e-20)), axis=-1,
                        keepdims=True)
    else:
        ignore_index = op.attr("ignore_index", -100)
        if label.ndim == x.ndim and label.shape[-1] == 1:
            flat_label = label.reshape(label.shape[:-1])
        else:
            flat_label = label
        valid = flat_label != ignore_index
        safe_label = jnp.where(valid, flat_label, 0)
        p = jnp.take_along_axis(
            x, safe_label.astype(jnp.int32)[..., None], axis=-1)
        loss = -jnp.log(jnp.clip(p, 1e-20)) * valid[..., None].astype(x.dtype)
    ctx.set_out(op, "Y", loss)


@register("softmax_with_cross_entropy")
def _softmax_xent(ctx, op):
    logits = ctx.in1(op, "Logits")
    if logits.dtype == jnp.bfloat16:   # AMP: loss math in fp32
        logits = logits.astype(jnp.float32)
    label = ctx.in1(op, "Label")
    log_sm = jax.nn.log_softmax(logits, axis=-1)
    if op.attr("soft_label", False):
        loss = -jnp.sum(label * log_sm, axis=-1, keepdims=True)
    else:
        lp, _ = _gather_label_prob(log_sm, label)
        loss = -lp
    ctx.set_out(op, "Softmax", jnp.exp(log_sm))
    ctx.set_out(op, "Loss", loss)


def hard_label_rows(logits, label):
    """ROWS of the hard-label `softmax_with_cross_entropy` without their
    softmax: logits ``[rows, V]`` and label ``[rows, 1]`` or ``[rows]``
    -> ``(loss, top, total)``, each ``[rows, 1]``, bit for bit the loss
    `_softmax_xent` gives those rows (``jax.nn.log_softmax``'s
    arithmetic, read at the label's column alone) and the two parts of
    the rows' log-sum-exp, ``top + log(total)``: all that
    `hard_label_rows_grad` needs of the forward."""
    if logits.dtype == jnp.bfloat16:   # AMP: loss math in fp32
        logits = logits.astype(jnp.float32)
    top = jnp.max(logits, axis=-1, initial=-jnp.inf, keepdims=True)
    shifted = logits - top
    total = jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    picked, _ = _gather_label_prob(shifted, label)
    return -(picked - jnp.log(total)), top, total


def hard_label_rows_grad(logits, label, top, total, g):
    """The gradient of `hard_label_rows`' loss in its logits under the
    rows' OWN cotangents g ``[rows, 1]``, in the logits' dtype: ``g
    (softmax - onehot)``, written as autodiff writes it for
    `_softmax_xent` (``(g / total) exp(logits - top)``, and ``- g`` at
    the label's column), so the bits are its bits; rounded to the
    logits' dtype where the cast's transpose rounds it."""
    wide = logits.astype(jnp.float32) if logits.dtype == jnp.bfloat16 \
        else logits
    spread = (g / total) * jnp.exp(wide - top)
    label = label.reshape(-1, 1).astype(jnp.int32)
    label = jnp.where(label < 0, label + wide.shape[-1], label)
    at_label = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 1) == label
    return jnp.where(at_label, spread - g, spread).astype(logits.dtype)


@register("exit_distribution")
def _exit_distribution(ctx, op):
    """X ``[R, ...]``, an exit gate's logits visit by visit -> Out, the
    LOG of the exit distribution, float32 whatever X is: with ``lambda_t
    = sigmoid(X_t)`` and ``S_t = prod_{j<=t} (1 - lambda_j)``, ``p_t =
    lambda_t S_{t-1}`` for t < R and ``p_R = S_{R-1}`` (the remainder:
    the last gate is not read), so that the p sum to 1. In log space:
    ``log(1 - sigmoid(x)) = log_sigmoid(-x)`` summed along the visits."""
    x = ctx.in1(op, "X").astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-x[:-1]), axis=0)   # log S_1..
    survived = jnp.concatenate([jnp.zeros_like(x[:1]), stay])
    leave = jnp.concatenate([jax.nn.log_sigmoid(x[:-1]),
                             jnp.zeros_like(x[:1])])
    ctx.set_out(op, "Out", leave + survived)


@register("sigmoid_cross_entropy_with_logits")
def _sigmoid_xent(ctx, op):
    x = ctx.in1(op, "X")
    label = ctx.in1(op, "Label")
    # stable: max(x,0) - x*z + log(1+exp(-|x|))
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ctx.set_out(op, "Out", loss)


@register("hinge_loss")
def _hinge_loss(ctx, op):
    logits = ctx.in1(op, "Logits")
    labels = ctx.in1(op, "Labels")
    ctx.set_out(op, "Loss",
                jax.nn.relu(1.0 - (2.0 * labels - 1.0) * logits))


@register("huber_loss")
def _huber_loss(ctx, op):
    x = ctx.in1(op, "X")
    y = ctx.in1(op, "Y")
    delta = op.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r,
                     delta * (ar - 0.5 * delta))
    ctx.set_out(op, "Residual", r)
    ctx.set_out(op, "Out", loss)


@register("log_loss")
def _log_loss(ctx, op):
    p = ctx.in1(op, "Predicted")
    label = ctx.in1(op, "Labels")
    eps = op.attr("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    ctx.set_out(op, "Loss", loss)


@register("smooth_l1_loss")
def _smooth_l1(ctx, op):
    x = ctx.in1(op, "X")
    y = ctx.in1(op, "Y")
    sigma = op.attr("sigma", 1.0)
    in_w = ctx.in1(op, "InsideWeight")
    out_w = ctx.in1(op, "OutsideWeight")
    d = x - y
    if in_w is not None:
        d = d * in_w
    s2 = sigma * sigma
    ad = jnp.abs(d)
    elem = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
    if out_w is not None:
        elem = elem * out_w
    ctx.set_out(op, "Diff", d)
    ctx.set_out(op, "Out", jnp.sum(elem, axis=tuple(range(1, elem.ndim)),
                                   keepdims=True).reshape(x.shape[0], 1))


@register("rank_loss")
def _rank_loss(ctx, op):
    label = ctx.in1(op, "Label")
    left = ctx.in1(op, "Left")
    right = ctx.in1(op, "Right")
    d = left - right
    loss = jnp.maximum(d, 0) - d * label + jnp.log1p(jnp.exp(-jnp.abs(d)))
    ctx.set_out(op, "Out", loss)


@register("margin_rank_loss")
def _margin_rank_loss(ctx, op):
    label = ctx.in1(op, "Label")
    x1 = ctx.in1(op, "X1")
    x2 = ctx.in1(op, "X2")
    margin = op.attr("margin", 0.0)
    out = jax.nn.relu(-label * (x1 - x2) + margin)
    ctx.set_out(op, "Out", out)
    ctx.set_out(op, "Activated", (out > 0).astype(x1.dtype))


@register("modified_huber_loss")
def _modified_huber_loss(ctx, op):
    x = ctx.in1(op, "X")
    y = ctx.in1(op, "Y")
    z = (2.0 * y - 1.0) * x
    loss = jnp.where(z < -1.0, -4.0 * z,
                     jnp.square(jnp.maximum(0.0, 1.0 - z)))
    ctx.set_out(op, "IntermediateVal", z)
    ctx.set_out(op, "Out", loss)


@register("nce", stateful_rng=True)   # samples negatives from the stream
def _nce(ctx, op):
    """Noise-contrastive estimation (operators/nce_op.cc) — full-softmax-free
    training of big output layers. Samples negatives uniformly."""
    x = ctx.in1(op, "Input")            # [B, D]
    label = ctx.in1(op, "Label")        # [B, T]
    w = ctx.in1(op, "Weight")           # [C, D]
    b = ctx.in1(op, "Bias")             # [C]
    num_neg = op.attr("num_neg_samples", 10)
    num_classes = op.attr("num_total_classes", w.shape[0])
    batch = x.shape[0]
    if label.ndim == 1:
        label = label[:, None]
    num_true = label.shape[1]

    neg = jax.random.randint(ctx.rng(), (batch, num_neg), 0, num_classes)
    samples = jnp.concatenate([label.astype(jnp.int32), neg], axis=1)
    sw = jnp.take(w, samples, axis=0)                # [B, T+K, D]
    logits = jnp.einsum("bd,bkd->bk", x, sw)
    if b is not None:
        logits = logits + jnp.take(b, samples)
    labels01 = jnp.concatenate(
        [jnp.ones((batch, num_true)), jnp.zeros((batch, num_neg))], axis=1)
    # noise prob = uniform
    logits = logits - jnp.log(jnp.asarray(num_classes, jnp.float32))
    per = jnp.maximum(logits, 0) - logits * labels01 + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
    cost = jnp.sum(per, axis=1, keepdims=True)
    if op.input("SampleWeight"):
        # per-example weight scales the example's whole cost
        # (nce_op.cc:97 sample_weight)
        swt = ctx.in1(op, "SampleWeight").reshape(batch, 1)
        cost = cost * swt.astype(cost.dtype)
    ctx.set_out(op, "Cost", cost)
    ctx.set_out(op, "SampleLogits", logits)
    ctx.set_out(op, "SampleLabels", samples)
