"""Plain float32 reference of Ouro-2.6B's training step, the LoopLM's
first training stage (``configs/ouro-2.6b-train.json``; source
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json,
``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741).

Straightforward ``jax.numpy``: no kernel; attention a head and a block
of ``ROW_BLOCK`` query rows at a time against all keys with the mask
written out (``afmoe_lm.attention``), the head ``ROW_BLOCK`` rows at a
time, so that 32 visits of a layer at 8,192 rows fit. Every matmul runs
at ``highest``. It imports nothing of the program. What it computes
(the equations of ISSUE 59), with R = ``total_ut_steps`` and s the
stream ``[T, d]``:

1. one layer, SANDWICH norms, four RMSNorms: ``a = Attn(N1(s))``, ``s =
   s + N1'(a)``, ``f = W_down(silu(W_gate h) * (W_up h))`` with ``h =
   N2(s)``, ``s = s + N2'(f)``. Attn: ``q, k, v = h Wq, h Wk, h Wv`` (H
   heads of D each; ``num_key_value_heads`` = H: plain multi-head), no
   bias, no QK-norm, no gate; q and k turned by their rows' positions
   (rotate-half, theta ``rope_theta``); causal softmax attention at
   scale ``D^-0.5``; ``Wo``.
2. the loop: ``s^(0) = E[x]``; for t = 1..R: ``s^(t) = N_f(Stack(
   s^(t-1)))``: the SAME layers with the same parameters at every t, and
   the ONE final norm at the end of every visit, its output both read
   by the head and carried into the next visit.
3. at every visit ``logits^(t) = s^(t) W_head`` (the one head) and the
   exit gate ``lambda_t = sigmoid(s^(t) w_g + b_g)``.
4. the exit distribution of a token, in log space: ``S_0 = 1``, ``S_t =
   prod_{j<=t} (1 - lambda_j)``, ``p_t = lambda_t S_{t-1}`` for t < R,
   ``p_R = S_{R-1}`` (the remainder), so ``sum_t p_t = 1``.
5. the cost: ``sum_tokens m [sum_t p_t ell^(t) - beta H(p)] /
   sum_tokens m``, ``ell^(t)`` the token's next-token cross-entropy at
   visit t, ``H(p) = -sum_t p_t log p_t``, ``beta`` the configuration's
   ``entropy_weight``.

Departures from the published description, each in the configuration's
``assumed`` too: the four norms a layer and ``N_f`` at every visit with
its output carried on are the released ``modeling_ouro.py``'s; no
projection has a bias (the source's config has no ``attention_bias``);
the gate is a ``Linear(d, 1)`` with bias; ``beta`` 0.1. Not here: the
second training stage (the gate alone, on the loss's improvement) and
early exit at inference.

``operands`` is for the CONTROL alone (as in ``opt_lm.py``): every
matmul's operands held in that dtype, per-tensor scaled; the gate's
product stays float32, as the program keeps it.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "w_out" [d, V],
"gate_w" [d, 1], "gate_b" [1], "layers": [{"ln1", "ln1_post", "ln2",
"ln2_post" [d], "wq", "wk", "wv" [d, H D], "wo" [H D, d], "gate", "up"
[d, f], "down" [f, d]}]}``: ONE tree for the stack. Where ``"visits"``
is there it holds R such lists, an UNTIED copy of the stack a visit
(``tests/test_looped_lm.py`` holds a shared parameter's gradient to the
sum of the copies').
"""

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import ROW_BLOCK, attention
from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, _rope


def layer(s, p, cfg, mm):
    """The stream s [T, d] after one layer of parameters p."""
    heads, d_head = cfg["num_attention_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    t = s.shape[0]
    pos = jnp.arange(t)
    h = _rms(s, p["ln1"], eps)
    turned = lambda w, n: _rope(mm(h, w).reshape(t, n, d_head), pos, theta)
    a = attention(turned(p["wq"], heads),
                  turned(p["wk"], cfg["num_key_value_heads"]),
                  mm(h, p["wv"]).reshape(t, -1, d_head), None, mm)
    s = s + _rms(mm(a, p["wo"]), p["ln1_post"], eps)
    h = _rms(s, p["ln2"], eps)
    f = mm(jax.nn.silu(mm(h, p["gate"])) * mm(h, p["up"]), p["down"])
    return s + _rms(f, p["ln2_post"], eps)


def states(p, tokens, cfg, operands=None):
    """tokens [T] -> ``s^(1) .. s^(R)`` [R, T, d]: the stream after
    each visit's final norm."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)

    def visit(s, stack):
        for one in stack:
            s = layer(s, one, cfg, mm)
        s = _rms(s, p["final_norm"], cfg["rms_norm_eps"])
        return s, s

    s = p["word_emb"][tokens]
    if "visits" in p:                    # an untied copy a visit
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *p["visits"])
        return jax.lax.scan(visit, s, stacked)[1]
    return jax.lax.scan(lambda s, _: visit(s, p["layers"]), s, None,
                        length=cfg["total_ut_steps"])[1]


def exit_log_p(gates):
    """The gate's logits [R, ...] -> ``log p_t`` [R, ...]."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gates[:-1]), axis=0)
    nothing = jnp.zeros_like(gates[:1])
    return jnp.concatenate([jax.nn.log_sigmoid(gates[:-1]), nothing]) \
        + jnp.concatenate([nothing, stay])


def gate_logits(p, s):
    """s [..., d] -> the exit gate's logit [...], float32."""
    return (s @ p["gate_w"])[..., 0] + p["gate_b"][0]


def _p32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)


def token_terms(params, tokens, target, cfg):
    """(``ell`` [R, T], ``log p`` [R, T]) of one sequence: each visit's
    next-token cross-entropy and the exit distribution."""
    p = _p32(params)
    s = states(p, tokens, cfg)
    block = min(ROW_BLOCK, s.shape[1])
    cut = lambda a: a.reshape((-1, block) + a.shape[1:])

    def rows(args):
        xb, tb = args
        logp = jax.nn.log_softmax(xb @ p["w_out"])
        return -jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    ell = jax.lax.map(lambda one: jax.lax.map(
        rows, (cut(one), cut(target))).reshape(-1), s)
    return ell, exit_log_p(gate_logits(p, s))


def visit_losses(params, src, label, mask, cfg):
    """(each visit's masked mean cross-entropy [R], the masked mean of
    ``p_t`` [R], the masked mean entropy) of batch ``src`` [B, T]: what
    the program sums on the device (``<name>_loss_sum_<t>``, the exit
    step, the entropy)."""
    with jax.default_matmul_precision("highest"):
        ell, log_p = jax.lax.map(
            lambda a: token_terms(params, a[0], a[1], cfg), (src, label))
    p = jnp.exp(log_p)                                  # [B, R, T]
    mean = lambda v: jnp.sum(v * mask[:, None], (0, 2)) / jnp.sum(mask)
    entropy = -jnp.sum(p * log_p, 1)
    return mean(ell), mean(p), jnp.sum(entropy * mask) / jnp.sum(mask)


def lm_loss(params, src, label, mask, cfg):
    """The train step's cost on batch ``src`` [B, T] against ``label``,
    weighted by ``mask``: ``sum m [sum_t p_t ell^(t) - beta H(p)] / sum
    m``."""
    with jax.default_matmul_precision("highest"):
        ell, log_p = jax.lax.map(
            lambda a: token_terms(params, a[0], a[1], cfg), (src, label))
    p = jnp.exp(log_p)
    per_token = jnp.sum(p * ell, 1) \
        + cfg["entropy_weight"] * jnp.sum(p * log_p, 1)
    return jnp.sum(per_token * mask) / jnp.sum(mask)


def logits_at(params, tokens, first, count, cfg, operands=None):
    """``[count, V + R]`` after positions ``first`` .. ``first + count -
    1`` of the one sequence ``tokens`` [T]: the LAST visit's next-token
    logits and, beside them, the R ``log p_t``."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        s = jax.lax.dynamic_slice_in_dim(
            states(p, tokens, cfg, operands), first, count, axis=1)
        return jnp.concatenate([r(s[-1]) @ r(p["w_out"]),
                                exit_log_p(gate_logits(p, s)).T], axis=1)
