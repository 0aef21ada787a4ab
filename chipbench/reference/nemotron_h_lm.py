"""Plain float32 reference of NVIDIA-Nemotron-3-Nano-30B-A3B's training
step on ONE chip's share of a 16-way expert-parallel deployment
(``configs/nemotron-3-nano-30b-a3b-train-ep16.json``; source
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json,
``model_type`` ``nemotron_h``; the family's report arXiv:2504.03624, the
mixer Mamba-2, arXiv:2405.21060).

Straightforward ``jax.numpy``: no kernel, no chunk, no sort, no grouped
matmul; the Mamba-2 recurrence a row at a time (``lax.scan`` over T,
sums of products, no matmul in it), the convolution a loop over its
taps on an array padded with zeros in front, attention a head and a
block of query rows at a time against all keys with the mask written
out, every held expert on every row. Every matmul runs at ``highest``.
It imports nothing of the program. What it computes (the equations of
ISSUE 62):

* ``x = Embed(ids)``, not scaled; layer l, of the kind the l-th
  character of ``hybrid_override_pattern`` names, is ``x = x +
  Mixer(RMSNorm(x))`` (eps ``layer_norm_epsilon``) and nothing else;
  after the last layer one RMSNorm, ``logits = x W_head`` with a head
  of its own, next-token cross-entropy against ``label``, mean over
  ``mask``;
* ``M``: ``d_inner = mamba_num_heads x mamba_head_dim``; ``[z | xBC |
  dt] = h W_in`` (``W_in [d, 2 d_inner + 2 G N + H]``); ``xBC =
  silu(conv(xBC) + b)``, causal, depthwise, ``conv_kernel`` taps,
  ``(xBC_conv)_t = sum_i w[i] xBC_(t - K + 1 + i)``; split ``x_t [H,
  P]``, ``B_t [G, N]``, ``C_t [G, N]``; ``dt = softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; ``S_t[h] = exp(dt_t[h] A[h])
  S_(t-1)[h] + dt_t[h] x_t[h] B_t[g]^T`` with ``g = h // (H / G)``,
  ``S_0 = 0``; ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``; ``y = RMSNorm(y
  * silu(z)) * w``, the gate FIRST and the norm over each of the G
  groups of ``d_inner / G`` channels by itself; ``out = y W_out``;
* ``E``: ``s = sigmoid(h W_r)`` over ALL experts; the k largest of ``s
  + bias``; weights ``s`` at the chosen over (their sum + 1e-20)
  (``norm_topk_prob``) times ``routed_scaling_factor``; expert e is
  ``W_down_e relu(W_up_e h)^2`` (two matrices); the sum runs over the
  chosen experts THIS CHIP HOLDS (ids ``first_expert`` .. + the number
  held), what the others would add is left out; the shared expert, the
  same form at ``moe_shared_expert_intermediate_size``, is added for
  every row (``shared=False`` leaves it out: the sum of a group's shares adds it once, a
  test's business);
* ``*``: ``q, k, v = h Wq, h Wk, h Wv`` (H heads of D; Hkv; Hkv), no
  rotation, no bias; ``s_ij = q_i . k_j / sqrt(D)`` kept where ``j <=
  i``; head j reads key/value head ``j // (H / Hkv)``; ``out =
  softmax(s) v Wo``.

``choices`` (``[expert layers, T, k]``, the program's routing) and
``near_tie``: a row's proposed experts stand in for the reference's own
top-k only where every one of them scores (with the bias) within
``near_tie`` of the reference's own k-th largest; everywhere else the
reference routes by itself (``sdar_lm.routed``). ``operands`` is for
the CONTROL alone (as in ``opt_lm.py``): every matmul's operands held
in that dtype, per-tensor scaled; the router, the convolution, the
recurrence and the norms stay float32, as the program keeps them.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "head" [d, V],
"layers": [{"norm" [d]; an M layer "w_in", "conv_w" [K, C], "conv_b"
[C], "dt_bias", "a_log", "d" [H], "norm_w" [d_inner], "w_out"; an E
layer "router" [d, E], "bias" [E], "w_up" [Eh, d, f], "w_down" [Eh, f,
d], "shared_up" [d, fs], "shared_down" [fs, d]; a * layer "wq", "wk",
"wv", "wo"}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import ROW_BLOCK, _p32, attention
from chipbench.reference.lfm2_lm import short_conv
from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, routed

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
NORM_TOPK_EPS = 1e-20


def kinds(cfg):
    """The layers' kinds: the first ``num_hidden_layers`` characters of
    the published pattern."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def recurrence(x, dt, a, b, c, d):
    """x [T, H, P], dt [T, H], a and d [H], b and c [T, G, N] -> y [T,
    H, P]: the state ``[H, P, N]`` a row at a time."""
    per_group = x.shape[1] // b.shape[1]

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, per_group, 0), jnp.repeat(c_t,
                                                             per_group, 0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1) + d[:, None] * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32),
        (x, dt, b, c))
    return y


def mamba2(p, h, cfg, mm):
    """The Mamba-2 mixer of h [T, d]."""
    t = h.shape[0]
    heads, p_head = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_inner, d_bc = heads * p_head, groups * n
    z, xbc, dt = jnp.split(mm(h, p["w_in"]),
                           [d_inner, 2 * d_inner + 2 * d_bc], axis=-1)
    xbc = jax.nn.silu(short_conv(xbc, p["conv_w"]) + p["conv_b"])
    x, b, c = jnp.split(xbc, [d_inner, d_inner + d_bc], axis=-1)
    y = recurrence(x.reshape(t, heads, p_head),
                   jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
                   b.reshape(t, groups, n), c.reshape(t, groups, n),
                   p["d"]).reshape(t, d_inner)
    gated = (y * jax.nn.silu(z)).reshape(t, groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg["layer_norm_epsilon"])
    return mm(normed.reshape(t, d_inner) * p["norm_w"], p["w_out"])


def router_weights(p, h, cfg, proposed=None, near_tie=0.0):
    """h [T, d] -> weights [T, E] over ALL experts, 0 at the ones not
    chosen: float32."""
    k = cfg["num_experts_per_tok"]
    n_exp = p["router"].shape[1]
    score = jax.nn.sigmoid(h @ p["router"])
    top_i = routed(score + p["bias"], k, proposed, near_tie)
    chosen = jnp.any(top_i[:, :, None] == jnp.arange(n_exp), axis=1)
    weight = jnp.where(chosen, score, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True)
                           + NORM_TOPK_EPS)
    return weight * cfg["routed_scaling_factor"]


def expert_layer(p, h, cfg, first, held, mm, proposed=None, near_tie=0.0,
                 shared=True):
    """What the `held` experts with ids from `first` (``p["w_up"]`` and
    ``p["w_down"]`` hold those alone) give on rows h [T, d], plus the
    shared expert's where `shared`."""
    weight = router_weights(p, h, cfg, proposed, near_tie)

    def one_expert(y, e):                # every held expert, every row
        w_up, w_down, w_e = e
        return y + w_e[:, None] * mm(relu2(mm(h, w_up)), w_down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["w_up"], p["w_down"], weight[:, first:first + held].T))
    if shared:
        y = y + mm(relu2(mm(h, p["shared_up"])), p["shared_down"])
    return y


def hidden(params, tokens, cfg, choices=None, near_tie=0.0, operands=None):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head, eps = cfg["head_dim"], cfg["layer_norm_epsilon"]
    t = tokens.shape[0]
    x = params["word_emb"][tokens]
    at = 0
    for kind, p in zip(kinds(cfg), params["layers"]):
        h = _rms(x, p["norm"], eps)
        if kind == MAMBA:
            x = x + mamba2(p, h, cfg, mm)
        elif kind == ATTENTION:
            q = mm(h, p["wq"]).reshape(t, heads, d_head)
            k = mm(h, p["wk"]).reshape(t, kv_heads, d_head)
            v = mm(h, p["wv"]).reshape(t, kv_heads, d_head)
            x = x + mm(attention(q, k, v, None, mm), p["wo"])
        else:
            x = x + expert_layer(p, h, cfg, cfg["first_expert"],
                                 cfg["num_experts"], mm,
                                 None if choices is None else choices[at],
                                 near_tie)
            at += 1
    return x


def lm_loss(params, src, label, mask, cfg):
    """Mean next-token cross-entropy of batch ``src`` [B, T] against
    ``label``, weighted by ``mask``: the train step's cost. The head
    runs on ``ROW_BLOCK`` rows at a time."""
    p = _p32(params)
    with jax.default_matmul_precision("highest"):
        def one(args):
            tokens, target, weight = args
            x = _rms(hidden(p, tokens, cfg), p["final_norm"],
                     cfg["layer_norm_epsilon"])
            block = min(ROW_BLOCK, x.shape[0])

            def rows(args):
                xb, tb, wb = args
                logp = jax.nn.log_softmax(xb @ p["head"])
                return -jnp.sum(jnp.take_along_axis(
                    logp, tb[:, None], -1)[:, 0] * wb)

            cut = lambda a: a.reshape((-1, block) + a.shape[1:])
            return jnp.sum(jax.lax.map(rows, (cut(x), cut(target),
                                              cut(weight))))
        return jnp.sum(jax.lax.map(one, (src, label, mask))) / jnp.sum(mask)


def logits_at(params, tokens, first, count, cfg, choices=None,
              near_tie=0.0, operands=None):
    """Next-token logits ``[count, V]`` after positions ``first`` ..
    ``first + count - 1`` of the one sequence ``tokens`` [T]."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        x = hidden(p, tokens, cfg, choices, near_tie, operands)
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        return r(_rms(rows, p["final_norm"], cfg["layer_norm_epsilon"])) \
            @ r(p["head"])
