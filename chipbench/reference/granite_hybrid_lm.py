"""Plain float32 reference of granite-4.0-h-micro's training step on ONE
chip of an 8-way vocabulary-parallel group
(``configs/granite-4.0-h-micro-train-vp8.json``; source
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
``model_type`` ``granitemoehybrid``; the mixer Mamba-2,
arXiv:2405.21060).

Straightforward ``jax.numpy``: no kernel, no chunk; the Mamba-2
recurrence a row at a time (``lax.scan`` over T, sums of products, no
matmul in it), the convolution a loop over its taps on an array padded
with zeros in front, attention a head and a block of ``ROW_BLOCK``
query rows at a time against all keys with the mask written out, the
head and the loss ``ROW_BLOCK`` rows at a time, so that 8,192 rows fit
on the chip. Every matmul runs at ``highest``. It imports nothing of
the program; the row-by-row recurrence, the taps' loop, the norm and the
control's rounding are the other references' own (``nemotron_h_lm``,
``lfm2_lm``, ``sdar_lm``, ``opt_lm``). What it computes (the equations of ISSUE 64), every number
from the configuration:

* ``x = embedding_multiplier * E[ids]``; layer l is ``x = x +
  residual_multiplier * Mixer_l(RMSNorm(x))``, then ``x = x +
  residual_multiplier * MLP(RMSNorm(x))`` (two norms a layer, eps
  ``rms_norm_eps``), the mixer of the kind ``layer_types[l]`` names;
  after the last layer one RMSNorm, ``logits = (x E^T) /
  logits_scaling`` against the SAME table (``tie_word_embeddings``),
  next-token cross-entropy against ``label``, mean over ``mask``;
* the MLP (``num_local_experts`` 0: the family's shared MLP alone):
  ``[a | b] = h W_in`` (``W_in [d, 2 f]``, ``f =
  shared_intermediate_size``), ``W_out (silu(a) * b)``, no bias;
* ``mamba``: ``d_inner = mamba_n_heads x mamba_d_head``; ``[z | xBC |
  dt] = h W_in`` (``W_in [d, 2 d_inner + 2 G N + H]``, side by side in
  the published order); ``xBC = silu(conv(xBC) + b)``, causal,
  depthwise, ``mamba_d_conv`` taps, ``(xBC_conv)_t = sum_i w[i] xBC_(t
  - K + 1 + i)``; split ``x_t [H, P]``, ``B_t [G, N]``, ``C_t [G,
  N]``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``S_t[h]
  = exp(dt_t[h] A[h]) S_(t-1)[h] + dt_t[h] x_t[h] B_t[g]^T`` with ``g =
  h // (H / G)`` (at ``mamba_n_groups`` 1 EVERY head reads the one
  group), ``S_0 = 0``; ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``; ``y =
  RMSNorm(y * silu(z)) * w``, the gate FIRST and the norm over each of
  the G groups of ``d_inner / G`` channels by itself (at one group:
  over all of them); ``out = y W_out``;
* ``attention``: ``q, k, v = h Wq, h Wk, h Wv`` (H heads of ``D =
  hidden_size / num_attention_heads``; Hkv; Hkv), no bias, NO rotation
  (``position_embedding_type`` ``nope``); ``s_ij = q_i . k_j *
  attention_multiplier`` (NOT ``D^-0.5``) kept where ``j <= i``; head j
  reads key/value head ``j // (H / Hkv)``; ``out = softmax(s) v Wo``.

Departures from the source, each on the accurate side: everything is
float32 (the source computes in bfloat16 with a float32 scan state);
the logits are over the vocabulary's slice the configuration holds
(``vocab_size`` rows of the table), as the deployment's loss on this
chip would be before its exchange; the depth is the configuration's
``num_hidden_layers``, the first entries of ``layer_types``.

``operands`` is for the CONTROL alone (as in ``opt_lm.py``): every
matmul's operands held in that dtype, per-tensor scaled; the
convolution, the recurrence, the norms and the multipliers stay
float32, as the program keeps them.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "layers": [{"norm"
[d], "ffn_norm" [d], "ffn_in" [d, 2 f], "ffn_out" [f, d]; a mamba layer
"w_in", "conv_w" [K, C], "conv_b" [C], "dt_bias", "a_log", "d" [H],
"norm_w" [d_inner], "w_out"; an attention layer "wq", "wk", "wv",
"wo"}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import ROW_BLOCK, _p32
from chipbench.reference.lfm2_lm import short_conv
from chipbench.reference.nemotron_h_lm import recurrence
from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms

MAMBA, ATTENTION = "mamba", "attention"


def kinds(cfg):
    """The layers' kinds: the first ``num_hidden_layers`` entries of the
    published ``layer_types``."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def mamba2(p, h, cfg, mm):
    """The Mamba-2 mixer of h [T, d]."""
    t = h.shape[0]
    heads, p_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
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
        jnp.mean(gated * gated, -1, keepdims=True) + cfg["rms_norm_eps"])
    return mm(normed.reshape(t, d_inner) * p["norm_w"], p["w_out"])


def attention(q, k, v, scale, mm):
    """q [T, H, D], k and v [T, Hkv, D] -> [T, H D]: causal softmax of
    the scores times `scale`, no position signal."""
    t, heads, d = q.shape
    group = heads // k.shape[1]
    block = min(ROW_BLOCK, t)
    at = jnp.arange(t)
    q, k, v = (x.transpose(1, 0, 2) for x in (q, k, v))

    def one(args):
        head, first = args
        qb = jax.lax.dynamic_slice_in_dim(q[head], first, block)
        kh, vh = k[head // group], v[head // group]
        seen = (first + jnp.arange(block))[:, None] >= at[None, :]
        s = jnp.where(seen, mm(qb, kh.T) * scale, -jnp.inf)
        return mm(jax.nn.softmax(s, -1), vh)

    grid = jnp.stack(jnp.meshgrid(jnp.arange(heads),
                                  jnp.arange(0, t, block), indexing="ij"),
                     -1).reshape(-1, 2)
    out = jax.lax.map(one, (grid[:, 0], grid[:, 1]))     # [H T/b, b, D]
    return out.reshape(heads, t, d).transpose(1, 0, 2).reshape(t, heads * d)


def mlp(p, h, mm):
    a, b = jnp.split(mm(h, p["ffn_in"]), 2, axis=-1)
    return mm(jax.nn.silu(a) * b, p["ffn_out"])


def hidden(params, tokens, cfg, operands=None):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head, eps = cfg["hidden_size"] // heads, cfg["rms_norm_eps"]
    joins = cfg["residual_multiplier"]
    t = tokens.shape[0]
    x = cfg["embedding_multiplier"] * params["word_emb"][tokens]
    for kind, p in zip(kinds(cfg), params["layers"]):
        h = _rms(x, p["norm"], eps)
        if kind == MAMBA:
            mixed = mamba2(p, h, cfg, mm)
        else:
            q = mm(h, p["wq"]).reshape(t, heads, d_head)
            k = mm(h, p["wk"]).reshape(t, kv_heads, d_head)
            v = mm(h, p["wv"]).reshape(t, kv_heads, d_head)
            mixed = mm(attention(q, k, v, cfg["attention_multiplier"], mm),
                       p["wo"])
        x = x + joins * mixed
        x = x + joins * mlp(p, _rms(x, p["ffn_norm"], eps), mm)
    return x


def lm_loss(params, src, label, mask, cfg, operands=None):
    """Mean next-token cross-entropy of batch ``src`` [B, T] against
    ``label``, weighted by ``mask``: the train step's cost. The head
    runs on ``ROW_BLOCK`` rows at a time."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        table = r(p["word_emb"]).T

        def one(args):
            tokens, target, weight = args
            x = r(_rms(hidden(p, tokens, cfg, operands), p["final_norm"],
                       cfg["rms_norm_eps"]))
            block = min(ROW_BLOCK, x.shape[0])

            def rows(args):
                xb, tb, wb = args
                logp = jax.nn.log_softmax(
                    xb @ table / cfg["logits_scaling"])
                return -jnp.sum(jnp.take_along_axis(
                    logp, tb[:, None], -1)[:, 0] * wb)

            cut = lambda a: a.reshape((-1, block) + a.shape[1:])
            return jnp.sum(jax.lax.map(rows, (cut(x), cut(target),
                                              cut(weight))))
        return jnp.sum(jax.lax.map(one, (src, label, mask))) / jnp.sum(mask)


def logits_at(params, tokens, first, count, cfg, operands=None):
    """Next-token logits ``[count, V]`` after positions ``first`` ..
    ``first + count - 1`` of the one sequence ``tokens`` [T]."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        x = hidden(p, tokens, cfg, operands)
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        return r(_rms(rows, p["final_norm"], cfg["rms_norm_eps"])) \
            @ r(p["word_emb"]).T / cfg["logits_scaling"]
