"""Plain float32 reference of Olmo-Hybrid-7B's training step on ONE
chip's share of a 2-way head-parallel deployment
(``configs/olmo-hybrid-7b-train-tp2.json``; source
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
``model_type`` ``olmo_hybrid``: OLMo 3's post-norm block round two kinds
of mixer, linear attention under a gated delta rule in three layers of
four and full attention with no position signal in the fourth).

Straightforward ``jax.numpy``: no kernel and no chunk; the delta rule
ROW BY ROW, a ``lax.scan`` over the T rows with the ``[H, d_k, d_v]``
state (another algorithm than the program's chunk walk: that is the
point); the convolutions a loop over the taps on a padded array;
attention a head and a block of ``ROW_BLOCK`` query rows at a time
against all keys with the mask written out. Every matmul runs at
``highest``. It imports nothing of the program. What it computes (the
equations of ISSUE 53), the stream x ``[T, d]``, no bias anywhere:

* ``x = E[ids]``; layer l: ``x = x + RMSNorm(Mixer(x))``, then ``x = x
  + RMSNorm(W_down(silu(x W_gate) * (x W_up)))``, each norm on the
  sublayer's OUTPUT (``w * y / sqrt(mean(y^2) + eps)``) and none on its
  input; after the last layer one RMSNorm, ``logits = x W_head`` (a
  matrix of its own), next-token cross-entropy against ``label``, mean
  over ``mask``.
* ``full_attention``: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)``,
  each over the WHOLE projection held here under a weight as long as
  it, ``v = x W_v``; heads of ``head_dim``; no position signal; causal
  softmax at ``head_dim^-0.5``; ``attn W_o``.
* ``linear_attention``, H heads, keys of ``d_k``, values of ``d_v``:
  ``q' = silu(conv(x W_q))``, ``k' = silu(conv(x W_k))``, ``v =
  silu(conv(x W_v))``, ``conv`` a causal depthwise convolution of 4
  taps, zeros before the sequence; a head and row: ``qn = q' /
  sqrt(sum(q'^2) + 1e-6) * d_k^-0.5``, ``kn = k' / sqrt(sum(k'^2) +
  1e-6)``, ``beta = 2 sigmoid(x W_b)``, ``g = -exp(A_log) *
  softplus(x W_a + dt_bias)``, ``a = exp(g)``; ``S_t = a_t S_(t-1) +
  kn_t (beta_t (v_t - a_t S_(t-1)^T kn_t))^T``, ``S_0 = 0``, ``o_t =
  S_t^T qn_t``; ``y = RMSNorm_dv(o) * silu(x W_g)`` under one weight
  ``[d_v]`` for every head; ``y W_o``.

A chip holds ``linear_num_value_heads`` heads of each linear layer and
``num_attention_heads`` of the full layer, the columns of the
projections and the rows of ``W_o`` that are theirs; what the other
heads would add to ``W_o``'s sum is left out. ``mean_squares`` is for
the head-share test alone: the whole projection's mean square a row,
``(of q, of k)``, handed to a share of the full layer in place of its
own columns' (in the deployment the pair would exchange that number).

``operands`` is for the CONTROL alone (as in ``opt_lm.py``): every
matmul's operands held in that dtype, per-tensor scaled; the rule,
which runs no matmul, stays float32. ``state_dtype`` is for a second
control: the rule's state held in it between rows and each row's decay
rounded to it (the configuration states float32).

``params``: ``{"word_emb" [V, d], "final_norm" [d], "head" [d, V],
"layers": [{"ln1", "ln2" [d], "ffn": (gate, up, down), and by kind:
"wq", "wk" [d, H d_k], "wv", "wg" [d, H d_v], "wa", "wb" [d, H],
"conv_q", "conv_k" [4, H d_k], "conv_v" [4, H d_v], "a_log", "dt_bias"
[H], "o_norm" [d_v], "wo" [H d_v, d] | "wq", "wk", "wv" [d, H D],
"q_norm", "k_norm" [H D], "wo" [H D, d]}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import ROW_BLOCK, _p32, attention
from chipbench.reference.lfm2_lm import short_conv
from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms

LINEAR = "linear_attention"
L2_EPS = 1e-6
BETA_SCALE = 2.0            # linear_allow_neg_eigval


def conv_silu(u, w):
    """u [T, C], w [K, C] -> ``silu(sum_i w[i] * u_(t - K + 1 + i))``,
    each channel by itself, zeros before the sequence (the loop over
    the taps on a padded array is ``lfm2_lm.short_conv``)."""
    return jax.nn.silu(short_conv(u, w))


def delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """The gated delta rule of one sequence, a row at a time: q and k
    [T, H, d_k] (normed, the query scaled), v [T, H, d_v], g and beta
    [T, H] -> o [T, H, d_v]."""
    def row(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        a_t = jnp.exp(g_t).astype(state_dtype).astype(jnp.float32)
        s = s.astype(jnp.float32) * a_t[:, None, None]
        seen = jnp.sum(s * k_t[:, :, None], 1)               # S^T k
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - seen))[:, None, :]
        return s.astype(state_dtype), jnp.sum(s * q_t[:, :, None], 1)

    s0 = jnp.zeros(k.shape[1:] + v.shape[2:], state_dtype)
    return jax.lax.scan(row, s0, (q, k, v, g, beta))[1]


def linear_mixer(p, x, heads, d_k, d_v, eps, mm, state_dtype=jnp.float32):
    """The delta-rule mixer's output [T, d] from the `heads` heads that
    ``p`` holds."""
    t = x.shape[0]
    part = lambda key, taps, d: conv_silu(mm(x, p[key]), p[taps]).reshape(
        t, heads, d)
    q, k, v = (part("wq", "conv_q", d_k), part("wk", "conv_k", d_k),
               part("wv", "conv_v", d_v))
    unit = lambda u: u * jax.lax.rsqrt(
        jnp.sum(u * u, -1, keepdims=True) + L2_EPS)
    beta = BETA_SCALE * jax.nn.sigmoid(mm(x, p["wb"]))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(mm(x, p["wa"]) + p["dt_bias"])
    o = delta_rule(unit(q) * d_k ** -0.5, unit(k), v, g, beta, state_dtype)
    y = _rms(o, p["o_norm"], eps) * jax.nn.silu(
        mm(x, p["wg"]).reshape(t, heads, d_v))
    return mm(y.reshape(t, heads * d_v), p["wo"])


def full_mixer(p, x, heads, d_head, eps, mm, mean_squares=None):
    """The full-attention mixer's output [T, d] from the `heads` heads
    that ``p`` holds; ``mean_squares``: the module's docstring."""
    t = x.shape[0]

    def normed(y, w, mean_square):
        if mean_square is None:
            return _rms(y, w, eps)
        return y * jax.lax.rsqrt(mean_square + eps) * w

    of_q, of_k = mean_squares or (None, None)
    q = normed(mm(x, p["wq"]), p["q_norm"], of_q).reshape(t, heads, d_head)
    k = normed(mm(x, p["wk"]), p["k_norm"], of_k).reshape(t, heads, d_head)
    v = mm(x, p["wv"]).reshape(t, heads, d_head)
    return mm(attention(q, k, v, None, mm), p["wo"])


def mlp(p, x, mm):
    w = p["ffn"]
    return mm(jax.nn.silu(mm(x, w[0])) * mm(x, w[1]), w[2])


def hidden(params, tokens, cfg, operands=None, state_dtype=jnp.float32):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    eps = cfg["rms_norm_eps"]
    x = params["word_emb"][tokens]
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        if kind == LINEAR:
            mixed = linear_mixer(p, x, cfg["linear_num_value_heads"],
                                 cfg["linear_key_head_dim"],
                                 cfg["linear_value_head_dim"], eps, mm,
                                 state_dtype)
        else:
            mixed = full_mixer(p, x, cfg["num_attention_heads"],
                               cfg["head_dim"], eps, mm)
        x = x + _rms(mixed, p["ln1"], eps)
        x = x + _rms(mlp(p, x, mm), p["ln2"], eps)
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
                     cfg["rms_norm_eps"])
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


def logits_at(params, tokens, first, count, cfg, operands=None,
              state_dtype=jnp.float32):
    """Next-token logits ``[count, V]`` after positions ``first`` ..
    ``first + count - 1`` of the one sequence ``tokens`` [T]."""
    p = _p32(params)
    r = _held_in(operands)
    with jax.default_matmul_precision("highest"):
        x = hidden(p, tokens, cfg, operands, state_dtype)
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        return r(_rms(rows, p["final_norm"], cfg["rms_norm_eps"])) \
            @ r(p["head"])
