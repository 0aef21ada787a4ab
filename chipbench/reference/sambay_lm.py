"""Plain float32 reference of Phi-4-mini-flash-reasoning's training step
on ONE chip's share of an 8-way vocabulary-parallel deployment
(``configs/phi4-mini-flash-train-vp8.json``; source
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json,
``model_type`` ``phi4flash``: the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with the differential attention of arXiv:2410.05258).

Straightforward ``jax.numpy``: no kernel; the selective scan a
``lax.scan`` over the T steps with the ``[d_inner, d_state]`` state;
attention a differential head and a block of ``ROW_BLOCK`` query rows at
a time against all keys with the mask written out, so that 8,192 rows
fit; differential attention as the two softmaxes of its equations.
Every matmul runs at ``highest``. It imports nothing of the program.
What it computes (the equations of ISSUE 40), the stream x ``[T, d]``:

* ``x = Embed(ids)``: no scale, no position signal anywhere; after the
  last layer LayerNorm, the head ``Embed^T`` (tied), next-token
  cross-entropy against ``label``, mean over ``mask``.
* layer l of kind ``layer_kinds[l]``: ``x = x + Mixer(LN1(x))``, ``x =
  x + W_down(silu(W_gate h) * (W_up h))`` with ``h = LN2(x)``; LN is
  LayerNorm with weight and bias, ``layer_norm_eps``.
* ``mamba`` / ``mamba_memory``: ``s = h W_in_s``, ``z = h W_in_z``;
  ``s_t = silu(b_c + sum_{i<4} w_i * s_{t-3+i})`` per channel, zeros
  before the sequence; ``d, B_t, C_t = s_t W_x_dt, s_t W_x_b, s_t
  W_x_c``; ``dt_t = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``H_t = exp(dt_t A) * H_{t-1} + (dt_t s_t) B_t^T`` (``[d_inner,
  d_state]``, ``H_0 = 0``); ``y_t = H_t C_t + D * s_t``; ``out = (y *
  silu(z)) W_out``. ``mamba_memory`` also keeps ``m = y``.
* ``gmu``: ``out = (m * silu(h W_in)) W_out``, m the memory.
* ``sliding`` / ``full``: ``q, k, v = h W + b`` (H, Hkv, Hkv heads of
  D); differential head p of H / 2 = (q_2p, q_2p+1); key/value pair r
  of Hkv / 2 = (k_2r, k_2r+1), ``v_r = [v_2r; v_2r+1]``; p reads r = p
  // (H / Hkv); ``a1 = softmax(q_2p k_2r^T / sqrt(D) + mask) v_r``,
  ``a2 = softmax(q_2p+1 k_2r+1^T / sqrt(D) + mask) v_r``; ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``, ``lam0(l) = 0.8 - 0.6
  exp(-0.3 l)``; ``o_p = RMSNorm_2D(a1 - lam a2) * (1 - lam0(l))``
  under one weight [2D]; ``out = [o_0 ..] W_o + b_o``. Mask: ``j <=
  i``, on ``sliding`` also ``i - j < sliding_window``. ``full`` keeps
  its k and v.
* ``cross``: ``q = h W_q + b``, k and v those the ``full`` layer kept,
  the same differential form under its own ``lam`` and RMSNorm, causal.

``operands`` is for the CONTROL alone (as in ``opt_lm.py``): every
matmul's operands held in that dtype, per-tensor scaled. ``state_dtype``
is for a second control: the scan's state held in it between steps
(the configuration states float32).

``params``: ``{"word_emb" [V, d], "final_norm": (w, b), "layers": [{
"ln1", "ln2": (w, b), "ffn": (gate, up, down), and by kind: "in_s",
"in_z" [d, di], "conv_w" [4, di], "conv_b" [di], "x_dt" [di, R], "x_b",
"x_c" [di, N], "dt" [R, di], "dt_b" [di], "a_log" [di, N], "d" [di],
"out" [di, d] | "in" [d, di], "out" [di, d] | "wq" [d, H D], "wq_b",
"wo" [H D, d], "wo_b", "lq1", "lk1", "lq2", "lk2" [D], "subln" [2D]
and, but on ``cross``, "wk", "wk_b", "wv", "wv_b" [d, Hkv D]}]}``.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.reference.opt_lm import _held_in, _ln

ROW_BLOCK = 1024
SUBLN_EPS = 1e-5


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def scan(s, dt, a, b, c, d, state_dtype=jnp.float32):
    """The selective scan of one sequence: s, dt [T, C]; a [C, N]; b,
    c [T, N]; d [C] -> y [T, C]."""
    def step(h, xs):
        s_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h.astype(jnp.float32) \
            + (dt_t * s_t)[:, None] * b_t[None, :]
        return h.astype(state_dtype), jnp.sum(h * c_t[None, :], -1) \
            + d * s_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, state_dtype),
                        (s, dt, b, c))
    return y


def mamba(p, h, mm, state_dtype):
    """(the mixer's output [T, d], its scan output y [T, di])."""
    t = h.shape[0]
    s, z = mm(h, p["in_s"]), mm(h, p["in_z"])
    k = p["conv_w"].shape[0]
    before = jnp.concatenate([jnp.zeros((k - 1, s.shape[1])), s])
    s = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][i] * before[i:i + t] for i in range(k)))
    dt = jax.nn.softplus(mm(mm(s, p["x_dt"]), p["dt"]) + p["dt_b"])
    y = scan(s, dt, -jnp.exp(p["a_log"]), mm(s, p["x_b"]), mm(s, p["x_c"]),
             p["d"], state_dtype)
    return mm(y * jax.nn.silu(z), p["out"]), y


def diff_attention(q, k, v, window, mm):
    """q [T, H, D], k and v [T, Hkv, D] -> (a1, a2), each [T, H / 2,
    2D]: the two softmaxes of every differential head, causal, within
    `window` keys where given."""
    t, heads, d = q.shape
    per_pair = heads // k.shape[1]        # differential heads a kv pair
    block = min(ROW_BLOCK, t)
    at = jnp.arange(t)
    q, k, v = (x.transpose(1, 0, 2) for x in (q, k, v))

    def one(args):
        head, first = args               # a QUERY head, one of the two
        p, turn = head // 2, head % 2
        r = p // per_pair
        qb = jax.lax.dynamic_slice_in_dim(q[head], first, block)
        key = k[2 * r + turn]
        value = jnp.concatenate([v[2 * r], v[2 * r + 1]], -1)
        ahead = (first + jnp.arange(block))[:, None] - at[None, :]
        seen = ahead >= 0 if window is None \
            else (ahead >= 0) & (ahead < window)
        s = jnp.where(seen, mm(qb, key.T) * d ** -0.5, -jnp.inf)
        return mm(jax.nn.softmax(s, -1), value)

    grid = jnp.stack(jnp.meshgrid(jnp.arange(heads),
                                  jnp.arange(0, t, block), indexing="ij"),
                     -1).reshape(-1, 2)
    out = jax.lax.map(one, (grid[:, 0], grid[:, 1]))    # [H T/b, b, 2D]
    out = out.reshape(heads // 2, 2, t, 2 * d)
    return out[:, 0].transpose(1, 0, 2), out[:, 1].transpose(1, 0, 2)


def attention(p, h, kv, layer, window, cfg, mm):
    """(the mixer's output, (k, v)); `kv` None: the layer's own."""
    t = h.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    q = (mm(h, p["wq"]) + p["wq_b"]).reshape(t, heads, d)
    if kv is None:
        kv = ((mm(h, p["wk"]) + p["wk_b"]).reshape(t, kv_heads, d),
              (mm(h, p["wv"]) + p["wv_b"]).reshape(t, kv_heads, d))
    a1, a2 = diff_attention(q, kv[0], kv[1], window, mm)
    lam0 = lambda_init(layer)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0
    o = a1 - lam * a2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + SUBLN_EPS) \
        * p["subln"] * (1.0 - lam0)
    return mm(o.reshape(t, -1), p["wo"]) + p["wo_b"], kv


def hidden(params, tokens, cfg, operands=None, state_dtype=jnp.float32):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    eps = cfg["layer_norm_eps"]
    x = params["word_emb"][tokens]
    memory = kept = None
    for l, (kind, p) in enumerate(zip(cfg["layer_kinds"],
                                      params["layers"])):
        h = _ln(x, *p["ln1"], eps)
        if kind in ("mamba", "mamba_memory"):
            out, y = mamba(p, h, mm, state_dtype)
            if kind == "mamba_memory":
                memory = y
        elif kind == "gmu":
            out = mm(memory * jax.nn.silu(mm(h, p["in"])), p["out"])
        else:
            out, kv = attention(
                p, h, kept if kind == "cross" else None, l,
                cfg["sliding_window"] if kind == "sliding" else None,
                cfg, mm)
            if kind == "full":
                kept = kv
        x = x + out
        h = _ln(x, *p["ln2"], eps)
        w = p["ffn"]
        x = x + mm(jax.nn.silu(mm(h, w[0])) * mm(h, w[1]), w[2])
    return x


def _p32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)


def lm_loss(params, src, label, mask, cfg):
    """Mean next-token cross-entropy of batch ``src`` [B, T] against
    ``label``, weighted by ``mask``: the train step's cost. The head
    runs on ``ROW_BLOCK`` rows at a time."""
    p = _p32(params)
    with jax.default_matmul_precision("highest"):
        def one(args):
            tokens, target, weight = args
            x = _ln(hidden(p, tokens, cfg), *p["final_norm"],
                    cfg["layer_norm_eps"])
            block = min(ROW_BLOCK, x.shape[0])

            def rows(args):
                xb, tb, wb = args
                logp = jax.nn.log_softmax(xb @ p["word_emb"].T)
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
        return r(_ln(rows, *p["final_norm"], cfg["layer_norm_eps"])) \
            @ r(p["word_emb"].T)
