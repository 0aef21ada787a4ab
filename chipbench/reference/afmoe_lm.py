"""Plain float32 reference of Trinity-Mini's training step on ONE chip's
share of a 16-way expert-parallel deployment
(``configs/trinity-mini-train-ep16.json``; source
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json,
``model_type`` ``afmoe``).

Straightforward ``jax.numpy``: no kernel, no sort, no grouped matmul;
attention a head and a block of ``ROW_BLOCK`` query rows at a time
against all keys with the mask written out, so that 16,384 rows fit.
Every matmul runs at ``highest``. It imports nothing of the program.
What it computes (the equations of ISSUE 38):

* ``x = Embed(ids) * sqrt(d)``; after the last layer RMSNorm, the untied
  head, next-token cross-entropy against ``label``, mean over ``mask``;
* layer l, with ``kind = layer_types[l]``: ``h = RMSNorm(x)``; ``q, k,
  v, g = h Wq, h Wk, h Wv, h Wg`` (H heads of D; Hkv; Hkv; H D); q and
  k RMSNorm'd over each head under one weight [D] each; on a
  ``sliding_attention`` layer q and k turned by their rows' positions
  (RoPE, rotate-half, theta ``rope_theta``), on a ``full_attention``
  layer NOT; ``s_ij = q_i . k_j / sqrt(D)`` kept where ``j <= i`` and,
  on a sliding layer, ``i - j < sliding_window``; head h reads
  key/value head ``h // (H / Hkv)``; ``a = softmax(s) v * sigmoid(g)``;
  ``x = x + RMSNorm(a Wo)``;
* then ``h = RMSNorm(x)``; a dense layer (``l < num_dense_layers``):
  ``f = Wdown(silu(Wgate h) * (Wup h))``; else ``p = sigmoid(h Wr)``
  over ALL experts, the k largest of ``p + bias``, weights ``p`` at
  the chosen over their sum (``route_norm``) times ``route_scale``,
  ``f = Shared(h) + sum_e w_e Expert_e(h)`` over the chosen experts
  THIS CHIP HOLDS (ids ``first_expert`` .. + the number held), every
  held expert evaluated on every row; what the other experts would add
  is left out; ``x = x + RMSNorm(f)``.

``choices`` (``[routed layers, T, k]``, the program's routing) and
``near_tie``: a row's proposed experts stand in for the reference's own
top-k only where every one of them scores (with the bias) within
``near_tie`` of the reference's own k-th largest; everywhere else the
reference routes by itself (``sdar_lm.routed``). ``operands`` is for
the CONTROL alone (as in ``opt_lm.py``): every matmul's operands held
in that dtype, per-tensor scaled; the router stays float32, as the
program keeps it.

``params``: ``{"word_emb" [V, d], "final_norm" [d], "w_out" [d, V],
"layers": [{"ln1", "ln1_post", "ln2", "ln2_post" [d], "wq", "wg" [d,
H D], "wk", "wv" [d, Hkv D], "q_norm", "k_norm" [D], "wo" [H D, d], and
either "ffn": (gate, up, down) or "shared": (gate, up, down), "router"
[d, E], "bias" [E], "w_gate", "w_up" [Eh, d, f], "w_down" [Eh, f,
d]}]}``.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.opt_lm import _held_in
from chipbench.reference.sdar_lm import _rms, _rope, routed

SLIDING = "sliding_attention"
ROW_BLOCK = 1024


def attention(q, k, v, window, mm):
    """q [T, H, D], k and v [T, Hkv, D] -> [T, H D]: causal softmax
    attention, within `window` keys where given."""
    t, heads, d = q.shape
    group = heads // k.shape[1]
    block = min(ROW_BLOCK, t)
    at = jnp.arange(t)
    q, k, v = (x.transpose(1, 0, 2) for x in (q, k, v))

    def one(args):
        head, first = args
        qb = jax.lax.dynamic_slice_in_dim(q[head], first, block)
        kh, vh = k[head // group], v[head // group]
        ahead = (first + jnp.arange(block))[:, None] - at[None, :]
        seen = ahead >= 0 if window is None \
            else (ahead >= 0) & (ahead < window)
        s = jnp.where(seen, mm(qb, kh.T) * d ** -0.5, -jnp.inf)
        return mm(jax.nn.softmax(s, -1), vh)

    grid = jnp.stack(jnp.meshgrid(jnp.arange(heads),
                                  jnp.arange(0, t, block), indexing="ij"),
                     -1).reshape(-1, 2)
    out = jax.lax.map(one, (grid[:, 0], grid[:, 1]))     # [H T/b, b, D]
    return out.reshape(heads, t, d).transpose(1, 0, 2).reshape(t, heads * d)


def expert_layer(p, h, cfg, first, held, mm, proposed=None, near_tie=0.0,
                 shared=True):
    """The routed layer's output on rows h [T, d] from the `held`
    experts with ids from `first` (``p["w_gate"]`` .. hold those alone),
    plus the shared expert's where `shared`."""
    k, n_exp = cfg["num_experts_per_tok"], cfg["published"]["num_experts"]
    gated = lambda w: mm(jax.nn.silu(mm(h, w[0])) * mm(h, w[1]), w[2])
    score = jax.nn.sigmoid(h @ p["router"])                   # float32
    top_i = routed(score + p["bias"], k, proposed, near_tie)
    chosen = jnp.any(top_i[:, :, None] == jnp.arange(n_exp), axis=1)
    weight = jnp.where(chosen, score, 0.0)
    if cfg["route_norm"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    weight = weight * cfg["route_scale"]

    def one_expert(y, e):                # every held expert, every row
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * gated((w_gate, w_up, w_down)), None

    y, _ = jax.lax.scan(
        one_expert, gated(p["shared"]) if shared else jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"],
         weight[:, first:first + held].T))
    return y


def hidden(params, tokens, cfg, choices=None, near_tie=0.0, operands=None):
    """tokens [T] -> the stream after the last layer [T, d]."""
    r = _held_in(operands)
    mm = lambda a, b: r(a) @ r(b)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["word_emb"][tokens] * cfg["hidden_size"] ** 0.5
    at = 0
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        sliding = kind == SLIDING
        h = _rms(x, p["ln1"], eps)
        q = _rms(mm(h, p["wq"]).reshape(t, heads, d_head), p["q_norm"], eps)
        k = _rms(mm(h, p["wk"]).reshape(t, kv_heads, d_head), p["k_norm"],
                 eps)
        v = mm(h, p["wv"]).reshape(t, kv_heads, d_head)
        if sliding:
            q = _rope(q, pos, float(cfg["rope_theta"]))
            k = _rope(k, pos, float(cfg["rope_theta"]))
        a = attention(q, k, v, cfg["sliding_window"] if sliding else None,
                      mm)
        a = a * jax.nn.sigmoid(mm(h, p["wg"]))
        x = x + _rms(mm(a, p["wo"]), p["ln1_post"], eps)
        h = _rms(x, p["ln2"], eps)
        if "ffn" in p:
            w = p["ffn"]
            f = mm(jax.nn.silu(mm(h, w[0])) * mm(h, w[1]), w[2])
        else:
            f = expert_layer(p, h, cfg, cfg["first_expert"],
                             cfg["num_experts"], mm,
                             None if choices is None else choices[at],
                             near_tie)
            at += 1
        x = x + _rms(f, p["ln2_post"], eps)
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
            x = _rms(hidden(p, tokens, cfg), p["final_norm"],
                     cfg["rms_norm_eps"])
            block = min(ROW_BLOCK, x.shape[0])

            def rows(args):
                xb, tb, wb = args
                logp = jax.nn.log_softmax(xb @ p["w_out"])
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
        return r(_rms(rows, p["final_norm"], cfg["rms_norm_eps"])) \
            @ r(p["w_out"])
