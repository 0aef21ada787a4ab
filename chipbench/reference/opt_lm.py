"""Plain float32 reference of the language model both configurations
run: the OPT-350m block (post-LN, ReLU, full multi-head attention, a
position table added to the word embedding; facebook/opt-350m
config.json) with the repo's three departures, listed under ``assumed``
in the configuration files: the word embedding is hidden-size wide and
scaled by sqrt(d) (OPT-350m embeds at 512 and projects), the position
table is a frozen sinusoid (OPT's is learned), and the output head is a
matrix of its own (OPT ties it to the embedding). q/k/v/o carry no bias
(``models/transformer.py`` builds them so); the FFN and the layer norms
do.

Straightforward ``jax.numpy``: no kernel, no cache, no batching beyond
``lax.map``. Every matmul runs at ``highest`` precision, since a TPU
multiplies float32 operands in bfloat16 passes by default.

``params``: ``{"word_emb" [V, d], "pos_emb" [L, d], "w_out" [d, V],
"layers": [{"wq", "wk", "wv", "wo" [d, d], "ln1": (scale, bias),
"ffn_w1" [d, f], "ffn_b1", "ffn_w2" [f, d], "ffn_b2", "ln2"}]}``.
"""

import jax
import jax.numpy as jnp


def _ln(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def hidden(params, tokens, n_head):
    """tokens [T] int -> the last layer's output [T, d], float32."""
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    t = tokens.shape[0]
    d = p32["word_emb"].shape[1]
    dk = d // n_head
    x = p32["word_emb"][tokens] * (d ** 0.5) + p32["pos_emb"][:t]
    future = jnp.arange(t)[None, :] > jnp.arange(t)[:, None]
    with jax.default_matmul_precision("highest"):
        for p in p32["layers"]:
            heads = lambda w: (x @ w).reshape(t, n_head, dk).transpose(
                1, 0, 2)
            q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
            s = jnp.einsum("hqd,hkd->hqk", q, k) * (dk ** -0.5)
            w = jax.nn.softmax(jnp.where(future[None], -jnp.inf, s), -1)
            a = jnp.einsum("hqk,hkd->hqd", w, v).transpose(
                1, 0, 2).reshape(t, d) @ p["wo"]
            x = _ln(x + a, *p["ln1"])
            f = jax.nn.relu(x @ p["ffn_w1"] + p["ffn_b1"]) \
                @ p["ffn_w2"] + p["ffn_b2"]
            x = _ln(x + f, *p["ln2"])
    return x


def logits_at(params, tokens, first, count, n_head):
    """Next-token logits [count, V] after positions first..first+count-1
    of ``tokens`` [T] (``count`` static)."""
    x = jax.lax.dynamic_slice_in_dim(hidden(params, tokens, n_head),
                                     first, count)
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(params["w_out"], jnp.float32)


def lm_loss(params, src, label, mask, n_head):
    """Mean next-token cross-entropy over a batch [B, T], weighted by
    ``mask``: the training program's loss."""
    def one(args):
        s, l, m = args
        with jax.default_matmul_precision("highest"):
            logits = hidden(params, s, n_head) \
                @ jnp.asarray(params["w_out"], jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.sum(jnp.take_along_axis(logp, l[:, None], 1)[:, 0]
                        * m)
    return jnp.sum(jax.lax.map(one, (src, label, mask))) / jnp.sum(mask)
