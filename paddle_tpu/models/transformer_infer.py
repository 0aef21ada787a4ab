"""Transformer inference: KV-cached incremental decode + beam search.

Reference parity: the decode path of test_machine_translation.py (While loop
+ beam_search ops over the RNN/transformer decoder) and the C++ inference
engine's transformer serving story. TPU-first: instead of interpreting the
training Program per token, the trained parameters are *extracted* from the
Program/Scope (in parameterized-op order, with loud role assertions) into a
pure-JAX incremental decoder — one jitted function containing the whole
generation loop (models/decoding.py lax.scan), KV caches updated with
dynamic_update_slice, beam reordering as a batched gather.

Works on any model built by models/transformer.transformer(); if the
builder's op sequence changes, the cursor assertions fail loudly rather
than silently mis-wiring weights.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import decoding
from ..ops import paged_attention as _paged_ops

__all__ = ["extract_params", "TransformerInfer"]

# every array a paged state dict may carry for the KV pool itself:
# codes + (when quantized, ISSUE 20) the per-vector scales beside them
_POOL_KEYS = ("pool_k", "pool_v", "pool_ks", "pool_vs")


_PARAM_OPS = {
    "lookup_table": ("lookup", "W"),
    "mul": ("mul", "Y"),
    "matmul": ("mul", "Y"),
    "elementwise_add": ("bias", "Y"),
    "layer_norm": ("layer_norm", None),
}


def extract_params(program, scope):
    """Walk the program's ops in order; yield (role, arrays) for every op
    that consumes a persistable parameter. This is the bridge from the
    Program IR to the pure-JAX inference model.

    Transform-specialized programs (ISSUE 15) are first-class inputs: a
    ``fused_matmul_bias_act`` op emits its anchor's "mul" role and its
    "bias" role at the SAME stream position the unfused chain would
    have — a fused artifact replays into the identical parameter
    stream."""
    gb = program.global_block()
    persistable = {v.name for v in gb.vars.values() if v.persistable}

    def _take(names):
        return jnp.asarray(scope.find_var(names[0]))

    out = []
    for op in gb.ops:
        if op.type == "fused_matmul_bias_act":
            for role, names in (("mul", op.input("Y")),
                                ("bias", op.input("Bias"))):
                if names and names[0] in persistable:
                    out.append((role, [_take(names)]))
            continue
        if op.type not in _PARAM_OPS:
            continue
        role, slot = _PARAM_OPS[op.type]
        if role == "layer_norm":
            names = [op.input("Scale")[0], op.input("Bias")[0]]
            out.append((role, [jnp.asarray(scope.find_var(n))
                               for n in names]))
            continue
        names = op.input(slot)
        if not names or names[0] not in persistable:
            continue  # residual adds etc.
        out.append((role, [_take(names)]))
    return out


class _Cursor:
    def __init__(self, items):
        self._items = items
        self._i = 0

    def take(self, role):
        if self._i >= len(self._items):
            raise AssertionError("parameter stream exhausted wanting %r"
                                 % role)
        got_role, arrays = self._items[self._i]
        if got_role != role:
            raise AssertionError(
                "parameter stream mismatch at %d: wanted %r got %r — "
                "training builder and inference replayer out of sync"
                % (self._i, role, got_role))
        self._i += 1
        return arrays[0] if len(arrays) == 1 else arrays

    def done(self):
        if self._i != len(self._items):
            raise AssertionError("unconsumed parameters: %d of %d used"
                                 % (self._i, len(self._items)))


def _split_heads(x, n_head):
    # [rows, T, H*dk] -> [rows, H, T, dk]
    r, t = x.shape[0], x.shape[1]
    return x.reshape(r, t, n_head, -1).transpose(0, 2, 1, 3)


def _ln(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps) * scale + bias).astype(
        x.dtype)


class TransformerInfer:
    """Replays models/transformer.transformer() weights for fast decode.

    dtype=jnp.bfloat16 enables the bf16 serving mode (weights + KV
    caches bf16, score softmax / LN stats / log-probs f32) — see
    TransformerLMInfer for the measured decode gains."""

    def __init__(self, program, scope, n_layer, n_head, d_model, max_len,
                 bos_id=1, end_id=2, dtype=None):
        self.n_layer, self.n_head = n_layer, n_head
        self.d_model, self.max_len = d_model, max_len
        self.bos_id, self.end_id = bos_id, end_id
        stream = extract_params(program, scope)
        cur = _Cursor(stream)
        # --- encoder params (builder order: embed, n_layer x enc layer) ---
        self.src_word_emb = cur.take("lookup")
        self.src_pos_emb = cur.take("lookup")
        self.enc_layers = [self._take_attn_ffn(cur) for _ in range(n_layer)]
        # --- decoder params ---
        self.trg_word_emb = cur.take("lookup")
        self.trg_pos_emb = cur.take("lookup")
        self.dec_layers = [self._take_dec_layer(cur) for _ in range(n_layer)]
        self.w_out = cur.take("mul")
        cur.done()
        self._cast_params(dtype)

    def _cast_params(self, dtype):
        if dtype is None:
            return
        if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                    jnp.dtype(jnp.float32)):
            # _ln's f32-stats upcast and the score/softmax precision
            # story are built for bf16; fp16's 5-bit exponent would
            # silently degrade LN statistics
            raise ValueError(
                "infer dtype must be bfloat16 or float32; got %r"
                % (dtype,))
        cast = lambda a: a.astype(dtype) if hasattr(a, "astype") else a
        for name, val in list(vars(self).items()):
            if name.startswith("_") or name in (
                    "n_layer", "n_head", "d_model", "max_len", "bos_id",
                    "end_id"):
                continue
            setattr(self, name, jax.tree_util.tree_map(cast, val))

    @staticmethod
    def _take_mha(cur):
        return {"wq": cur.take("mul"), "wk": cur.take("mul"),
                "wv": cur.take("mul"), "wo": cur.take("mul")}

    def _take_attn_ffn(self, cur):
        p = {"attn": self._take_mha(cur)}
        p["ln1"] = cur.take("layer_norm")
        p["ffn_w1"], p["ffn_b1"] = cur.take("mul"), cur.take("bias")
        p["ffn_w2"], p["ffn_b2"] = cur.take("mul"), cur.take("bias")
        p["ln2"] = cur.take("layer_norm")
        return p

    def _take_dec_layer(self, cur):
        p = {"self": self._take_mha(cur)}
        p["ln1"] = cur.take("layer_norm")
        p["cross"] = self._take_mha(cur)
        p["ln2"] = cur.take("layer_norm")
        p["ffn_w1"], p["ffn_b1"] = cur.take("mul"), cur.take("bias")
        p["ffn_w2"], p["ffn_b2"] = cur.take("mul"), cur.take("bias")
        p["ln3"] = cur.take("layer_norm")
        return p

    # ------------------------------------------------------------------
    def _mha(self, p, q_in, kv_k, kv_v, bias):
        """q_in [rows, Tq, D]; kv_k/v [rows, H, Tk, dk]; bias broadcastable
        to [rows, H, Tq, Tk]."""
        h = self.n_head
        with jax.named_scope("attn"):
            q = _split_heads(q_in @ p["wq"], h)
            dk = q.shape[-1]
            s = jnp.einsum("rhqd,rhkd->rhqk", q * (dk ** -0.5), kv_k,
                           preferred_element_type=jnp.float32)
            if bias is not None:
                s = s + bias
            w = jax.nn.softmax(s, axis=-1).astype(kv_v.dtype)
            o = jnp.einsum("rhqk,rhkd->rhqd", w, kv_v)
            r, t = q_in.shape[0], q_in.shape[1]
            return o.transpose(0, 2, 1, 3).reshape(r, t, -1) @ p["wo"]

    def _kv(self, p, x):
        h = self.n_head
        with jax.named_scope("attn"):
            return (_split_heads(x @ p["wk"], h),
                    _split_heads(x @ p["wv"], h))

    def _ffn(self, p, x):
        with jax.named_scope("mlp"):
            hdn = jax.nn.relu(x @ p["ffn_w1"] + p["ffn_b1"])
            return hdn @ p["ffn_w2"] + p["ffn_b2"]

    def _head(self, x):
        """The output projection to vocabulary logits."""
        with jax.named_scope("head"):
            return x @ self.w_out

    def encode(self, src_tokens, src_mask):
        """src_tokens [B, T] int32, src_mask [B, T] float; → [B, T, D]."""
        t = src_tokens.shape[1]
        x = self.src_word_emb[src_tokens] * (self.d_model ** 0.5) \
            + self.src_pos_emb[:t][None]
        bias = (src_mask[:, None, None, :] - 1.0) * 1e9
        for p in self.enc_layers:
            k, v = self._kv(p["attn"], x)
            a = self._mha(p["attn"], x, k, v, bias)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        return x

    # ------------------------------------------------------------------
    def _init_decode_state(self, enc_out, src_mask, rows):
        """Pre-compute cross K/V; allocate self-attn caches [rows,...]."""
        reps = rows // enc_out.shape[0]
        enc_out = jnp.repeat(enc_out, reps, axis=0)
        src_mask = jnp.repeat(src_mask, reps, axis=0)
        dk = self.d_model // self.n_head
        state = {"cross_bias": (src_mask[:, None, None, :] - 1.0) * 1e9}
        for i, p in enumerate(self.dec_layers):
            ck, cv = self._kv(p["cross"], enc_out)
            state["cross_k%d" % i], state["cross_v%d" % i] = ck, cv
            state["k%d" % i] = jnp.zeros(
                (rows, self.n_head, self.max_len, dk), enc_out.dtype)
            state["v%d" % i] = jnp.zeros_like(state["k%d" % i])
        return state

    def _step_logits(self, tok, state, t):
        """One incremental decode step: tok [rows] i32 → logits [rows, V]."""
        x = self.trg_word_emb[tok] * (self.d_model ** 0.5) \
            + self.trg_pos_emb[t]
        x = x[:, None, :]                               # [rows, 1, D]
        pos_mask = (jnp.arange(self.max_len) <= t)      # keys valid ≤ t
        self_bias = jnp.where(pos_mask, 0.0, -1e9)[None, None, None, :]
        for i, p in enumerate(self.dec_layers):
            k_new, v_new = self._kv(p["self"], x)       # [rows, H, 1, dk]
            k = lax.dynamic_update_slice_in_dim(state["k%d" % i], k_new, t,
                                                axis=2)
            v = lax.dynamic_update_slice_in_dim(state["v%d" % i], v_new, t,
                                                axis=2)
            state["k%d" % i], state["v%d" % i] = k, v
            a = self._mha(p["self"], x, k, v, self_bias)
            x = _ln(x + a, *p["ln1"])
            c = self._mha(p["cross"], x, state["cross_k%d" % i],
                          state["cross_v%d" % i], state["cross_bias"])
            x = _ln(x + c, *p["ln2"])
            x = _ln(x + self._ffn(p, x), *p["ln3"])
        logits = x[:, 0, :] @ self.w_out
        return logits, state

    # ------------------------------------------------------------------
    def translate(self, src_tokens, src_mask, beam_size=4, max_out_len=None,
                  length_penalty=0.0):
        """Beam-search translate. Returns (sentences [B, beam, T] — best
        first, scores [B, beam])."""
        max_out = self._check_out_len(max_out_len)
        batch = src_tokens.shape[0]
        enc = self.encode(src_tokens, src_mask)
        state = self._init_decode_state(enc, src_mask, batch * beam_size)
        return decoding.beam_search(self._step_logits, state, self.bos_id,
                                    self.end_id, max_out, batch, beam_size,
                                    length_penalty)

    def _check_out_len(self, max_out_len):
        max_out = max_out_len or self.max_len
        if max_out > self.max_len:
            # beyond max_len the pos-emb gather and KV-cache writes would
            # silently clamp and corrupt the cache — fail loudly instead
            raise ValueError(
                "max_out_len %d exceeds the model's max_len %d"
                % (max_out, self.max_len))
        return max_out

    def translate_greedy(self, src_tokens, src_mask, max_out_len=None):
        max_out = self._check_out_len(max_out_len)
        batch = src_tokens.shape[0]
        enc = self.encode(src_tokens, src_mask)
        state = self._init_decode_state(enc, src_mask, batch)
        return decoding.greedy_search(self._step_logits, state, self.bos_id,
                                      self.end_id, max_out, batch)


class TransformerLMInfer(TransformerInfer):
    """KV-cached incremental decode for the decoder-only flagship LM
    (models/transformer.transformer_lm) — the generation path of the
    reference's RecurrentGradientMachine
    (gserver/gradientmachines/RecurrentGradientMachine.h:32), rebuilt as
    one jitted XLA while-loop over a static KV cache. Same param-stream
    replay as TransformerInfer; the lm builder's per-layer stream (4
    attention muls, ln, ffn w1/b1/w2/b2, ln) is exactly the encoder
    layer's, so the cursor helpers are inherited."""

    def __init__(self, program, scope, n_layer, n_head, d_model, max_len,
                 bos_id=1, end_id=2, dtype=None):
        """dtype=jnp.bfloat16 casts weights AND KV caches to bf16 —
        halves cache HBM traffic (the beam-reorder/attention cost of
        each decode step); score softmax and the token log-probs stay
        f32 (_mha's preferred_element_type + decoding's log_softmax
        cast), the standard TPU serving precision recipe."""
        self.n_layer, self.n_head = n_layer, n_head
        self.d_model, self.max_len = d_model, max_len
        self.bos_id, self.end_id = bos_id, end_id
        stream = extract_params(program, scope)
        cur = _Cursor(stream)
        self.word_emb = cur.take("lookup")
        self.pos_emb = cur.take("lookup")
        self.layers = [self._take_attn_ffn(cur) for _ in range(n_layer)]
        self.w_out = cur.take("mul")
        cur.done()
        self._cast_params(dtype)

    def _init_state(self, rows):
        dk = self.d_model // self.n_head
        dtype = self.word_emb.dtype
        return {("k%d" % i if half == 0 else "v%d" % i):
                jnp.zeros((rows, self.n_head, self.max_len, dk), dtype)
                for i in range(self.n_layer) for half in (0, 1)}

    def _step_logits(self, tok, state, t):
        """One incremental step: tok [rows] i32 → (logits [rows, V],
        state with this token's K/V written at cache slot t)."""
        x = self.word_emb[tok] * (self.d_model ** 0.5) + self.pos_emb[t]
        x = x[:, None, :]
        pos_mask = (jnp.arange(self.max_len) <= t)
        self_bias = jnp.where(pos_mask, 0.0, -1e9)[None, None, None, :]
        for i, p in enumerate(self.layers):
            k_new, v_new = self._kv(p["attn"], x)
            k = lax.dynamic_update_slice_in_dim(state["k%d" % i], k_new,
                                                t, axis=2)
            v = lax.dynamic_update_slice_in_dim(state["v%d" % i], v_new,
                                                t, axis=2)
            state["k%d" % i], state["v%d" % i] = k, v
            a = self._mha(p["attn"], x, k, v, self_bias)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        return x[:, 0, :] @ self.w_out, state

    # -- serving (paddle_tpu.serving continuous batching) --------------
    def _step_logits_slots(self, tok, state, pos, write_mask=None):
        """Per-slot incremental step for the continuous-batching serving
        engine: like ``_step_logits`` but every row (slot) reads/writes
        its OWN cache position, so requests at different depths share one
        compiled step. tok [S] i32, pos [S] i32 (next cache write index
        per slot) → (logits [S, V], state). ``write_mask`` [S] bool
        gates the cache writes: a slot that is idle or still PREFILLING
        (the engine writes its prompt chunk-by-chunk between decode
        steps) must not clobber cache entries with its stale tok/pos.

        Row math is identical to ``_step_logits`` (same _mha/_ln/_ffn
        helpers, same bias constants): a slot's logits depend only on its
        own row, which is what makes engine output token-identical to the
        standalone one-at-a-time decode (pinned in tests/test_serving.py).
        """
        x = self.word_emb[tok] * (self.d_model ** 0.5) + self.pos_emb[pos]
        x = x[:, None, :]                                # [S, 1, D]
        ar = jnp.arange(self.max_len)
        self_bias = jnp.where(ar[None, :] <= pos[:, None], 0.0,
                              -1e9)[:, None, None, :]    # [S, 1, 1, L]
        ridx = jnp.arange(tok.shape[0])
        # per-slot scatter write (the dynamic_update_slice analog with a
        # VECTOR of start positions); masked-out rows write at max_len,
        # which mode="drop" discards
        wpos = pos if write_mask is None else \
            jnp.where(write_mask, pos, self.max_len)
        for i, p in enumerate(self.layers):
            k_new, v_new = self._kv(p["attn"], x)        # [S, H, 1, dk]
            with jax.named_scope("kv.write"):
                k = state["k%d" % i].at[ridx, :, wpos, :].set(
                    k_new[:, :, 0, :], mode="drop")
                v = state["v%d" % i].at[ridx, :, wpos, :].set(
                    v_new[:, :, 0, :], mode="drop")
            state["k%d" % i], state["v%d" % i] = k, v
            a = self._mha(p["attn"], x, k, v, self_bias)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        return self._head(x[:, 0, :]), state

    # -- paged KV (serving.kvpool block pool, ISSUE 10/20) -------------
    def _init_paged_state(self, num_blocks, block_size, kv_quant=None):
        """Shared paged KV pool: K and V arrays of shape
        ``[num_blocks, n_layer, n_head, block_size, dk]``. Slots map
        logical cache positions to physical blocks through per-slot
        block tables (``serving.kvpool.BlockPool`` owns the host-side
        accounting); unassigned table entries read block 0, whose
        garbage the causal bias masks exactly like the dense path
        masks a recycled slot's stale tail.

        ``kv_quant`` ('int8' / 'fp8', ISSUE 20): the pools store codes
        at the quantized dtype plus ONE f32 scale per cached vector —
        ``pool_ks``/``pool_vs`` [num_blocks, n_layer, n_head,
        block_size] beside the pool. Scales init to 1 so block 0's
        zero codes dequantize to the exact zeros the fp32 pool holds."""
        dk = self.d_model // self.n_head
        dtype = self.word_emb.dtype
        shape = (int(num_blocks), self.n_layer, self.n_head,
                 int(block_size), dk)
        spec = _paged_ops.kv_quant_spec(kv_quant)
        if spec is None:
            return {"pool_k": jnp.zeros(shape, dtype),
                    "pool_v": jnp.zeros(shape, dtype)}
        qdtype, _ = spec
        return {"pool_k": jnp.zeros(shape, qdtype),
                "pool_v": jnp.zeros(shape, qdtype),
                "pool_ks": jnp.ones(shape[:-1], jnp.float32),
                "pool_vs": jnp.ones(shape[:-1], jnp.float32)}

    # -- shared pool addressing (ISSUE 20: exactly ONE implementation) -
    def _pool_write(self, pools, i, wphys, off, k_new, v_new):
        """Write layer ``i``'s new K/V vectors into the pool:
        ``k_new``/``v_new`` [S, H, C, dk] land at
        ``(wphys[s, c], i, :, off[s, c])`` with ``wphys``/``off``
        [S, C] int32 (C = 1 for the single decode step). Out-of-bounds
        ``wphys`` rows (the write-mask convention: masked rows point at
        ``num_blocks``) drop via ``mode="drop"``. THE one pool-write
        implementation — every paged entry point (step, speculative,
        prefill, drafter) routes here. Quantized pools quantize per
        stored vector here (codes + per-position scale, ISSUE 20)."""
        with jax.named_scope("kv.write"):
            for name, sname, val in (
                    ("pool_k", "pool_ks", k_new), ("pool_v", "pool_vs",
                                                   v_new)):
                v = val.transpose(0, 2, 1, 3)        # [S, C, H, dk]
                if sname in pools:
                    codes, scale = _paged_ops.quantize_kv(
                        v, pools[name].dtype)
                    pools[name] = pools[name].at[
                        wphys, i, :, off, :].set(codes, mode="drop")
                    pools[sname] = pools[sname].at[
                        wphys, i, :, off].set(scale, mode="drop")
                else:
                    pools[name] = pools[name].at[
                        wphys, i, :, off, :].set(
                            v.astype(pools[name].dtype), mode="drop")
        return pools

    def _pool_gather(self, pools, i, btab):
        """THE dense block-table gather (the ``serving_block_kernel=0``
        escape hatch): layer ``i``'s K/V for every table row, gathered
        in position order and sliced back to the dense
        ``[S, H, max_len, dk]`` axis — position j of the key axis is
        logical position j, bit-for-bit the PR-10 math. ``btab``
        [S, max_blocks] int32 (or one [max_blocks] prefill row).
        Quantized pools dequantize on the gathered blocks."""
        bt = btab if btab.ndim == 2 else btab[None]
        s = bt.shape[0]
        dk = self.d_model // self.n_head
        out = []
        with jax.named_scope("kv.read"):
            for name, sname in (("pool_k", "pool_ks"),
                                ("pool_v", "pool_vs")):
                g = pools[name][:, i][bt]        # [S, NB, H, bs, dk]
                if sname in pools:
                    g = _paged_ops.dequantize_kv(
                        g, pools[sname][:, i][bt])
                out.append(g.transpose(0, 2, 1, 3, 4).reshape(
                    s, self.n_head, -1, dk)[:, :, :self.max_len])
        return out

    def _mha_paged(self, p, q_in, pools, i, btab, qpos, nblk, bias,
                   block_kernel, attn_unroll=1):
        """Paged-pool attention + output projection for queries
        ``q_in`` [S, C, D]. ``block_kernel=False`` gathers the dense
        axis through ``_pool_gather`` and runs ``_mha`` (the PR-10
        escape hatch); ``True`` runs the ISSUE-20 block-chain kernel
        (``ops/paged_attention``): online softmax over only the first
        ``nblk`` block-table columns, keys at cache positions
        ``<= qpos[s, c]`` attending — the causal-bias predicate,
        block-walked. Both paths produce the same tokens (the identity
        lattice pins them); the kernel's cost scales with blocks held,
        not ``max_len``."""
        if not block_kernel:
            k, v = self._pool_gather(pools, i, btab)
            return self._mha(p, q_in, k, v, bias)
        h = self.n_head
        with jax.named_scope("attn"):
            q = _split_heads(q_in @ p["wq"], h)
            dk = q.shape[-1]
            bt = btab if btab.ndim == 2 else btab[None]
            # FULL pool + static layer index: the kernel gathers (block,
            # layer) pairs; a pools[name][:, i] slice here would copy
            # the whole pool every step (capacity-proportional)
            o = _paged_ops.paged_attention(
                (q * (dk ** -0.5)).astype(jnp.float32),
                pools["pool_k"], pools["pool_v"], bt, qpos,
                nblk=nblk,
                k_scale=pools.get("pool_ks"),
                v_scale=pools.get("pool_vs"),
                block_group=attn_unroll, layer=i)
            o = o.astype(q_in.dtype)
            r, t = q_in.shape[0], q_in.shape[1]
            return o.transpose(0, 2, 1, 3).reshape(r, t, -1) @ p["wo"]

    @staticmethod
    def _pool_slice(state):
        """The pool entries of a paged state dict (codes + scales)."""
        return {n: state[n] for n in _POOL_KEYS if n in state}

    def _step_logits_paged(self, tok, state, pos, btab, write_mask=None,
                           n_layers=None, block_kernel=False,
                           attn_unroll=1):
        """Per-slot incremental step over the PAGED pool: like
        ``_step_logits_slots`` but each slot's K/V live in the shared
        block pool, addressed through its block table ``btab``
        [S, max_blocks] int32. Pool addressing (write + read) routes
        through the shared ``_pool_write`` / ``_mha_paged`` helpers
        (ISSUE 20): ``block_kernel=False`` gathers the dense
        ``[S, H, max_len, dk]`` axis so position j of the key axis is
        logical position j and greedy logits are bitwise the dense
        step's (the PR-10 bring-up math, now the escape hatch);
        ``block_kernel=True`` (the engine default) walks only the
        longest live block chain with the online-softmax kernel —
        token streams stay pinned identical, compute stops scaling
        with pool capacity.

        ``n_layers`` (a trace-time constant) runs only the FIRST n
        layers — the speculative tier-B drafter (ISSUE 13): a
        truncated pass over the same weights and pool proposes tokens,
        writing draft K/V only at layer rows the full-depth scoring
        dispatch immediately overwrites."""
        nb, bs = state["pool_k"].shape[0], state["pool_k"].shape[3]
        x = self.word_emb[tok] * (self.d_model ** 0.5) + self.pos_emb[pos]
        x = x[:, None, :]                                # [S, 1, D]
        ar = jnp.arange(self.max_len)
        self_bias = jnp.where(ar[None, :] <= pos[:, None], 0.0,
                              -1e9)[:, None, None, :]    # [S, 1, 1, L]
        blk = pos // bs
        off = pos % bs
        phys = jnp.take_along_axis(btab, blk[:, None], axis=1)[:, 0]
        # masked-out rows write at num_blocks, which mode="drop"
        # discards (the write-mask semantics of the dense path)
        wphys = phys if write_mask is None else \
            jnp.where(write_mask, phys, nb)
        qpos = pos[:, None]                              # [S, 1]
        # block-walk bound: the longest LIVE chain in the batch (an
        # idle slot's stale pos must not widen every slot's walk)
        live = pos if write_mask is None else \
            jnp.where(write_mask, pos, 0)
        nblk = jnp.minimum(jnp.max(live) // bs + 1, btab.shape[1])
        pools = self._pool_slice(state)
        layers = self.layers if n_layers is None \
            else self.layers[:n_layers]
        for i, p in enumerate(layers):
            k_new, v_new = self._kv(p["attn"], x)        # [S, H, 1, dk]
            pools = self._pool_write(pools, i, wphys[:, None],
                                     off[:, None], k_new, v_new)
            a = self._mha_paged(p["attn"], x, pools, i, btab, qpos,
                                nblk, self_bias, block_kernel,
                                attn_unroll)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        state.update(pools)
        return self._head(x[:, 0, :]), state

    def _spec_logits_paged(self, toks, state, pos, btab, n_valid,
                           write_mask=None, block_kernel=False,
                           attn_unroll=1):
        """Speculative scoring (ISSUE 13): logits at ALL ``C = γ+1``
        positions of every slot in ONE paged-attention dispatch.
        ``toks`` [S, C] holds each slot's current token followed by its
        γ drafted tokens; position j is written/read at cache position
        ``pos[s] + j`` through the slot's block-table row, and the
        logits at index j are the model's next-token distribution
        AFTER consuming ``toks[s, :j+1]`` — exactly what the j-th
        single step of ``_step_logits_paged`` would produce, which is
        what the engine's accept-longest-prefix rule verifies against.

        Ragged per-slot draft lengths ride the same masked-scatter
        machinery as the chunk prefill: ``n_valid`` [S] is the number
        of valid DRAFT tokens per slot, so positions ``j > n_valid[s]``
        (and every position of a ``write_mask``-False slot) write at
        index ``num_blocks`` and drop; their logits are garbage the
        acceptance math never reads. The causal bias masks cache
        positions beyond each query, so a rejected draft's stale K/V
        from a PREVIOUS dispatch is never attended before the dispatch
        that re-writes it.

        Pool addressing rides the same ``_pool_write`` /
        ``_mha_paged`` helpers as the single step (ISSUE 20): with
        ``block_kernel=True`` the γ+1-query variant of the block-chain
        kernel scores all C positions while walking only the live
        chains — the second dense-gather path this method used to
        carry is gone."""
        nb, bs = state["pool_k"].shape[0], state["pool_k"].shape[3]
        s, c = toks.shape
        cpos = pos[:, None] + jnp.arange(c)[None, :]     # [S, C]
        gather_pos = jnp.minimum(cpos, self.max_len - 1)
        x = self.word_emb[toks] * (self.d_model ** 0.5) \
            + self.pos_emb[gather_pos]                   # [S, C, D]
        ar = jnp.arange(self.max_len)
        # query j attends cache keys <= pos+j (its own K/V is written
        # below before the attention reads the pool)
        bias = jnp.where(ar[None, None, :] <= cpos[:, :, None], 0.0,
                         -1e9)[:, None, :, :]            # [S, 1, C, L]
        blk = jnp.minimum(cpos // bs, btab.shape[1] - 1)
        off = cpos % bs
        phys = jnp.take_along_axis(btab, blk, axis=1)    # [S, C]
        valid = jnp.arange(c)[None, :] <= n_valid[:, None]
        if write_mask is not None:
            valid = valid & write_mask[:, None]
        wphys = jnp.where(valid, phys, nb)               # OOB → dropped
        qpos = jnp.minimum(cpos, self.max_len - 1)
        live = pos if write_mask is None else \
            jnp.where(write_mask, pos, 0)
        nblk = jnp.minimum(jnp.max(live + (c - 1)) // bs + 1,
                           btab.shape[1])
        pools = self._pool_slice(state)
        for i, p in enumerate(self.layers):
            k_new, v_new = self._kv(p["attn"], x)        # [S, H, C, dk]
            pools = self._pool_write(pools, i, wphys, off, k_new,
                                     v_new)
            a = self._mha_paged(p["attn"], x, pools, i, btab, qpos,
                                nblk, bias, block_kernel, attn_unroll)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        state.update(pools)
        return self._head(x), state                      # [S, C, V]

    def _prefill_chunk_paged(self, state, toks, start, n_valid,
                             btab_row, block_kernel=False,
                             attn_unroll=1):
        """Teacher-forced chunk prefill into the paged pool for ONE
        slot whose block table is ``btab_row`` [max_blocks] int32: the
        paged twin of ``_prefill_chunk_slot`` (same fixed chunk shape,
        masked padded tail, output head dead-coded). A prefix-cache
        hit never reaches here for the cached positions — the engine
        advances the cursor past them — but the chunk's attention DOES
        read the shared cached blocks through the table. Pool
        addressing rides the shared ``_pool_write`` / ``_mha_paged``
        helpers (ISSUE 20); the block kernel walks only the blocks up
        to this chunk's last valid position."""
        nb, bs = state["pool_k"].shape[0], state["pool_k"].shape[3]
        c = toks.shape[0]
        idx = jnp.arange(c)
        cpos = start + idx                               # [C]
        valid = idx < n_valid
        gather_pos = jnp.where(valid,
                               jnp.minimum(cpos, self.max_len - 1), 0)
        x = self.word_emb[toks] * (self.d_model ** 0.5) \
            + self.pos_emb[gather_pos]
        x = x[None]                                      # [1, C, D]
        ar = jnp.arange(self.max_len)
        bias = jnp.where(ar[None, :] <= cpos[:, None], 0.0,
                         -1e9)[None, None, :, :]         # [1, 1, C, L]
        blk = jnp.minimum(cpos // bs, btab_row.shape[0] - 1)
        off = cpos % bs
        wphys = jnp.where(valid, btab_row[blk], nb)      # OOB → dropped
        qpos = jnp.minimum(cpos, self.max_len - 1)[None]  # [1, C]
        nblk = jnp.minimum(
            (start + jnp.maximum(n_valid, 1) - 1) // bs + 1,
            btab_row.shape[0])
        pools = self._pool_slice(state)
        for i, p in enumerate(self.layers):
            k_new, v_new = self._kv(p["attn"], x)        # [1, H, C, dk]
            pools = self._pool_write(pools, i, wphys[None], off[None],
                                     k_new, v_new)
            a = self._mha_paged(p["attn"], x, pools, i, btab_row, qpos,
                                nblk, bias, block_kernel, attn_unroll)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        state.update(pools)
        return state

    def _prefill_chunk_slot(self, state, slot, toks, start, n_valid):
        """Teacher-forced chunk prefill for ONE slot: write the K/V of
        ``toks[:n_valid]`` at cache positions ``start..start+n_valid-1``.
        toks is a FIXED-size chunk (one compile per chunk length); the
        padded tail is masked out of the writes. No logits are computed —
        the output head is dead code here and XLA drops it — so prefill
        steps cost attention+FFN only."""
        c = toks.shape[0]
        idx = jnp.arange(c)
        cpos = start + idx                               # [C]
        valid = idx < n_valid
        gather_pos = jnp.where(valid,
                               jnp.minimum(cpos, self.max_len - 1), 0)
        x = self.word_emb[toks] * (self.d_model ** 0.5) \
            + self.pos_emb[gather_pos]
        x = x[None]                                      # [1, C, D]
        ar = jnp.arange(self.max_len)
        # chunk query i attends cache keys j <= start+i (its own K/V is
        # written below before the attention reads the cache)
        bias = jnp.where(ar[None, :] <= cpos[:, None], 0.0,
                         -1e9)[None, None, :, :]         # [1, 1, C, L]
        wpos = jnp.where(valid, cpos, self.max_len)      # OOB → dropped
        for i, p in enumerate(self.layers):
            k_new, v_new = self._kv(p["attn"], x)        # [1, H, C, dk]
            with jax.named_scope("kv.write"):
                k = state["k%d" % i].at[slot, :, wpos, :].set(
                    k_new[0].transpose(1, 0, 2), mode="drop")
                v = state["v%d" % i].at[slot, :, wpos, :].set(
                    v_new[0].transpose(1, 0, 2), mode="drop")
            state["k%d" % i], state["v%d" % i] = k, v
            a = self._mha(p["attn"], x, k[slot][None], v[slot][None],
                          bias)
            x = _ln(x + a, *p["ln1"])
            x = _ln(x + self._ffn(p, x), *p["ln2"])
        return state

    def generate(self, batch, max_out_len=None, beam_size=1,
                 length_penalty=0.0):
        """Generate from BOS. beam_size=1 → greedy ((tokens [B, T],
        scores [B])); beam_size>1 → beam search ((tokens [B, beam, T],
        scores [B, beam]))."""
        max_out = self._check_out_len(max_out_len)
        if beam_size > 1:
            state = self._init_state(batch * beam_size)
            return decoding.beam_search(
                self._step_logits, state, self.bos_id, self.end_id,
                max_out, batch, beam_size, length_penalty)
        state = self._init_state(batch)
        return decoding.greedy_search(self._step_logits, state,
                                      self.bos_id, self.end_id, max_out,
                                      batch)


_LM_PNAMES = ("word_emb", "pos_emb", "layers", "w_out")


def _small_lm_for_analysis(dtype=None):
    """The tiny flagship-LM build the analyzer entries trace (2L/d32,
    max_len 16 — device-free beyond startup init on whatever
    JAX_PLATFORMS provides)."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        from .transformer import transformer_lm
        transformer_lm(vocab_size=64, max_len=16, n_layer=2, n_head=2,
                       d_model=32, d_inner=64)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return TransformerLMInfer(main, scope, n_layer=2, n_head=2,
                                  d_model=32, max_len=16, dtype=dtype)


def analysis_entry_infer():
    """Static-analyzer entry: bf16 KV-cached greedy decode — the
    serving graph whose precision invariants (bf16 weights/caches, f32
    softmax + LN stats + log-probs) the dtype-promotion rule verifies
    statically. Params are passed as an argument pytree (not closed
    over) so the recompile-hazard rule sees the real serving
    signature."""
    infer = _small_lm_for_analysis(dtype=jnp.bfloat16)
    params = {n: getattr(infer, n) for n in _LM_PNAMES}

    def fn(params):
        for n in _LM_PNAMES:
            setattr(infer, n, params[n])
        return infer.generate(2, max_out_len=8)

    return fn, (params,)


def analysis_entry_serving_megastep():
    """Static-analyzer entry for the ISSUE-7 fused-K serving decode:
    the continuous-batching engine's megastep body — K=4 slot decode
    iterations (``_step_logits_paged`` through the per-slot block
    tables + the greedy/sampled per-slot state) scanned into ONE
    device program over the shared paged-KV pool. Traces the REAL
    ``serving.Engine._megastep_impl`` so the recompile-hazard rule's
    scanned-unit heuristic sees the production fused body (K is a
    static trace constant: varying it recompiles the whole unit), and
    the dtype rule audits the megastep at the same bf16-weights /
    f32-score precision contract as the plain decode entry. Since
    ISSUE 20 the engine default routes attention through the
    block-chain kernel, so the traced body carries the dynamic
    chain-walk (a while_loop inside the scan) the rules now audit."""
    from ..serving.engine import Engine

    infer = _small_lm_for_analysis(dtype=jnp.bfloat16)
    eng = Engine(infer, slots=2, prefill_chunk=4, megastep=4,
                 name="analysis")
    # tracing only: the scheduler thread is stopped before the entry is
    # handed to the analyzer (megastep_impl is a pure function of
    # state + block tables)
    eng.close()
    params = {n: getattr(infer, n) for n in _LM_PNAMES}
    state = dict(eng._state)
    btab = eng._btab_all()

    def fn(params, state, btab):
        for n in _LM_PNAMES:
            setattr(infer, n, params[n])
        state, emits, fins = eng._megastep_impl(state, btab)
        return emits, fins, state["score"]

    return fn, (params, state, btab)
