"""Architecture ``sambay``: Phi-4-mini-flash-reasoning's training step
as ``paddle_tpu/models/hybrid_ssm.py`` builds it (no position signal;
layers of six kinds by ``layer_kinds``: Mamba mixers with a selective
scan of 16 states a channel, one of which keeps its scan output as the
MEMORY; differential attention, 40 query and 20 key/value heads of 64
in pairs against values of 128, under a window of ``sliding_window``
keys, full, or as cross-attention onto the full layer's keys and
values; gated memory units; a SiLU-gated MLP and two LayerNorms with
bias in every layer; the head tied to the embedding, next-token loss;
every layer a ``layers.recompute`` region). The reference is
``reference/sambay_lm.py``; a configuration asks for this file with
``"arch": "sambay"``.

What the harness feeds: ``src``, ``label`` (the next tokens) and
``mask``. ``logits`` are ``[B, T, V]``; ``correct`` compares the last
``check_rows`` rows of the first sequence, where the scans have run
nearly T steps, a window row sees ``sliding_window`` keys and a full
or cross row all before it. The model chooses nothing: no
``router_choices``.

The limits, each with the readings it was set from (my chip runs, PR
40, one v5e, the cell's own size: 6 layers, one 8,192-token sequence,
the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 3.5e-2: the program's bf16-AMP forward against
  the float32 reference reads 1.305e-2 to 1.776e-2 in twenty readings
  (six untraced and five traced benchmark runs from a clean export on
  eleven seeds, ``control.py``'s seeds 91-93 and 101-106); the fp8
  control 1.747e-1 to 2.327e-1 on those nine seeds, 9.8 times the
  program's largest (``control.py`` exit 0, ``separates`` true both
  times). 3.5e-2 is 1.97 times the program's largest (fresh seeds read
  higher) and a fifth of the control's smallest. A SECOND control, the
  reference with the scan's state held in bfloat16 between steps
  (``bf16_state_logits_at``), reads 9.09e-3, 1.652e-2 and 1.254e-1 on
  seeds 91, 92 and 93: it fails this limit on one seed of three and
  lies inside the program's own noise on the others, so NO limit of
  this comparison reliably sees the state's precision (how far a
  bfloat16 state drifts depends on how long the slowest channels
  remember, against 8,192 steps); what holds the kernels to a float32
  state is ``tests/test_selective_scan.py`` and ``chip_smoke.py
  --phases scan`` (``PERF.md`` section 7 says what a limit that saw it
  would need).
* ``LOSS_RTOL`` 2.5e-4, the harness's accepted cells' (Xing's and
  Trinity's): the first step's bf16-AMP loss against the reference's
  reads 2.69e-7 to 3.15e-5 in thirteen runs, a mean over 8,192 tokens,
  so the limit leaves eight times of room. No precision control parts
  from it (a fresh model's loss is about ln V whatever the precision);
  what it guards is a dropped term, and the logits guard those too:
  ``tests/chipbench/test_chipbench_sambay.py`` plants eight through
  the driver and sees ``correct`` false.
"""

import numpy as np

from chipbench.reference import sambay_lm

TRAIN_LOGITS_RTOL = 3.5e-2
LOSS_RTOL = 2.5e-4
# every matmul is a scoped ``mul``: the projections of the five mixers,
# the MLP's three and the tied head (a ``mul`` with ``transpose_Y``)
MATMUL_SCOPES = ("mul",)
NAME = "sambay"         # the program's parameter prefix
MAMBA = ("mamba", "mamba_memory")
ATTENTION = ("sliding", "full", "cross")


def _mamba(cfg):
    """(d_inner, d_state, d_conv, dt_rank): the configuration's
    ``mamba`` group, Mamba-1's defaults at this hidden size."""
    m = cfg["mamba"]
    return (m["expand"] * cfg["hidden_size"], m["d_state"], m["d_conv"],
            m["dt_rank"])


def _kinds(cfg):
    return cfg["layer_kinds"][:cfg["num_hidden_layers"]]


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.hybrid_ssm import hybrid_ssm_lm
    d_inner, d_state, d_conv, dt_rank = _mamba(cfg)
    return hybrid_ssm_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq_len,
        layer_kinds=_kinds(cfg), d_model=cfg["hidden_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=_head_dim(cfg),
        window=cfg["sliding_window"], d_ffn=cfg["intermediate_size"],
        d_inner=d_inner, d_state=d_state, d_conv=d_conv, dt_rank=dt_rank,
        ln_eps=cfg["layer_norm_eps"],
        embedding_std=cfg["embedding_init_std"], recompute=True,
        scan_chunk=cfg.get("scan_chunk", 0),
        scan_force=cfg.get("scan_force", ""), name=NAME)


# the reference's keys of a mixer by the layer's kind, and the part of
# the program's parameter name where it is not the key itself
_ATTENTION = ("wq", "wq_b", "wo", "wo_b", "lq1", "lk1", "lq2", "lk2", "subln")
_KEYS = {"mamba": ("in_s", "in_z", "conv_w", "conv_b", "x_dt", "x_b", "x_c",
                   "dt", "dt_b", "a_log", "d", "out"),
         "gmu": ("in", "out"), "cross": _ATTENTION,
         "full": _ATTENTION + ("wk", "wk_b", "wv", "wv_b")}
_KEYS.update(mamba_memory=_KEYS["mamba"], sliding=_KEYS["full"])
_NAMED = {"a_log": "scan_a_log", "d": "scan_d", "subln": "diff_subln",
          **{key: "diff_" + key for key in ("lq1", "lk1", "lq2", "lk2")}}


def parameter_names(cfg):
    """The reference's tree with the program's parameter NAMES at its
    leaves."""
    pair = lambda at: (at + "_w", at + "_b")

    def layer(i, kind):
        at = "%s_l%d" % (NAME, i)
        p = {key: "%s_%s" % (at, _NAMED.get(key, key))
             for key in _KEYS[kind]}
        p.update(ln1=pair(at + "_ln1"), ln2=pair(at + "_ln2"),
                 ffn=tuple("%s_ffn_%s" % (at, part)
                           for part in ("gate", "up", "down")))
        return p

    return {"word_emb": NAME + "_word_emb",
            "final_norm": pair(NAME + "_final_norm"),
            "layers": [layer(i, kind)
                       for i, kind in enumerate(_kinds(cfg))]}


def params_of_program(program, scope, cfg):
    """HOST arrays, by the names ``hybrid_ssm_lm`` gives its
    parameters."""
    import jax
    return jax.tree.map(lambda name: np.asarray(scope.find_var(name)),
                        parameter_names(cfg))


def program_counters(program, scope):
    """What the flash and scan dispatches counted at trace time in this
    process, by path: ``flash_lowerings`` ``{"pallas": n, "dense": n}``
    and ``scan_lowerings`` ``{"pallas/fwd": n, ...}``. A cell's run
    shows here that no attention went the dense way and no scan the
    step-loop way."""
    from paddle_tpu.monitor import metrics

    def by(name, *labels):
        counter = metrics.registry().get(name)
        out = {}
        if counter is not None:
            for key, v in counter.snapshot().items():
                tag = "/".join(key[counter.label_names.index(l)]
                               for l in labels)
                out[tag] = out.get(tag, 0) + v
        return out

    return {"flash_lowerings": by("ptpu_flash_lowerings_total", "path"),
            "scan_lowerings": by("ptpu_scan_lowerings_total", "path",
                                 "direction")}


# -- the reference (``reference/sambay_lm.py``) ------------------------------

def lm_loss(params, src, label, mask, cfg):
    return sambay_lm.lm_loss(params, src, label, mask, cfg)


def logits_at(params, tokens, first, count, cfg):
    return sambay_lm.logits_at(params, tokens, first, count, cfg)


def control_logits_at(params, tokens, first, count, cfg):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul; the scan's state stays float32."""
    import jax.numpy as jnp
    return sambay_lm.logits_at(params, tokens, first, count, cfg,
                               operands=jnp.float8_e4m3fn)


def bf16_state_logits_at(params, tokens, first, count, cfg):
    """A second control: the float32 reference with the scan's state
    held in bfloat16 between steps, which the configuration's float32
    state has to part from by a limit too."""
    import jax.numpy as jnp
    return sambay_lm.logits_at(params, tokens, first, count, cfg,
                               state_dtype=jnp.bfloat16)


# -- the arithmetic ---------------------------------------------------------

def mixer_parameters(cfg, kind):
    """The matmul weights of one layer's mixer."""
    d = cfg["hidden_size"]
    d_inner, d_state, _, dt_rank = _mamba(cfg)
    q = cfg["num_attention_heads"] * _head_dim(cfg)
    kv = cfg["num_key_value_heads"] * _head_dim(cfg)
    return {"mamba": 2 * d * d_inner + d_inner * (dt_rank + 2 * d_state)
            + dt_rank * d_inner + d_inner * d,
            "gmu": 2 * d * d_inner,
            "cross": 2 * d * q,
            "full": 2 * d * q + 2 * d * kv}[
                {"mamba_memory": "mamba", "sliding": "full"}.get(kind, kind)]


def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: each
    layer's mixer by its kind and its MLP (gate, up, down); the head
    over the rows of the vocabulary held here."""
    d = cfg["hidden_size"]
    return (sum(mixer_parameters(cfg, kind)
                + 3 * d * cfg["intermediate_size"] for kind in _kinds(cfg))
            + d * cfg["vocab_size"])


def useful_scores(seq_len, window=None):
    """The scores one softmax of one sequence needs: every key up to a
    query's own, or under a window its own and the window - 1 before
    it."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _score_flops(cfg):
    """Forward + backward FLOPs a useful score of one softmax costs,
    its key D wide and its value 2D: q k^T (2 D) and p v (4 D) forward;
    s again (2 D), dp (4 D), dv (4 D), dq and dk (2 D each) backward:
    20 D. (A head whose value is as wide as its key costs 14 D, the
    other cells' count.)"""
    return 20 * _head_dim(cfg)


def _layer_scores(cfg, seq_len):
    """{kind: the useful scores of that kind's layers} of one sequence,
    every softmax (one a query head)."""
    out = dict.fromkeys(ATTENTION, 0)
    for kind in _kinds(cfg):
        if kind in ATTENTION:
            out[kind] += cfg["num_attention_heads"] * useful_scores(
                seq_len, cfg["sliding_window"] if kind == "sliding"
                else None)
    return out


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, NO recompute (the
    backward twice the forward): 6 a touched weight, and a token's
    share of its sequence's useful scores. The scans are no matmul and
    are not here (``scan_updates_per_step``). At ``seq_len`` 0 the
    matmuls outside attention alone."""
    if not seq_len:
        return 6 * touched_parameters(cfg)
    return 6 * touched_parameters(cfg) + _score_flops(cfg) * sum(
        _layer_scores(cfg, seq_len).values()) / seq_len


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: the window
    layers' band and the full and cross layers' causal scores, every
    softmax, 20 D each. The recompute's second forward is in the
    kernels' time and not in this count; nor are the zero lanes the
    kernels contract beside a query (``flash_diff_bthd``)."""
    return batch * _score_flops(cfg) * sum(
        _layer_scores(cfg, seq_len).values())


def _scans(cfg):
    return sum(kind in MAMBA for kind in _kinds(cfg))


def scan_bytes_per_step(cfg, batch, seq_len, itemsize=2):
    """The bytes the selective scans of one train step must move, each
    operand read once and each result written once a pass, operands of
    `itemsize` bytes (bf16 under AMP). A forward pass reads s, dt ``[T,
    C]`` and B_t, C_t ``[T, N]`` and writes y; the backward reads
    those and dy and writes ds, ddt, dB_t, dC_t. Under per-layer
    recompute a step runs the forward TWICE (it runs: its bytes are
    counted) and the backward once: 11 ``[T, C]`` and 8 ``[T, N]``
    values a Mamba layer. A, D and their gradients are a few hundred
    KB; the states saved at chunk boundaries and the lane-broadcast
    copies of B_t and C_t are the kernels' own choice, not bytes the
    algorithm needs, and not here."""
    d_inner, d_state = _mamba(cfg)[:2]
    return _scans(cfg) * batch * seq_len * itemsize * (
        11 * d_inner + 8 * d_state)


def scan_updates_per_step(cfg, batch, seq_len):
    """State updates (one ``H[c, n]`` one step on) of one train step:
    T C N a pass, three passes a Mamba layer (forward, recomputed,
    backward)."""
    d_inner, d_state = _mamba(cfg)[:2]
    return 3 * _scans(cfg) * batch * seq_len * d_inner * d_state
