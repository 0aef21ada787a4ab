"""Architecture ``afmoe``: Trinity-Mini's training step as
``paddle_tpu/models/windowed_moe.py`` builds it (the embedding times
sqrt(d); layers of two kinds by ``layer_types``: sliding-window layers
with RoPE and a window of ``sliding_window`` keys, full layers with no
position signal; grouped-query attention, 32 heads of 128 reading 4,
QK-norm, an output gate, four RMSNorms a layer; ``num_dense_layers``
leading dense SiLU-gated FFNs, then a shared expert beside
sigmoid-routed top-8 of 128 experts of 1024 with a selection bias and
no auxiliary loss, of which this chip holds 8; untied head, next-token
loss; every layer a ``layers.recompute`` region). The reference is
``reference/afmoe_lm.py``; a configuration asks for this file with
``"arch": "afmoe"``.

What the harness feeds: ``src``, ``label`` (the next tokens) and
``mask``. ``logits`` are ``[B, T, V]``; ``correct`` compares the last
``check_rows`` rows of the first sequence, where a window row sees
``sliding_window`` keys and a full row all before it. Choices come
stacked ``[routed layers, 1, T, 8]``, fetched from inside the recompute
regions of the ``for_test`` clone.

The limits, each with the readings it was set from (my chip runs, PR
38, one v5e, the cell's own size: 5 layers, one 16,384-token sequence,
the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 2.5e-2: the program's bf16-AMP forward against
  the float32 reference handed the program's choices reads 8.74e-3 to
  1.083e-2 in fourteen readings of the configuration as shipped (eight
  benchmark runs on eight seeds and ``control.py``'s seeds 81-86), and
  8.34e-3 to 1.035e-2 in fifteen more under the router's first
  initialisation (N(0, 0.02): the configuration's ``assumed`` says why
  it went); the fp8 control handed the same choices 1.214e-1 to
  1.392e-1 on seeds 81-86 (1.143e-1 to 1.488e-1 on 71-76 before), 11.2
  times the program's largest (``control.py`` exit 0, ``separates``
  true, both times). 2.5e-2 is 2.3 times the program's largest (fresh
  seeds read higher, and four norms a layer renormalise what bf16
  rounded) and a fifth of the control's smallest.
* ``NEAR_TIE`` 5e-2: how far under the reference's own cut (its eighth
  largest of score + bias, as a share of it) the program's differing
  choices may lie for the reference to take them; SDAR's and Xing's
  limit under the same rule, where the largest reading was 1.24e-2
  (``archs/sdar.py``). Not read apart here: with every proposal within
  it the logits read as above, and a router that takes wrong experts
  lies under the cut by most of it and fails ``TRAIN_LOGITS_RTOL``.
* ``LOSS_RTOL`` 2.5e-4, Xing's: the first step's bf16-AMP loss against
  the reference's, which routes by itself, reads 1.87e-7 to 1.08e-5 in
  seventeen runs: a mean over 16,384 tokens. No precision control parts
  from it (a fresh model's loss is about ln V whatever the precision);
  what it guards is a dropped term, and the logits guard those too:
  ``tests/chipbench/test_chipbench_afmoe.py`` plants six (the window
  bound, RoPE on the full layer too, the output gate, ``route_scale``,
  the post-norms, the embedding's ``sqrt(d)``) through the driver and
  sees ``correct`` false.
"""

import numpy as np

from chipbench.reference import afmoe_lm

TRAIN_LOGITS_RTOL = 2.5e-2
LOSS_RTOL = 2.5e-4
NEAR_TIE = 5e-2
# the projections, the gate's, the dense FFN, the shared experts and the
# head are ``mul`` ops; the routed experts' grouped matmuls are XLA's
# ``ragged-dot-*`` kernels, which no scope holds (as ``archs/sdar.py``):
# the cell is not on ``matmul_roof_pct``'s list.
MATMUL_SCOPES = ("mul",)
NAME = "afmoe"          # the program's parameter prefix
SLIDING = "sliding_attention"


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.windowed_moe import windowed_moe_lm
    return windowed_moe_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq_len,
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        n_dense=cfg["num_dense_layers"], d_model=cfg["hidden_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], d_dense=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["route_norm"],
        route_scale=float(cfg["route_scale"]),
        bias_update_rate=cfg["load_balance_coeff"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        embedding_std=cfg["embedding_init_std"],
        router_std=cfg["router_init_std"], recompute=True, name=NAME)


def _ops(program):
    """The program's ops in order, those of its recompute regions in
    the regions' place."""
    def walk(block):
        for op in block.ops:
            if op.type == "recompute_block":
                yield from walk(op.attr("sub_block"))
            else:
                yield op
    return list(walk(program.global_block()))


def _routed(program):
    return [op for op in _ops(program) if op.type == "routed_experts"]


def params_of_program(program, scope, cfg):
    """HOST arrays, by the names ``windowed_moe_lm`` gives its
    parameters (the forward's run, which comes before the reference for
    a model that chooses, donates the scope's)."""
    get = lambda name: np.asarray(scope.find_var(name))
    three = lambda at: tuple(get("%s_%s" % (at, part))
                             for part in ("gate", "up", "down"))

    def layer(i):
        at = "%s_l%d" % (NAME, i)
        p = {key: get("%s_%s" % (at, key)) for key in (
            "ln1", "ln1_post", "ln2", "ln2_post", "wq", "wk", "wv", "wg",
            "q_norm", "k_norm", "wo")}
        if i < cfg["num_dense_layers"]:
            p["ffn"] = three(at + "_ffn")
        else:
            p["shared"] = three(at + "_shared")
            p.update({key: get("%s_moe.%s" % (at, key)) for key in (
                "router", "bias", "w_gate", "w_up", "w_down")})
        return p

    return {"word_emb": get(NAME + "_word_emb"),
            "final_norm": get(NAME + "_final_norm"),
            "w_out": get(NAME + "_head"),
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def router_choices(program):
    return [op.output("Indices")[0] for op in _routed(program)]


def program_counters(program, scope):
    """``expert_rows``: the rows that chose each of the 128 experts,
    summed over the routed layers and over every train step the program
    ran; ``steps``: those steps (the first routed layer's count);
    ``selection_bias_abs_max``: the largest selection bias, a layer
    each; ``window_scores_computed`` and ``window_scores_useful``: what
    the flash kernels' lowerings under a window added to
    ``ptpu_flash_band_scores_total`` in this process (counted at trace
    time, a batch row and head each, forward and backward walks: the
    scores the walks compute and the scores the band holds; nothing
    where the dense form ran, as on the CPU)."""
    from paddle_tpu.monitor import metrics
    routed = _routed(program)
    read = lambda op, slot: np.asarray(scope.find_var(op.input(slot)[0]))
    loads = [read(op, "Load").astype(np.int64) for op in routed]
    out = {"expert_rows": np.sum(loads, axis=0).tolist(),
           "steps": read(routed[0], "Steps").reshape(-1).tolist(),
           "selection_bias_abs_max": [
               float(np.abs(read(op, "Bias")).max()) for op in routed]}
    band = metrics.registry().get("ptpu_flash_band_scores_total")
    if band is not None:
        for kind in ("computed", "useful"):
            out["window_scores_" + kind] = [float(sum(
                v for key, v in band.snapshot().items()
                if key[band.label_names.index("kind")] == kind))]
    return out


# -- the reference (``reference/afmoe_lm.py``) -------------------------------

def lm_loss(params, src, label, mask, cfg):
    """No choices: the train step's cannot be fetched without another
    executable than the window's; ``LOSS_RTOL`` is set with that
    said."""
    return afmoe_lm.lm_loss(params, src, label, mask, cfg)


def _choices(choices, cfg):
    return None if choices is None else choices.reshape(
        choices.shape[0], -1, cfg["num_experts_per_tok"])


def logits_at(params, tokens, first, count, cfg, choices=None):
    return afmoe_lm.logits_at(params, tokens, first, count, cfg,
                              _choices(choices, cfg), NEAR_TIE)


def control_logits_at(params, tokens, first, count, cfg, choices=None):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul, routed exactly as ``logits_at`` routes given the same
    ``choices`` (the router stays float32)."""
    import jax.numpy as jnp
    return afmoe_lm.logits_at(params, tokens, first, count, cfg,
                              _choices(choices, cfg), NEAR_TIE,
                              operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: a
    layer's attention (q, k, v, the gate, o); the dense FFN or a shared
    expert, the router over all experts and the held experts a token
    expects (top-k times the share held here); the head."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = d * (3 * q + 2 * kv)
    expert = 3 * d * cfg["moe_intermediate_size"]
    everyone = cfg["published"]["num_experts"]
    held_a_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / everyone
    dense, layers = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    return (layers * attention + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * (cfg["num_shared_experts"] * expert
                                  + d * everyone + held_a_token * expert)
            + d * cfg["vocab_size"])


def useful_scores(seq_len, window=None):
    """The scores one head of one sequence needs: every key up to a
    query's own, or under a window its own and the window - 1 before
    it."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _score_flops(cfg):
    """Forward + backward FLOPs a useful score costs: q k^T and p v
    forward, s again, dp, dv, dq and dk backward, 2 D each: 14 D."""
    return 14 * cfg["head_dim"]


def _layer_scores(cfg, seq_len):
    """(the full layers' useful scores, the window layers') of one
    sequence, all heads."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    sliding = sum(kind == SLIDING for kind in kinds)
    heads = cfg["num_attention_heads"]
    return ((len(kinds) - sliding) * heads * useful_scores(seq_len),
            sliding * heads * useful_scores(seq_len, cfg["sliding_window"]))


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, NO recompute (the
    backward twice the forward): 6 a touched weight, and a token's
    share of its sequence's useful scores, the full layers' causal and
    the window layers' band. At ``seq_len`` 0 the matmuls outside
    attention alone."""
    if not seq_len:
        return 6 * touched_parameters(cfg)
    return 6 * touched_parameters(cfg) + _score_flops(cfg) * sum(
        _layer_scores(cfg, seq_len)) / seq_len


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: the full
    layers' causal scores and the window layers' band, every head, 14 D
    each. The recompute's second forward is in the kernels' time and
    not in this count."""
    return batch * _score_flops(cfg) * sum(_layer_scores(cfg, seq_len))


def window_flash_flops_per_step(cfg, batch, seq_len):
    """The window layers' share of ``flash_flops_per_step``: the band's
    useful scores alone."""
    return batch * _score_flops(cfg) * _layer_scores(cfg, seq_len)[1]


def expert_flops_per_pair(cfg):
    """Forward + backward FLOPs of one (row, held expert) pair: three
    matmuls of d x f, forward and twice that backward."""
    return 18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
