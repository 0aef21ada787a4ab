"""Architecture ``lfm2``: LFM2-8B-A1B's training step as
``paddle_tpu/models/conv_moe.py`` builds it (a pre-norm block with two
RMSNorms; layers of two kinds by ``layer_types``: ``conv`` layers whose
token mixer is a doubly gated short convolution and nothing else, ``C *
conv(B * X)`` of ``conv_L_cache`` taps between two projections, and
``full_attention`` layers, grouped-query attention of 32 heads of 64
reading 8, QK-norm then RoPE; ``num_dense_layers`` leading dense
SiLU-gated FFNs, then sigmoid-routed top-4 of 32 experts of 1792 with a
selection bias, 1e-6 in the chosen weights' sum and no shared expert
and no auxiliary loss, of which this chip holds 8; the head the
embedding's own table, next-token loss; every layer a
``layers.recompute`` region). The reference is
``reference/lfm2_lm.py``; a configuration asks for this file with
``"arch": "lfm2"``.

What the harness feeds: ``src``, ``label`` (the next tokens) and
``mask``. ``logits`` are ``[B, T, V]``; ``correct`` compares the last
``check_rows`` rows of the first sequence, where an attention row sees
every key before it. Choices come stacked ``[routed layers, 1, T, 4]``,
fetched from inside the recompute regions of the ``for_test`` clone.
``layer_types`` stays at its 24 published entries; the first
``num_hidden_layers`` are read. The config has no ``head_dim``: a head
is ``hidden_size / num_attention_heads`` wide (a rehearsal states one).

The limits, each with the readings it was set from (my chip runs, PR
49, one v5e, the cell's own size: 5 layers, one 32,768-token sequence,
the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 5e-2: the program's bf16-AMP forward against
  the float32 reference handed the program's choices reads 1.476e-2 to
  2.091e-2 in 24 readings of the configuration as shipped (twelve
  benchmark runs on twelve seeds, five of them traced, 1.48e-2 to
  1.89e-2; ``control.py``'s twelve seeds, 1.476e-2 to 2.091e-2); the
  fp8 control handed the same choices 2.156e-1 to 4.887e-1 on
  ``control.py``'s twelve seeds (2147483977, 1357924680, 46021,
  2147483877, 717171717, 3000000411, 81-86; exit 0, ``separates``
  true), 10.3 times the program's largest there. 5e-2 is 2.4 times the
  program's largest (fresh seeds read higher) and 0.23 of the control's
  smallest. The program reads higher than the other routed cells'
  (3.5e-3 to 1.08e-2) and as ``phi4flash_train_T8k``'s (1.3e-2 to
  1.8e-2), the other tied head: the table is N(0, 0.02) so that the
  logits have unit scale, the stream then starts at rms 0.02 and is
  what the bf16 sublayers add to it, where a stream that starts at an
  exact embedding of rms 1 carries their rounding at a fraction
  (``tests/chipbench/test_chipbench_lfm2.py`` rehearses at 1.0 and
  reads 2e-3 to 6e-3 for the same arithmetic).
* ``NEAR_TIE`` 5e-2: how far under the reference's own cut (its fourth
  largest of score + bias, as a share of it) the program's differing
  choices may lie for the reference to take them; SDAR's, Xing's,
  Trinity's and SmallThinker's limit under the same rule, where the
  largest reading was 1.24e-2 (``archs/sdar.py``). Not read apart
  here: with every proposal within it the logits read as above, and a
  router that takes wrong experts lies under the cut by most of it and
  fails ``TRAIN_LOGITS_RTOL``.
* ``LOSS_RTOL`` 7.5e-4, this architecture's own: the first step's
  bf16-AMP loss against the reference's, which routes by itself, reads
  5.97e-6 to 2.08e-4 in nineteen runs (the first 5.29e-5; six over
  1e-4): a mean over 32,768 tokens of logits that carry the error
  above, whose noise a log-sum-exp turns into a positive bias that
  scatters by seed. The accepted routed cells' 2.5e-4 leaves the first
  reading 4.7 times of room and the largest 1.2, so one fresh seed in
  some tens would read over it with nothing wrong; 7.5e-4 is 3.6 times
  the largest and 14 times the first. No precision control parts from
  it (a fresh model's loss is about ln V whatever the precision); what
  it guards is a dropped term, and the logits guard those too:
  ``tests/chipbench/test_chipbench_lfm2.py`` plants seven (the gates
  changing places, an activation behind the taps, the taps back to
  front, q and k unturned, softmax scores, the four weights not
  normalised, query head j on key head j modulo their count) through
  the driver and sees ``correct`` false.
"""

import numpy as np

from chipbench.reference import lfm2_lm

TRAIN_LOGITS_RTOL = 5e-2
LOSS_RTOL = 7.5e-4
NEAR_TIE = 5e-2
# the projections, the dense FFN and the tied head are ``mul`` ops; the
# routed experts' grouped matmuls are XLA's ``ragged-dot-*`` kernels,
# which no scope holds (as ``archs/sdar.py``): the cell is not on
# ``matmul_roof_pct``'s list.
MATMUL_SCOPES = ("mul",)
NAME = "lfm2"           # the program's parameter prefix
CONV = "conv"


def _kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.conv_moe import conv_moe_lm
    return conv_moe_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq_len,
        layer_types=_kinds(cfg), n_dense=cfg["num_dense_layers"],
        d_model=cfg["hidden_size"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        head_dim=lfm2_lm.head_dim(cfg), conv_width=cfg["conv_L_cache"],
        d_dense=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        norm_topk_eps=lfm2_lm.NORM_TOPK_EPS,
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        bias_update_rate=cfg["bias_update_rate"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["norm_eps"],
        embedding_std=cfg["embedding_init_std"],
        router_std=cfg["router_init_std"], recompute=True, name=NAME)


def _routed(program):
    """The program's ``routed_experts`` ops in order, out of their
    recompute regions."""
    def walk(block):
        for op in block.ops:
            if op.type == "recompute_block":
                yield from walk(op.attr("sub_block"))
            elif op.type == "routed_experts":
                yield op
    return list(walk(program.global_block()))


def params_of_program(program, scope, cfg):
    """HOST arrays, by the names ``conv_moe_lm`` gives its parameters
    (the forward's run, which comes before the reference for a model
    that chooses, donates the scope's); each routed layer's selection
    bias as the scope holds it."""
    get = lambda name: np.asarray(scope.find_var(name))

    def layer(i, kind):
        at = "%s_l%d" % (NAME, i)
        named = lambda pairs: {key: get("%s_%s" % (at, suffix))
                               for key, suffix in pairs}
        p = named([("ln1", "ln1"), ("ln2", "ln2")])
        if kind == CONV:
            p.update(named([("w_in", "in"), ("conv_w", "conv_w"),
                            ("w_out", "out")]))
        else:
            p.update(named([(key, key) for key in (
                "wq", "wk", "wv", "q_norm", "k_norm", "wo")]))
        if i < cfg["num_dense_layers"]:
            p["ffn"] = tuple(get("%s_ffn_%s" % (at, part))
                             for part in ("gate", "up", "down"))
        else:
            p.update({key: get("%s_moe.%s" % (at, key)) for key in (
                "router", "bias", "w_gate", "w_up", "w_down")})
        return p

    return {"word_emb": get(NAME + "_word_emb"),
            "final_norm": get(NAME + "_final_norm"),
            "layers": [layer(i, kind) for i, kind in enumerate(_kinds(cfg))]}


def router_choices(program):
    return [op.output("Indices")[0] for op in _routed(program)]


def program_counters(program, scope):
    """``expert_rows``: the rows that chose each of the 32 experts,
    summed over the routed layers and over every train step the program
    ran; ``steps``: those steps (the first routed layer's count);
    ``selection_bias_abs_max``: the largest selection bias, a layer
    each."""
    routed = _routed(program)
    read = lambda op, slot: np.asarray(scope.find_var(op.input(slot)[0]))
    loads = [read(op, "Load").astype(np.int64) for op in routed]
    return {"expert_rows": np.sum(loads, axis=0).tolist(),
            "steps": read(routed[0], "Steps").reshape(-1).tolist(),
            "selection_bias_abs_max": [
                float(np.abs(read(op, "Bias")).max()) for op in routed]}


# -- the reference (``reference/lfm2_lm.py``) ---------------------------------

def lm_loss(params, src, label, mask, cfg):
    """No choices: the train step's cannot be fetched without another
    executable than the window's; ``LOSS_RTOL`` is set with that
    said."""
    return lfm2_lm.lm_loss(params, src, label, mask, cfg)


def _choices(choices, cfg):
    return None if choices is None else choices.reshape(
        choices.shape[0], -1, cfg["num_experts_per_tok"])


def logits_at(params, tokens, first, count, cfg, choices=None):
    return lfm2_lm.logits_at(params, tokens, first, count, cfg,
                             _choices(choices, cfg), NEAR_TIE)


def control_logits_at(params, tokens, first, count, cfg, choices=None):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul, routed exactly as ``logits_at`` routes given the same
    ``choices`` (the router stays float32)."""
    import jax.numpy as jnp
    return lfm2_lm.logits_at(params, tokens, first, count, cfg,
                             _choices(choices, cfg), NEAR_TIE,
                             operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: a
    conv layer's two projections (``d x 3d`` and ``d x d``; the taps
    are no matmul), an attention layer's four (q and o ``d x H D``, k
    and v ``d x Hkv D``); the dense FFN, or the router over all experts
    and the held experts a token expects (top-k times the share held
    here); the tied head, once."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * lfm2_lm.head_dim(cfg)
    kv = cfg["num_key_value_heads"] * lfm2_lm.head_dim(cfg)
    kinds = _kinds(cfg)
    conv = sum(kind == CONV for kind in kinds)
    everyone = cfg["published"]["num_experts"]
    held_a_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / everyone
    dense = cfg["num_dense_layers"]
    return (conv * 4 * d * d + (len(kinds) - conv) * d * (2 * q + 2 * kv)
            + dense * 3 * d * cfg["intermediate_size"]
            + (len(kinds) - dense) * (
                d * everyone
                + held_a_token * 3 * d * cfg["moe_intermediate_size"])
            + d * cfg["vocab_size"])


def useful_scores(seq_len):
    """The scores one head of one sequence needs: every key up to a
    query's own."""
    return seq_len * (seq_len + 1) // 2


def _score_flops(cfg):
    """Forward + backward FLOPs a useful score costs: q k^T and p v
    forward, s again, dp, dv, dq and dk backward, 2 D each: 14 D."""
    return 14 * lfm2_lm.head_dim(cfg)


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: the
    attention layers' causal scores, every head, 14 D each. A region
    keeps the forward kernel's output (PR 42), so it runs once a
    layer."""
    full = sum(kind != CONV for kind in _kinds(cfg))
    return batch * _score_flops(cfg) * full * cfg["num_attention_heads"] \
        * useful_scores(seq_len)


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, NO recompute (the
    backward twice the forward): 6 a touched weight, and a token's
    share of its sequence's useful scores. At ``seq_len`` 0 the matmuls
    outside attention alone. The convolution's taps and gates, some 8
    operations a channel and row, are not counted: 0.003% of a row's
    matmuls."""
    if not seq_len:
        return 6 * touched_parameters(cfg)
    return 6 * touched_parameters(cfg) \
        + flash_flops_per_step(cfg, 1, seq_len) / seq_len


def expert_flops_per_pair(cfg):
    """Forward + backward FLOPs of one (row, held expert) pair: three
    matmuls of d x f, forward and twice that backward."""
    return 18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def short_conv_bytes_per_step(cfg, batch, seq_len, dtype_bytes=2):
    """The bytes the ``gated_short_conv`` ops of one train step have to
    move, every conv layer a recompute region: a forward reads X ``[T,
    3C]`` and writes ``[T, C]``, and runs twice (the region's second
    forward); the backward reads X and dy ``[T, C]`` and writes dX
    ``[T, 3C]``: 15 C values a row and layer in all. The filter and its
    gradient, ``[K, C]`` float32, are nothing beside them."""
    conv = sum(kind == CONV for kind in _kinds(cfg))
    return conv * batch * seq_len * 15 * cfg["hidden_size"] * dtype_bytes
