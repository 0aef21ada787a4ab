"""Architecture ``smallthinker``: SmallThinker-21BA3B-Instruct's
training step as ``paddle_tpu/models/prerouted_moe.py`` builds it (a
pre-norm block with two RMSNorms; the ROUTER reads the layer's input
as it arrives, before the first norm and before attention: softmax
top-6 of 64; grouped-query attention, 28 heads of 128 reading 4, no
QK-norm; layers of two kinds by two published lists,
``sliding_window_layout`` and ``rope_layout``: window-4096 layers with
RoPE, full layers with no position signal, 3:1; every layer an expert
layer, ReLU-gated experts of 768 with no shared expert, of which this
chip holds 16; untied head, next-token loss, no auxiliary loss; every
layer a ``layers.recompute`` region). The reference is
``reference/smallthinker_lm.py``; a configuration asks for this file
with ``"arch": "smallthinker"``.

What the harness feeds: ``src``, ``label`` (the next tokens) and
``mask``. ``logits`` are ``[B, T, V]``; ``correct`` compares the last
``check_rows`` rows of the first sequence, where a window row sees
``sliding_window_size`` keys and a full row all before it. Choices
come stacked ``[layers, 1, T, 6]``, fetched from inside the recompute
regions of the ``for_test`` clone. The two layout lists stay at their
52 published entries (``cells.is_width`` reads ``window`` in a key's
name as a width, so the list may not be in ``reduced``); the first
``num_hidden_layers`` of each are read.

The limits, each with the readings it was set from (my chip runs, PR
46, one v5e, the cell's own size: 4 layers, one 16,384-token sequence,
the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 1.5e-2: the program's bf16-AMP forward against
  the float32 reference handed the program's choices reads 3.50e-3 to 5.78e-3
  in nineteen readings of the configuration as shipped (twelve benchmark runs on twelve seeds, six of them traced, 3.63e-3 to 4.94e-3; one more traced run; ``control.py``'s seeds 2147483977, 1357924680, 46021, 2147483877, 717171717 and 3000000411, which had read widest at router 0.3); the
  fp8 control handed the same choices 3.98e-2 to 6.43e-2 on ``control.py``'s
  seeds (exit 0, ``separates`` true), 6.9 times the program's
  largest. 1.5e-2 is 2.6 times the program's largest and 0.38
  of the control's smallest. The router reads the UN-NORMED stream, so
  bf16's error in the sublayers' outputs reaches its logits times its
  weights, and the reading grows with ``router_init_std``: at embedding
  1.0 and router 0.3 it read 7.0e-3 to 5.38e-2 in 29 readings against a
  control of 0.107 to 0.514, and no limit holds (the configuration's
  ``assumed`` has the other pairs tried).
* ``NEAR_TIE`` 5e-2: how far under the reference's own cut (its sixth
  largest float32 probability, as a share of it) the program's
  differing choices may lie for the reference to take them; SDAR's,
  Xing's and Trinity's limit under the same rule, where the largest
  reading was 1.24e-2 (``archs/sdar.py``). Not read apart here: with
  every proposal within it the logits read as above, and a router that
  takes wrong experts lies under the cut by most of it and fails
  ``TRAIN_LOGITS_RTOL``.
* ``LOSS_RTOL`` 2.5e-4, Xing's and Trinity's: the first step's bf16-AMP
  loss against the reference's, which routes by itself, reads
  9.0e-8 to 2.07e-6 in thirteen runs: a mean over 16,384 tokens. No precision
  control parts from it (a fresh model's loss is about ln V whatever
  the precision); what it guards is a dropped term, and the logits
  guard those too: ``tests/chipbench/test_chipbench_smallthinker.py``
  plants seven (the router on the normed stream, SiLU for ReLU, RoPE on
  the full layer too, none on a window layer, the window bound gone,
  the six weights not normalised, query head j on key head j % 4)
  through the driver and sees ``correct`` false.
"""

import numpy as np

from chipbench.reference import smallthinker_lm

TRAIN_LOGITS_RTOL = 1.5e-2
LOSS_RTOL = 2.5e-4
NEAR_TIE = 5e-2
# q/k/v/o and the head are ``mul`` ops; the experts' grouped matmuls are
# XLA's ``ragged-dot-*`` kernels, which no scope holds (as
# ``archs/sdar.py``): the cell is not on ``matmul_roof_pct``'s list.
MATMUL_SCOPES = ("mul",)
NAME = "st"             # the program's parameter prefix


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.prerouted_moe import prerouted_moe_lm
    layers = cfg["num_hidden_layers"]
    return prerouted_moe_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq_len,
        window_layout=cfg["sliding_window_layout"][:layers],
        rope_layout=cfg["rope_layout"][:layers],
        d_model=cfg["hidden_size"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window_size"],
        d_expert=cfg["moe_ffn_hidden_size"],
        num_experts=cfg["published"]["moe_num_primary_experts"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        top_k=cfg["moe_num_active_primary_experts"],
        norm_topk=cfg["norm_topk_prob"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
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
    """HOST arrays, by the names ``prerouted_moe_lm`` gives its
    parameters (the forward's run, which comes before the reference for
    a model that chooses, donates the scope's)."""
    get = lambda name: np.asarray(scope.find_var(name))

    def layer(i):
        at = "%s_l%d" % (NAME, i)
        p = {key: get("%s_%s" % (at, key))
             for key in ("ln1", "ln2", "wq", "wk", "wv", "wo")}
        p.update({key: get("%s_moe.%s" % (at, key))
                  for key in ("router", "w_gate", "w_up", "w_down")})
        return p

    return {"word_emb": get(NAME + "_word_emb"),
            "final_norm": get(NAME + "_final_norm"),
            "w_out": get(NAME + "_head"),
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def router_choices(program):
    return [op.output("Indices")[0] for op in _routed(program)]


def program_counters(program, scope):
    """``expert_rows``: the rows that chose each of the 64 experts,
    summed over the layers and over every train step the program ran;
    ``steps``: those steps (the first layer's count);
    ``expert_gate_active`` and ``expert_gate_units``: over the (row,
    held expert) pairs of those steps and layers, the hidden units a
    ReLU gate left on (``h2 W_gate > 0``) and the hidden units there
    were (pairs x 768), which the expert layer sums on the device in
    float32; ``window_scores_computed`` and ``window_scores_useful``:
    what the flash kernels' lowerings under a window added to
    ``ptpu_flash_band_scores_total`` in this process (counted at trace
    time, a batch row and head each, forward and backward walks;
    nothing where the dense form ran, as on the CPU). A program whose
    expert layer keeps no gate count (the parent of PR 46 has none)
    leaves the two gate counters out."""
    from paddle_tpu.monitor import metrics
    routed = _routed(program)
    read = lambda op, slot: np.asarray(scope.find_var(op.input(slot)[0]))
    loads = [read(op, "Load").astype(np.int64) for op in routed]
    out = {"expert_rows": np.sum(loads, axis=0).tolist(),
           "steps": read(routed[0], "Steps").reshape(-1).tolist()}
    if all(op.input("GateOn") for op in routed):
        on = np.sum([read(op, "GateOn").astype(np.float64)
                     for op in routed], axis=0)
        out["expert_gate_active"], out["expert_gate_units"] = (
            [float(on[0])], [float(on[1])])
    band = metrics.registry().get("ptpu_flash_band_scores_total")
    if band is not None:
        for kind in ("computed", "useful"):
            out["window_scores_" + kind] = [float(sum(
                v for key, v in band.snapshot().items()
                if key[band.label_names.index("kind")] == kind))]
    return out


# -- the reference (``reference/smallthinker_lm.py``) -------------------------

def lm_loss(params, src, label, mask, cfg):
    """No choices: the train step's cannot be fetched without another
    executable than the window's; ``LOSS_RTOL`` is set with that
    said."""
    return smallthinker_lm.lm_loss(params, src, label, mask, cfg)


def _choices(choices, cfg):
    return None if choices is None else choices.reshape(
        choices.shape[0], -1, cfg["moe_num_active_primary_experts"])


def logits_at(params, tokens, first, count, cfg, choices=None):
    return smallthinker_lm.logits_at(params, tokens, first, count, cfg,
                                     _choices(choices, cfg), NEAR_TIE)


def control_logits_at(params, tokens, first, count, cfg, choices=None):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul, routed exactly as ``logits_at`` routes given the same
    ``choices`` (the router stays float32)."""
    import jax.numpy as jnp
    return smallthinker_lm.logits_at(params, tokens, first, count, cfg,
                                     _choices(choices, cfg), NEAR_TIE,
                                     operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: a
    layer's attention (q and o ``d x H D``, k and v ``d x Hkv D``), the
    router over all experts and the held experts a token expects (top-k
    times the share held here: 1.5 of 5.9 M); the head."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    everyone = cfg["published"]["moe_num_primary_experts"]
    held_a_token = cfg["moe_num_active_primary_experts"] \
        * cfg["num_experts"] / everyone
    return (cfg["num_hidden_layers"] * (
        d * (2 * q + 2 * kv) + d * everyone
        + held_a_token * 3 * d * cfg["moe_ffn_hidden_size"])
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
    kinds = cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]
    heads = cfg["num_attention_heads"]
    return ((len(kinds) - sum(kinds)) * heads * useful_scores(seq_len),
            sum(kinds) * heads * useful_scores(
                seq_len, cfg["sliding_window_size"]))


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
    each. A region keeps the forward kernel's output (PR 42), so it
    runs once a layer."""
    return batch * _score_flops(cfg) * sum(_layer_scores(cfg, seq_len))


def window_flash_flops_per_step(cfg, batch, seq_len):
    """The window layers' share of ``flash_flops_per_step``: the band's
    useful scores alone."""
    return batch * _score_flops(cfg) * _layer_scores(cfg, seq_len)[1]


def expert_flops_per_pair(cfg):
    """Forward + backward FLOPs of one (row, held expert) pair: three
    matmuls of d x f, forward and twice that backward."""
    return 18 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]
