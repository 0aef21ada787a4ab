"""Architecture ``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B's
training step as ``paddle_tpu/models/nemotron_h.py`` builds it (a layer
is ONE sublayer behind one RMSNorm, its kind a character of
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, 64 heads of 64 in 8
groups that share ``B_t`` and ``C_t`` of 128 states, a convolution of 4
taps with bias, the gate before a norm over groups of 512; ``E``
sigmoid-routed top-6 of 128 ungated relu^2 experts of 1856 with a
selection bias, 1e-20 in the chosen weights' sum, a scaling of 2.5 and
one shared expert of 3712, of which this chip holds 8; ``*``
grouped-query attention of 32 heads of 128 reading 2, no position
signal; a head of its own, next-token loss; every layer a
``layers.recompute`` region). The reference is
``reference/nemotron_h_lm.py``; a configuration asks for this file with
``"arch": "nemotron_h"``.

What the harness feeds: ``src``, ``label`` (the next tokens) and
``mask``. ``logits`` are ``[B, T, V]``; ``correct`` compares the last
``check_rows`` rows of the first sequence, where an attention row sees
every key before it and a Mamba-2 state has 8,000 rows behind it.
Choices come stacked ``[expert layers, 1, T, 6]``, fetched from inside
the recompute regions of the ``for_test`` clone.
``hybrid_override_pattern`` stays at its 52 published characters; the
first ``num_hidden_layers`` are read. The program holds the published
``in_proj`` as its five column blocks and the convolution's filter as
its three (``params_of_program`` puts them side by side again, in the
published order ``[z | x | B | C | dt]`` and ``[x | B | C]``).

The limits, each with the readings it was set from (my chip runs, PR
62, one v5e, the cell's own size: 9 layers, one 8,192-token sequence,
the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 2.5e-2: the program's bf16-AMP forward against
  the float32 reference handed the program's choices reads 6.62e-3 to
  9.10e-3 in forty-one readings (``control.py``'s twelve seeds
  2147483977, 1357924680, 46021, 2147483877, 717171717, 3000000411 and
  81-86 twice, the first tree 6.907e-3 to 8.907e-3 and the final tree
  7.018e-3 to 8.342e-3; seventeen benchmark runs on eleven seeds, three
  traced, 6.62e-3 to 9.10e-3); the fp8 control handed the same choices
  1.043e-1 to 1.292e-1 and 1.033e-1 to 1.257e-1 on ``control.py``'s
  twelve (exit 0, ``separates`` true both times: 11.7 and 12.4 times
  the program's largest there). 2.5e-2 is 2.7 times the program's
  largest (fresh seeds read higher) and 0.24 of the control's smallest.
  The program reads as the routed cells whose stream starts at an exact
  embedding of rms 1 do (3.5e-3 to 1.1e-2).
* ``NEAR_TIE`` 5e-2: how far under the reference's own cut (its sixth
  largest of score + bias, as a share of it) the program's differing
  choices may lie for the reference to take them; the five other routed
  cells' limit under the same rule, where the largest reading was
  1.24e-2 (``archs/sdar.py``). Not read apart here: with every proposal
  within it the logits read as above, and a router that takes wrong
  experts lies under the cut by most of it and fails
  ``TRAIN_LOGITS_RTOL``
  (``tests/chipbench/test_chipbench_nemotron_h.py`` hands the reference
  far-off choices: it does not take them, and taken by force they part
  the logits by more than the limit).
* ``LOSS_RTOL`` 2.5e-4, the accepted routed cells': the first step's
  bf16-AMP loss against the reference's, which routes by itself, reads
  4.8e-7 to 2.95e-5 in seventeen runs (the first 9.59e-6: 26 times of
  room; the largest 8 times), a mean over 8,192 tokens. No precision control parts from it
  (a fresh model's loss is about ln V whatever the precision); what it
  guards is a dropped term, and the logits guard those too: the faults
  file beside the test plants six through the driver and sees
  ``correct`` false.
"""

import numpy as np

from chipbench.reference import nemotron_h_lm
from chipbench.reference.nemotron_h_lm import ATTENTION, EXPERTS, MAMBA, kinds

TRAIN_LOGITS_RTOL = 2.5e-2
LOSS_RTOL = 2.5e-4
NEAR_TIE = 5e-2
# the projections, the shared expert and the head are ``mul`` ops; the
# routed experts' grouped matmuls are XLA's ``ragged-dot-*`` kernels and
# the scan's products are inside its Pallas kernels, which no scope of
# these holds (as ``archs/sdar.py``): the cell is not on
# ``matmul_roof_pct``'s list.
MATMUL_SCOPES = ("mul",)
NAME = "nh"             # the program's parameter prefix
# the rows of a chunk the scan's arithmetic is counted at: the
# published ``chunk_size``. A walk in longer chunks does more work
# inside a chunk for fewer passes of the state; the count stays here.
SSD_CHUNK = 128


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.nemotron_h import nemotron_h_lm as model
    return model(
        vocab_size=cfg["vocab_size"], seq_len=seq_len, pattern=kinds(cfg),
        d_model=cfg["hidden_size"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_ssm_head=cfg["mamba_num_heads"],
        ssm_head_dim=cfg["mamba_head_dim"], n_group=cfg["n_groups"],
        d_state=cfg["ssm_state_size"], d_conv=cfg["conv_kernel"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        num_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["num_experts"], first_expert=cfg["first_expert"],
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        norm_topk_eps=nemotron_h_lm.NORM_TOPK_EPS,
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        bias_update_rate=cfg["bias_update_rate"],
        rms_eps=cfg["layer_norm_epsilon"], dt_min=cfg["time_step_min"],
        dt_max=cfg["time_step_max"],
        embedding_std=cfg["embedding_init_std"],
        router_std=cfg["router_init_std"], recompute=True,
        scan_chunk=cfg.get("scan_chunk", 0), name=NAME)


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
    """HOST arrays, by the names ``nemotron_h_lm`` gives its parameters
    (the forward's run, which comes before the reference for a model
    that chooses, donates the scope's); the in_proj's five blocks and
    the filter's three side by side as published; each expert layer's
    selection bias as the scope holds it."""
    get = lambda name: np.asarray(scope.find_var(name))

    def layer(i, kind):
        at = "%s_l%d" % (NAME, i)
        named = lambda pairs: {key: get("%s_%s" % (at, suffix))
                               for key, suffix in pairs}
        side = lambda fmt, parts: np.concatenate(
            [get(at + fmt % part) for part in parts], -1)
        p = named([("norm", "norm")])
        if kind == MAMBA:
            p.update(named([("dt_bias", "dt_bias"), ("a_log", "scan_a_log"),
                            ("d", "scan_d"), ("norm_w", "gnorm"),
                            ("w_out", "out")]))
            p["w_in"] = side("_in_%s", ("z", "x", "b", "c", "dt"))
            p["conv_w"] = side("_conv_%s_w", ("x", "b", "c"))
            p["conv_b"] = side("_conv_%s_b", ("x", "b", "c"))
        elif kind == ATTENTION:
            p.update(named([(key, key) for key in ("wq", "wk", "wv", "wo")]))
        else:
            p.update({key: get("%s_moe.%s" % (at, key)) for key in (
                "router", "bias", "w_up", "w_down")})
            p.update(named([("shared_up", "shared_up"),
                            ("shared_down", "shared_down")]))
        return p

    return {"word_emb": get(NAME + "_word_emb"),
            "final_norm": get(NAME + "_final_norm"),
            "head": get(NAME + "_head"),
            "layers": [layer(i, kind) for i, kind in enumerate(kinds(cfg))]}


def router_choices(program):
    return [op.output("Indices")[0] for op in _routed(program)]


def program_counters(program, scope):
    """``expert_rows``: the rows that chose each of the 128 experts,
    summed over the expert layers and over every train step the program
    ran; ``steps``: those steps (the first expert layer's count);
    ``selection_bias_abs_max``: the largest selection bias, a layer
    each; ``expert_gate_active`` and ``expert_gate_units``: over the
    (row, held expert) pairs of those steps and layers, the hidden
    units the ReLU left on (``h W_up > 0``: the rest are exact zeros
    behind the square) and the hidden units there were (pairs x 1856),
    which the expert layer sums on the device in float32."""
    routed = _routed(program)
    read = lambda op, slot: np.asarray(scope.find_var(op.input(slot)[0]))
    loads = [read(op, "Load").astype(np.int64) for op in routed]
    on = np.sum([read(op, "GateOn").astype(np.float64) for op in routed],
                axis=0)
    return {"expert_rows": np.sum(loads, axis=0).tolist(),
            "steps": read(routed[0], "Steps").reshape(-1).tolist(),
            "selection_bias_abs_max": [
                float(np.abs(read(op, "Bias")).max()) for op in routed],
            "expert_gate_active": [float(on[0])],
            "expert_gate_units": [float(on[1])]}


# -- the reference (``reference/nemotron_h_lm.py``) ---------------------------

def lm_loss(params, src, label, mask, cfg):
    """No choices: the train step's cannot be fetched without another
    executable than the window's; ``LOSS_RTOL`` is set with that
    said."""
    return nemotron_h_lm.lm_loss(params, src, label, mask, cfg)


def _choices(choices, cfg):
    return None if choices is None else choices.reshape(
        choices.shape[0], -1, cfg["num_experts_per_tok"])


def logits_at(params, tokens, first, count, cfg, choices=None):
    return nemotron_h_lm.logits_at(params, tokens, first, count, cfg,
                                   _choices(choices, cfg), NEAR_TIE)


def control_logits_at(params, tokens, first, count, cfg, choices=None):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul, routed exactly as ``logits_at`` routes given the same
    ``choices`` (the router, the convolution and the recurrence stay
    float32)."""
    import jax.numpy as jnp
    return nemotron_h_lm.logits_at(params, tokens, first, count, cfg,
                                   _choices(choices, cfg), NEAR_TIE,
                                   operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def _mamba(cfg):
    """(d_inner, a group's B_t and C_t together, heads)."""
    heads = cfg["mamba_num_heads"]
    return (heads * cfg["mamba_head_dim"],
            2 * cfg["n_groups"] * cfg["ssm_state_size"], heads)


def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: a
    Mamba-2 layer's two projections (``d x (2 d_inner + 2 G N + H)``
    and ``d_inner x d``; the taps and the scan are apart); an attention
    layer's four (q and o ``d x H D``, k and v ``d x Hkv D``); an expert
    layer's router over all experts, its shared expert and the held
    experts a token expects (top-k times the share held here), TWO
    matrices each; the head, which is not the embedding's table."""
    d = cfg["hidden_size"]
    d_inner, d_bc, heads = _mamba(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    everyone = cfg["published"]["n_routed_experts"]
    held_a_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / everyone
    per_kind = {
        MAMBA: d * (2 * d_inner + d_bc + heads) + d_inner * d,
        ATTENTION: d * (2 * q + 2 * kv),
        EXPERTS: d * everyone
        + 2 * d * cfg["moe_shared_expert_intermediate_size"]
        + held_a_token * 2 * d * cfg["moe_intermediate_size"]}
    return sum(per_kind[kind] for kind in kinds(cfg)) \
        + d * cfg["vocab_size"]


def useful_scores(seq_len):
    """The scores one head of one sequence needs: every key up to a
    query's own."""
    return seq_len * (seq_len + 1) // 2


def _score_flops(cfg):
    """Forward + backward FLOPs a useful score costs: q k^T and p v
    forward, s again, dp, dv, dq and dk backward, 2 D each: 14 D."""
    return 14 * cfg["head_dim"]


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: the
    attention layers' causal scores, every head, 14 D each. A region
    keeps the forward kernel's output (PR 42), so it runs once a
    layer."""
    full = sum(kind == ATTENTION for kind in kinds(cfg))
    return batch * _score_flops(cfg) * full * cfg["num_attention_heads"] \
        * useful_scores(seq_len)


def ssd_flops_per_token(cfg):
    """FORWARD FLOPs of one Mamba-2 layer's scan a token, in chunks of
    `SSD_CHUNK` rows L, from the shapes alone: a head's ``(C B^T .
    Lam) u`` (``2 L P``), its state read ``C S^T`` and its state's
    update ``u^T B`` (``2 N P`` each), and a group's ``C B^T`` (``2 L
    N``), which its heads share."""
    p, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    return cfg["mamba_num_heads"] * (2 * SSD_CHUNK * p + 4 * n * p) \
        + cfg["n_groups"] * 2 * SSD_CHUNK * n


def _scans(cfg):
    return sum(kind == MAMBA for kind in kinds(cfg))


def ssd_flops_per_step(cfg, batch, seq_len):
    """FLOPs of the scans of one train step, every Mamba-2 layer a
    recompute region: the forward twice (the second forward's are
    counted, because it runs) and a backward of twice the forward
    (two cotangent products a product; what a backward makes again of
    its forward is not counted)."""
    return _scans(cfg) * batch * seq_len * 4 * ssd_flops_per_token(cfg)


def ssd_bytes_per_step(cfg, batch, seq_len, dtype_bytes=2):
    """The bytes the scans of one train step have to move, whatever
    implements them: a forward reads x ``[T, d_inner]``, ``B_t`` and
    ``C_t`` ``[T, G N]`` each and writes y ``[T, d_inner]``, twice; the
    backward reads x, ``B_t``, ``C_t`` and dy and writes dx, ``dB_t``
    and ``dC_t``. The steps ``[T, H]`` float32 are a hundredth of that
    and the chunk states a walk saves are its own affair: neither is
    counted."""
    d_inner, d_bc, _ = _mamba(cfg)
    forward = 2 * d_inner + d_bc
    backward = 3 * d_inner + 2 * d_bc
    return _scans(cfg) * batch * seq_len * dtype_bytes * (
        2 * forward + backward)


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, NO recompute (the
    backward twice the forward): 6 a touched weight, and with a
    `seq_len` a token's share of its sequence's useful scores and the
    scans' products (three forwards' worth). At ``seq_len`` 0 the
    matmuls of the ``mul`` scopes and the experts alone. The
    convolution's taps, the step sizes and the gate-and-norm, some 30
    operations a channel and row, are not counted: 0.03% of a row's
    matmuls."""
    if not seq_len:
        return 6 * touched_parameters(cfg)
    return 6 * touched_parameters(cfg) \
        + flash_flops_per_step(cfg, 1, seq_len) / seq_len \
        + _scans(cfg) * 3 * ssd_flops_per_token(cfg)


def expert_flops_per_pair(cfg):
    """Forward + backward FLOPs of one (row, held expert) pair: TWO
    matmuls of d x f (no gate matrix), forward and twice that
    backward."""
    return 12 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
