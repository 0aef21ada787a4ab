"""Architecture ``joyai``: JoyAI-LLM-Flash's training step as
``paddle_tpu/models/latent_moe.py`` builds it (a PLAIN residual stream,
``x + F(RMSNorm(x))``; multi-head latent attention, 32 heads of 128 + 64
reading ONE rotary key, values 128 wide, rotary pairs interleaved as
published, plain frequencies at theta 3.2e7; one leading dense
SiLU-gated FFN of 7168, then a shared expert beside sigmoid-routed top-8
of 256 experts of 768 with a selection bias and no auxiliary loss, of
which this chip holds 16; untied head, next-token loss; and behind the
last layer ONE multi-token-prediction module, a second loss through the
same embedding table and the same head; every layer and the module's
block a ``layers.recompute`` region). It is ``archs/xing.py``'s model
file on its other stream and with its module. The reference is
``reference/joyai_lm.py``; a configuration asks for this file with
``"arch": "joyai"``.

What the harness feeds: ``src``, ``label`` (the next tokens; the module
looks them up in the table and shifts them once more for its own
target) and ``mask``. ``logits`` are ``[B, T, 2 V]``, the main model's
and behind them the module's: ``correct`` compares the last
``check_rows`` rows of the first sequence, which see the longest
contexts, and so holds the module's head to the reference in the
driver's one comparison. Choices come stacked ``[routed layers + 1, 1,
T, 8]``, the module's router last, fetched from inside the recompute
regions of the ``for_test`` clone. The published ``q_b``, ``kv_a`` and
``kv_b`` matrices are held as their column blocks, and the 64 rotary
columns of ``q_b_pe`` (a head's) and of ``kv_a_pe`` DE-INTERLEAVED,
evens then odds, so that the kernels' rotate-half turns the published
pairs ``(2i, 2i + 1)`` (the configuration's ``assumed`` says so);
``params_of_program`` hands the reference those columns back in the
published order, and the reference rotates interleaved pairs.

The limits, each with the readings it was set from (my chip runs, PR
55, one v5e, the cell's own size: 5 layers and the module, one
8192-token sequence, the last 64 rows; ``PERF.md`` section 4 has the
table):

* ``TRAIN_LOGITS_RTOL`` 2e-2, Xing's: the program's bf16-AMP forward
  against the float32 reference handed the program's choices reads
  5.86e-3 to 8.07e-3 in twenty-four readings on twenty-four seeds
  (``control.py``'s twelve, 6.37e-3 to 7.30e-3, and twelve benchmark
  runs, three of them traced, 5.86e-3 to 8.07e-3), the largest
  difference over BOTH halves of the logits, the main model's and the
  module's; the fp8
  control handed the same choices 7.99e-2 to 9.65e-2 on those twelve,
  10.9 times the program's largest there (``control.py`` exit 0,
  ``separates`` true) and 9.9 times the largest of all. 2e-2 is 2.5
  times the program's largest and a quarter of the control's
  smallest.
* ``NEAR_TIE`` 5e-2: how far under the reference's own cut (its eighth
  largest of score + bias, as a share of it) the program's differing
  choices may lie for the reference to take them; SDAR's, Xing's,
  Trinity's and SmallThinker's limit under the same rule, where the
  largest reading was 1.24e-2 (``archs/sdar.py``). Not read apart here:
  with every proposal within it the logits read as above, and a router
  that takes wrong experts lies under the cut by most of it and fails
  ``TRAIN_LOGITS_RTOL``.
* ``LOSS_RTOL`` 2.5e-4, Xing's: the first step's bf16-AMP cost,
  ``L_main + 0.3 L_mtp``, against the reference's, which routes by
  itself: reads 0 to 1.92e-5 in twelve runs on twelve seeds (ten
  under 1.1e-5): a mean over 8,192 tokens twice, where a router's flip
  moves a row only if a held expert is in it, one time in sixteen.
  No precision control parts from it (a fresh
  model's loss is about ln V a term whatever the precision); what it
  guards is a dropped term: without ``mtp_loss_weight``, with
  ``eh_proj``'s two halves swapped, without the rotary part of the
  score or with the rotary columns in the wrong order the first loss
  or the logits part by more than a limit
  (``tests/chipbench/test_chipbench_joyai_faults.py`` plants six
  faults through the driver and sees ``correct`` false). It cannot be
  counted on to see the module's TARGET one place short: either target
  costs a fresh model about ln V (that test's docstring has the
  readings; ``tests/test_latent_moe_mtp.py`` holds the head's gradient).
"""

import numpy as np

from chipbench.reference import joyai_lm

TRAIN_LOGITS_RTOL = 2e-2
LOSS_RTOL = 2.5e-4
NEAR_TIE = 5e-2
# the projections, the dense FFN, the shared experts, eh_proj and both
# uses of the head are ``mul`` ops; the routed experts' grouped matmuls
# are XLA's ``ragged-dot-*`` kernels, which no scope holds (as
# ``archs/xing.py``): the cell is not on ``matmul_roof_pct``'s list.
MATMUL_SCOPES = ("mul",)
NAME = "joyai"          # the program's parameter prefix
MODULE = "mtp"          # the ``layers.module`` the model builds it in


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.latent_moe import latent_moe_lm
    return latent_moe_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq_len,
        n_layer=cfg["num_hidden_layers"],
        n_dense=cfg["first_k_dense_replace"], d_model=cfg["hidden_size"],
        n_head=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], d_nope=cfg["qk_nope_head_dim"],
        d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_dense=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"], top_k=cfg["num_experts_per_tok"],
        norm_topk=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        bias_update_rate=cfg["bias_update_rate"], hc_mult=None,
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=cfg["rope_scaling"], rms_eps=cfg["rms_norm_eps"],
        embedding_std=cfg["embedding_init_std"],
        n_nextn=cfg["num_nextn_predict_layers"],
        nextn_weight=cfg["mtp_loss_weight"], recompute=True, name=NAME)


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
    """The five routers' ops: the routed layers', then the module's."""
    return [op for op in _ops(program) if op.type == "routed_experts"]


def _published_order(w, heads):
    """w ``[rows, heads x Dr]`` as the program holds a rotary
    projection (a head's columns evens then odds) -> the published
    order, pairs ``(2i, 2i + 1)`` side by side."""
    rows = w.shape[0]
    return w.reshape(rows, heads, 2, -1).transpose(0, 1, 3, 2).reshape(
        rows, -1)


def params_of_program(program, scope, cfg):
    """HOST arrays, by the names ``latent_moe_lm`` gives its parameters
    (the forward's run, which comes before the reference for a model
    that chooses, donates the scope's), the rotary columns in the
    published order."""
    get = lambda name: np.asarray(scope.find_var(name))
    three = lambda at: tuple(get("%s_%s" % (at, part))
                             for part in ("gate", "up", "down"))

    def block(at, dense):
        p = {key: get("%s_%s" % (at, key)) for key in (
            "ln1", "ln2", "q_a", "q_norm", "q_b_nope", "q_b_pe", "kv_a_c",
            "kv_a_pe", "kv_norm", "kv_b_k", "kv_b_v", "o")}
        p["q_b_pe"] = _published_order(p["q_b_pe"],
                                       cfg["num_attention_heads"])
        p["kv_a_pe"] = _published_order(p["kv_a_pe"], 1)
        if dense:
            p["ffn"] = three(at + "_ffn")
        else:
            p["shared"] = three(at + "_shared")
            p.update({key: get("%s_moe.%s" % (at, key)) for key in (
                "router", "bias", "w_gate", "w_up", "w_down")})
        return p

    at = NAME + "_mtp"
    return {"word_emb": get(NAME + "_word_emb"),
            "final_norm": get(NAME + "_final_norm"),
            "w_out": get(NAME + "_head"),
            "layers": [block("%s_l%d" % (NAME, i),
                             i < cfg["first_k_dense_replace"])
                       for i in range(cfg["num_hidden_layers"])],
            "mtp": {**block(at, False),
                    **{key: get("%s_%s" % (at, key)) for key in (
                        "enorm", "hnorm", "eh_proj", "shared_head_norm")}}}


def router_choices(program):
    return [op.output("Indices")[0] for op in _routed(program)]


def program_counters(program, scope):
    """``expert_rows``: the rows that chose each of the 256 experts,
    summed over all five routers (four layers' and the module's) and
    over every train step the program ran; ``steps``: those steps (the
    first routed layer's count); ``selection_bias_abs_max``: the
    largest selection bias, a router each; ``main_loss`` and
    ``mtp_loss``: the two terms of the cost before the module's is
    weighed, each summed on the device over those steps (a program
    without the sums, the parent's, leaves them out)."""
    routed = _routed(program)
    read = lambda op, slot: np.asarray(scope.find_var(op.input(slot)[0]))
    loads = [read(op, "Load").astype(np.int64) for op in routed]
    out = {"expert_rows": np.sum(loads, axis=0).tolist(),
           "steps": read(routed[0], "Steps").reshape(-1).tolist(),
           "selection_bias_abs_max": [
               float(np.abs(read(op, "Bias")).max()) for op in routed]}
    for term in ("main_loss", "mtp_loss"):
        total = scope.find_var("%s_%s_sum" % (NAME, term))
        if total is not None:
            out[term] = np.asarray(total, np.float64).reshape(-1).tolist()
    return out


# -- the reference (``reference/joyai_lm.py``) -------------------------------

def lm_loss(params, src, label, mask, cfg):
    """``L_main + mtp_loss_weight L_mtp``. No choices: the train step's
    cannot be fetched without another executable than the window's;
    ``LOSS_RTOL`` is set with that said."""
    return joyai_lm.lm_loss(params, src, label, mask, cfg)


def _choices(choices, cfg):
    return None if choices is None else choices.reshape(
        choices.shape[0], -1, cfg["num_experts_per_tok"])


def logits_at(params, tokens, first, count, cfg, choices=None):
    return joyai_lm.logits_at(params, tokens, first, count, cfg,
                              _choices(choices, cfg), NEAR_TIE)


def control_logits_at(params, tokens, first, count, cfg, choices=None):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul, routed exactly as ``logits_at`` routes given the same
    ``choices`` (the routers stay float32)."""
    import jax.numpy as jnp
    return joyai_lm.logits_at(params, tokens, first, count, cfg,
                              _choices(choices, cfg), NEAR_TIE,
                              operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def _attention_parameters(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * rq + rq * heads * (dn + dr) + d * (rkv + dr)
            + rkv * heads * (dn + dv) + heads * dv * d)


def _blocks(cfg):
    """The blocks with attention: the layers and the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def _routed_ffn_parameters(cfg):
    """What a token passes in a routed block's FFN: the shared expert,
    the router over all experts and the held experts a token expects
    (top-k times the share held here: the held pairs alone)."""
    d = cfg["hidden_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    everyone = cfg["published"]["n_routed_experts"]
    held_a_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / everyone
    return (cfg["n_shared_experts"] * expert + d * everyone
            + held_a_token * expert)


def module_parameters(cfg):
    """The matmul weights one token passes in ONE multi-token-prediction
    module, forward: ``eh_proj``, a routed block and the head once
    more."""
    d = cfg["hidden_size"]
    return (2 * d * d + _attention_parameters(cfg)
            + _routed_ffn_parameters(cfg) + d * cfg["vocab_size"])


def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: a
    layer's attention (q_a, q_b, kv_a, kv_b, o); the dense FFN or a
    routed block's (``_routed_ffn_parameters``); the head; and the
    module's (``module_parameters``: its block, ``eh_proj`` and the head
    a second time)."""
    d = cfg["hidden_size"]
    dense, layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    return (layers * _attention_parameters(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * _routed_ffn_parameters(cfg)
            + d * cfg["vocab_size"]
            + cfg["num_nextn_predict_layers"] * module_parameters(cfg))


def _score_macs(cfg):
    """(forward, backward) multiply-adds a useful score costs: q k^T
    over D + Dr and p v over Dv; backward s again, dp, dv, and dq, dk
    over D + Dr: 320 and 832 at 128 + 64 against 128."""
    key = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return key + cfg["v_head_dim"], 3 * key + 2 * cfg["v_head_dim"]


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, NO recompute (the
    backward twice the forward): 6 a touched weight, and a token's
    share of its sequence's causal-useful scores, T / 2 a head and
    block, at 2 x (320 + 832), the module's block among the blocks. At
    ``seq_len`` 0 the matmuls outside attention alone."""
    fwd, bwd = _score_macs(cfg)
    return 6 * touched_parameters(cfg) + seq_len // 2 * 2 * (fwd + bwd) \
        * cfg["num_attention_heads"] * _blocks(cfg)


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: T^2 / 2
    causal-useful scores a sequence, head and block (six: five layers
    and the module's), each 2 x (320 forward + 832 backward). The
    recompute's second forward is in the kernels' time and not in this
    count."""
    fwd, bwd = _score_macs(cfg)
    return seq_len * seq_len * (fwd + bwd) * cfg["num_attention_heads"] \
        * _blocks(cfg) * batch


def flash_flops_split(cfg):
    """(forward, backward) shares of ``flash_flops_per_step``: 320 /
    1152 and 832 / 1152, as ``archs/xing.py``."""
    fwd, bwd = _score_macs(cfg)
    return fwd / (fwd + bwd), bwd / (fwd + bwd)


def expert_flops_per_pair(cfg):
    """Forward + backward FLOPs of one (row, held expert) pair: three
    matmuls of d x f, forward and twice that backward."""
    return 18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
