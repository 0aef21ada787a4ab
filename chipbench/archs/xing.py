"""Architecture ``xing``: Xing4.0-29B-A4B's training step as
``paddle_tpu/models/latent_moe.py`` builds it (a residual stream of
four lanes under manifold-constrained hyper-connections; multi-head
latent attention, 32 heads of 128 + 64 reading ONE rotary key, values
128 wide, YaRN; one leading dense SiLU-gated FFN of 9216, then a shared
expert beside sigmoid-routed top-4 of 64 experts of 1024 with a
selection bias and no auxiliary loss, of which this chip holds 8;
untied head, next-token loss; every layer a ``layers.recompute``
region). The reference is ``reference/xing_lm.py``; a configuration
asks for this file with ``"arch": "xing"``.

What the harness feeds: ``src``, ``label`` (the next tokens) and
``mask``. ``logits`` are ``[B, T, V]``; ``correct`` compares the last
``check_rows`` rows of the first sequence, which see the longest
contexts. Choices come stacked ``[routed layers, 1, T, 4]``, fetched
from inside the recompute regions of the ``for_test`` clone. The
published ``q_b``, ``kv_a`` and ``kv_b`` matrices are held as their
column blocks (a parameter each: the configuration's ``assumed`` says
so), and the parameter tree names them so.

The limits, each with the readings it was set from (my chip runs, PR
34, one v5e, the cell's own size: 5 layers, one 4096-token sequence,
the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 2e-2: the program's bf16-AMP forward against
  the float32 reference handed the program's choices reads 5.27e-3 to
  7.09e-3 in twenty-one readings (fifteen benchmark runs on fifteen
  seeds and ``control.py``'s seeds 61-66); the fp8 control handed the
  same choices 8.40e-2 to 1.69e-1 on those six, 11.9 times the
  program's largest (``control.py`` exit 0, ``separates`` true). 2e-2
  is 2.8 times the program's largest and under a quarter of the
  control's smallest.
* ``NEAR_TIE`` 5e-2: how far under the reference's own cut (its fourth
  largest of score + bias, as a share of it) the program's differing
  choices may lie for the reference to take them; SDAR's limit under
  the same rule, where the largest reading was 1.24e-2 (``archs/
  sdar.py``). Not read apart here: with every proposal within it the
  logits read as above, and a router that takes wrong experts lies
  under the cut by most of it and fails ``TRAIN_LOGITS_RTOL``.
* ``LOSS_RTOL`` 2.5e-4: the first step's bf16-AMP loss against the
  reference's, which routes by itself, reads 6.8e-6 to 7.66e-5 in
  fifteen runs (median 2.0e-5): a mean over 4,096 tokens, half of
  SDAR's or OPT's, of which some hundred route otherwise than float32
  would; three times the largest is 2.3e-4. No precision control parts
  from it (a fresh model's loss is about ln V whatever the precision);
  what it guards is a dropped term: without
  ``routed_scaling_factor``, with ``H_post`` short of its 2 or with
  the rotary part of the score dropped
  (``tests/chipbench/test_chipbench_xing.py`` plants all three through
  the driver and sees ``correct`` false).
"""

import numpy as np

from chipbench.reference import xing_lm

TRAIN_LOGITS_RTOL = 2e-2
LOSS_RTOL = 2.5e-4
NEAR_TIE = 5e-2
# the projections, the dense FFN, the shared experts and the head are
# ``mul`` ops; the routed experts' grouped matmuls are XLA's
# ``ragged-dot-*`` kernels, which no scope holds (as ``archs/sdar.py``):
# the cell is not on ``matmul_roof_pct``'s list.
MATMUL_SCOPES = ("mul",)
NAME = "xing"           # the program's parameter prefix


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
        bias_update_rate=cfg["bias_update_rate"], hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=cfg["rope_scaling"], rms_eps=cfg["rms_norm_eps"],
        embedding_std=cfg["embedding_init_std"], recompute=True, name=NAME)


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
    """HOST arrays, by the names ``latent_moe_lm`` gives its parameters
    (the forward's run, which comes before the reference for a model
    that chooses, donates the scope's)."""
    get = lambda name: np.asarray(scope.find_var(name))
    three = lambda at: tuple(get("%s_%s" % (at, part))
                             for part in ("gate", "up", "down"))

    def layer(i):
        at = "%s_l%d" % (NAME, i)
        p = {key: get("%s_%s" % (at, key)) for key in (
            "ln1", "ln2", "q_a", "q_norm", "q_b_nope", "q_b_pe", "kv_a_c",
            "kv_a_pe", "kv_norm", "kv_b_k", "kv_b_v", "o")}
        for hc in ("hc_attn", "hc_ffn"):
            p[hc] = {key: get("%s_%s.%s" % (at, hc, key))
                     for key in ("proj", "alpha", "bias")}
        if i < cfg["first_k_dense_replace"]:
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
    """``expert_rows``: the rows that chose each of the 64 experts,
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


# -- the reference (``reference/xing_lm.py``) --------------------------------

def lm_loss(params, src, label, mask, cfg):
    """No choices: the train step's cannot be fetched without another
    executable than the window's; ``LOSS_RTOL`` is set with that
    said."""
    return xing_lm.lm_loss(params, src, label, mask, cfg)


def _choices(choices, cfg):
    return None if choices is None else choices.reshape(
        choices.shape[0], -1, cfg["num_experts_per_tok"])


def logits_at(params, tokens, first, count, cfg, choices=None):
    return xing_lm.logits_at(params, tokens, first, count, cfg,
                             _choices(choices, cfg), NEAR_TIE)


def control_logits_at(params, tokens, first, count, cfg, choices=None):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul, routed exactly as ``logits_at`` routes given the same
    ``choices`` (router and hyper-connection coefficients stay
    float32)."""
    import jax.numpy as jnp
    return xing_lm.logits_at(params, tokens, first, count, cfg,
                             _choices(choices, cfg), NEAR_TIE,
                             operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def touched_parameters(cfg):
    """The matmul weights one token passes on this chip, forward: a
    layer's attention (q_a, q_b, kv_a, kv_b, o) and two hyper-connection
    projections; the dense FFN or a shared expert, the router over all
    experts and the held experts a token expects (top-k times the share
    held here); the head."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    n = cfg["hc_mult"]
    attention = (d * rq + rq * heads * (dn + dr) + d * (rkv + dr)
                 + rkv * heads * (dn + dv) + heads * dv * d)
    hyper = 2 * n * d * n * (n + 2)
    expert = 3 * d * cfg["moe_intermediate_size"]
    everyone = cfg["published"]["n_routed_experts"]
    held_a_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / everyone
    dense, layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    return (layers * (attention + hyper)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * (cfg["n_shared_experts"] * expert
                                  + d * everyone + held_a_token * expert)
            + d * cfg["vocab_size"])


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
    layer, at 2 x (320 + 832). At ``seq_len`` 0 the matmuls outside
    attention alone."""
    fwd, bwd = _score_macs(cfg)
    return 6 * touched_parameters(cfg) + seq_len // 2 * 2 * (fwd + bwd) \
        * cfg["num_attention_heads"] * cfg["num_hidden_layers"]


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: T^2 / 2
    causal-useful scores a sequence, head and layer, each 2 x (320
    forward + 832 backward). The recompute's second forward is in the
    kernels' time and not in this count."""
    fwd, bwd = _score_macs(cfg)
    return seq_len * seq_len * (fwd + bwd) * cfg["num_attention_heads"] \
        * cfg["num_hidden_layers"] * batch


def flash_flops_split(cfg):
    """(forward, backward) shares of ``flash_flops_per_step``: 320 /
    1152 and 832 / 1152 here, not the 2 / 7 and 5 / 7 of a head whose
    key and value are one width."""
    fwd, bwd = _score_macs(cfg)
    return fwd / (fwd + bwd), bwd / (fwd + bwd)


def expert_flops_per_pair(cfg):
    """Forward + backward FLOPs of one (row, held expert) pair: three
    matmuls of d x f, forward and twice that backward."""
    return 18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
