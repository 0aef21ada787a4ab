"""Architecture ``ouro``: Ouro-2.6B's training step, the LoopLM's first
training stage, as ``paddle_tpu/models/looped_lm.py`` builds it (ONE
stack of sandwich-norm layers, 16 heads of 128, plain multi-head,
rotary q and k at theta 1e6, a SiLU-gated FFN of 5632, visited
``total_ut_steps`` = 4 times under the same parameters through
``layers.repeat``; the one final norm, the one untied head, a
cross-entropy and an exit gate behind EVERY visit; the cost the exit
distribution's expected loss less ``entropy_weight`` times its entropy;
every layer, and every visit's head and loss, a ``layers.recompute``
region). The reference is ``reference/ouro_lm.py``; a configuration
asks for this file with ``"arch": "ouro"``.

What the harness feeds: ``src``, ``label`` and ``mask``. ``logits`` are
``[B, T, V + R]``: the LAST visit's next-token logits and behind them
the R ``log p_t`` of the row's exit distribution, so ``correct``'s one
comparison of the last ``check_rows`` rows holds the whole loop (the
last visit's stream has passed all 32 layer visits and four final
norms) and all R gates (each reads its own visit's stream) to the
reference. The model routes nothing: no ``router_choices``.

The limits, each with the readings it was set from (my chip runs, PR
59, one v5e, the cell's own size: 8 layers x 4 visits, one 8192-token
sequence, the last 64 rows; ``PERF.md`` section 4 has the table):

* ``TRAIN_LOGITS_RTOL`` 6e-2, this configuration's own: the program's
  bf16-AMP forward against the float32 reference reads 1.65e-2 to
  2.90e-2 in forty readings on forty seeds (``control.py``'s
  twenty-four in two calls, 1.65e-2 to 2.84e-2, and sixteen benchmark
  runs, 1.72e-2 to 2.90e-2), the largest difference over the last
  visit's logits AND the four ``log p_t``: three times what the cells of 5 to 8 layer
  visits read (5.9e-3 to 8.1e-3, ``archs/joyai.py``), as 32 layer
  visits in a row would have it (a rounding of 2^-9 a product, some
  130 products deep: sqrt(130) x 2e-3 = 2.2e-2), and over the 2e-2 the
  other architectures state, which two seeds in three would fail for
  no fault. The fp8 control reads 2.08e-1 to 4.31e-1 on those
  twenty-four seeds, 7.2 times the program's largest of all
  (``control.py``: ``separates`` true in both calls, exit 0 under this
  limit). 6e-2 is 2.1 times the program's largest and
  3.5 times under the control's smallest; with a ratio of 7.2 between
  them no limit leaves both the 2.5 times and the quarter that the
  shallower cells have.
* ``LOSS_RTOL`` 2.5e-4, the accepted cells' (Xing's, JoyAI's): the
  first step's bf16-AMP cost against the reference's. No precision
  control parts from it (a fresh model's every ``ell^(t)`` is about ln
  V = 10.8 whatever the precision, and the entropy term is 0.1 x 1.2);
  what it guards is a dropped or misweighed term. The heads of visits 1
  to 3 reach ``correct`` through this loss alone, visit t weighed by
  ``p_t`` (0.5, 0.25, 0.125 and the remainder 0.125 at initialisation,
  where every gate reads about 0): a RELATIVE error e in one visit's
  mean cross-entropy moves the cost by ``p_t`` e, so the limit passes an
  error of 5e-4 in visit 1's loss, 1e-3 in visit 2's and 2e-3 in visit
  3's or 4's: 0.005 to 0.02 nats of 10.8. A visit's head that is
  WRONG (another weight, another target, a visit's stream taken before
  the final norm) moves its loss by far more
  (``tests/chipbench/test_chipbench_ouro_faults.py`` plants such faults
  through the driver and sees ``correct`` false); a head computed in a
  LOWER PRECISION moves it by less than that, and is the same op on the
  same weight as the last visit's, which the logits hold to 6e-2. The
  four ``loss_sum``s cannot be compared a visit at a time inside the
  driver, which compares one loss; ``tests/test_looped_lm.py`` holds
  each visit's loss to the reference's on the CPU.
"""

import numpy as np

from chipbench.reference import ouro_lm

TRAIN_LOGITS_RTOL = 6e-2
LOSS_RTOL = 2.5e-4
# q/k/v/o, the FFN's three and the head are ``mul`` ops (the gate's
# [d, 1] product too: 2 d FLOPs a row, nothing beside the others)
MATMUL_SCOPES = ("mul",)
NAME = "ouro"           # the program's parameter prefix
LAYER_KEYS = ("ln1", "ln1_post", "ln2", "ln2_post", "wq", "wk", "wv", "wo")


# -- the program ------------------------------------------------------------

def build(cfg, seq_len):
    from paddle_tpu.models.looped_lm import looped_lm
    return looped_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq_len,
        n_layer=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_inner=cfg["intermediate_size"], ut_steps=cfg["total_ut_steps"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        entropy_weight=cfg["entropy_weight"], recompute=True, name=NAME)


def params_of_program(program, scope, cfg):
    """The scope's own arrays by the names ``looped_lm`` gives its
    parameters: ONE tree for the stack, whatever the visits."""
    get = lambda name: scope.find_var("%s_%s" % (NAME, name))
    layer = lambda i: {
        **{key: get("l%d_%s" % (i, key)) for key in LAYER_KEYS},
        **{key: get("l%d_ffn_%s" % (i, key))
           for key in ("gate", "up", "down")}}
    return {"word_emb": get("word_emb"), "final_norm": get("final_norm"),
            "w_out": get("head"), "gate_w": get("gate_w"),
            "gate_b": get("gate_b"),
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def program_counters(program, scope):
    """What the program summed on the device over every train step it
    ran: ``visit_loss`` (each visit's masked mean cross-entropy, R
    sums), ``exit_step`` (the mean expected exit step ``sum_t t p_t``),
    ``entropy`` (the exit distribution's mean entropy) and ``steps``
    (the steps summed over: the mean of ``sum_t p_t``, 1 a step). A
    program without a sum leaves it out."""
    read = lambda name: scope.find_var("%s_%s" % (NAME, name))
    total = lambda v: float(np.asarray(v, np.float64).reshape(-1)[0])
    out = {}
    visits = [read("loss_sum_%d" % t)
              for t in range(1, int(program_visits(program)) + 1)]
    if visits and all(v is not None for v in visits):
        out["visit_loss"] = [total(v) for v in visits]
    for key, name in (("exit_step", "exit_step_sum"),
                      ("entropy", "entropy_sum"), ("steps", "steps_sum")):
        if read(name) is not None:
            out[key] = [total(read(name))]
    return out


def program_visits(program):
    """The visits of the program's loop: its ``repeat`` op's ``times``
    (0 where it has none)."""
    return next((op.attr("times") for op in program.global_block().ops
                 if op.type == "repeat"), 0)


# -- the reference (``reference/ouro_lm.py``) --------------------------------

def lm_loss(params, src, label, mask, cfg):
    return ouro_lm.lm_loss(params, src, label, mask, cfg)


def logits_at(params, tokens, first, count, cfg):
    return ouro_lm.logits_at(params, tokens, first, count, cfg)


def control_logits_at(params, tokens, first, count, cfg):
    """The control of ``TRAIN_LOGITS_RTOL``: fp8 e4m3 operands in every
    matmul of the stack and the head (the gate stays float32, as the
    program keeps it)."""
    import jax.numpy as jnp
    return ouro_lm.logits_at(params, tokens, first, count, cfg,
                             operands=jnp.float8_e4m3fn)


# -- the arithmetic ---------------------------------------------------------

def _layer_parameters(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2 * d * q + 2 * d * kv + 3 * d * cfg["intermediate_size"]


def visit_parameters(cfg):
    """The matmul weights one token passes in ONE visit, forward: the
    stack, the head and the gate."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * _layer_parameters(cfg) \
        + d * cfg["vocab_size"] + d


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, NO recompute (the
    backward twice the forward), EVERY visit's matmuls and every
    visit's head counted: the work done, 6 a weight passed a visit, and
    a token's share of its sequence's causal-useful scores, T / 2 a
    head, layer and visit, forward 2 matmuls and backward 5 of 2 D
    each. At ``seq_len`` 0 the matmuls outside attention alone."""
    visits = cfg["total_ut_steps"]
    return visits * (6 * visit_parameters(cfg) + seq_len // 2 * 14
                     * cfg["num_attention_heads"] * cfg["head_dim"]
                     * cfg["num_hidden_layers"])


def flash_flops_per_step(cfg, batch, seq_len):
    """Useful FLOPs of the flash kernels in one train step: T^2 / 2
    causal-useful scores a sequence, head, layer and visit (32 calls at
    8 layers x 4 visits), forward 2 matmuls and backward 5 of 2 D each.
    The recompute's second forward is in the kernels' time and not in
    this count."""
    return 7 * seq_len * seq_len * cfg["num_attention_heads"] \
        * cfg["head_dim"] * cfg["num_hidden_layers"] \
        * cfg["total_ut_steps"] * batch


def decode_step_bytes(cfg, dtype_bytes, live_kv_tokens, rows):
    """Bytes one decode step must read on this chip: the weights held
    here once A VISIT (the stack is read ``total_ut_steps`` times a
    token; of the embedding only the step's rows) and K and V of every
    layer and visit, which each keep their own. No cell reads it yet."""
    d, visits = cfg["hidden_size"], cfg["total_ut_steps"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    stack = cfg["num_hidden_layers"] * (_layer_parameters(cfg) + 4 * d)
    return dtype_bytes * (visits * (stack + d + d * cfg["vocab_size"] + d)
                          + rows * d + 2 * visits
                          * cfg["num_hidden_layers"] * kv * live_kv_tokens)
