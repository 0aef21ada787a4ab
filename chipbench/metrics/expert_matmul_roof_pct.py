"""The expert layer's grouped matmuls' share of the compute roofline:
``expert_flops_per_pair`` (the architecture's: three matmuls of d x f,
forward and backward) times the (row, expert) pairs that fell on the
experts held here in the traced steps (from ``train.counters``: the
program's ``expert_rows`` over its ``steps``, the mean a step over the
run, since routing moves a little with the weights), over the peak bf16
rate, over the device time on chip 0 of the grouped-matmul kernels,
found BY NAME: XLA lowers ``lax.ragged_dot`` to kernels it names
``ragged-dot-*`` and strips the Program op's scope from them, so no
scope reader sees them; a Pallas kernel would be named
``grouped_matmul_*``. The backward recomputes the forward's hidden
activations: that time is in the divisor and its FLOPs are not counted,
so this reads the useful share. None where the architecture counts no
``expert_rows`` or no such kernel ran."""
from chipbench import cells, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("ragged-dot", "grouped_matmul")


def seconds(window):
    """Device seconds of the grouped-matmul kernels on chip 0."""
    return sum(op["dur"] for op in window["ops"]
               if op["kind"].startswith(KERNELS))


def held_pairs_a_step(run):
    """Mean pairs a step on the experts held here, or None."""
    counters = run["train"].get("counters") or {}
    rows, steps = counters.get("expert_rows"), counters.get("steps")
    cfg = run["config"]
    if not rows or not steps or not steps[0] or "first_expert" not in cfg:
        return None
    first = cfg["first_expert"]
    return sum(rows[first:first + cfg["num_experts"]]) / steps[0]


def read(run):
    window = spans.of(run)
    pairs = held_pairs_a_step(run)
    if not window or pairs is None:
        return None
    _, steps = spans.step_program(window)
    busy = seconds(window)
    arch = cells.load_arch(run["config"]["arch"])
    if not steps or not busy or not hasattr(arch, "expert_flops_per_pair"):
        return None
    flops = steps * pairs * arch.expert_flops_per_pair(run["config"])
    spans.say("expert_matmul_roof_pct: %.0f pairs a step on held experts, "
              "%.6f s in the grouped-matmul kernels in %d steps"
              % (pairs, busy, steps))
    return 100.0 * flops / run["peaks"]["flops_bf16"] / busy
