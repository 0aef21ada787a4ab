"""The selective-scan kernels' share of the HBM roofline: the bytes the
scans of the traced steps must move (the architecture's
``scan_bytes_per_step``, from shapes: each operand read once and each
result written once a pass; a step under per-layer recompute runs the
forward twice and the backward once, and the recomputed forward's bytes
are counted, because it runs) over the peak HBM rate, over the device
time on chip 0 of the kernels named ``selective_scan_fwd`` and
``selective_scan_bwd`` (``pl.pallas_call(name=...)``). The scan has no
matmul: the bandwidth floor is its roofline, and what holds it above the
floor is the vector unit's work, about ten operations a state update.
The log line gives each kernel's seconds and the state updates a second
(``scan_updates_per_step``). What the kernels move beside the count (the
states saved at chunk boundaries, the lane-broadcast copies of B_t and
C_t) lowers the share and cannot raise it. None where the architecture
states no scan's bytes or no kernel of the names ran."""
from chipbench import cells, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("selective_scan_fwd", "selective_scan_bwd")


def read(run):
    window = spans.of(run)
    arch = cells.load_arch(run["config"]["arch"])
    if not window or not hasattr(arch, "scan_bytes_per_step"):
        return None
    _, steps = spans.step_program(window)
    seconds = {k: spans.device_time(window, kind=k, kernel=True)
               for k in KERNELS}
    total = sum(seconds.values())
    if not steps or not total:
        return None
    t = run["train"]
    size = run["config"], t["batch"], t["seq_len"]
    need = steps * arch.scan_bytes_per_step(*size) / run["chips"]
    spans.say("scan_hbm_roof_pct: %s in %d steps; %.4g bytes needed; "
              "%.4g state updates a second" % (
                  ", ".join("%s %.6f s" % kv for kv in seconds.items()),
                  steps, need, steps * arch.scan_updates_per_step(*size)
                  / run["chips"] / total))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / total
