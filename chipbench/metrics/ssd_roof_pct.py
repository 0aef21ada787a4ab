"""The state-space-dual scan kernels' share of their roofline: what the
Mamba-2 scans of the traced steps need (the architecture's
``ssd_flops_per_step`` and ``ssd_bytes_per_step``, from shapes alone
whatever implements the scan: a step under per-layer recompute runs the
forward twice and the backward once, and the second forward's work is
counted, because it runs) at the chip's peaks, the LARGER of bytes over
the peak HBM rate and FLOPs over the peak bf16 rate, over the device
time on chip 0 of the kernels named ``ssd_scan_fwd`` and
``ssd_scan_bwd`` (``pl.pallas_call(name=...)``). At 64 heads of 64, 128
states and chunks of 128 rows the two floors lie close together (the
bytes' is some 1.3 times the products'), so the log line gives both,
and each kernel's seconds. What the kernels do beside the count (the
chunk states a forward saves and a backward reads, the Gram product and
the decays a backward makes again, the masked half of a chunk's
products) lowers the share and cannot raise it. None where the
architecture states no scan's work or no kernel of the names ran."""
from chipbench import cells, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")


def read(run):
    window = spans.of(run)
    arch = cells.load_arch(run["config"]["arch"])
    if not window or not hasattr(arch, "ssd_bytes_per_step"):
        return None
    _, steps = spans.step_program(window)
    seconds = {k: spans.device_time(window, kind=k, kernel=True)
               for k in KERNELS}
    total = sum(seconds.values())
    if not steps or not total:
        return None
    t = run["train"]
    size = run["config"], t["batch"], t["seq_len"]
    by_bytes = steps * arch.ssd_bytes_per_step(*size) / run["chips"] \
        / run["peaks"]["hbm_bytes_per_s"]
    by_flops = steps * arch.ssd_flops_per_step(*size) / run["chips"] \
        / run["peaks"]["flops_bf16"]
    spans.say("ssd_roof_pct: %s in %d steps; the bytes need %.6f s, the "
              "products %.6f s" % (
                  ", ".join("%s %.6f s" % kv for kv in seconds.items()),
                  steps, by_bytes, by_flops))
    return 100.0 * max(by_bytes, by_flops) / total
