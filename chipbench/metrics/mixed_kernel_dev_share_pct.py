"""The share of device time spent in kernels that hold two or more
Program ops: the device time of the step's kernels whose row of the
kernel ledger (``paddle_tpu.trace.kernels``) names two or more scopes,
over busy time (chip 0; ``chipbench/kernels.py``). A profile books a
kernel's whole time to ONE of them (``root_scope``), so on this share
every scope reader (``optimizer_dev_share_pct``, ``xent_dev_share_pct``,
the ``*_glue_dev_share_pct``) is a floor and ``dense_matmul_roof_pct``
reads low.

The log lines: the ten pairs ``root's type <- rider's type`` with most
seconds (a kernel counts once for each type of rider it carries: ``mul
<- adam`` is the time of the products' kernels that also hold Adam's
update), then the ten kernels that hold NO product with most seconds,
each with the GB/s its DECLARED bytes would mean. That figure is not a
metric: declared bytes over-count what a body reads through broadcasts
and partial reads (PERF.md section 7), so it is printed to be seen
against a real trace first."""
from chipbench import kernels, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s"
LINES = 10


def read(run):
    window = kernels.of(run)
    if window is None:
        return None
    steps = window["steps"]
    mixed, count, pairs, plain = 0.0, 0, {}, []
    for name, k in window["kernels"].items():
        row = k["row"]
        if not row["dots"] and row["opcode"] != "while":
            plain.append((k["dur"], name, k))
        scopes = [s for s in row["scopes"] if s]
        if len(scopes) < 2:
            continue
        mixed += k["dur"]
        count += 1
        root = spans.scope_type(row["root_scope"]) or "(no scope)"
        riders = {spans.scope_type(s) for s in scopes
                  if s != row["root_scope"]}
        for rider in riders:
            pairs[root, rider] = pairs.get((root, rider), 0.0) + k["dur"]
    for (root, rider), s in sorted(pairs.items(),
                                   key=lambda kv: -kv[1])[:LINES]:
        spans.say("mixed_kernel_dev_share_pct: %s <- %s %.3f ms a step"
                  % (root, rider, 1e3 * s / steps))
    for dur, name, k in sorted(plain, key=lambda p: -p[0])[:LINES]:
        row = k["row"]
        moved = (row["bytes_in"] + row["bytes_out"]) * k["runs"]
        spans.say(
            "mixed_kernel_dev_share_pct: no product in %s (%s, %s): "
            "%.3f ms a step, %.1f MB declared a run, %.1f GB/s by "
            "declared bytes" % (
                name, row["custom_call_target"] or row["fusion_kind"]
                or row["opcode"], row["root_scope"] or "no scope",
                1e3 * dur / steps, 1e-6 * moved / k["runs"],
                1e-9 * moved / dur if dur else 0.0))
    spans.say(
        "mixed_kernel_dev_share_pct: %.6f s of %.6f s of the step's "
        "device ops sit in %d kernels of two or more Program ops; %d "
        "device op(s) found no kernel row (%.6f s)" % (
            mixed, window["total"], count, window["unjoined"],
            window["lost"]))
    return spans.busy_share_pct(run, mixed)
