"""The traced step's device ops joined to the PROGRAM's account of its
kernels.

``paddle_tpu.trace.kernels()`` is the program's kernel ledger (PR 66):
one row for every instruction of the compiled step that runs as a
device op, under the name the profiler's event carries (``fusion.412``),
read from the executable's optimised HLO: the Program ops XLA fused
into it (``scopes``, keys of the op ledger's rows), the products in it
with their FLOPs (``dots``), the bytes it declares and XLA's own
estimate of its cycles. ``chipbench/oplog.py`` books a device op's WHOLE
time to the one scope its name carries; ``of(run)`` gives each device op
of the step's program its kernel row by ``name`` and, through
``scopes``, the op-ledger rows of everything in it, so that four readers
can tell apart what that booking lumps together:
``wgrad_matmul_roof_pct`` and ``dgrad_matmul_roof_pct`` (the dense
products' weight and operand gradients, ``grads``),
``mixed_kernel_dev_share_pct`` (the time on which every scope reader is
a floor) and ``compiled_step_hbm_pct`` (the header's ``memory``).

A device op that finds no row is counted and said with the seconds it
holds: a join that silently loses a tenth of the step is worse than
none. A tree with no ``trace.kernels`` (the parent of the PR that added
it) gives None everywhere, and the metrics are left out of the line.
The ledger is asked for where ``oplog``'s is: by a reader, after the
timed window and after ``setup_s`` was taken, so its parse is in no
end-to-end metric.
"""

import math
import time

from chipbench import oplog, spans

KINDS = ("w", "x", "either")
_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


def ledger():
    """``paddle_tpu.trace.kernels``, or None where the program keeps no
    kernel ledger."""
    try:
        from paddle_tpu import trace
    except ImportError:
        return None
    return getattr(trace, "kernels", None)


def table():
    """``(header, rows)`` of the train step's build, None where there is
    no ledger or no such build."""
    read = ledger()
    return None if read is None else read(root=oplog.ROOT, backward=True)


def of(run):
    """The step's kernels with their rows, read once and kept on
    ``run``: ``header`` (the kernel table's), ``steps``, ``kernels``
    (``{name: {"row", "dur" (seconds in the traced steps), "runs",
    "ops" ({scope: op-ledger row} of what is fused into it)}}``),
    ``total`` (the device seconds of the step program's ops),
    ``unjoined`` and ``lost`` (the count of device ops that find no
    kernel row and the seconds they hold); None, with the reason said,
    where there is no window, no ledger or no table."""
    if "kernel_window" not in run:
        run["kernel_window"] = _window(run)
    return run["kernel_window"]


def _window(run):
    if ledger() is None:
        spans.say("kernel ledger: the program keeps none "
                  "(no paddle_tpu.trace.kernels)")
        return None
    window = oplog.of(run)
    if window is None:
        return None
    began = time.perf_counter()
    got = table()
    if got is None:
        spans.say("kernel ledger: no build of %s with a backward_marker "
                  "was compiled ahead of its first call" % oplog.ROOT)
        return None
    header, rows = got
    by_name = {r["name"]: r for r in rows}
    kernels, missing, total = {}, {}, 0.0
    for op in spans.of(run)["ops"]:
        if op["program"] != window["program"]:
            continue
        total += op["dur"]
        row = by_name.get(op["name"])
        if row is None:
            missing[op["name"]] = missing.get(op["name"], 0.0) + op["dur"]
            continue
        k = kernels.get(op["name"])
        if k is None:
            k = kernels[op["name"]] = {
                "row": row, "dur": 0.0, "runs": 0,
                "ops": {s: window["rows"].get(int(s.rsplit(".", 1)[1]))
                        for s in row["scopes"]
                        if s and s not in spans.SERVING_SCOPES}}
        k["dur"] += op["dur"]
        k["runs"] += 1
    lost = sum(missing.values())
    spans.say(
        "kernel ledger: the build of step %s, module %s, %d rows parsed "
        "from %d bytes of HLO in %.3f s, read and joined in %.3f s; %d "
        "kernels joined; %d device ops of %s find no row and hold %.6f s "
        "of %.6f (%.3f%%)%s" % (
            header["step"], header["module"], header["count"],
            header["text_bytes"], header["parse_seconds"],
            time.perf_counter() - began, len(kernels),
            len(missing), window["program"], lost, total,
            100.0 * lost / total if total else 0.0,
            "".join("; %s %.6f s" % kv for kv in sorted(
                missing.items(), key=lambda kv: -kv[1])[:5])))
    return {"header": header, "steps": window["steps"],
            "kernels": kernels, "total": total,
            "unjoined": len(missing), "lost": lost, "oplog": window}


# -- the dense products' gradients ------------------------------------------

def _flat(shape):
    return tuple(d for d in shape if d != 1)


def grad_kind(dot, row):
    """Which gradient of the op row's ``[M, K] x [K, N]`` a backward
    product is: ``"w"`` (it sums over the M rows), ``"x"`` (over the N
    columns), ``"either"`` where M = N and the shapes do not tell (the
    operand gradient reads an operand of the weight's shape, the weight
    gradient writes a result of it), None where its FLOPs are not the
    row's 2 MKN, or 2 (M / b) KN for a whole b."""
    _, lhs, rhs, result, contracted, flops = dot
    m, k, n = row["mkn"]
    # a head that runs in row blocks (PR 60) is M / b rows a product
    m, rest = divmod(flops, 2 * k * n)
    if rest or not m or row["mkn"][0] % m or contracted not in (m, n):
        return None
    if m != n:
        return "w" if contracted == m else "x"
    weight = {_flat((k, n))} | {
        _flat(shape) for slot in row["inputs"].values()
        for name, shape, _ in slot if name in row["weights"] and shape}
    reads, writes = (_flat(lhs) in weight or _flat(rhs) in weight,
                     _flat(result) in weight)
    return "x" if reads and not writes else \
        "w" if writes and not reads else "either"


def grads(run):
    """The kernels that hold a dense product's gradient, by kind:
    ``{"w" | "x" | "either": [{"kernel", "name", "dots": [(op row, dot,
    kind)], "flops" (one run's)}]}`` and the list of mismatches
    ``(kernel name, op_name, flops, 2 MKN)``. A kernel is booked ``w``
    or ``x`` where every gradient in it is of that kind, else
    ``either``; one that holds a product whose FLOPs are not its row's
    is left out and said. None where there is no window."""
    window = of(run)
    if window is None:
        return None
    if "grads" in window:
        return window["grads"]
    out, wrong = {kind: [] for kind in KINDS}, []
    for name, k in window["kernels"].items():
        found = []
        for dot in k["row"]["dots"]:
            op_name = dot[0] or ""
            if oplog.pass_of(op_name) != "bwd":
                continue
            row = k["ops"].get(spans.parse_op_name(op_name)[1])
            if not row or row["type"] not in oplog.DENSE \
                    or "mkn" not in row:
                continue
            kind = grad_kind(dot, row)
            if kind is None:
                m, kk, n = row["mkn"]
                wrong.append((name, op_name, dot[5], 2 * m * kk * n))
            found.append((row, dot, kind))
        if not found or any(kind is None for _, _, kind in found):
            continue
        kinds = {kind for _, _, kind in found}
        out[kinds.pop() if len(kinds) == 1 else "either"].append({
            "kernel": k, "name": name, "dots": found,
            "flops": sum(dot[5] for _, dot, _ in found)})
    for name, op_name, flops, want in wrong[:8]:
        spans.say("kernel ledger: %s holds %s at %d FLOPs and its op row "
                  "says 2 MKN = %d: left out" % (name, op_name, flops,
                                                 want))
    window["grads"] = out, wrong
    return window["grads"]


def _riders(held, own):
    """``adam 12, silu 4 (nested)``: the instructions a kernel group
    carries beside its own products' scopes, by op type, a kernel."""
    counts, nested = {}, set()
    for h in held:
        row = h["kernel"]["row"]
        for scope, n in row["scopes"].items():
            if scope and scope not in own:
                what = spans.scope_type(scope)
                counts[what] = counts.get(what, 0) + n
                if scope in row["nested"]:
                    nested.add(what)
    return ", ".join("%s %g%s" % (what, round(n / len(held), 1),
                                  " (nested)" if what in nested else "")
                     for what, n in sorted(counts.items(),
                                           key=lambda kv: -kv[1])) or "none"


def grad_roof_pct(run, kind, metric, lines=12):
    """Share of the bf16 peak the dense products' gradients of ``kind``
    (``"w"`` / ``"x"``) reach: their FLOPs by the kernel ledger (each
    checked against its op row's 2 MKN) times the runs of the kernels
    that hold them, over the peak, over those kernels' whole device
    time. Says the gradients by weight family, furthest from the peak's
    time first, then the books. None where there is no window or no
    such kernel ran."""
    got = grads(run)
    if got is None:
        return None
    by_kind, wrong = got
    window, peak = of(run), run["peaks"]["flops_bf16"]
    steps = window["steps"]
    fams = {}
    for h in by_kind[kind]:
        row = max(h["dots"], key=lambda rdk: rdk[1][5])[0]
        fams.setdefault(oplog.family(row), []).append(h)
    seconds = lambda held: sum(h["kernel"]["dur"] for h in held)
    flops = lambda held: sum(h["flops"] * h["kernel"]["runs"]
                             for h in held)
    room = lambda held: seconds(held) - flops(held) / peak
    for fam, held in sorted(fams.items(),
                            key=lambda kv: -room(kv[1]))[:lines]:
        moved = sum((h["kernel"]["row"]["bytes_in"]
                     + h["kernel"]["row"]["bytes_out"]) for h in held)
        own = sum(_product_bytes(row, dot) for h in held
                  for row, dot, _ in h["dots"])
        cycles = [(h["kernel"]["dur"], h["kernel"]["runs"]
                   * h["kernel"]["row"]["estimated_cycles"])
                  for h in held if h["kernel"]["row"]["estimated_cycles"]]
        spans.say(
            "%s: %s %d x %d, M %d, %d kernel(s): %.3f ms a step at "
            "%.1f%% of the peak; they move %.2f x the products' own "
            "arrays (%.1f MB a kernel); riders a kernel: %s; %s" % (
                metric, fam[0], fam[2], fam[3], fam[1], len(held),
                1e3 * seconds(held) / steps,
                100.0 * flops(held) / peak / seconds(held),
                moved / own if own else 0.0, 1e-6 * moved / len(held),
                _riders(held, {"%s.%d" % (row["type"], row["seq"])
                               for h in held for row, _, _ in h["dots"]}),
                "%.3f ns a cycle XLA estimated" % (
                    1e9 * sum(s for s, _ in cycles)
                    / sum(c for _, c in cycles)) if cycles
                else "no estimate of XLA's"))
    total = {k: (flops(v), seconds(v)) for k, v in by_kind.items()}
    times = {}
    for held in by_kind.values():
        for h in held:
            for row, dot, k in h["dots"]:
                key = row["seq"], k, dot[4]
                times[key] = times.get(key, 0) + dot[5]
    rows = window["oplog"]["rows"]
    twice = sorted(seq for (seq, k, _), f in times.items()
                   if f > 2 * math.prod(rows[seq]["mkn"]) * (
                       len(rows[seq]["grads"]) if k == "either" else 1))
    theirs = steps * sum(f["flops"]["bwd"]
                         for f in oplog.dense(window["oplog"]).values())
    ours = sum(f for f, _ in total.values())
    spans.say(
        "%s: %d families (%d not shown); weight gradients %.3f TFLOP in "
        "%.6f s, operand gradients %.3f in %.6f, either %.3f in %.6f "
        "(%d kernels), in %d steps; together %.6f TFLOP and the op "
        "ledger's backward %.6f (%+.3f%%); %d product(s) left out for "
        "their FLOPs; %d gradient(s) are in the step more than once%s" % (
            metric, len(fams), max(0, len(fams) - lines),
            1e-12 * total["w"][0], total["w"][1], 1e-12 * total["x"][0],
            total["x"][1], 1e-12 * total["either"][0],
            total["either"][1], len(by_kind["either"]), steps,
            1e-12 * ours, 1e-12 * theirs,
            100.0 * (ours - theirs) / theirs if theirs else 0.0,
            len(wrong), len(twice),
            ":" * bool(twice) + "".join(" row %d" % seq
                                        for seq in twice[:8])))
    mine, held = total[kind]
    return 100.0 * mine / peak / held if held else None


def _product_bytes(row, dot):
    """The bytes of a product's own three arrays at the dtype the op
    row says its operands have."""
    _, lhs, rhs, result, _, _ = dot
    size = _ITEMSIZE.get(row.get("operand_dtype"), 4)
    return size * (math.prod(lhs) + math.prod(rhs) + math.prod(result))
