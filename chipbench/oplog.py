"""The traced step's device ops joined to the PROGRAM's own account of
them.

``paddle_tpu.trace.ops()`` is the program's op ledger (PR 51): where a
build lowers a Program op under the device scope ``<type>.<seq>`` it
leaves one row under the same ``seq``: the op's inputs and outputs with
their shapes, the parameters among them (``weights``), the recompute
region it sits in (``region``) and whether the region's plan keeps its
result (``kept``), and for a ``mul`` / ``matmul`` the ``[M, K] x [K, N]``
it runs at (``mkn``) and which of its gradients the step takes
(``grads``). A device op's scope (``spans.parse_op_name``) is the key of
its row, so ``of(run)`` gives every device op of the step's program its
row, and the four readers ``dense_matmul_roof_pct`` (with its ``_fwd_``
and ``_bwd_`` parts) and ``second_forward_dev_share_pct`` read work from
the program's shapes and not from a file per architecture.

The table asked for is the newest build of ``exe.step`` whose Program
has a ``backward_marker``: the train step's (the start-up program and
the ``for_test`` forward are builds of a jitted ``step`` too). Every
joined row is held to its scope's own type: a table of another build
does not pass.

The pass is told from the raw ``op_name`` (``spans.parse_op_name`` gives
a region's ops direction None): ``rematted_computation/`` in the name is
a region's **second forward** (jax.checkpoint's name for what the
backward runs again; ``tests/test_op_ledger.py`` pins it for this jax),
otherwise ``transpose(jvp(`` is the **backward**, otherwise the
**forward** (the optimizer's ops, bare, count there). Of names XLA
joined with ``;`` the one that gave the scope decides, as it decides the
scope.

A tree with no ``trace.ops`` (the parent of the PR that added it) gives
None everywhere, and the metrics are left out of the line.
"""

import re

from chipbench import spans

ROOT = "exe.step"
DENSE = ("mul", "matmul")
PASSES = ("fwd", "second", "bwd")


def ledger():
    """``paddle_tpu.trace.ops``, or None where the program keeps no op
    ledger."""
    try:
        from paddle_tpu import trace
    except ImportError:
        return None
    return getattr(trace, "ops", None)


def pass_of(op_name):
    """``"fwd"``, ``"second"`` (a region's second forward) or ``"bwd"``
    from a device op's whole ``op_name``."""
    parts = (op_name or "").split(";")
    one = next((p for p in parts if spans.parse_op_name(p)[1]), parts[0])
    if "rematted_computation/" in one:
        return "second"
    return "bwd" if "transpose(jvp(" in one else "fwd"


def of(run):
    """The step's device ops with their rows, read once and kept on
    ``run``: ``header`` (the table's), ``rows`` (``{seq: row}``),
    ``program``, ``steps`` (traced runs of it), ``ops`` (every device
    op of the program: ``{"dur", "kind", "pass", "scope", "row"}``,
    ``row`` None for an op with no scope or no row) and ``unjoined``
    (the count of ops that carry a scope with no row: 0 where the table
    is whole); None, with the reason said, where there is no window, no
    ledger, no table, or a table of another build."""
    if "op_window" not in run:
        run["op_window"] = _window(run)
    return run["op_window"]


def _window(run):
    window = spans.of(run)
    if not window:
        return None
    read = ledger()
    if read is None:
        spans.say("op ledger: the program keeps none "
                  "(no paddle_tpu.trace.ops)")
        return None
    program, steps = spans.step_program(window)
    table = read(root=ROOT, backward=True)
    if not program or table is None:
        spans.say("op ledger: %s" % (
            "no build of %s with a backward_marker" % ROOT
            if program else "the window holds no program's runs"))
        return None
    header, rows = table
    rows = {r["seq"]: r for r in rows}
    ops, unjoined = [], 0
    for op in window["ops"]:
        if op["program"] != program:
            continue
        scope, row = op["scope"], None
        if scope and scope not in spans.SERVING_SCOPES:
            row = rows.get(int(scope.rsplit(".", 1)[1]))
            if row is None:
                unjoined += 1
            elif row["type"] != spans.scope_type(scope):
                spans.say("op ledger: the device op %s is scoped %s and "
                          "row %d of the table is a %s: the table is "
                          "another build's" % (op["name"], scope,
                                               row["seq"], row["type"]))
                return None
        ops.append({"dur": op["dur"], "kind": op["kind"], "scope": scope,
                    "pass": pass_of(op["op_name"]), "row": row})
    spans.say("op ledger: the build of step %s, %d rows; %d device ops "
              "of %s in %d traced steps, %d of them carry a scope with "
              "no row" % (header["step"], header["count"], len(ops),
                          program, steps, unjoined))
    return {"header": header, "rows": rows, "program": program,
            "steps": steps, "ops": ops, "unjoined": unjoined}


# -- the dense matmuls ------------------------------------------------------

def family(row):
    """A product's family: its weight's name with every run of digits
    folded to ``#`` (``layer_#_ffn_up``; a product of two activations
    has no weight and is named by its op), and the shape it runs at."""
    name = "+".join(re.sub(r"\d+", "#", w) for w in row["weights"]) \
        or "(%s of no weight)" % row["type"]
    return (name,) + tuple(row["mkn"])


def dense(window):
    """The step's dense products by family, from the rows that state an
    ``mkn``: ``{family: {"ops", "flops": {pass: FLOPs a step},
    "seconds": {pass: device seconds in the traced steps}}}``. A
    forward is 2 MKN, the backward 2 MKN for each gradient in
    ``grads``; a second forward is credited what it executes, 2 MKN for
    each op that has second-forward time, which ``roof_pct`` leaves
    out."""
    out, of_row = {}, {}
    again = {op["row"]["seq"] for op in window["ops"]
             if op["row"] and op["pass"] == "second"}
    for seq, row in window["rows"].items():
        if row["type"] not in DENSE or "mkn" not in row:
            continue
        m, k, n = row["mkn"]
        fam = of_row[seq] = out.setdefault(family(row), {
            "ops": 0, "flops": dict.fromkeys(PASSES, 0),
            "seconds": dict.fromkeys(PASSES, 0.0)})
        fam["ops"] += 1
        fam["flops"]["fwd"] += 2 * m * k * n
        fam["flops"]["second"] += 2 * m * k * n * (seq in again)
        fam["flops"]["bwd"] += 2 * m * k * n * len(row["grads"])
    for op in window["ops"]:
        fam = op["row"] and of_row.get(op["row"]["seq"])
        if fam:
            fam["seconds"][op["pass"]] += op["dur"]
    return out


def roof_pct(run, passes):
    """Share of the bf16 peak the dense products reach in ``passes``:
    the FLOPs of those of them that are useful (a second forward is
    executed and credited nothing) x the traced steps, over the peak,
    over the device time of the ops scoped to the products' rows in
    ``passes``. ``(value, families, window)``; the value None where
    there is no table or no such time."""
    window = of(run)
    if window is None:
        return None, {}, None
    fams = dense(window)
    seconds = sum(f["seconds"][p] for f in fams.values() for p in passes)
    if not seconds:
        return None, fams, window
    flops = window["steps"] * sum(
        f["flops"][p] for f in fams.values() for p in passes
        if p != "second")
    return (100.0 * flops / run["peaks"]["flops_bf16"] / seconds, fams,
            window)
