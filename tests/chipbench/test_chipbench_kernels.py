"""``chipbench/kernels.py`` and the four readers of the kernel ledger
(ISSUE 66) over a SYNTHETIC run: a small train Program is run through
``Executor`` on the CPU, so that ``paddle_tpu.trace.kernels`` and
``trace.ops`` are the program's own, and the window's device ops are
written from the kernel ledger's names with made-up durations (no
traced CPU window, so none of ``test_chipbench_oplog.py``'s flake). The
program: ``[32, 16]`` rows through four products, ``x [16, 24]``,
``x [24, 32]`` (M = N, told by the weight's shape), ``x [32, 32]``
(M = K = N: ``either``) and ``x [32, 8]``, a ReLU between each two, a
squared mean, Adam.

Also here: the four new entries of ``BENCHMARK.json``, read off the file
by name, and the assertions of the three tests that pin the exact set
of metrics a cell is listed on (``test_chipbench_ouro.py``'s,
``test_chipbench_granite_hybrid.py``'s and the latter's stand-in for
``test_chipbench_nemotron_h.py``'s, marked in ``tests/conftest.py``),
run against the benchmark less this PR's four entries."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells, kernels, oplog, spans            # noqa: E402

NEW = ("wgrad_matmul_roof_pct", "dgrad_matmul_roof_pct",
       "mixed_kernel_dev_share_pct", "compiled_step_hbm_pct")
STEPS, PEAK, ROWS = 3, 1e9, 32
WIDTHS = (("a", 24), ("b", 32), ("c", 32), ("d", 8))


def _ledgers():
    """Run the program's train step once; ``(kernel ledger, op
    ledger)`` of its build."""
    import paddle_tpu as fluid
    from paddle_tpu import trace
    from paddle_tpu.core import unique_name
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()), unique_name.guard("ck_"):
        h = fluid.layers.data("x", [16])
        for name, width in WIDTHS:
            h = fluid.layers.fc(
                h, width, bias_attr=False,
                act=None if name == "d" else "relu",
                param_attr=fluid.ParamAttr(name="ck_%s" % name))
        loss = fluid.layers.mean(fluid.layers.square(h))
        fluid.optimizer.Adam(1e-3).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((ROWS, 16), np.float32)},
                fetch_list=[loss])
    return (trace.kernels("exe.step", True), trace.ops("exe.step", True))


def _run_of(rows, extra=()):
    """A traced run's dict as the readers see it: every kernel row a
    device op of ``step`` in each of STEPS runs, the i-th lasting
    (i + 1) ms; ``extra`` device ops by (name, seconds)."""
    ops, t = [], 0.0
    for _ in range(STEPS):
        for i, r in enumerate(rows):
            dur = 1e-3 * (i + 1)
            ops.append(spans.device_op("%%%s = f32[] %s()" % (
                r["name"], r["opcode"]), t, dur, r["op_name"]))
            t += dur
        for name, dur in extra:
            ops.append(spans.device_op(name, t, dur, "jit(step)/x"))
            t += dur
    for op in ops:
        op["program"] = "step"      # (an op with no metadata has none)
    window = {"host": [], "ops": ops, "compiles": None, "modules": [
        {"program": "step", "start": i * t / STEPS, "dur": t / STEPS}
        for i in range(STEPS)]}
    return {"trace": {"busy_s": t}, "spans": window,
            "peaks": {"flops_bf16": PEAK}}


@pytest.fixture(scope="module")
def built():
    """(kernel rows, {weight: op row}, a run whose two windows are
    read)."""
    (_, rows), (_, op_rows) = _ledgers()
    run = _run_of(rows)
    assert kernels.of(run) is not None
    return rows, {r["weights"][0]: r for r in op_rows
                  if r["type"] == "mul"}, run


def _dur(rows, name):
    return STEPS * 1e-3 * (1 + [r["name"] for r in rows].index(name))


def _holds(rows, seq, contracted):
    """The names of the kernels that hold the backward product of
    ``mul.<seq>`` that sums over ``contracted``."""
    return [r["name"] for r in rows for d in r["dots"]
            if spans.parse_op_name(d[0])[1] == "mul.%d" % seq
            and oplog.pass_of(d[0]) == "bwd" and d[4] == contracted]


def test_the_join_gives_every_device_op_its_row_and_its_op_rows(built):
    rows, products, run = built
    window = kernels.of(run)
    assert window["unjoined"] == 0 and window["lost"] == 0.0
    assert set(window["kernels"]) == {r["name"] for r in rows}
    assert window["steps"] == STEPS
    assert window["total"] == pytest.approx(run["trace"]["busy_s"])
    for name, k in window["kernels"].items():
        assert k["runs"] == STEPS
        assert k["dur"] == pytest.approx(_dur(rows, name))
        assert set(k["ops"]) == {s for s in k["row"]["scopes"] if s}
        for scope, row in k["ops"].items():
            assert "%s.%d" % (row["type"], row["seq"]) == scope
    assert kernels.of(run) is window            # read once, kept on run


def test_the_weight_and_the_operand_gradients_are_read_apart(built,
                                                             capsys):
    """``a`` takes its weight's gradient alone (its operand is the
    feed); ``b``'s two are told at M = N by the weight's shape; ``c``'s
    (M = K = N) are in neither; ``d``'s by what they sum over. Each
    metric is exact FLOPs over its kernels' whole time."""
    rows, products, run = built
    by_kind, wrong = kernels.grads(run)
    assert not wrong
    names = lambda kind: sorted(h["name"] for h in by_kind[kind])
    a, b, c, d = (products["ck_%s" % n] for n in "abcd")
    assert [r["mkn"] for r in (a, b, c, d)] == [
        (32, 16, 24), (32, 24, 32), (32, 32, 32), (32, 32, 8)]
    # b: told by the shapes where the sizes cannot
    (b_w,) = [r["name"] for r in rows for x in r["dots"]
              if spans.parse_op_name(x[0])[1] == "mul.%d" % b["seq"]
              and oplog.pass_of(x[0]) == "bwd" and x[3] == (24, 32)]
    (b_x,) = [r["name"] for r in rows for x in r["dots"]
              if spans.parse_op_name(x[0])[1] == "mul.%d" % b["seq"]
              and oplog.pass_of(x[0]) == "bwd" and x[3] == (32, 24)]
    want_w = sorted(_holds(rows, a["seq"], 32) + [b_w]
                    + _holds(rows, d["seq"], 32))
    want_x = sorted([b_x] + _holds(rows, d["seq"], 8))
    assert names("w") == want_w and len(want_w) == 3
    assert names("x") == want_x and len(want_x) == 2
    assert names("either") == sorted(_holds(rows, c["seq"], 32)) \
        and len(names("either")) == 2
    flops = lambda *ps: STEPS * sum(2 * p["mkn"][0] * p["mkn"][1]
                                    * p["mkn"][2] for p in ps)
    seconds = lambda held: sum(_dur(rows, n) for n in held)
    wgrad = cells.load_metric("wgrad_matmul_roof_pct").read(run)
    dgrad = cells.load_metric("dgrad_matmul_roof_pct").read(run)
    assert wgrad == pytest.approx(
        100.0 * flops(a, b, d) / PEAK / seconds(want_w))
    assert dgrad == pytest.approx(
        100.0 * flops(b, d) / PEAK / seconds(want_x))
    said = capsys.readouterr().out
    assert "wgrad_matmul_roof_pct: ck_a 16 x 24, M 32, 1 kernel(s)" in said
    assert "dgrad_matmul_roof_pct: ck_b 24 x 32, M 32, 1 kernel(s)" in said
    assert "ck_c" not in said
    # the books: the two ledgers count the same backward
    assert "(+0.000%); 0 product(s) left out" in said
    assert "either %.3f in %.6f (2 kernels)" % (
        1e-12 * flops(c, c), seconds(names("either"))) in said


@pytest.mark.parametrize("op_name", [
    "jit(step)/transpose(jvp(mul.226))/dot_general",
    "jit(step)/jvp(silu.2)/jit(silu)/mul",
    "jit(step)/adam.463/mul",
    "jit(step)/transpose(jvp(jvp()))/checkpoint/mul.22/dot_general",
    "jit(step)/transpose(jvp(mul.7))/rematted_computation/dot_general",
    "jit(step)/jvp(while.2)/body/mul.7/dot_general",
    "jit(decode)/kv.read/gather",
    "jit(step)/convert_element_type",
])
def test_the_program_tells_a_scope_and_a_pass_as_the_benchmark_does(op_name):
    """``trace/hlo.py`` writes the two rules down once more (the program
    imports nothing of the benchmark): a kernel row's ``root_scope`` has
    to be the scope ``spans.parse_op_name`` books the device op to, and
    its ``passes`` the passes ``oplog.pass_of`` tells."""
    from paddle_tpu.trace import hlo
    assert hlo.scope_of(op_name) == spans.parse_op_name(op_name)[1]
    assert hlo.pass_of(op_name) == oplog.pass_of(op_name)
    assert hlo.SERVING_SCOPES == spans.SERVING_SCOPES


@pytest.mark.parametrize("dot, mkn, want", [
    # M != N: by what the product sums over
    (("n", (64, 16), (64, 8), (16, 8), 64, 2 * 64 * 16 * 8),
     (64, 16, 8), "w"),
    (("n", (64, 8), (16, 8), (64, 16), 8, 2 * 64 * 16 * 8),
     (64, 16, 8), "x"),
    # M = N: the operand gradient reads the weight, the weight gradient
    # writes its shape; a trailing 1 (the TPU's window) does not count
    (("n", (32, 32), (24, 32), (32, 24), 32, 2 * 32 * 24 * 32),
     (32, 24, 32), "x"),
    (("n", (32, 24), (32, 32), (24, 32, 1), 32, 2 * 32 * 24 * 32),
     (32, 24, 32), "w"),
    (("n", (32, 32), (32, 32), (32, 32), 32, 2 * 32 ** 3),
     (32, 32, 32), "either"),
    # a head in row blocks: M / 4 rows a product
    (("n", (16, 8), (16, 24), (8, 24), 16, 2 * 16 * 8 * 24),
     (64, 8, 24), "w"),
    (("n", (16, 24), (8, 24), (16, 8), 24, 2 * 16 * 8 * 24),
     (64, 8, 24), "x"),
    # FLOPs that are not the row's: left out
    (("n", (64, 8), (16, 8), (64, 16), 8, 2 * 64 * 16 * 8 + 2),
     (64, 16, 8), None),
    (("n", (64, 16), (16, 8), (64, 8), 16, 2 * 64 * 16 * 8),
     (64, 16, 8), None),                     # sums over K: a forward
])
def test_a_gradients_kind(dot, mkn, want):
    row = {"mkn": mkn, "weights": ("w",), "inputs": {
        "X": (("x", (mkn[0], mkn[1]), "float32"),),
        "Y": (("w", (mkn[1], mkn[2]), "float32"),)}}
    assert kernels.grad_kind(dot, row) == want


def test_a_product_whose_flops_are_not_its_rows_is_said_and_left_out(
        built, capsys, monkeypatch):
    rows, products, _ = built
    (name,) = _holds(rows, products["ck_d"]["seq"], 8)
    bent = [dict(r, dots=tuple(d[:5] + (d[5] + 2,) for d in r["dots"]))
            if r["name"] == name else r for r in rows]
    header = kernels.table()[0]
    monkeypatch.setattr(kernels, "ledger",
                        lambda: lambda root, backward: (header, bent))
    run = _run_of(rows)
    by_kind, wrong = kernels.grads(run)
    assert [w[0] for w in wrong] == [name]
    assert name not in {h["name"] for held in by_kind.values()
                        for h in held}
    assert "%s holds" % name in capsys.readouterr().out
    cells.load_metric("dgrad_matmul_roof_pct").read(run)
    assert "1 product(s) left out" in capsys.readouterr().out


def test_a_gradient_that_is_in_the_step_twice_is_said(built, capsys,
                                                      monkeypatch):
    """XLA may run a product in each of two kernels that read it
    (``ouro_train_T8k``: ``ffn_down``'s operand gradient in eight
    layers): the FLOPs are what is executed, the books show the surplus
    over the op ledger's and name the row."""
    rows, products, _ = built
    d = products["ck_d"]
    (name,) = _holds(rows, d["seq"], 8)
    (row,) = [r for r in rows if r["name"] == name]
    header = kernels.table()[0]
    more = rows + [dict(row, name=name + ".again")]
    monkeypatch.setattr(kernels, "ledger",
                        lambda: lambda root, backward: (header, more))
    run = _run_of(more)
    by_kind, wrong = kernels.grads(run)
    assert not wrong and len(by_kind["x"]) == 3
    cells.load_metric("dgrad_matmul_roof_pct").read(run)
    said = capsys.readouterr().out
    assert "1 gradient(s) are in the step more than once: row %d" \
        % d["seq"] in said
    assert "(+0.000%)" not in said


def test_an_op_that_finds_no_row_is_counted_and_said(built, capsys):
    rows, _, _ = built
    run = _run_of(rows, extra=[("%fusion.999 = f32[] fusion()", 0.25)])
    window = kernels.of(run)
    assert window["unjoined"] == 1
    assert window["lost"] == pytest.approx(0.25 * STEPS)
    said = capsys.readouterr().out
    assert "1 device ops of step find no row and hold 0.750000 s" in said
    assert "fusion.999 0.750000 s" in said
    share = cells.load_metric("mixed_kernel_dev_share_pct").read(run)
    assert share is not None
    assert "1 device op(s) found no kernel row (0.750000 s)" \
        in capsys.readouterr().out


def test_the_mixed_kernels_share_and_its_pairs(built, capsys):
    """The time of the kernels that name two or more Program ops, over
    busy time; a pair for each type of rider beside the root's."""
    rows, _, run = built
    mixed = [r for r in rows if len([s for s in r["scopes"] if s]) > 1]
    assert mixed                       # (XLA's CPU fusions mix some too)
    share = cells.load_metric("mixed_kernel_dev_share_pct").read(run)
    assert share == pytest.approx(
        100.0 * sum(_dur(rows, r["name"]) for r in mixed)
        / run["trace"]["busy_s"])
    said = capsys.readouterr().out
    r = mixed[0]
    root = spans.scope_type(r["root_scope"]) or "(no scope)"
    rider = sorted({spans.scope_type(s) for s in r["scopes"]
                    if s and s != r["root_scope"]})[0]
    assert "mixed_kernel_dev_share_pct: %s <- %s " % (root, rider) in said
    assert "sit in %d kernels of two or more Program ops" % len(mixed) \
        in said
    assert "GB/s by declared bytes" in said


def test_the_compiled_steps_share_of_the_devices_memory(built, capsys,
                                                        monkeypatch):
    """The CPU states no limit: None. With one, arguments + outputs -
    aliased + temporaries over it."""
    reader = cells.load_metric("compiled_step_hbm_pct")
    header, rows = kernels.table()
    assert header["memory"]["bytes_limit"] is None
    assert reader.read({}) is None
    memory = dict(header["memory"], argument=6 * 2 ** 30,
                  output=5 * 2 ** 30, alias=4 * 2 ** 30, temp=2 ** 30,
                  bytes_limit=16 * 2 ** 30)
    monkeypatch.setattr(kernels, "ledger", lambda: lambda root, backward: (
        dict(header, memory=memory), rows))
    assert reader.read({}) == pytest.approx(50.0)
    assert "= %d bytes of the device's %d" % (
        8 * 2 ** 30, 16 * 2 ** 30) in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_tree_without_the_ledger_reads_none(built, name, monkeypatch):
    """The parent of this PR has no ``trace.kernels``: every reader
    returns None and its metric is left out of the line."""
    from paddle_tpu import trace
    rows, _, _ = built
    monkeypatch.delattr(trace, "kernels")
    assert kernels.ledger() is None and kernels.table() is None
    run = _run_of(rows)
    assert cells.load_metric(name).read(run) is None
    assert kernels.of(run) is None


@pytest.mark.parametrize("name", NEW)
def test_the_entry_in_benchmark_json(name):
    """Found by name, listing all twelve cells (read off the file: no
    pin on the entry's position, none on what else a cell is listed
    on), and as its reader states it."""
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    for cell in bench["workloads"]:
        assert cell["name"] in entry["workloads"]
    assert len(bench["workloads"]) >= 12
    reader = cells.load_metric(name)
    assert (entry["unit"], entry["source"], entry["layer"],
            entry["moves"]) == (reader.UNIT, reader.SOURCE, reader.LAYER,
                                reader.MOVES)
    assert entry["moves"] == "tokens_per_s" and entry["unit"] == "%"
    assert entry["better"] == ("higher" if "roof" in name else "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("theirs, pin", [
    ("test_chipbench_ouro", "test_the_entries_in_benchmark_json"),
    ("test_chipbench_granite_hybrid", "test_the_entries_in_benchmark_json"),
    ("test_chipbench_granite_hybrid",
     "test_pr_62s_pin_of_the_scan_readers_lists_as_pr_62_left_them"),
])
def test_a_cells_pinned_lists_are_as_its_pr_left_them(theirs, pin,
                                                      monkeypatch):
    """``test_the_entries_in_benchmark_json`` of the two files holds the
    set of metrics its cell is listed on to ``LISTS | NEW`` exactly, and
    Granite's stand-in for Nemotron's (PR 62's pin, run against the
    lists less the later CELLS) holds Nemotron's cell to the same; this
    PR lists every cell on four metrics more (ISSUE 66 asks for that:
    Granite is the cell the kernel ledger is most for), so the three
    fail and are marked where the pins are (``tests/conftest.py``).
    Here their assertions run against the benchmark less this PR's
    four entries. What a stripped copy cannot see is asserted first:
    the cell IS on the four lists."""
    module = __import__(theirs)
    sound = cells.load_json
    bench = sound(os.path.join(ROOT, "BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in NEW:
        assert module.CELL in lists[name], name

    def less_these_four(path):
        bench = sound(path)
        if os.path.basename(path) != "BENCHMARK.json":
            return bench
        return {**bench, "per_layer": [m for m in bench["per_layer"]
                                       if m["name"] not in NEW]}
    monkeypatch.setattr(cells, "load_json", less_these_four)
    if pin.startswith("test_pr_62s"):
        getattr(module, pin)(monkeypatch)
    else:
        getattr(module, pin)()
