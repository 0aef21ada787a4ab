"""The four readers of the program's op ledger
(``dense_matmul_roof_pct`` with its ``_fwd_`` and ``_bwd_`` parts,
``second_forward_dev_share_pct``; ``chipbench/oplog.py``): on a window
made by hand, whose device ops carry the ``op_name``s of the three
passes as ``PERF.md`` section 3 records them and whose table has four
rows, by hand arithmetic; None, with the reason said, where there is no
window, no ledger, no table or another build's; and through ``run.py``
in a traced CPU rehearsal of ``opt350m_train`` and of a cell that trains
under ``layers.recompute``, the step's own table under planted device
ops."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells, oplog, spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ("dense_matmul_roof_pct", "dense_matmul_fwd_roof_pct",
         "dense_matmul_bwd_roof_pct", "second_forward_dev_share_pct")
REGIONED = ["xing4_train_T4k", "trinity_train_T16k", "phi4flash_train_T8k",
            "smallthinker_train_T16k", "lfm2_train_T32k"]
PEAK, STEPS, BUSY = 100e12, 2, 0.5
UP, HEAD = (4096, 1024, 4096), (4096, 1024, 32000)
F_UP, F_HEAD = (2 * 4096 * 1024 * 4096, 2 * 4096 * 1024 * 32000)

# (op_name, seconds in each traced step): the names are PERF.md section
# 3's, a region's ops bare under ``checkpoint``
OPS = [
    ("jit(step)/jvp()/checkpoint/mla_attention.9/jit(_fwd_pallas)/"
     "flash_fwd/pallas_call:", 0.010),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
     "mla_attention.9/mul:", 0.003),
    # layer 3's up: not kept, so its backward makes it again
    ("jit(step)/jvp()/checkpoint/mul.10/dot_general:", 0.0010),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/mul.10/"
     "dot_general:", 0.0011),
    ("jit(step)/transpose(jvp())/checkpoint/mul.10/dot_general:", 0.0024),
    # layer 7's: kept, no second forward; its backward a fusion whose
    # first name has no scope, so the second decides scope and pass
    ("jit(step)/jvp()/checkpoint/mul.12/dot_general:", 0.0010),
    ("jit(step)/transpose(jvp())/checkpoint/add_any:;"
     "jit(step)/transpose(jvp())/checkpoint/mul.12/dot_general:", 0.0026),
    # the head: only x's gradient is taken
    ("jit(step)/jvp(mul.226)/dot_general:", 0.008),
    ("jit(step)/transpose(jvp(mul.226))/dot_general:", 0.009),
    # an optimizer op the table has no row for, an op with no scope
    ("jit(step)/adam.300/mul:", 0.001),
    ("jit(step)/while/body/ragged-dot:", 0.004),
]


def _rows():
    mul = lambda seq, weight, mkn, grads, region, kept: {
        "seq": seq, "type": "mul", "weights": (weight,), "mkn": mkn,
        "grads": grads, "region": region, "kept": kept,
        "operand_dtype": "bfloat16", "inputs": {}, "outputs": {}}
    return [
        {"seq": 9, "type": "mla_attention", "weights": (), "region": 0,
         "kept": None, "inputs": {}, "outputs": {}},
        mul(10, "layer_3_ffn_up", UP, ("x", "w"), 0, None),
        mul(12, "layer_7_ffn_up", UP, ("x", "w"), 1, "mul_out"),
        mul(226, "word_emb", HEAD, ("x",), None, None)]


def _run(ops=OPS, traced=True):
    window, t = {"host": [], "compiles": None, "ops": [], "modules": []}, 0.0
    for _ in range(STEPS):
        t0 = t
        for i, (name, dur) in enumerate(ops):
            window["ops"].append(spans.device_op("fusion.%d" % i, t, dur,
                                                 name))
            t += dur
        window["modules"].append({"program": "step", "start": t0,
                                  "dur": t - t0})
    # a short program beside the step, with a scope of its own
    window["ops"].append(spans.device_op(
        "fusion.99", t, 0.001, "jit(forward)/mul.10/dot_general:"))
    window["modules"].append({"program": "forward", "start": t,
                              "dur": 0.001})
    return {"trace": {"busy_s": BUSY} if traced else None,
            "spans": window if traced else None,
            "peaks": {"flops_bf16": PEAK}}


@pytest.fixture
def ledger(monkeypatch):
    """Plants the table ``oplog.ledger()`` will give, and says what was
    asked for."""
    asked = []

    def plant(rows, step=3):
        def read(root=None, backward=None):
            asked.append((root, backward))
            if rows is None:
                return None
            return ({"root": root, "backward": backward, "step": step,
                     "t_build": 1.0, "count": len(rows)},
                    [dict(r) for r in rows])
        monkeypatch.setattr(oplog, "ledger", lambda: read)
        return asked
    return plant


def _read(name, run):
    return cells.load_metric(name).read(run)


def test_the_four_by_hand(ledger, capsys):
    asked = ledger(_rows())
    run = _run()
    pct = lambda flops, seconds: 100.0 * flops / PEAK / seconds
    # forward: 2 MKN of the three products over their forward seconds
    assert _read("dense_matmul_fwd_roof_pct", run) == pytest.approx(
        pct(2 * F_UP + F_HEAD, 0.0010 + 0.0010 + 0.008))
    # backward: two gradients of each up, one of the head
    assert _read("dense_matmul_bwd_roof_pct", run) == pytest.approx(
        pct(4 * F_UP + F_HEAD, 0.0024 + 0.0026 + 0.009))
    # all three passes in the time, the second forward credited nothing
    assert _read("dense_matmul_roof_pct", run) == pytest.approx(
        pct(6 * F_UP + 2 * F_HEAD, 0.0010 + 0.0011 + 0.0024 + 0.0010
            + 0.0026 + 0.008 + 0.009))
    # the ops named rematted_computation/ over busy time
    assert _read("second_forward_dev_share_pct", run) == pytest.approx(
        100.0 * STEPS * (0.003 + 0.0011) / BUSY)
    assert set(asked) == {("exe.step", True)} and len(asked) == 1
    out = capsys.readouterr().out
    assert "the build of step 3, 4 rows; 22 device ops of step in 2 " \
        "traced steps, 2 of them carry a scope with no row" in out


def test_the_log_lines_name_families_and_what_the_plan_kept(ledger, capsys):
    ledger(_rows())
    run = _run()
    _read("dense_matmul_roof_pct", run)
    _read("second_forward_dev_share_pct", run)
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[chipbench] dense_matmul_roof_pct")
             or l.startswith("[chipbench] second_forward")]
    # the head is furthest from the peak in ms a step: it comes first;
    # the second forward's types by their time
    head, up, books, total, mla, mul = lines
    share = lambda flops, ms: 100.0 * flops / PEAK / (1e-3 * ms)
    assert head.startswith(
        "[chipbench] dense_matmul_roof_pct: word_emb 1024 x 32000, M "
        "4096, 1 op(s): forward 8.000 ms a step at %.1f%% of the peak, "
        "second forward 0.000 at 0.0%%, backward 9.000 at %.1f%%"
        % (share(F_HEAD, 8.0), share(F_HEAD, 9.0)))
    # two layers' weights fold into one family; one of its two ops ran
    # a second forward, credited here what it executed
    assert up.startswith(
        "[chipbench] dense_matmul_roof_pct: layer_#_ffn_up 1024 x 4096, "
        "M 4096, 2 op(s): forward 2.000 ms a step at %.1f%% of the "
        "peak, second forward 1.100 at %.1f%%, backward 5.000 at %.1f%%"
        % (share(2 * F_UP, 2.0), share(F_UP, 1.1), share(4 * F_UP, 5.0)))
    assert "2 families (0 not shown) hold 0.050200 s and the ops " \
        "scoped mul / matmul 0.050200 s in 2 steps" in books
    assert "2 device ops of the step carry a scope with no row" in books
    assert "4.100 ms a step in 2 regions of 3 ops" in total
    assert mul.endswith("mul 1.100 ms a step, 1 of 2 kept")
    assert mla.endswith("mla_attention 3.000 ms a step, none kept")


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(gated_short_conv.4)/mul:", "fwd"),
    ("jit(step)/jvp()/checkpoint/mla_attention.9/mul:", "fwd"),
    ("jit(step)/transpose(jvp(mul.226))/dot_general:", "bwd"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/causal_attention.10/"
     "window/jit(_bwd_pallas)/flash_bwd_dq/pallas_call:", "bwd"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "causal_attention.10/window/concatenate:", "second"),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
     "gated_short_conv.4/add:", "second"),
    ("jit(step)/adam.7/mul:", "fwd"),
    # of joined names the one that gives the scope decides
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/add:;"
     "jit(step)/transpose(jvp())/checkpoint/mul.12/dot_general:", "bwd"),
    ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
     "while/body/ragged-dot:", "second"),
    (None, "fwd")],
    ids=["forward", "forward_in_a_region", "backward",
         "backward_in_a_region", "second_forward_of_a_kernel_scope",
         "second_forward", "optimizer", "joined_names", "no_scope",
         "no_name"])
def test_the_pass_is_told_from_the_op_name(op_name, want):
    assert oplog.pass_of(op_name) == want


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("why", ["not traced", "no ledger", "no table",
                                 "another build's table",
                                 "no program's runs"])
def test_reader_returns_none_and_says_why(ledger, monkeypatch, capsys,
                                          name, why):
    ledger(_rows())
    run, said = _run(), None
    if why == "not traced":
        run = _run(traced=False)
    elif why == "no ledger":
        monkeypatch.setattr(oplog, "ledger", lambda: None)
        said = "the program keeps none"
    elif why == "no table":
        ledger(None)
        said = "no build of exe.step with a backward_marker"
    elif why == "another build's table":
        rows = _rows()
        rows[1]["type"] = "scale"
        ledger(rows)
        said = "scoped mul.10 and row 10 of the table is a scale"
    else:
        run["spans"]["modules"] = []
        said = "the window holds no program's runs"
    assert _read(name, run) is None
    out = capsys.readouterr().out
    assert said is None or said in out


def test_a_table_with_no_region_has_no_second_forward(ledger):
    rows = [dict(r, region=None, kept=None) for r in _rows()]
    ledger(rows)
    run = _run()
    assert _read("second_forward_dev_share_pct", run) is None
    assert _read("dense_matmul_roof_pct", run) > 0


def test_the_real_ledger_absent_is_none(monkeypatch):
    """A tree with no table (the parent of the PR that added it): the
    guard is ``getattr``, as ``steps.ledger``'s is."""
    from paddle_tpu import trace
    assert oplog.ledger() is trace.ops
    monkeypatch.delattr(trace, "ops")
    assert oplog.ledger() is None
    assert oplog.of(_run()) is None


def test_the_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = bench["per_layer"][-4:]
    assert [m["name"] for m in entries] == list(NAMES)
    everywhere = [w["name"] for w in bench["workloads"]]
    for m in entries:
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            "%", "device_trace", "train executor", "tokens_per_s")
        mod = cells.load_metric(m["name"])
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"])
    assert [m["better"] for m in entries] == ["higher"] * 3 + ["lower"]
    assert [m["workloads"] for m in entries] == [everywhere] * 3 + [REGIONED]


# the traced rehearsal: run.py's own path with what a CPU cannot give
# taken out, as tests/chipbench/test_chipbench_steps.py does it (the
# reduction stubbed, the cell cut to the four metrics in a copy), and
# the device ops planted FROM THE STEP'S OWN TABLE: one a pass for every
# row, a product's at half the peak, so that the join runs over the real
# program's scopes and the readers over its real shapes
REHEARSAL = """
import runpy, sys
sys.path.insert(0, %(tmp)r)
from chipbench import cells, spans, tracing
tracing.reduce_rows = lambda rows, chips=1: {
    'window_s': 1.0, 'busy_s': 0.5, 'modules': {}, 'ops': {},
    'device_ops': [], 'idle_gaps': []}
PEAK, STEPS = 197e12, 2


def planted(trace_dir=None):
    from paddle_tpu import trace
    header, rows = trace.ops(root='exe.step', backward=True)
    ops, t = [], 0.0
    for step in range(STEPS):
        for r in rows:
            scope = '%%s.%%d' %% (r['type'], r['seq'])
            inside = r['region'] is not None
            names = {'fwd': 'jit(step)/jvp()/checkpoint/%%s/x:' if inside
                     else 'jit(step)/jvp(%%s)/x:',
                     'bwd': 'jit(step)/transpose(jvp())/checkpoint/%%s/x:'
                     if inside else 'jit(step)/transpose(jvp(%%s))/x:'}
            if inside and not r['kept']:
                names['second'] = ('jit(step)/transpose(jvp())/checkpoint/'
                                   'rematted_computation/%%s/x:')
            for which, name in names.items():
                dur = 1e-6
                if 'mkn' in r:
                    m, k, n = r['mkn']
                    dur = 2 * m * k * n * (len(r['grads']) if which == 'bwd'
                                           else 1) / (0.5 * PEAK)
                ops.append(spans.device_op('fusion.%%d' %% len(ops), t,
                                           dur, name %% scope))
                t += dur
    return {'host': [], 'ops': ops, 'compiles': None, 'modules': [
        {'program': 'step', 'start': 0.0, 'dur': t / STEPS}] * STEPS}


spans.load = planted
read = cells.read_metrics


def with_peaks(cell, group, run, here=cells.HERE):
    run['peaks'] = {'flops_bf16': PEAK}
    return read(cell, group, run, here)


cells.read_metrics = with_peaks
sys.argv = ['chipbench/run.py', '--workload', %(cell)r, '--seed',
            '3000000019', '--seconds', '2', '--trace', '1', '--rehearse']
runpy.run_path('chipbench/run.py', run_name='__main__')
"""


@pytest.mark.parametrize("cell", ["opt350m_train", "lfm2_train_T32k"])
def test_traced_rehearsal_prints_the_lines(tmp_path, cell):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] in NAMES]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "-c", REHEARSAL % {"tmp": str(tmp_path),
                                            "cell": cell}],
        cwd=str(tmp_path), env=env, text=True, capture_output=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu"
    regioned = cell in REGIONED
    assert set(last["metrics"]) == set(NAMES if regioned else NAMES[:3])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # every product was planted at half the peak in each pass
    assert m["dense_matmul_fwd_roof_pct"] == pytest.approx(50.0)
    assert m["dense_matmul_bwd_roof_pct"] == pytest.approx(50.0)
    assert "0 of them carry a scope with no row" in p.stdout
    assert " families (0 not shown) hold " in p.stdout
    if regioned:
        # a CPU states no memory limit: the plan keeps no product, every
        # one runs a second forward and is credited nothing for it
        assert 30.0 < m["dense_matmul_roof_pct"] < 50.0
        assert m["second_forward_dev_share_pct"] > 0
        assert "second_forward_dev_share_pct: mul " in p.stdout
        assert "_ffn_up " in p.stdout and "_word_emb " in p.stdout
    else:
        assert m["dense_matmul_roof_pct"] == pytest.approx(50.0)
        assert "second_forward_dev_share_pct:" not in p.stdout
        # OPT's model names no weight: the layers' own names, folded
        assert "dense_matmul_roof_pct: fc_#.w_# " in p.stdout
