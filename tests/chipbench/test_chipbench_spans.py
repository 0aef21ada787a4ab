"""chipbench/spans.py and the readers built on it: the parsing of
``op_name`` scopes, self time, the wire-format decoder against a real
profile, the compile log's seconds, and each reader's value on a small
recorded window (``recorded_spans.json``: two traced steps of the train
cell on a TPU v5e, PR 24) against numbers worked out by hand from the
fixture's rows."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, peaks, spans, tracing  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


# -- names ------------------------------------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(mul.226)/dot_general:", ("step", "mul.226", "fwd")),
    ("jit(step)/transpose(jvp(layer_norm.47))/reduce_sum:",
     ("step", "layer_norm.47", "bwd")),
    ("jit(step)/adam.6/mul", ("step", "adam.6", None)),
    ("jit(step)/jvp(softmax_with_cross_entropy.535)/jit(log_softmax)/sub:",
     ("step", "softmax_with_cross_entropy.535", "fwd")),
    ("jit(step)/jvp(sp_attention.145)/flash_fwd/pallas_call:",
     ("step", "sp_attention.145", "fwd")),
    # a fusion that merged two ops: the first name with a scope decides
    ("jit(step)/jvp(reshape.361)/reshape;jit(step)/jvp(mul.357)/reshape:",
     ("step", "reshape.361", "fwd")),
    ("jit(step)/convert_element_type;jit(step)/transpose(jvp(mul.5))/dot",
     ("step", "mul.5", "bwd")),
    # the serving programs' scopes, also under control flow
    ("jit(_step_impl)/kv.read/gather", ("_step_impl", "kv.read", None)),
    ("jit(_megastep_impl)/while/body/attn/dot_general:",
     ("_megastep_impl", "attn", None)),
    ("jit(_prefill_impl)/kv.write/scatter", ("_prefill_impl", "kv.write",
                                             None)),
    ("jit(_step_impl)/sample/argmax", ("_step_impl", "sample", None)),
    # no scope: the RNG seed program, a bare primitive, nothing at all
    ("jit(_threefry_seed)/threefry2x32:", ("_threefry_seed", None, None)),
    ("jit(step)/mul", ("step", None, None)),
    ("", (None, None, None)),
    (None, (None, None, None)),
])
def test_parse_op_name(op_name, want):
    assert spans.parse_op_name(op_name) == want


@pytest.mark.parametrize("scope, want", [
    ("mul.226", "mul"), ("softmax_with_cross_entropy.535",
                         "softmax_with_cross_entropy"),
    ("kv.read", "kv.read"), ("attn", "attn"), (None, None)])
def test_scope_type(scope, want):
    assert spans.scope_type(scope) == want


def test_device_op_row():
    op = spans.device_op(
        '%flash_fwd.28 = (bf16[64,2048,64]) custom-call(bf16[64,2048,64] '
        '%x), custom_call_target="tpu_custom_call"', 1.5, 0.25,
        "jit(step)/jvp(sp_attention.145)/flash_fwd/pallas_call:")
    assert (op["name"], op["kind"], op["kernel"]) == (
        "flash_fwd.28", "flash_fwd", True)
    assert (op["program"], op["scope"], op["direction"]) == (
        "step", "sp_attention.145", "fwd")
    plain = spans.device_op("%copy.7 = f32[2] copy(f32[2] %p)", 0, 1, None)
    assert (plain["kind"], plain["kernel"], plain["scope"]) == (
        "copy", False, None)


# -- self time --------------------------------------------------------------

def _span(name, start, dur, thread="main"):
    return {"name": name, "start": start, "dur": dur, "thread": thread,
            "args": {}}


def test_self_time_nested_and_overlapping_children():
    root = _span("exe.step", 10.0, 1.0)
    host = [root,
            _span("exe.feed", 10.0, 0.1),          # [10.0, 10.1]
            _span("exe.dispatch", 10.3, 0.4),      # [10.3, 10.7]
            _span("inner", 10.4, 0.1),             # inside dispatch
            _span("overlap", 10.6, 0.2),           # [10.6, 10.8]
            _span("exe.step", 11.5, 1.0),          # the next root
            _span("elsewhere", 10.2, 0.5, "loop")]  # another thread
    # children cover [10.0, 10.1] and [10.3, 10.8]: 0.6 of the 1.0
    assert spans.self_time(root, host) == pytest.approx(0.4)
    assert spans.self_time(host[2], host) == pytest.approx(0.3)
    assert spans.self_time(host[3], host) == pytest.approx(0.1)
    assert [c["name"] for c in spans.children(root, host)] == [
        "exe.feed", "exe.dispatch", "inner", "overlap"]
    assert spans.children(root, host, "exe.dispatch") == [host[2]]


def test_self_ms_is_the_median_over_roots_with_the_child():
    host = [_span("exe.step", 0.0, 0.010), _span("exe.dispatch", 0.001,
                                                 0.006),
            _span("exe.step", 1.0, 0.020), _span("exe.dispatch", 1.001,
                                                 0.008),
            _span("exe.step", 2.0, 0.030), _span("exe.dispatch", 2.001,
                                                 0.010),
            _span("exe.step", 3.0, 9.0), _span("exe.build", 3.1, 8.0)]
    run = {"trace": {}, "spans": {"host": host, "ops": [], "modules": [],
                                  "compiles": None}}
    assert spans.self_ms(run, "exe.step", ("exe.dispatch",)) == \
        pytest.approx(12.0)            # 4, 12, 20 ms; the built step out
    assert spans.self_ms(run, "engine.step", ("engine.dispatch",)) is None
    assert spans.self_ms({"setup_s": 1.0}, "exe.step", ()) is None


# -- the decoder, against a profile made here -------------------------------

def test_load_reads_annotations_like_profiledata(tmp_path):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.span("exe.step", step=41):
            with trace.phase("exe.dispatch", step=41):
                jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        with trace.phase("engine.prefill", step=2, rid="abc"):
            pass
    finally:
        jax.profiler.stop_trace()
    window = spans.load(str(tmp_path))
    assert window["ops"] == [] and window["modules"] == []   # no chip
    by_name = {s["name"]: s for s in window["host"]}
    assert set(by_name) == {"exe.step", "exe.dispatch", "engine.prefill"}
    assert by_name["exe.step"]["args"] == {"step": 41}
    assert by_name["engine.prefill"]["args"] == {"step": 2, "rid": "abc"}
    (child,) = spans.children(by_name["exe.step"], window["host"])
    assert child is by_name["exe.dispatch"]
    # the same events as jax's own reader gives, to the nanosecond
    rows = [r for r in tracing.load_rows(str(tmp_path))
            if r["name"] in by_name]
    assert len(rows) == 3
    for r in rows:
        assert by_name[r["name"]]["start"] == pytest.approx(
            r["start"], abs=2e-9)
        assert by_name[r["name"]]["dur"] == pytest.approx(r["dur"],
                                                          abs=2e-9)
    assert isinstance(window["compiles"], list)
    assert spans.load(str(tmp_path / "nothing_here")) is None


def test_fields_decodes_the_wire_format():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64, field 4
    # fixed32
    buf = memoryview(bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                            0x19, 1, 0, 0, 0, 0, 0, 0, 0,
                            0x25, 2, 0, 0, 0]))
    got = [(f, w, v if isinstance(v, int) else bytes(v))
           for f, w, v in spans._fields(buf)]
    assert got == [(1, 0, 300), (2, 2, b"ab"),
                   (3, 1, bytes([1, 0, 0, 0, 0, 0, 0, 0])),
                   (4, 5, bytes([2, 0, 0, 0]))]
    with pytest.raises(ValueError):
        list(spans._fields(memoryview(bytes([0x0B]))))   # group: refused


# -- the compile log --------------------------------------------------------

def _compile_run(log, setup_s=100.0):
    return {"trace": {}, "setup_s": setup_s,
            "spans": {"host": [], "ops": [], "modules": [],
                      "compiles": log}}


def _row(what, fun, end, seconds):
    return {"what": what, "fun_name": fun, "end": end, "seconds": seconds}


def test_compile_seconds_union_split_and_cut(monkeypatch):
    trace_, lower = ("jaxpr_trace_duration",
                     "jaxpr_to_mlir_module_duration")
    log = [_row(trace_, "inner", 1012.0, 1.0),     # [1011, 1012] nested
           _row(trace_, "step", 1015.0, 5.0),      # [1010, 1015]
           _row(lower, "jit(step)", 1018.0, 2.0),  # [1016, 1018]
           _row("backend_compile_duration", "jit(step)", 1030.0, 9.0),
           _row(trace_, "late", 1150.0, 0.5),      # inside the window
           _row("cache_hits", None, 1030.0, 0.0)]
    run = _compile_run(log)
    # no T_START in __main__: the whole log counts
    monkeypatch.delattr(sys.modules["__main__"], "T_START",
                        raising=False)
    total, by_fun, late = spans.compile_seconds(run, (trace_, lower))
    assert total == pytest.approx(7.5) and late == 0
    # with run.py's T_START the window starts at 1000 + 100
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 1000.0,
                        raising=False)
    total, by_fun, late = spans.compile_seconds(run, (trace_, lower))
    assert total == pytest.approx(7.0)      # nested trace counted once
    assert by_fun == {"inner": pytest.approx(1.0),
                      "step": pytest.approx(5.0),
                      "jit(step)": pytest.approx(2.0)}
    assert late == 1
    read = cells.load_metric("setup_trace_lower_s.train").read
    assert read(run) == pytest.approx(7.0)
    assert cells.load_metric("setup_compile_s.train").read(run) == \
        pytest.approx(9.0)
    # a program that keeps no log (the parent): the metric is left out
    assert read(_compile_run(None)) is None
    assert spans.compile_seconds({"setup_s": 1.0}, (trace_,)) is None


def test_setup_enter_reads_the_recorded_phases():
    """``recorded_run_phases.json``: a warm run of the train cell on a
    TPU v5e (PR 28). ``setup_enter_s`` is the first stamp, the driver's
    phases follow in order, and the last is ``setup_s``."""
    with open(os.path.join(HERE, "recorded_run_phases.json")) as f:
        run = json.load(f)
    assert "TPU v5e" in run["what"] and "PR 28" in run["what"]
    assert cells.load_metric("setup_enter_s").read(run) == 14.3
    at = list(run["setup_phases"].values())
    assert list(run["setup_phases"])[0] == "entered" and at == sorted(at)
    assert at[-1] == pytest.approx(run["setup_s"], abs=0.06)
    # most of a warm set-up is the driver's own work, not the start
    assert cells.load_metric("setup_s").read(run) > 2 * at[0]


# -- the readers on the recorded window -------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_spans.json")) as f:
        fx = json.load(f)
    ops = [spans.device_op(*row) for row in fx["ops"]]
    rows = [{"plane": DEV, "line": tracing.OP_LINE, "name": o["name"],
             "start": o["start"], "dur": o["dur"], "kernel": o["kernel"]}
            for o in ops]
    rows += [{"plane": DEV, "line": tracing.MODULE_LINE,
              "name": "jit_%s(1)" % m["program"], "start": m["start"],
              "dur": m["dur"]} for m in fx["modules"]]
    cell = cells.load_cell(ROOT, "opt350m_train")
    run = {"trace": tracing.reduce_rows(rows, 1),
           "spans": {"host": fx["host"], "ops": ops,
                     "modules": fx["modules"],
                     "compiles": fx["compiles"]},
           "config": cell["config_file"], "chips": 1,
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "setup_s": fx["setup_s"], "t_start": fx["t_start"],
           "train": {"batch": 4, "seq_len": 2048,
                     "tokens_per_step": 8192}}
    return fx, run


def _sum(fx, keep):
    """Seconds of the fixture's op rows [text, start, dur, op_name]
    that ``keep(text, op_name)`` accepts: the hand's way, on strings."""
    return sum(row[2] for row in fx["ops"] if keep(row[0], row[3] or ""))


def test_recorded_window_is_what_it_says(recorded):
    fx, run = recorded
    assert "TPU v5e" in fx["what"] and "PR 24" in fx["what"]
    window = run["spans"]
    assert spans.step_program(window) == ("step", 2)
    assert [h["name"] for h in fx["host"]].count("exe.step") == 2
    # the flash kernels are all there, and nothing else is a kernel
    kernels = {o["kind"] for o in window["ops"] if o["kernel"]}
    assert kernels == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert sum(o["kernel"] for o in window["ops"]) == 3 * 24 * 2
    # the kernels' three times are the time flash_roof_pct divides by
    _, seconds = spans.roof_pct(run, cells.load_metric(
        "flash_roof_pct").KERNELS, 1.0)
    assert set(seconds) == kernels
    assert sum(seconds.values()) == pytest.approx(_sum(
        fx, lambda text, op: "tpu_custom_call" in text), rel=1e-12)
    # and scoped plus unscoped is the sum of chip 0's op times
    scoped = sum(o["dur"] for o in window["ops"] if o["scope"])
    assert scoped + spans.device_time(window, scope=None) == \
        pytest.approx(spans.ops_total(window), rel=1e-12)


# name -> the value worked out by hand from recorded_spans.json's rows
# (the sums of durations are in test_readers_against_plain_sums, by
# plain string tests; the literals here were computed once from them):
HAND = {
    "flash_roof_pct": 17.926660511449594,    # PR 23's reader read this
    "flash_fwd_roof_pct": 19.24216802923744,
    "flash_bwd_roof_pct": 17.449480947624618,
    "matmul_roof_pct": 91.7969420232566,     # the fixture keeps only
    "optimizer_dev_share_pct": 0.9937549562063067,    # ops >= 100 us
    "unscoped_dev_share_pct": 3.6254277605662635,
    "xent_dev_share_pct": 2.4207862932261937,
    "exe_self_ms.train": 4.262624500000001,
    "setup_trace_lower_s.train": 13.985782000000007,
    "setup_compile_s.train": 133.386636,
}


@pytest.mark.parametrize("name", [
    "flash_fwd_roof_pct", "flash_bwd_roof_pct", "matmul_roof_pct",
    "xent_dev_share_pct", "optimizer_dev_share_pct",
    "unscoped_dev_share_pct", "exe_self_ms.train",
    "setup_trace_lower_s.train", "setup_compile_s.train",
    "flash_roof_pct"])
def test_reader_on_the_recorded_window(recorded, monkeypatch, name):
    fx, run = recorded
    monkeypatch.setattr(sys.modules["__main__"], "T_START",
                        fx["t_start"], raising=False)
    value = cells.load_metric(name).read(run)
    assert value == pytest.approx(HAND[name], rel=1e-6)


@pytest.mark.parametrize("change", ["as recorded", "another kernel",
                                    "fused backward"])
def test_flash_reader_finds_its_kernels_by_name(recorded, change):
    """``flash_roof_pct`` reads the number PR 23's reader read (every
    ``tpu_custom_call`` of the step's program) to 1e-12; one more
    Pallas kernel of another name in the step (a grouped matmul) is not
    flash time, which PR 23's reader would have counted; and the
    backward's rows renamed to one ``flash_bwd`` read the same."""
    fx, run = recorded
    rows = [list(row) for row in fx["ops"]]
    if change == "another kernel":
        text, start, dur, op_name = next(
            row for row in rows if row[0].startswith("%flash_fwd"))
        rows.append([text.replace("%flash_fwd.", "%grouped_matmul."),
                     start + dur, 10 * dur, op_name.replace(
                         "flash_fwd", "grouped_matmul")])
    if change == "fused backward":
        for row in rows:
            row[0] = re.sub(r"^%flash_bwd_(dq|dkv)\.", "%flash_bwd.",
                            row[0])
    ops = [spans.device_op(*row) for row in rows]
    assert sum(o["kernel"] for o in ops) == 3 * 24 * 2 + (
        change == "another kernel")
    changed = dict(run, spans=dict(run["spans"], ops=ops))
    assert cells.load_metric("flash_roof_pct").read(changed) == \
        pytest.approx(HAND["flash_roof_pct"], rel=1e-12)


def test_readers_against_plain_sums(recorded):
    """The same numbers from the fixture's rows by plain string tests
    and the formulas written out: 197 TFLOP/s; a step's flash kernels
    need 7 x 2048^2 x 1024 x 24 x 4 FLOPs, forward 2/7 of them; a
    token's matmuls 3 x (24 x (8 x 1024^2 + 4 x 1024 x 4096)
    + 2 x 1024 x 50272) FLOPs; two steps of 8192 tokens."""
    fx, run = recorded
    busy = run["trace"]["busy_s"]
    flash = 7 * 2048 ** 2 * 1024 * 24 * 4 * 2 / 197e12
    fwd = _sum(fx, lambda text, op: text.startswith("%flash_fwd"))
    bwd = _sum(fx, lambda text, op: text.startswith("%flash_bwd"))
    read = lambda name: cells.load_metric(name).read(run)
    assert read("flash_fwd_roof_pct") == pytest.approx(
        100 * flash * 2 / 7 / fwd)
    assert read("flash_bwd_roof_pct") == pytest.approx(
        100 * flash * 5 / 7 / bwd)
    matmul = 3 * (24 * (8 * 1024 ** 2 + 4 * 1024 * 4096)
                  + 2 * 1024 * 50272) * 8192 * 2 / 197e12
    mul = _sum(fx, lambda text, op: "(mul." in op.split(";")[0])
    assert read("matmul_roof_pct") == pytest.approx(100 * matmul / mul)
    adam = _sum(fx, lambda text, op: "/adam." in op.split(";")[0])
    assert read("optimizer_dev_share_pct") == pytest.approx(
        100 * adam / busy)
    # no "<op_type>.<seq>" path component in any of the op's names
    bare = _sum(fx, lambda text, op: not re.search(
        r"[/(][A-Za-z_]\w*\.\d+[/)]", op))
    assert read("unscoped_dev_share_pct") == pytest.approx(
        100 * bare / busy)
    # the loss: the fixture's softmax_with_cross_entropy is op 535 of
    # the differentiated forward, and the ops after it are the loss's
    def of_the_loss(text, op):
        m = re.search(r"jvp\([A-Za-z_]\w*\.(\d+)\)", op.split(";")[0])
        return bool(m) and int(m.group(1)) >= 535
    assert any("softmax_with_cross_entropy.535" in (row[3] or "")
               for row in fx["ops"])
    assert read("xent_dev_share_pct") == pytest.approx(
        100 * _sum(fx, of_the_loss) / busy)
    roots = [h for h in fx["host"] if h["name"] == "exe.step"]
    calls = [h for h in fx["host"] if h["name"] == "exe.dispatch"]
    own = sorted(1e3 * (r["dur"] - c["dur"]) for r, c in zip(roots, calls))
    assert read("exe_self_ms.train") == pytest.approx(sum(own) / 2)


@pytest.mark.parametrize("names", [
    {},                                          # today's two kernels
    {"flash_bwd_dq": "flash_bwd", "flash_bwd_dkv": "flash_bwd"},
    {"flash_bwd_dq": "flash_bwd"},               # one renamed, one not
], ids=["dq+dkv", "fused", "mixed"])
def test_flash_bwd_reader_survives_a_fused_backward(recorded, capsys,
                                                    names):
    """The backward's kernels under the names they have today, and the
    same rows renamed to one ``flash_bwd`` (a fused backward): the same
    seconds, so the same number, and the log line lists what it
    found."""
    fx, run = recorded
    ops = []
    for text, start, dur, op_name in fx["ops"]:
        for old, new in names.items():
            text = text.replace("%" + old + ".", "%" + new + ".")
        ops.append(spans.device_op(text, start, dur, op_name))
    renamed = dict(run, spans=dict(run["spans"], ops=ops))
    value = cells.load_metric("flash_bwd_roof_pct").read(renamed)
    assert value == pytest.approx(HAND["flash_bwd_roof_pct"], rel=1e-9)
    found = set(names.values()) | ({"flash_bwd_dq", "flash_bwd_dkv"}
                                   - set(names))
    line = [text for text in capsys.readouterr().out.splitlines()
            if "flash_bwd_roof_pct:" in text][-1]
    assert set(re.findall(r"(flash_bwd\w*) \d", line)) == found
    # the forward's reader sees none of it
    assert cells.load_metric("flash_fwd_roof_pct").read(renamed) == \
        pytest.approx(HAND["flash_fwd_roof_pct"], rel=1e-9)


def test_matmul_reader_divides_by_every_scope_of_the_architecture(
        recorded, monkeypatch):
    """An architecture whose matmuls are two Program op types (an
    expert matmul beside ``mul``; here ``adam`` stands in for it): the
    divisor is the device time of both."""
    fx, run = recorded
    first = lambda op: op.split(";")[0]
    mul = _sum(fx, lambda text, op: "(mul." in first(op))
    adam = _sum(fx, lambda text, op: "/adam." in first(op))
    assert adam > 0
    monkeypatch.setattr(arith, "matmul_scopes",
                        lambda cfg: ("mul", "adam"))
    assert cells.load_metric("matmul_roof_pct").read(run) == \
        pytest.approx(HAND["matmul_roof_pct"] * mul / (mul + adam))


def test_roof_pct_sums_what_it_finds_and_is_none_for_nothing(recorded):
    _, run = recorded
    both, seconds = spans.roof_pct(
        run, ("flash_bwd_dq", "flash_bwd_dkv", "flash_bwd"), 5.0 / 7.0)
    assert set(seconds) == {"flash_bwd_dq", "flash_bwd_dkv"}
    dq, s_dq = spans.roof_pct(run, ("flash_bwd_dq", "no_such"), 5.0 / 7.0)
    assert set(s_dq) == {"flash_bwd_dq"}
    assert dq == pytest.approx(both * sum(seconds.values())
                               / seconds["flash_bwd_dq"])
    assert spans.roof_pct(run, ("no_such",), 1.0) == (None, {})


@pytest.mark.parametrize("name", [
    "flash_fwd_roof_pct", "flash_bwd_roof_pct", "matmul_roof_pct",
    "xent_dev_share_pct", "optimizer_dev_share_pct",
    "unscoped_dev_share_pct", "exe_self_ms.train",
    "setup_trace_lower_s.train", "setup_compile_s.train",
    "pool_move_dev_share_pct", "engine_self_ms.serve", "flash_roof_pct",
    "setup_enter_s"])
def test_reader_leaves_its_metric_out_where_there_is_nothing(name):
    """A run that was not traced, and a traced run of a program that
    has no such span, scope, kernel name or log (the parent of PR 24):
    None, never an exception."""
    read = cells.load_metric(name).read
    assert read({"setup_s": 1.0, "train": {}}) is None
    bare = [spans.device_op("%fusion.1 = f32[] fusion()", 0.0, 1.0,
                            "jit(step)/dot_general"),
            spans.device_op('%jvp_sp_attention.35_.1 = custom-call(), '
                            'custom_call_target="tpu_custom_call"',
                            1.0, 1.0, None)]
    run = {"trace": {"busy_s": 2.0}, "setup_s": 1.0, "chips": 1,
           "config": {"arch": "opt"}, "peaks": {"flops_bf16": 197e12},
           "train": {"batch": 4, "seq_len": 2048,
                     "tokens_per_step": 8192},
           "spans": {"host": [], "compiles": None, "ops": bare,
                     "modules": [{"program": "step", "start": 0.0,
                                  "dur": 2.0}]}}
    if name == "unscoped_dev_share_pct":      # everything is unscoped
        assert read(run) == pytest.approx(100.0)
    else:
        assert read(run) is None


def test_serving_readers_on_the_recorded_serving_window():
    """``recorded_spans_serve.json``: iterations of the chat mix on the
    serving engine, TPU v5e, PR 24 (no cell runs it yet)."""
    with open(os.path.join(HERE, "recorded_spans_serve.json")) as f:
        fx = json.load(f)
    ops = [spans.device_op(*row) for row in fx["ops"]]
    busy = sum(e - s for s, e in tracing._union(
        (o["start"], o["start"] + o["dur"]) for o in ops))
    run = {"trace": {"busy_s": busy},
           "spans": {"host": fx["host"], "ops": ops,
                     "modules": fx["modules"], "compiles": None}}
    moved = sum(row[2] for row in fx["ops"]
                if "/kv.read/" in (row[3] or "").split(";")[0]
                or "/kv.write/" in (row[3] or "").split(";")[0])
    assert moved > 0
    assert cells.load_metric("pool_move_dev_share_pct").read(run) == \
        pytest.approx(100 * moved / busy)
    steps = [h for h in fx["host"] if h["name"] == "engine.step"]
    own = []
    for s in steps:
        kids = [h for h in fx["host"] if h["thread"] == s["thread"]
                and h["name"] in ("engine.dispatch", "engine.fetch")
                and s["start"] <= h["start"]
                and h["start"] + h["dur"] <= s["start"] + s["dur"]]
        if kids:
            own.append(1e3 * (s["dur"] - sum(k["dur"] for k in kids)))
    own.sort()
    assert len(own) >= 3
    median = own[len(own) // 2] if len(own) % 2 else \
        0.5 * (own[len(own) // 2 - 1] + own[len(own) // 2])
    assert cells.load_metric("engine_self_ms.serve").read(run) == \
        pytest.approx(median)
