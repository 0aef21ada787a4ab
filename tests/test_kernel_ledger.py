"""The kernel ledger (``paddle_tpu.trace.kernels``, ISSUE 66): the build
of a train step keeps the executable the executor compiled ahead of the
step's first call, and the first read parses its optimised HLO into one
row for every instruction that runs as a device op: the Program ops XLA
fused into it (``scopes``: keys of the same build's op rows), the
products in it with their FLOPs (``dots``) and its bytes.

The program of these tests: three products with a ReLU between each two
(``[64, 16] x [16, 32]``, ``x [32, 24]``, ``x [24, 8]``), a squared
mean, Adam.
``trace/hlo.py`` is held to short literal snippets of HLO text and to a
step compiled for a DESCRIBED v5e (``tests/tpu_compile_test.py``).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import trace
from paddle_tpu.core import unique_name
from paddle_tpu.trace import hlo
from paddle_tpu.trace import runtime as RT

from tpu_compile_test import chip, topo  # noqa: F401

_M = 64


def _program(prefix):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(x, 32, act="relu", bias_attr=False,
                            param_attr=fluid.ParamAttr(name=prefix + "up"))
        h = fluid.layers.fc(h, 24, act="relu", bias_attr=False,
                            param_attr=fluid.ParamAttr(name=prefix + "mid"))
        y = fluid.layers.fc(h, 8, bias_attr=False,
                            param_attr=fluid.ParamAttr(name=prefix + "down"))
        loss = fluid.layers.mean(fluid.layers.square(y))
        forward = main.clone(for_test=True)
        fluid.optimizer.Adam(1e-3).minimize(loss)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return main, forward, scope, {"x": np.ones((_M, 16), np.float32)}, loss


def _run(program, scope, feeds, loss, steps=1):
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        for _ in range(steps):
            exe.run(program, feed=feeds, fetch_list=[loss])
    return exe


@pytest.fixture(scope="module")
def ledgers():
    """((header, rows) of the kernel ledger, (header, rows) of the op
    ledger) of the program's train step, built by one run."""
    main, _, scope, feeds, loss = _program("kl_")
    _run(main, scope, feeds, loss)
    return (trace.kernels(root="exe.step", backward=True),
            trace.ops(root="exe.step", backward=True))


# -- (a) the table of a train step ------------------------------------------

def test_the_header_is_the_op_tables_own(ledgers):
    (header, rows), (op_header, _) = ledgers
    for key in ("root", "backward", "step"):
        assert header[key] == op_header[key]
    assert header["root"] == "exe.step" and header["backward"] is True
    assert header["count"] == len(rows) > 0
    assert header["module"] == "jit_step"
    assert header["text_bytes"] > 0 and header["parse_seconds"] > 0
    memory = header["memory"]
    assert set(memory) == {"argument", "output", "alias", "temp",
                           "bytes_limit"}
    # the CPU states no limit; the state is donated, so outputs alias it
    assert memory["bytes_limit"] is None
    assert memory["argument"] > 0 and 0 < memory["alias"] <= memory["output"]


def test_a_rows_fields_are_plain_values(ledgers):
    (_, rows), _ = ledgers
    plain = (int, float, str, type(None))

    def is_plain(v):
        if isinstance(v, tuple):
            return all(map(is_plain, v))
        return isinstance(v, plain)
    names = [r["name"] for r in rows]
    assert len(set(names)) == len(names)
    for r in rows:
        assert set(r) == {
            "name", "opcode", "fusion_kind", "computation",
            "custom_call_target", "operands", "results", "bytes_in",
            "bytes_out", "dots", "scopes", "nested", "root_scope",
            "op_name", "passes", "estimated_cycles"}
        assert all(is_plain(v) for k, v in r.items() if k != "scopes")
        assert all(isinstance(s, str) and isinstance(n, int)
                   for s, n in r["scopes"].items())
        assert set(r["passes"]) <= {"fwd", "second", "bwd"}
        assert not r["name"].startswith("%")
        assert r["opcode"] not in ("parameter", "tuple", "bitcast",
                                   "get-tuple-element", "constant")


def test_a_rows_scopes_are_keys_of_the_builds_op_rows(ledgers):
    (_, rows), (_, op_rows) = ledgers
    keys = {"%s.%d" % (r["type"], r["seq"]) for r in op_rows}
    named = {s for r in rows for s in r["scopes"] if s}
    assert named and named <= keys
    assert {r["root_scope"] for r in rows} - {None} <= keys
    # every product and every Adam update of the program is in some kernel
    assert {k for k in keys if k.startswith(("mul.", "adam."))} <= named


def test_every_product_is_found_forward_and_in_each_of_its_gradients(
        ledgers):
    """Every ``mul`` row finds one forward product among the ``dots``
    and one for each gradient its ``grads`` names, each at 2 MKN: the
    weight gradient sums over the M rows, the operand gradient over the
    N columns (the first product's operand is the feed: ``w`` alone)."""
    (_, rows), (_, op_rows) = ledgers
    dots = [d for r in rows for d in r["dots"]]
    muls = [r for r in op_rows if r["type"] == "mul"]
    assert [r["grads"] for r in muls] == [("w",), ("x", "w"), ("x", "w")]
    assert len(dots) == sum(1 + len(r["grads"]) for r in muls)
    for row in muls:
        m, k, n = row["mkn"]
        mine = [d for d in dots
                if hlo.scope_of(d[0]) == "mul.%d" % row["seq"]]
        assert all(d[5] == 2 * m * k * n for d in mine)
        forward = [d for d in mine if hlo.pass_of(d[0]) == "fwd"]
        backward = [d for d in mine if hlo.pass_of(d[0]) == "bwd"]
        assert [d[4] for d in forward] == [k]
        assert sorted(d[4] for d in backward) == sorted(
            {"w": m, "x": n}[g] for g in row["grads"])


def test_the_startup_programs_and_the_forwards_builds_have_no_table():
    main, forward, scope, feeds, loss = _program("kf_")
    assert RT._OP_BUILDS[-1]["backward"] is False       # the start-up's
    assert RT._OP_BUILDS[-1]["kernels"] is None
    assert trace.kernels(root="exe.step", backward=False) is None
    _run(forward, scope, feeds, loss)
    assert trace.ops(root="exe.step", backward=False)[0]["count"] > 0
    assert trace.kernels(root="exe.step", backward=False) is None


# -- (d) one trace, one lowering, one compile -------------------------------

def test_a_step_with_no_region_is_compiled_ahead_once(monkeypatch):
    """A train Program with NO recompute region is lowered and compiled
    ahead of its first call, once: the call then finds the executable
    (the compile log holds one trace, one lowering and one compile of
    ``step``), a second call compiles nothing, and nothing has asked
    the executable for its text."""
    from paddle_tpu.monitor import runtime
    real, calls = jax.stages.Lowered.compile, []
    monkeypatch.setattr(jax.stages.Lowered, "compile",
                        lambda self, *a, **kw: calls.append(self)
                        or real(self, *a, **kw))
    texts = []
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self, *a, **kw: texts.append(self) or "")
    main, _, scope, feeds, loss = _program("ko_")
    since = time.perf_counter()
    of_step = lambda: sorted(
        r["what"] for r in runtime.compile_log()
        if r["end"] >= since and r["fun_name"] in ("step", "jit(step)"))
    once = ["backend_compile_duration", "jaxpr_to_mlir_module_duration",
            "jaxpr_trace_duration"]
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(main, feed=feeds, fetch_list=[loss])
        assert of_step() == once and len(calls) == 1
        for _ in range(2):
            exe.run(main, feed=feeds, fetch_list=[loss])
    assert of_step() == once and len(calls) == 1
    assert not texts
    table = RT._OP_BUILDS[-1]["kernels"]
    assert table["rows"] is None and table["compiled"] is not None


# -- (e) read lazily, once, and evicted with the build ----------------------

def test_reading_twice_parses_once_and_lets_the_executable_go(monkeypatch):
    main, _, scope, feeds, loss = _program("kt_")
    _run(main, scope, feeds, loss)
    kept = RT._OP_BUILDS[-1]["kernels"]
    assert kept["rows"] is None and kept["compiled"] is not None
    parses = []
    real = hlo.kernel_rows
    monkeypatch.setattr(hlo, "kernel_rows",
                        lambda text: parses.append(len(text)) or real(text))
    first = trace.kernels("exe.step", True)
    again = trace.kernels("exe.step", True)
    assert len(parses) == 1 and parses[0] == first[0]["text_bytes"]
    assert kept["compiled"] is None and "text" not in kept
    assert first == again
    # the rows handed out are copies
    first[1][0]["scopes"]["mine"] = 1
    first[1][0]["name"] = "mine"
    assert trace.kernels("exe.step", True) == again


def test_a_ninth_build_evicts_the_firsts_table_with_its_op_rows():
    main, _, scope, feeds, loss = _program("k9_")
    _run(main, scope, feeds, loss)
    mine = RT._OP_BUILDS[-1]
    assert mine["kernels"] is not None and mine["rows"]
    for _ in range(RT._OP_BUILDS.maxlen):
        assert any(t is mine for t in RT._OP_BUILDS)
        RT.op_table(False)
    assert not any(t is mine for t in RT._OP_BUILDS)
    assert trace.kernels(root="exe.step", backward=True) is None \
        or trace.kernels(root="exe.step", backward=True)[0]["step"] \
        != mine["step"]


# -- (c) the parser's cases --------------------------------------------------

def _module(*computations):
    return "HloModule jit_step, is_scheduled=true\n\n" + "\n\n".join(
        computations) + "\n"


def _entry(body, third="%c = f32[4,8,16]{2,1,0}"):
    return """ENTRY %%main.9 (a: bf16[64,16], b: bf16[16,32], c: f32[4,8,16]) -> f32[64,32] {
  %%a = bf16[64,16]{1,0} parameter(0)
  %%b = bf16[16,32]{1,0} parameter(1)
  %s parameter(2)
%s
}""" % (third, body)


_CASES = {
    "dot_with_contracting_dims": (_module(_entry(
        '  ROOT %dot.1 = f32[64,32]{1,0} dot(%a, %b), '
        'lhs_contracting_dims={1}, rhs_contracting_dims={0}, '
        'metadata={op_name="jit(step)/jvp(mul.3)/dot_general" '
        'stack_frame_id=4}')), "dot.1", {
            "opcode": "dot", "root_scope": "mul.3", "passes": ("fwd",),
            "dots": (("jit(step)/jvp(mul.3)/dot_general", (64, 16),
                      (16, 32), (64, 32), 16, 2 * 64 * 32 * 16),),
            "bytes_in": 2 * (64 * 16 + 16 * 32), "bytes_out": 4 * 64 * 32,
            "scopes": {"mul.3": 1}, "computation": "main.9"}),
    "convolution_with_dim_labels": (_module(_entry(
        '  ROOT %convolution.7 = f32[16,32]{1,0:T(8,128)} '
        'convolution(%a, %x), dim_labels=fb_io->bf, '
        'metadata={op_name="jit(step)/transpose(jvp(mul.3))/dot_general"}',
        "%x = bf16[64,32]{1,0:T(8,128)(2,1)}")),
        "convolution.7", {
            "opcode": "convolution", "root_scope": "mul.3",
            "passes": ("bwd",),
            "dots": (("jit(step)/transpose(jvp(mul.3))/dot_general",
                      (64, 16), (64, 32), (16, 32), 64,
                      2 * 16 * 32 * 64),)}),
    "convolution_with_a_window": (_module(_entry(
        '  ROOT %convolution.8 = f32[16,16,1]{1,0,2} convolution(%c, %c), '
        'window={size=4}, dim_labels=0fb_0io->bf0, '
        'metadata={op_name="jit(step)/transpose(jvp(mul.5))/dot_general"}'
        )), "convolution.8", {
            "dots": (("jit(step)/transpose(jvp(mul.5))/dot_general",
                      (4, 8, 16), (4, 8, 16), (16, 16, 1), 32,
                      2 * 16 * 16 * 32),)}),
    "a_batched_product": (_module(_entry(
        '  ROOT %dot.2 = f32[4,8,8]{2,1,0} dot(%c, %c), '
        'lhs_batch_dims={0}, lhs_contracting_dims={2}, '
        'rhs_batch_dims={0}, rhs_contracting_dims={2}, '
        'metadata={op_name="jit(step)/jvp(matmul.6)/dot_general"}')),
        "dot.2", {
            "dots": (("jit(step)/jvp(matmul.6)/dot_general", (4, 8, 16),
                      (4, 8, 16), (4, 8, 8), 16, 2 * 4 * 8 * 8 * 16),),
            "root_scope": "matmul.6"}),
    "a_while_bodys_instructions": (_module(
        """%body.3 (t: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %t = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %h = f32[8,8]{1,0} get-tuple-element(%t), index=1
  %dot.4 = f32[8,8]{1,0} dot(%h, %h), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(while.2)/body/mul.7/dot_general"}
  ROOT %tuple.1 = (s32[], f32[8,8]{1,0}) tuple(%i, %dot.4)
}""",
        """%cond.3 (t.1: (s32[], f32[8,8])) -> pred[] {
  %t.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%t.1), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}""",
        """ENTRY %main.9 (t.2: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %t.2 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %while.5 = (s32[], f32[8,8]{1,0}) while(%t.2), condition=%cond.3, body=%body.3
}"""), "dot.4", {
            "computation": "body.3", "root_scope": "while.2",
            "dots": (("jit(step)/jvp(while.2)/body/mul.7/dot_general",
                      (8, 8), (8, 8), (8, 8), 8, 1024),)}),
    "a_tpu_custom_call": (_module(_entry(
        '  %flash_fwd.3 = bf16[64,16]{1,0:T(8,128)(2,1)} custom-call(%a, '
        '/*index=1*/%b), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={bf16[64,16]{1,0}, bf16[16,32]{1,0}}, '
        'metadata={op_name="jit(step)/jvp(flash_attention.12)/flash_fwd/'
        'pallas_call" stack_frame_id=57}, backend_config={'
        '"custom_call_config":{"body":"TUzvUg(FNT)ElS"}}\n'
        '  ROOT %copy.1 = f32[64,32]{1,0} copy(%x.1)',
        "%x.1 = f32[64,32]{1,0}")), "flash_fwd.3", {
            "opcode": "custom-call", "fusion_kind": None,
            "custom_call_target": "tpu_custom_call",
            "root_scope": "flash_attention.12", "dots": (),
            "bytes_in": 2 * (64 * 16 + 16 * 32), "bytes_out": 2 * 64 * 16,
            "operands": (("bf16", (64, 16)), ("bf16", (16, 32)))}),
    "a_dynamic_update_slice_root": (_module(
        """%fused_computation.4 (p0: f32[4,8,16], p1: f32[8,16], p2: s32[]) -> f32[4,8,16] {
  %p0 = f32[4,8,16]{2,1,0} parameter(0)
  %p1 = f32[8,16]{1,0} parameter(1)
  %bitcast.2 = f32[1,8,16]{2,1,0} bitcast(%p1)
  %p2 = s32[] parameter(2)
  %zero = s32[] constant(0)
  ROOT %dynamic_update_slice.0 = f32[4,8,16]{2,1,0} dynamic-update-slice(%p0, %bitcast.2, %p2, %zero, %zero), metadata={op_name="jit(step)/scan.4/dynamic_update_slice"}
}""",
        """%fused_computation.5 (p0.1: f32[4,8,16], p1.1: s32[]) -> f32[8,16] {
  %p0.1 = f32[4,8,16]{2,1,0} parameter(0)
  %p1.1 = s32[] parameter(1)
  %zero.1 = s32[] constant(0)
  %dynamic_slice.5 = f32[1,8,16]{2,1,0} dynamic-slice(%p0.1, %p1.1, %zero.1, %zero.1), dynamic_slice_sizes={1,8,16}, metadata={op_name="jit(step)/scan.4/dynamic_slice"}
  ROOT %bitcast.3 = f32[8,16]{1,0} bitcast(%dynamic_slice.5)
}""",
        """ENTRY %main.9 (c: f32[4,8,16], d: f32[8,16], i: s32[]) -> f32[4,8,16] {
  %c = f32[4,8,16]{2,1,0} parameter(0)
  %d = f32[8,16]{1,0} parameter(1)
  %i = s32[] parameter(2)
  %fusion.8 = f32[8,16]{1,0} fusion(%c, %i), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/scan.4/dynamic_slice"}
  ROOT %fusion.7 = f32[4,8,16]{2,1,0} fusion(%c, %fusion.8, %i), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/scan.4/dynamic_update_slice"}, backend_config={"window_config":{"estimated_cycles":"1811"}}
}"""), "fusion.7", {
            # in place: the slice that is put in, and its index
            "bytes_in": 4 * 8 * 16 + 4, "bytes_out": 4 * 8 * 16,
            "fusion_kind": "kLoop", "estimated_cycles": 1811,
            "results": (("f32", (4, 8, 16)),),
            "scopes": {"scan.4": 1, "": 1}}),
    "a_line_with_no_metadata": (_module(_entry(
        '  %copy.3 = f32[4,8,16]{0,1,2} copy(%c)\n'
        '  ROOT %dot.1 = f32[64,32]{1,0} dot(%a, %b), '
        'lhs_contracting_dims={1}, rhs_contracting_dims={0}')),
        "copy.3", {
            "opcode": "copy", "op_name": None, "root_scope": None,
            "scopes": {"": 1}, "passes": (), "dots": (),
            "estimated_cycles": None, "bytes_in": 4 * 4 * 8 * 16,
            "bytes_out": 4 * 4 * 8 * 16}),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_parser_reads(case):
    text, name, want = _CASES[case]
    module, rows = hlo.kernel_rows(text)
    assert module == "jit_step"
    by_name = {r["name"]: r for r in rows}
    assert len(by_name) == len(rows)
    row = by_name[name]
    assert {k: row[k] for k in want} == want
    # a text is read the same from its lines
    assert hlo.kernel_rows(iter(text.split("\n"))) == (module, rows)


def test_the_parser_gives_rows_to_what_runs_and_to_nothing_else():
    """The rows are the entry's and the loop's work: no parameter, no
    tuple, no instruction of a fused computation; the ``while`` is there
    as a parent; a slice read in a fused body counts the slice."""
    text, _, _ = _CASES["a_while_bodys_instructions"]
    assert [(r["name"], r["computation"])
            for r in hlo.kernel_rows(text)[1]] == [
        ("while.5", "main.9"), ("lt", "cond.3"), ("dot.4", "body.3")]
    text, _, _ = _CASES["a_dynamic_update_slice_root"]
    rows = {r["name"]: r for r in hlo.kernel_rows(text)[1]}
    assert sorted(rows) == ["fusion.7", "fusion.8"]
    assert rows["fusion.8"]["bytes_in"] == 4 * 8 * 16 + 4
    assert rows["fusion.8"]["bytes_out"] == 4 * 8 * 16


@pytest.mark.parametrize("op_name, scope, which", [
    ("jit(step)/transpose(jvp(mul.226))/dot_general", "mul.226", "bwd"),
    ("jit(step)/jvp(silu.2)/jit(silu)/mul", "silu.2", "fwd"),
    ("jit(step)/adam.463/mul", "adam.463", "fwd"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/mul.22/dot_general",
     "mul.22", "bwd"),
    ("jit(step)/transpose(jvp(mul.7))/rematted_computation/dot_general",
     "mul.7", "second"),
    ("jit(decode)/kv.read/gather", "kv.read", "fwd"),
    ("jit(step)/convert_element_type", None, "fwd"),
])
def test_a_scope_and_a_pass_are_told_from_an_op_name(op_name, scope,
                                                     which):
    assert hlo.scope_of(op_name) == scope
    assert hlo.pass_of(op_name) == which


# -- (b) a step compiled for the described chip -----------------------------

def test_the_weight_gradients_kernel_on_a_described_v5e(chip):
    """Two products with a SiLU between and Adam on the second weight,
    compiled for a described v5e: the TPU's HLO writes the weight
    gradient of ``mul.3`` as ONE ``convolution`` in a fusion that also
    holds Adam's update of the weight and, in a nested fusion, the
    activation made again for the product's operand. The row reads the
    product at 2 x 8192 x 4096 x 1024 FLOPs, names ``adam.5`` and
    ``silu.2`` among its scopes and ``mul.3`` as its root, and its bytes
    are the entry instruction's declared operands and results."""
    m, k, n = 8192, 4096, 1024

    def loss_of(w1, w2, x):
        with jax.named_scope("mul.1"):
            h = jnp.dot(x, w1.astype(jnp.bfloat16))
        with jax.named_scope("silu.2"):
            h = jax.nn.silu(h)
        with jax.named_scope("mul.3"):
            y = jnp.dot(h, w2.astype(jnp.bfloat16))
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    def adam(w, g, m1, m2):
        m1 = 0.9 * m1 + 0.1 * g
        m2 = 0.999 * m2 + 0.001 * g * g
        return w - 1e-3 * m1 / (jnp.sqrt(m2) + 1e-8), m1, m2

    def step(w1, w2, m1, m2, x):
        loss, (g1, g2) = jax.value_and_grad(loss_of, (0, 1))(w1, w2, x)
        with jax.named_scope("adam.5"):
            w2, m1, m2 = adam(w2, g2.astype(jnp.float32), m1, m2)
        return loss, w1 - 1e-3 * g1, w2, m1, m2

    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    f32, bf16 = jnp.float32, jnp.bfloat16
    compiled = jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(
        aval((1024, k), f32), aval((k, n), f32), aval((k, n), f32),
        aval((k, n), f32), aval((m, 1024), bf16)).compile()
    text = compiled.as_text()
    module, rows = hlo.kernel_rows(text)
    assert module == "jit_step"
    held = [r for r in rows for d in r["dots"]
            if hlo.scope_of(d[0]) == "mul.3" and hlo.pass_of(d[0]) == "bwd"
            and d[4] == m]
    assert len(held) == 1
    (row,) = held
    assert row["opcode"] == "fusion" and row["fusion_kind"] == "kOutput"
    (dot,) = row["dots"]
    assert dot[5] == 2 * m * k * n and set(dot[3]) - {1} == {k, n}
    assert row["root_scope"] == "mul.3"
    assert "adam.5" in row["scopes"] and "silu.2" in row["scopes"]
    assert "silu.2" in row["nested"] and "adam.5" not in row["nested"]
    assert {"fwd", "bwd"} <= set(row["passes"])
    assert row["estimated_cycles"] > 0
    # the entry instruction as the text declares it
    line = next(l for l in text.split("\n")
                if l.lstrip().startswith("%%%s = " % row["name"])
                or l.lstrip().startswith("ROOT %%%s = " % row["name"]))
    results, rest = line.split(" fusion(", 1)
    assert row["results"] == hlo.leaves(results.split(" = ", 1)[1])
    assert row["bytes_out"] == sum(map(hlo.nbytes, row["results"]))
    assert len(row["operands"]) == rest.split(")", 1)[0].count("%")
    assert row["bytes_in"] == sum(map(hlo.nbytes, row["operands"]))
    # every product of the step is in some row, each once: the first
    # product (as many FLOPs as the second) takes no operand gradient
    assert [d[5] for r in rows for d in r["dots"]] == [2 * m * k * n] * 5


def test_the_gated_mlps_products_read_values_on_a_described_v5e(
        chip, monkeypatch):
    """Two layers of ``h + W_down(silu_mul(hn W_gate, hn W_up))``, each
    a ``layers.recompute`` region whose plan keeps both products, bf16
    AMP and Adam, the Program's own step compiled for a described v5e
    (ISSUE 67): the op's results are values behind barriers, so NO
    kernel that holds a product makes the activation or its gradient
    again on an operand (``silu_mul.<n>`` in no such row's ``nested``;
    where XLA makes them is the epilogue of the product before), every
    product is in some row once, and ``ffn_down``'s weight gradient
    declares the bytes of its three arrays and Adam's moments, no
    more."""
    from paddle_tpu.models.latent_moe import gated_ffn
    from paddle_tpu.ops import control_flow as CF
    from test_recompute_kinds import _V5E_LIMIT
    from test_tpu_compile_regions import _step
    t, d, f, layers_n = 8192, 2048, 8192, 2
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: _V5E_LIMIT)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.amp.amp_guard(True), fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()), unique_name.guard("glu_"):
        h = fluid.layers.data("x", [t, d])
        for i in range(layers_n):
            with fluid.layers.recompute():
                h = h + gated_ffn(fluid.layers.rms_norm(h), f, "glu%d" % i)
        loss = fluid.layers.mean(fluid.layers.square(h))
        fluid.optimizer.Adam(1e-3).minimize(loss)
        _, step, args, _ = _step(main, startup, loss.name, {
            "x": np.zeros((1, t, d), np.float32)}, chip)
        compiled = step.lower(*args).compile()
    assert int(CF._PLAN.value(kind=CF.MUL_OUT, what="admitted")) \
        == 2 * layers_n
    _, rows = hlo.kernel_rows(compiled.as_text())
    gated = lambda names: sorted(s for s in names
                                 if s.startswith("silu_mul."))
    products = [r for r in rows if r["dots"]]
    assert len(gated({s for r in rows for s in r["scopes"]})) == layers_n
    assert [(r["name"], gated(r["nested"])) for r in products
            if gated(r["nested"])] == []
    # three products a layer, each forward, by its operand and by its
    # weight, the kept ones not again
    assert sorted(d[5] for r in products for d in r["dots"]) \
        == [2 * t * d * f] * (9 * layers_n)
    downs = {"mul.%d" % (int(s.split(".")[1]) + 1)
             for r in rows for s in gated(r["scopes"])}
    by_weight = [r for r in products for dot in r["dots"]
                 if hlo.scope_of(dot[0]) in downs
                 and hlo.pass_of(dot[0]) == "bwd" and dot[4] == t]
    assert len(by_weight) == layers_n
    for row in by_weight:
        assert any(s.startswith("adam.") for s in row["scopes"])
        # hidden, the cotangent (bf16; float32 from the loss itself),
        # the weight and Adam's two moments, and a few scalars
        assert row["bytes_in"] <= 2 * t * f + 4 * t * d + 3 * 4 * f * d \
            + 1024, row
    assert min(r["bytes_in"] for r in by_weight) \
        <= 2 * t * f + 2 * t * d + 3 * 4 * f * d + 1024
