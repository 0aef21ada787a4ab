"""The routed cells' dropless expert layer and its row kernels
(``parallel/moe.py``, ``ops/moe_rows.py``) compiled for a described v5e
(tests/tpu_compile_test.py says how and why): 16,384 rows over 16 held
of 128 experts, Xing4.0's 4,096 rows, SmallThinker's ReLU-gated 16 of 64
and Nemotron's 8 of 128 with no gate matrix, whose grouped matmuls are
the kernels of ops/grouped_matmul.py (ISSUE 63) or XLA's own
`ragged-dot` kernels; and the traced program of the layer's gradient
held to the written backward's count of grouped matmuls and to their
operands' types (ISSUE 47), whichever of the two made them.
"""

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.extend.core import Literal  # noqa: E402


# rows, d, f as published, experts, held, top-k, the experts' activation
# ("relu2": an expert of two matrices, no gate)
SHAPES = {"sdar_train_bd4k": (16384, 2048, 768, 128, 16, 8, "silu"),
          "xing4_train_T4k": (4096, 3584, 1024, 64, 8, 4, "silu"),
          "smallthinker_train_T16k": (16384, 2560, 768, 64, 16, 6, "relu"),
          "nemotron3nano_train_T8k": (8192, 2688, 1856, 128, 8, 6, "relu2")}
KERNELS = ("grouped_matmul_rows", "grouped_matmul_rows_t",
           "grouped_matmul_by_expert")


def _layer_gradient(shape, sharding=None):
    """(value and the five gradients of one expert layer as the cells'
    steps call it: float32 rows and router, bfloat16 experts; its
    arguments' shapes)."""
    from paddle_tpu.parallel import moe
    n, d, f, e, held, k, gate = shape
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=sharding)
    w_in = sds((held, d, f), jnp.bfloat16)
    gated = gate != "relu2"

    def loss(x, wr, *w):
        out, aux, _, _ = moe.routed_experts(
            x, wr, *(w if gated else (None,) + w), e, 0, k, force="pallas",
            activation=gate)
        return out.astype(jnp.float32).sum() + aux

    # the value too: XLA drops a forward whose result nobody reads
    return jax.value_and_grad(loss, argnums=tuple(range(4 + gated))), (
        sds((n, d), jnp.float32), sds((d, e), jnp.float32),
        *[w_in] * (1 + gated), sds((held, f, d), jnp.bfloat16))


def _jaxprs(jaxpr):
    """A jaxpr and the jaxprs inside it."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _jaxprs(sub)


def _grouped_matmul(eqn):
    """(lhs, rhs) of an equation that IS a grouped matmul, XLA's op or
    a kernel of ops/grouped_matmul.py (its operands stand last, behind
    the grid's extent and the three tables of visits), else None."""
    if eqn.primitive.name == "ragged_dot_general":
        return eqn.invars[:2]
    if eqn.primitive.name == "pallas_call" and str(
            eqn.params["name"]).startswith("grouped_matmul"):
        return eqn.invars[-2:]
    return None


def _sums_of_grouped_matmuls(jaxpr):
    """The additions whose two terms are both grouped matmuls' results,
    as they come or cast."""
    found = []
    for part in _jaxprs(jaxpr):
        grouped = set()
        for eqn in part.eqns:
            name, ins = eqn.primitive.name, [
                v for v in eqn.invars if not isinstance(v, Literal)]
            # (a kernel is traced inside the jitted function that calls it)
            if _grouped_matmul(eqn) or eqn.params.get("name") in (
                    "_rows", "_by_expert") or (
                    name in ("convert_element_type", "transpose")
                    and ins and ins[0] in grouped):
                grouped.update(eqn.outvars)
            elif name in ("add", "add_any") and len(ins) == 2 and all(
                    v in grouped for v in ins):
                found.append(eqn)
    return found


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_the_backward_is_the_written_one(cell):
    """The guard that keeps autodiff from growing back (ISSUE 47), read
    off the traced program of the layer's gradient; nothing is compiled.
    A pass over a chunk is 2 grouped matmuls forward (gate and up side
    by side, down) and 5 backward (the hidden activations again, dh,
    dW_down, dxs, dW_gate-and-up): the forward's loop holds 2, the
    backward's 5 and chunk 0, run ahead of it, 5 more. Every one takes
    bfloat16 operands and sums in float32 (dxs alone is WRITTEN as
    bfloat16, as the parent rounded it); and no float32 value of a
    chunk's rows by the model's width is the sum of two others: the
    gate's and the up projection's cotangents are one product."""
    from paddle_tpu.parallel import moe
    n, d, f, e, held, k, gate = SHAPES[cell]
    fn, avals = _layer_gradient(SHAPES[cell])
    cap = 2 * n * k * held // e
    # the widest operand: gate and up side by side, or an ungated
    # expert's hidden width as it runs (in whole tiles)
    wide = 2 * f if gate != "relu2" else moe.hidden_width(f, gated=False)
    labels = dict(path="grouped_matmul", experts=str(e),
                  experts_held=str(held), top_k=str(k), score="softmax",
                  shared_expert="false", rows="pallas", activation=gate,
                  router_input="own")
    was = moe._LOWERINGS.value(**labels)
    top = jax.make_jaxpr(fn)(*avals).jaxpr
    # the kernel was forced: the counter says which path was lowered
    assert moe._LOWERINGS.value(**labels) == was + 1
    grouped = lambda jaxpr: [eqn for part in _jaxprs(jaxpr)
                             for eqn in part.eqns if _grouped_matmul(eqn)]
    assert len(grouped(top)) == 12
    loops = [len(grouped(eqn.params["body_jaxpr"].jaxpr))
             for eqn in top.eqns if eqn.primitive.name == "while"]
    assert sorted(m for m in loops if m) == [2, 5]
    rounded = []
    for eqn in grouped(top):
        lhs, rhs = (v.aval for v in _grouped_matmul(eqn))
        assert lhs.dtype == rhs.dtype == jnp.bfloat16, eqn
        out, = eqn.outvars
        if out.aval.dtype != jnp.float32:
            rounded.append((out.aval.dtype, out.aval.shape, lhs.shape))
    # dxs, chunk 0's and the loop's
    assert rounded == [(jnp.bfloat16, (cap, d), (cap, wide))] * 2
    sums = _sums_of_grouped_matmuls(top)
    assert not sums, sums[:2]


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_routed_experts_compile_for_v5e(chip, cell):
    """A routed cell's expert layer, forward and backward: twelve
    grouped matmuls inside and ahead of the two loops over chunks, on a
    chunk's rows (32,768; 4,096; 6,144 of Nemotron's ungated experts at
    their hidden width in whole tiles): no hidden activation of the
    worst case's N * top_k rows exists. ISSUE 63: forced, they are the
    kernels of ops/grouped_matmul.py under their own names, at the row
    tile the layer chooses and the column tiles `_tiles` / `_out_tiles`
    give: blocks that overflow VMEM are refused HERE. ISSUE 35: a
    chunk's rows go back to their tokens by `moe_scatter_add_rows`
    (once forward, once for dx of chunk 0, run ahead of the backward's
    loop since ISSUE 47, and once in that loop) and each accumulator
    leaves its slab by `moe_leave_slab`, under their own names; XLA
    scatters nothing of x's width (what is left of that kind is the
    pairs' weights, one number a place), and its gathers of a chunk's
    rows stay: x forward, x and dout backward. ISSUE 47: the grouped
    matmuls read bfloat16 operands alone (the parent's backward handed
    six of twelve a float32 one, rounded inside the kernel at twice the
    bytes)."""
    import re
    from paddle_tpu.parallel import moe
    n, d, f, e, held, k, gate = SHAPES[cell]
    fn, avals = _layer_gradient(SHAPES[cell], chip)
    text = _compiled_text(fn, *avals)
    assert "while" in text
    hidden = {int(rows) for rows in re.findall(
        r"(?:bf16|f32)\[(\d+),%d\]" % moe.hidden_width(
            f, gated=gate != "relu2"), text)}
    cap = 2 * n * k * held // e
    assert hidden and max(hidden) == cap
    operands = lambda name: re.findall(
        r"%%%s[.\d]* = \S+ custom-call\((.*?)\), custom_call_target" % name,
        text)
    made = {name: operands(name) for name in KERNELS + ("ragged-dot-none",)}
    assert {name: len(ops) for name, ops in made.items()} == {
        "grouped_matmul_rows": 4, "grouped_matmul_rows_t": 4,
        "grouped_matmul_by_expert": 4, "ragged-dot-none": 0}
    assert not any("f32[" in ops for calls in made.values() for ops in calls)
    assert len(operands("moe_scatter_add_rows")) == 3
    assert len(operands("moe_leave_slab")) == 2
    wide = lambda kind: [line for line in text.splitlines() if re.search(
        r" %s\(" % kind, line) and re.search(r"\[\d+,%d\]" % d, line)]
    assert not wide("scatter"), wide("scatter")[:2]
