"""The routed cells' dropless expert layer and its row kernels
(``parallel/moe.py``, ``ops/moe_rows.py``) compiled for a described v5e
(tests/tpu_compile_test.py says how and why): 16,384 rows over 16 held
of 128 experts, whose grouped matmuls are XLA's own `ragged-dot`
kernels, and Xing4.0's 4,096 rows.
"""

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@pytest.mark.parametrize("shape", [(16384, 2048, 768, 128, 16, 8),
                                   (4096, 3584, 1024, 64, 8, 4)],
                         ids=["sdar_train_bd4k", "xing4_train_T4k"])
def test_routed_experts_compile_for_v5e(chip, shape):
    """A routed cell's expert layer, forward and backward: grouped
    matmuls as XLA's ragged-dot kernels inside the two loops over chunks,
    on a chunk's rows (32,768; 4,096): no hidden activation of the worst
    case's N * top_k rows exists. ISSUE 35: a chunk's rows go back to
    their tokens by `moe_scatter_add_rows` (once forward, once for dx)
    and each accumulator leaves its slab by `moe_leave_slab`, under
    their own names; XLA scatters nothing of x's width (what is left of
    that kind is the pairs' weights, one number a place), and its
    gathers of a chunk's rows stay: x forward, x and dout backward."""
    import re
    from paddle_tpu.parallel import moe
    n, d, f, e, held, k = shape
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    x = sds((n, d), jnp.float32)
    wr = sds((d, e), jnp.float32)
    w_in, w_out = sds((held, d, f), jnp.bfloat16), sds((held, f, d),
                                                       jnp.bfloat16)

    def loss(x, wr, wg, wu, wd):
        out, aux, _, _ = moe.routed_experts(x, wr, wg, wu, wd, e, 0, k,
                                            force="pallas")
        return out.astype(jnp.float32).sum() + aux

    # the value too: XLA drops a forward whose result nobody reads
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                          x, wr, w_in, w_in, w_out)
    assert "ragged-dot" in text and "while" in text
    hidden = {int(rows) for rows in re.findall(
        r"(?:bf16|f32)\[(\d+),%d\]" % f, text)}
    cap = 2 * n * k * held // e
    assert hidden and max(hidden) == cap
    calls = lambda name: len(re.findall(
        r"%%%s[.\d]* = \S+ custom-call\(" % name, text))
    assert calls("moe_scatter_add_rows") == 2
    assert calls("moe_leave_slab") == 2
    wide = lambda kind: [line for line in text.splitlines() if re.search(
        r" %s\(" % kind, line) and re.search(r"\[\d+,%d\]" % d, line)]
    assert not wide("scatter"), wide("scatter")[:2]
