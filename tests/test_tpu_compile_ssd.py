"""The state-space-dual scan's kernel pair (``ops/ssd_scan.py``) and the
ungated expert layer compiled for a described v5e
(tests/tpu_compile_test.py says how and why) at
`nemotron3nano_train_T8k`'s shapes: 64 heads of 64 in 8 groups, 128
states, one sequence of 8,192 rows in chunks of 128, bfloat16 operands;
6 of 128 experts of 1,856, 8 held, two matrices each. And at
`granite4hmicro_train_T8k`'s, the same heads in ONE group (ISSUE 64: a
grid step that walked the whole group asked 25.75 MB of the backward's
16 MB of scoped VMEM). And each cell's WHOLE step under its regions'
plan (slow tests: a minute's compile each).
"""

import re

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@pytest.mark.parametrize("g", [8, 1], ids=["nemotrons_8_groups",
                                           "granites_one_group"])
def test_the_scan_kernels_compile_at_the_cells_shape(chip, g):
    """Forward and the written backward, one call each in the compiled
    program, under their names; the chunk states are float32. A grid
    step walks 8 heads: the group where a group has 8, an eighth of it
    where it has 64."""
    from paddle_tpu.ops import ssd_scan
    bsz, t, h, p, n = 1, 8192, 64, 64, 128
    assert ssd_scan._block_heads(h // g, p) == 8
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    args = (sds((bsz, t, h, p), jnp.bfloat16), sds((bsz, t, h), jnp.float32),
            sds((h,), jnp.float32), sds((bsz, t, g, n), jnp.bfloat16),
            sds((bsz, t, g, n), jnp.bfloat16), sds((h,), jnp.float32))
    loss = lambda *a: ssd_scan.ssd_scan(*a, force="pallas").astype(
        jnp.float32).sum()
    text = _compiled_text(jax.value_and_grad(loss, argnums=range(6)), *args)
    for name in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert len(re.findall(r"custom-call\(.*%s" % name, text)) == 1, name
    assert "f32[1,64,64,64,128]" in text        # [B, T / L, H, P, N]


@pytest.mark.slow       # (twenty seconds' compile on a CPU)
def test_the_ungated_expert_layer_compiles_at_the_cells_shape(chip):
    from paddle_tpu.parallel import moe
    n, d, f, e, held, k = 8192, 2688, 1856, 128, 8, 6
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)

    def loss(x, wr, wu, wd):
        out, aux, _, _ = moe.routed_experts(
            x, wr, None, wu, wd, e, 0, k, score="sigmoid", scaling=2.5,
            norm_eps=1e-20, force="pallas", activation="relu2")
        return out.astype(jnp.float32).sum() + aux

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
        sds((n, d), jnp.float32), sds((d, e), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, f, d), jnp.bfloat16))
    # (since ISSUE 63 the grouped matmuls are kernels too)
    assert "grouped_matmul_rows" in text and "moe_scatter_add_rows" in text


@pytest.mark.slow       # (the compile takes a minute on a CPU)
@pytest.mark.parametrize("cell,under", [
    ("nemotron3nano_train_T8k", 3 * 2 ** 30),
    ("granite4hmicro_train_T8k", 2 ** 30)])
def test_the_cells_step_fits_under_its_plan(chip, monkeypatch, cell, under):
    """`nemotron3nano_train_T8k`'s step, nine regions under the plan
    that keeps every candidate: arguments + temporaries stand 3 GiB
    under the v5e's limit, and the plan's reckoning within a tenth of
    what the compiler holds. `granite4hmicro_train_T8k`'s, ten regions
    of two sublayers each beside 12.35 GB of state (the tightest cell
    yet), under a plan that keeps 74 of 78 products: 1.45 GiB under,
    the plan's reckoning 9.9% over the compiler's. Since ISSUE 65 (the
    convolutions as kernels: no float32 copy of a mixer's x in HBM) the
    compiler holds 15.29 GB of Granite's step, 0.06 less, and the same
    plan, which fills the room it reckons (16.87 GB of the limit's
    16.91), reads 10.3% over: that case FAILS its last assertion, the
    tenth, until the plan reckons a region nearer to what the compiler
    holds (PERF.md section 7; the limit is not the thing to move)."""
    import paddle_tpu as fluid
    from paddle_tpu.ops import control_flow as CF
    from paddle_tpu.ops import (embedding_grad, flash_attention,
                                grouped_matmul, moe_rows, rotary,
                                selective_scan, ssd_scan)
    from test_recompute_kinds import _V5E_LIMIT, built_cell
    from test_tpu_compile_regions import _step
    for module in (flash_attention, rotary, moe_rows, grouped_matmul,
                   embedding_grad, ssd_scan, selective_scan):
        monkeypatch.setattr(module, "_on_tpu", lambda x: True)
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: _V5E_LIMIT)
    with fluid.amp.amp_guard(True):
        _, step, args, _ = _step(*built_cell(cell), chip)
        compiled = step.lower(*args).compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= _V5E_LIMIT - under, held
    last = CF._LAST
    reckoned = last["state"] + last["stream"] + max(
        last["head"] + last["kept"],
        last["region"] + last["kept_before_last"])
    text = compiled.as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    # (since ISSUE 65 the convolutions in front of the scan are kernels too)
    assert "ssm_conv_fwd" in text and "ssm_conv_bwd" in text
    assert abs(reckoned - held) < 0.1 * held, (reckoned, held)
