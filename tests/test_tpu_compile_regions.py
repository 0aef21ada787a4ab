"""A training cell's WHOLE step, recompute regions and their plan
included, compiled for a described v5e (tests/tpu_compile_test.py says
how and why): `lfm2_train_T32k`, five regions at 32,768 rows, where the
plan of what the regions keep (ops/control_flow.py _plan_kept) cannot
admit everything and the compiled step's memory_analysis() is what
confirms its reserve (ISSUE 52); and `ouro_train_T8k`, 36 regions a
step, four of them a head and its loss in row blocks (ISSUE 60: a slow
test). Nothing runs: the state's shapes are the start-up program's,
abstractly.
"""

import re

import numpy as np
import pytest

from tpu_compile_test import chip, topo  # noqa: F401

import jax  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.core.executor import _normalize_feeds  # noqa: E402
from paddle_tpu.ops import control_flow as CF  # noqa: E402
from paddle_tpu.ops import embedding_grad, flash_attention  # noqa: E402
from paddle_tpu.ops import grouped_matmul, moe_rows, rotary  # noqa: E402
from paddle_tpu.ops import short_conv  # noqa: E402
from paddle_tpu.parallel import moe  # noqa: E402
from test_recompute_kinds import (  # noqa: E402
    _V5E_LIMIT, abstract_state, built_cell, compiles)


@pytest.fixture
def on_the_chip(monkeypatch):
    """The dispatchers ask JAX for its backend, which is the CPU here:
    answer for the described chip, and hand the plan its limit. (The
    embedding's too: `lfm2_train_T32k`'s table is tied, so its lookup
    keeps XLA's scatter-add; the kernel there would sum inside the
    head's weight-gradient fusion and hold 1.56 GB more, PR 58.)"""
    for module in (flash_attention, rotary, moe_rows, grouped_matmul,
                   embedding_grad):
        monkeypatch.setattr(module, "_on_tpu", lambda x: True)
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: _V5E_LIMIT)


def _step(main, startup, loss, feeds, chip):
    """(executor, the jitted train step of `main` fetching `loss`, its
    arguments as values of no content on `chip`, a function that builds
    the step again)."""
    exe = fluid.Executor(fluid.CPUPlace())
    state = abstract_state(exe, startup)
    feeds, static_info = _normalize_feeds(feeds)
    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    args = ({n: on(v) for n, v in state.items()},
            {n: on(v) for n, v in feeds.items()}, on(jax.random.key(0)))
    build = lambda: exe._build(main, tuple(sorted(feeds)), (loss,),
                               tuple(sorted(state)), static_info)
    return exe, jax.jit(build(), donate_argnums=(0,)), args, build


def test_lfm2s_step_keeps_what_fits_and_compiles_a_gib_under_the_limit(
        chip, on_the_chip):
    """`lfm2_train_T32k`'s step under the plan: the compiled step's
    arguments + temporaries stand at least 1 GiB under the v5e's limit
    (with all 14 candidate products kept they stand 0.67 GiB under,
    at the parent's 4 of 14, all of layer 0, 1.48: PERF.md section 6,
    PR 52). Each kept product runs ONCE: the products under
    `rematted_computation` are those the plan passed over, no more.
    The router runs once in all four routed layers: no matmul, top-k,
    sort or gather of the chosen scores under `rematted_computation`.
    The last region's convolution result and weights are kept by
    name."""
    before = CF._KEPT_BYTES.snapshot()
    with fluid.amp.amp_guard(True):
        _, step, args, _ = _step(*built_cell("lfm2_train_T32k"), chip)
        compiled = step.lower(*args).compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= _V5E_LIMIT - 2 ** 30, held
    said = lambda kind: tuple(int(CF._PLAN.value(kind=kind, what=w))
                              for w in ("candidates", "admitted"))
    products, admitted = said(CF.MUL_OUT)
    assert products == 14 and 4 < admitted < 14
    assert said(moe.EXPERTS_ROUTE) == (4, 4)
    again = re.findall(r'op_name="[^"]*rematted_computation/([^"]*)"',
                       compiled.as_text())
    made_again = {re.match(r"mul\.\d+", n).group(0) for n in again
                  if re.match(r"mul\.\d+/dot_general", n)}
    assert len(made_again) == products - admitted
    assert not [n for n in again if re.search(
        r"/route/(dot_general|top_k|jit\(argsort\)|"
        r"jit\(take_along_axis\)/gather)", n)]
    kept = {n: CF._KEPT_BYTES.value(name=n) - before.get((n,), 0)
            for n in (short_conv.CONV_OUT, moe.EXPERTS_WEIGHTS)}
    assert kept == {short_conv.CONV_OUT: 2 * 32768 * 2048,
                    moe.EXPERTS_WEIGHTS: 2 * 8 * 3 * 2048 * 1792}


@pytest.mark.slow       # (the compile takes some three minutes on a CPU)
def test_ouros_step_with_its_heads_in_row_blocks_fits_as_the_plan_reckons(
        chip, on_the_chip):
    """`ouro_train_T8k`'s step, four visits of eight layer regions and
    a head region that runs in blocks of 1,024 rows: the compiled
    step's arguments + temporaries stand under the v5e's limit with no
    fall-back, and within 0.3 GB of what the plan reckoned (a region of
    the visited block at THREE times its values: at twice, the plan
    admits 64 products and the step stands 0.85 GiB over). Every
    product with the head carries its visit's `mul` row's scope; the
    rule is the region's recompute, and the blocks it makes again carry
    `rematted_computation` inside that scope, as many as the forward's,
    so a trace's readers book them as the second forward they are."""
    with fluid.amp.amp_guard(True):
        _, step, args, _ = _step(*built_cell("ouro_train_T8k"), chip)
        compiled = step.lower(*args).compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held < _V5E_LIMIT, held
    last = CF._LAST
    reckoned = last["state"] + last["stream"] + max(
        last["head"] + last["kept"],
        last["region"] + last["kept_before_last"])
    assert abs(reckoned - held) < 0.3e9, (reckoned, held)
    said = lambda kind, *whats: tuple(
        int(CF._PLAN.value(kind=kind, what=w)) for w in whats)
    assert said(CF.LOSS_BLOCKS, "regions", "rows") == (4, 1024)
    products, admitted = said(CF.MUL_OUT, "candidates", "admitted")
    assert products == 224 and admitted > 28
    heads = re.findall(r'(?m)^.*\b49152\b.* (?:convolution|fusion)\(.*'
                       r'op_name="([^"]*dot_general)"', compiled.as_text())
    assert all(re.search(r"/mul\.\d+/", n) for n in heads)
    again = [n for n in heads if "rematted_computation" in n]
    first = [n for n in heads if "transpose(jvp(" not in n]
    assert len(again) == len(first), (len(again), len(first), len(heads))
    scopes = lambda names: {re.search(r"/(mul\.\d+)/", n).group(1)
                            for n in names}
    assert scopes(again) == scopes(first) == scopes(heads) \
        and len(scopes(heads)) == 4
    assert all(re.search(r"/mul\.\d+/rematted_computation/dot_general$", n)
               for n in again)


def test_a_compile_that_runs_out_of_hbm_falls_back_to_a_plan_of_nothing(
        chip, on_the_chip, monkeypatch):
    """Executor._first_compile on a routed conv layer's step compiled
    for the described chip, its first compile made to fail as a TPU
    compile that runs out of HBM fails: the step is built and lowered
    again with a plan of nothing (no value carries a name the plan
    gives, nothing is saved but the kernels' results), compiled, and
    counted; the gauge holds the second compile's bytes."""
    from paddle_tpu.core import unique_name
    from paddle_tpu.models import conv_moe
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()), unique_name.guard("oom_"):
        cost, _ = conv_moe.conv_moe_lm(
            vocab_size=512, seq_len=1024, layer_types=(
                conv_moe.CONV, conv_moe.CONV), n_dense=0, d_model=256,
            n_head=4, n_kv_head=2, head_dim=64, conv_width=3, d_dense=512,
            d_expert=128, num_experts=8, experts_held=4, top_k=2)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
    feeds = {"src": np.zeros((1, 1024), np.int64),
             "label": np.zeros((1, 1024), np.int64),
             "mask": np.zeros((1, 1024), np.float32)}
    calls = compiles(monkeypatch, 1)
    fell = CF._FALLBACKS.value()
    with fluid.amp.amp_guard(True):
        exe, entry, args, build = _step(main, startup, cost.name, feeds,
                                        chip)
        kept = CF._KEPT_BYTES.snapshot()
        again = exe._first_compile(main, entry, args, build)
    assert len(calls) == 2 and again is not entry
    assert CF._FALLBACKS.value() - fell == 1
    # the first lowering counted what its plan kept, once; the second
    # kept nothing more
    first = {n: v - kept.get(n, 0)
             for n, v in CF._KEPT_BYTES.snapshot().items()}
    for kind in (CF.MUL_OUT, moe.EXPERTS_ROUTE):
        assert first[(kind,)] == CF._PLAN.value(
            kind=kind, what="admitted_bytes") > 0
    assert CF._COMPILED.value(what="temp") > 0
    assert CF._COMPILED.value(what="limit") == 0
    assert not getattr(exe, "_keep_nothing", False)
