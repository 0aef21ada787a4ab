"""``models/nemotron_h.py`` (ISSUE 62) on the CPU at a small size: a
layer is ONE sublayer in a region of its own, the ops each kind builds,
the parameters' names; a few train steps under bf16 AMP and per-layer
recompute against the same steps with neither; the scan never runs row
by row; and the regions' plan of the benchmark's cell at its own size
(the Program built, nothing run)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid                                  # noqa: E402
from paddle_tpu.models.nemotron_h import nemotron_h_lm     # noqa: E402
from paddle_tpu.monitor import metrics                      # noqa: E402
from paddle_tpu.ops import control_flow as CF               # noqa: E402
from paddle_tpu.parallel import moe                         # noqa: E402
from test_recompute_kinds import _cell_plan, _plan_says     # noqa: E402

PATTERN, B, T, V = "ME*M", 2, 48, 64


def _built(recompute, seed=5):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        cost, logits = nemotron_h_lm(
            V, T, PATTERN, d_model=32, n_head=4, n_kv_head=2, head_dim=8,
            n_ssm_head=4, ssm_head_dim=8, n_group=2, d_state=16, d_conv=4,
            d_expert=24, d_shared=40, num_experts=8, experts_held=4,
            first_expert=2, top_k=2, embedding_std=1.0, router_std=0.1,
            recompute=recompute, scan_chunk=16, name="nh")
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
    return main, startup, cost


def _feeds(seed=0):
    rng = np.random.RandomState(seed)
    return {"src": rng.randint(0, V, (B, T)).astype(np.int64),
            "label": rng.randint(0, V, (B, T)).astype(np.int64),
            "mask": np.ones((B, T), np.float32)}


def test_a_layer_is_one_sublayer_in_a_region_of_its_own():
    main, _, _ = _built(True)
    regions = [o for o in main.global_block().ops
               if o.type == "recompute_block"]
    assert len(regions) == len(PATTERN)
    kinds = [[m.type for m in r.attr("sub_block").ops] for r in regions]
    for ops in kinds:                       # one norm, one residual add
        assert ops.count("rms_norm") == 1 and ops[0] == "rms_norm"
        assert ops[-1] == "elementwise_add"
    mamba, experts, attention, _ = kinds
    assert mamba.count("mul") == 6 and mamba.count("ssm_conv") == 3
    for one in ("ssm_dt", "ssd_scan", "gated_group_norm"):
        assert mamba.count(one) == 1
    assert experts.count("routed_experts") == 1 \
        and experts.count("mul") == 2 and "relu" in experts \
        and "square" in experts and "silu_mul" not in experts
    assert attention.count("mul") == 4 \
        and attention.count("causal_attention") == 1
    assert not {"rope", "qk_norm_rope"} & set(sum(kinds, []))
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"nh_word_emb", "nh_head", "nh_final_norm", "nh_l0_norm",
            "nh_l0_in_z", "nh_l0_in_x", "nh_l0_in_b", "nh_l0_in_c",
            "nh_l0_in_dt", "nh_l0_conv_x_w", "nh_l0_conv_x_b",
            "nh_l0_conv_b_w", "nh_l0_conv_c_b", "nh_l0_dt_bias",
            "nh_l0_scan_a_log", "nh_l0_scan_d", "nh_l0_gnorm", "nh_l0_out",
            "nh_l1_moe.router", "nh_l1_moe.w_up", "nh_l1_moe.w_down",
            "nh_l1_shared_up", "nh_l1_shared_down", "nh_l2_wq", "nh_l2_wk",
            "nh_l2_wv", "nh_l2_wo"} <= names
    assert "nh_l1_moe.w_gate" not in names


def _trained(recompute, amp, steps=4):
    main, startup, cost = _built(recompute)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope), fluid.amp.amp_guard(amp):
        exe.run(startup)
        return [float(exe.run(main, feed=_feeds(i % 2),
                              fetch_list=[cost])[0]) for i in range(steps)]


def test_it_trains_and_recompute_changes_nothing():
    """Four Adam steps on two batches in turn: float32 with and without
    the regions give the same losses; bf16 AMP follows them within its
    rounding; the loss falls. The scan took the chunk walk: never the
    step loop, never the kernels (there is no TPU here)."""
    counter = metrics.registry().get("ptpu_ssd_lowerings_total")
    counter.clear()
    plain, regions = _trained(False, False), _trained(True, False)
    np.testing.assert_allclose(regions, plain, rtol=2e-5)
    np.testing.assert_allclose(_trained(True, True), plain, rtol=2e-2)
    assert plain[2] < plain[0] and plain[3] < plain[1]
    assert np.log(V) - 0.5 < plain[0] < np.log(V) + 1.0
    paths = {key[0] for key in counter.snapshot()}
    assert paths == {"chunked"}


def test_the_plan_of_the_cell_at_its_own_size(monkeypatch):
    """`nemotron3nano_train_T8k` (9 regions, 8,192 rows) under a v5e's
    limit: the plan admits every candidate, 27 products (a Mamba-2
    layer's five in-projections, the shared expert's up projection,
    attention's q, k, v: the last product of a branch is no candidate),
    and of the four expert layers the routers' results and the held
    experts' weights at TWO matrices an expert. A Mamba-2 region counts
    the chunk states its scan saves beside the scan's result."""
    _cell_plan(monkeypatch, "nemotron3nano_train_T8k")
    assert _plan_says(CF.MUL_OUT)[:2] == (27, 27)
    assert _plan_says(moe.EXPERTS_ROUTE)[:2] == (4, 4)
    # (two matrices an expert, at the 2,048 columns 1,856 are run at)
    assert _plan_says(moe.EXPERTS_WEIGHTS) == (
        4, 4, 4 * 2 * 8 * 2688 * 2048 * 2)
    assert _plan_says(moe.EXPERTS_OUT)[0] == 0      # an addition reads it
    last = CF._LAST
    assert last["state"] == pytest.approx(666962944 * 12, rel=1e-3)
    # the largest region is a Mamba-2 layer's, its scan's result with
    # 134 MB of chunk states beside it
    states = 4 * 8192 * 4096 * 128 // 128
    assert last["region"] > 2 * (states + 8192 * 4096 * 4)
