"""The regions' plan (ops/control_flow.py _plan_kept) where a block is
visited several times (``layers.repeat``, ISSUE 59): on a stated device
limit a region of a ``repeat`` block counts `times` times, the float32
gradients of the parameters that several visits read come off the
room, a region that ends in the loss is a head moment of its own; the
visits' regions and ops are numbered on in the op ledger; what is kept
changes no bit; and the plans of the seven standing cells that train
under ``layers.recompute`` are what they were at the parent of PR 59."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402

import paddle_tpu as fluid                                  # noqa: E402
from paddle_tpu import trace                                # noqa: E402
from paddle_tpu.models.looped_lm import looped_lm           # noqa: E402
from paddle_tpu.ops import control_flow as CF               # noqa: E402
from paddle_tpu.parallel import moe                         # noqa: E402
from test_recompute_kinds import _V5E_LIMIT, _cell_plan     # noqa: E402

KINDS = (CF.MUL_OUT, "short_conv_out", "delta_rule_out", moe.EXPERTS_OUT,
         moe.EXPERTS_ROUTE, moe.EXPERTS_WEIGHTS)
SAID = ("candidates", "admitted", "admitted_bytes")


def _said():
    return {k: [int(CF._PLAN.value(kind=k, what=w)) for w in SAID]
            for k in KINDS}


# -- the standing cells' plans, pinned ----------------------------------------

# Of the parent of PR 59 (commit 3b54a83), each cell's program built at
# its configuration's shapes under a v5e's limit (15.75 GiB) and bf16
# AMP: sha256 of the whole reckoning (the plan's line: limit, state,
# stream, head, region, kept, kept before the last; the counts by kind;
# how many ops are kept by which name; the names by region; both
# rooms), and three of its numbers in the clear: the bytes kept, the
# room before the last region and the room at the head.
PARENTS = {
    "joyai_train_T8k": ("0c8e4345e9fb17a6", 2979791168, 5021078072,
                        4063891992),
    "lfm2_train_T32k": ("35782a095da3bc86", 2413822080, 1621578340,
                        6453023320),
    "olmohybrid_train_T8k": ("30718c314a254382", 2749792256, 3154371748,
                             5931754648),
    "phi4flash_train_T8k": ("4edc2ad3cd14565d", 3068133376, 3672257708,
                            5572637856),
    "smallthinker_train_T16k": ("f429543a0d646cd4", 1715994880, 5037211756,
                                1800847464),
    "trinity_train_T16k": ("06015905bbb444d0", 3831496832, 4148678436,
                           5685694232),
    "xing4_train_T4k": ("796b702050d2781d", 2098462848, 2825879588,
                        5540594008)}


@pytest.mark.parametrize("workload", sorted(PARENTS))
def test_a_program_without_repeat_is_planned_as_at_the_parent(
        monkeypatch, workload):
    ops, names = _cell_plan(monkeypatch, workload)
    kinds = sorted(ops.values())
    budget, head_budget = (int(CF._PLAN.value(kind="all", what=w))
                           for w in ("budget_bytes", "head_budget_bytes"))
    whole = {"last": dict(CF._LAST),
             "kinds": {k: kinds.count(k) for k in set(kinds)},
             "names": {str(r): sorted(n) for r, n in sorted(names.items())},
             "said": _said(), "budget": budget, "head_budget": head_budget}
    digest = hashlib.sha256(json.dumps(
        whole, sort_keys=True).encode()).hexdigest()[:16]
    assert (CF._LAST["kept"], budget, head_budget) == PARENTS[workload][1:]
    assert digest == PARENTS[workload][0], whole


# -- the looped cell's plan ------------------------------------------------------

LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048      # 51.39 M
HEAD = 2048 * 49152                                        # 100.66 M
ROWS = 8192


def test_the_plan_at_ouros_shapes_counts_the_visits(monkeypatch, caplog):
    """`ouro_train_T8k`, 8 layers and a head region in a block of four
    visits: 36 regions a step. Every layer region's candidate counts
    four times: 8 x 7 products x 4 = 224 `mul` results; the head's
    product is none, for its region runs in row blocks (four regions,
    1,024 rows a block: the float32 block of 49,152 columns stays under
    256 MB) and makes no logits to keep. The room is less by the
    float32 gradients of everything the block reads (all but the
    table: 2.05 GB). A head region holds, at its backward, the logits'
    gradient whole in bf16 and of ONE block the logits in bf16 and two
    float32 values, 1.31 GB where the three ops under jax.checkpoint
    held `logits + 2 * softmax`, 4.03; so the moment that holds most is
    a LAYER region's backward, reckoned at three times its declared
    values in a block that is visited again (nine float32 and five
    bf16 `[8192, 2048]`, two bf16 and one float32 `[8192, 5632]`: 1.14
    GB; the compile for the described chip confirms the three:
    tests/test_tpu_compile_regions.py). What fits beside them, at the
    costliest a byte first, is the seven `down` products before the
    last layer's at all four visits (K 5632) and then, of the products
    of K 2048 in the step's order, the first four `[8192, 2048]`: 44 x
    33.5 MB, where the parent kept 28."""
    with caplog.at_level("INFO", logger=CF.__name__):
        ops, names = _cell_plan(monkeypatch, "ouro_train_T8k")
    assert names == {}
    said = _said()
    assert said[CF.MUL_OUT] == [224, 44, 44 * ROWS * 2048 * 2]
    assert set(ops.values()) == {CF.MUL_OUT} and len(ops) == 11
    assert [int(CF._PLAN.value(kind=CF.LOSS_BLOCKS, what=w))
            for w in ("regions", "rows")] == [4, 1024]
    # (the state: 12 bytes a parameter, Adam's powers and rate and the
    # program's seven sums beside them)
    state = 12 * (8 * LAYER + 2 * HEAD + 2048 + 2048 + 1)
    shared = 4 * (8 * LAYER + HEAD + 2048 + 2048 + 1)
    assert 0 < CF._LAST["state"] - (state + shared) < 4096
    wide, narrow = ROWS * 5632, ROWS * 2048
    layer = 9 * 4 * narrow + 5 * 2 * narrow + 2 * 2 * wide + 4 * wide
    assert CF._LAST["region"] == 3 * layer
    gradient, block = ROWS * 49152 * 2, 1024 * 49152 * (2 + 4 + 4)
    loss = ROWS * 4
    assert "holds %d bytes at its backward" % (gradient + block + loss) \
        in caplog.text
    # the stream: a float32 [8192, 2048] a layer visit and the final
    # norm's a visit (what a head region reads is that, and what it
    # hands on is a float32 a row)
    assert 0 <= CF._LAST["stream"] - 36 * ROWS * 2048 * 4 < 2 ** 27
    assert CF._LAST["kept"] == CF._LAST["kept_before_last"] \
        <= int(CF._PLAN.value(kind="all", what="budget_bytes"))
    # the last visit's logits, which the program hands out for a
    # forward run, are never made in a train step: not at the head
    assert CF._LAST["head"] < 2 ** 28


def test_the_plan_at_twice_a_visited_region_is_the_one_the_compile_refused(
        monkeypatch):
    """The three times of a region in a block that is visited again
    (CF._VISITED_REGION_TIMES) is EMPIRICAL; this pins the arithmetic of
    the reading it was taken from. At twice, as every other region is
    reckoned, `ouro_train_T8k`'s plan has 1.14 GB more room and admits
    64 of the 224 products, 2.62 GB: sixteen at all four visits, every
    layer's `down` product, all of layer 0's other six (its `ffn_gate`
    and `ffn_up` are `[8192, 5632]`) and layer 1's `wq` and `wk`. That
    plan stands under the limit by its own arithmetic, and the step
    compiled for
    the described v5e stood 0.85 GiB OVER it (temporaries 10.47 GB at
    2.62 kept; at three times: 9.25 at 1.48 kept, 0.31 GB under:
    PERF.md section 6, PR 60, the slow test of
    tests/test_tpu_compile_regions.py). Plan arithmetic only: nothing
    is compiled here."""
    monkeypatch.setattr(CF, "_VISITED_REGION_TIMES", 2)
    ops, _ = _cell_plan(monkeypatch, "ouro_train_T8k")
    wide, narrow = ROWS * 5632, ROWS * 2048
    layer = 9 * 4 * narrow + 5 * 2 * narrow + 2 * 2 * wide + 4 * wide
    assert CF._LAST["region"] == 2 * layer
    kept = 4 * (8 + 4 + 2) * 2 * narrow + 4 * 2 * 2 * wide
    assert _said()[CF.MUL_OUT] == [224, 64, kept] and len(ops) == 16
    assert round(kept / 1e9, 2) == 2.62
    reckoned = CF._LAST["state"] + CF._LAST["stream"] \
        + CF._LAST["region"] + CF._LAST["kept_before_last"]
    assert reckoned <= _V5E_LIMIT


def _looped_step(limit, monkeypatch, amp, visits=3):
    """One train step of a small looped model under a stated limit:
    (loss, gradients), the plan's counts and the op ledger's rows."""
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: limit)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"src": rng.randint(0, 64, (2, 16)).astype(np.int64),
            "label": rng.randint(0, 64, (2, 16)).astype(np.int64),
            "mask": np.ones((2, 16), np.float32)}
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.amp.amp_guard(amp):
        cost, _ = looped_lm(64, 16, 2, 32, 2, 2, 16, 48, visits, name="lv")
        grads = fluid.backward.append_backward(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed=feed, fetch_list=[cost] + [
            g for _, g in grads])
        _, rows = trace.ops(root=None, backward=True)
    return out, _said(), rows


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
def test_what_the_visits_keep_changes_no_bit(monkeypatch, amp):
    """With room for everything every candidate of every visit is kept
    (2 layers x 7 products, three visits: 42; the head's is none, its
    region runs in row blocks and makes no logits to keep), the counter
    says that each visit saved its own, and the loss and every gradient
    are the bits of the step that keeps nothing. Each primitive runs by
    itself (jax.disable_jit), as in tests/test_recompute_kinds.py."""
    with jax.disable_jit():
        none, _, _ = _looped_step(0, monkeypatch, amp)
        before = CF._KEPT_BYTES.value(name=CF.MUL_OUT)
        kept, said, rows = _looped_step(2 ** 40, monkeypatch, amp)
    assert said[CF.MUL_OUT][:2] == [42, 42]
    saved = CF._KEPT_BYTES.value(name=CF.MUL_OUT) - before
    assert saved > 0 and saved % said[CF.MUL_OUT][2] == 0
    assert all(np.abs(g).sum() > 0 for g in kept[1:])
    for a, b in zip(kept, none):
        np.testing.assert_array_equal(a, b)
    # the op ledger: a row an op of a VISIT, the visits' regions
    # numbered on (3 a visit), every kept product said so, the visits'
    # parts told by module
    inside = [r for r in rows if r["region"] is not None]
    assert sorted({r["region"] for r in inside}) == list(range(9))
    muls = [r for r in inside if r["type"] == "mul"]
    assert len(muls) == 45 and all(
        (r["kept"], r.get("row_blocks")) == (
            (None, (1, 32)) if r["module"] == "loop_head"
            else (CF.MUL_OUT, None)) for r in muls)
    heads = [r for r in rows if r["module"] == "loop_head"]
    assert sorted({r["region"] for r in heads if r["region"] is not None}) \
        == [2, 5, 8]
    assert sum(r["type"] == "softmax_with_cross_entropy" for r in heads) == 3
    gates = [r for r in rows if r["module"] == "exit" and r["type"] == "mul"]
    assert len(gates) == 3 and all(r["region"] is None for r in gates)
    # every product of the loop takes both gradients: the carried
    # stream is reached from the table, visit after visit
    assert all(r["grads"] == ("x", "w") for r in muls + gates)
    assert len({r["seq"] for r in rows}) == len(rows)


def test_one_visit_takes_nothing_off_the_room(monkeypatch):
    """A block visited once: no parameter is read by several visits,
    so the room is the plain program's; with two visits it is less by
    the float32 gradients of what the block reads (here everything but
    the table), and each region's candidates count twice."""
    said, last = {}, {}
    for visits in (1, 2):
        monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 30)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.amp.amp_guard(True):
            cost, _ = looped_lm(64, 16, 2, 32, 2, 2, 16, 48, visits,
                                name="lv")
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
            exe = fluid.Executor(fluid.CPUPlace())
            from paddle_tpu.core.registry import LowerContext
            from test_recompute_kinds import abstract_state
            env = dict(abstract_state(exe, startup), **{
                n: jax.ShapeDtypeStruct((2, 16), v.dtype)
                for n, v in main.global_block().vars.items() if v.is_data})
            CF._plan_kept(LowerContext(env, None, executor=exe,
                                       block=main.global_block()))
        said[visits], last[visits] = _said()[CF.MUL_OUT], dict(CF._LAST)
    table = 64 * 32
    weights = sum(int(np.prod(p.shape))
                  for p in main.global_block().all_parameters())
    assert said[1][0] == 14 and said[2][0] == 28
    # (and by the second visit's own sum of its loss, a float32)
    assert last[2]["state"] - last[1]["state"] == 4 * (weights - table) + 4
    assert said[2][2] == 2 * said[1][2]
