"""``norm_rope_dev_share_pct`` (PR 33) on two recorded windows
(``recorded_norm_rope.json``: ONE traced step of ``sdar_train_bd4k`` on
a TPU v5e from the parent of PR 33, whose ``rms_norm`` and ``rope`` ops
relay q and k for the heads' view, and one from PR 33, whose
``qk_norm_rope`` ops are a kernel pair; one shared seed), against the
fixture's rows by plain string tests, and on runs that hold nothing for
it to read."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells, peaks, spans, tracing  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
NAME = "norm_rope_dev_share_pct"
SCOPED = ("(rms_norm.", "(rope.", "(qk_norm_rope.")
MOVES = ("reshape", "copy", "concatenate")    # and pad_* / slice_* fusions


def _run(window):
    ops = [spans.device_op(*row) for row in window["ops"]]
    rows = [{"plane": DEV, "line": tracing.OP_LINE, "name": o["name"],
             "start": o["start"], "dur": o["dur"], "kernel": o["kernel"]}
            for o in ops]
    rows += [{"plane": DEV, "line": tracing.MODULE_LINE,
              "name": "jit_%s(1)" % m["program"], "start": m["start"],
              "dur": m["dur"]} for m in window["modules"]]
    return {"trace": tracing.reduce_rows(rows, 1),
            "spans": {"host": [], "ops": ops, "modules": window["modules"],
                      "compiles": None},
            "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_norm_rope.json")) as f:
        return json.load(f)


def _scoped(window):
    return [row for row in window["ops"]
            if any(s in (row[3] or "").split(";")[0] for s in SCOPED)]


def _moves(row):
    kind = tracing.op_name(row[0].split(" = ")[0].lstrip("%"))
    return kind in MOVES or kind.startswith(("pad_", "slice_"))


@pytest.mark.parametrize("side", ["parent", "change"])
def test_share_on_the_recorded_step(recorded, side):
    """The reading is the scoped rows' time over the step's busy time,
    kernels included; the parent's holds the relayouts the issue names,
    the change's none of them and both kernels."""
    window = recorded[side]
    run = _run(window)
    scoped = _scoped(window)
    want = 100 * sum(row[2] for row in scoped) / run["trace"]["busy_s"]
    assert cells.load_metric(NAME).read(run) == pytest.approx(want)
    assert want == pytest.approx(recorded["by_hand"][side], rel=1e-6)
    moved = sum(row[2] for row in scoped if _moves(row))
    kernels = {tracing.op_name(row[0].split(" = ")[0].lstrip("%"))
               for row in scoped if "tpu_custom_call" in row[0]}
    if side == "parent":
        assert want > 12 and moved > 0.015 and not kernels
    else:
        assert want < 5 and moved < 1e-4
        assert kernels == {"qk_norm_rope_fwd", "qk_norm_rope_bwd"}


@pytest.mark.parametrize("ops, want", [
    # a traced step with none of the three ops: the metric is left out
    ([("%fusion.1 = f32[] fusion()", 0.0, 1.0, "jit(step)/mul.3/dot_general")],
     None),
    # the kernels alone, forward and backward, a quarter of the window
    ([('%qk_norm_rope_fwd.1 = custom-call(), '
       'custom_call_target="tpu_custom_call"', 0.0, 0.2,
       "jit(step)/jvp(qk_norm_rope.9)/jit(_rotary_fwd)/qk_norm_rope_fwd/"
       "pallas_call:"),
      ('%qk_norm_rope_bwd.1 = custom-call(), '
       'custom_call_target="tpu_custom_call"', 0.2, 0.3,
       "jit(step)/transpose(jvp(qk_norm_rope.9))/jit(_rotary_bwd)/"
       "qk_norm_rope_bwd/pallas_call:")],
     25.0),
    # the three scopes add up; another program's and another op's do not
    ([("%reshape.7 = bf16[] reshape()", 0.0, 0.5,
       "jit(step)/jvp(rope.12)/reshape:"),
      ("%fusion.3 = f32[] fusion()", 0.5, 0.25,
       "jit(step)/transpose(jvp(rms_norm.11))/mul:"),
      ("%fusion.4 = f32[] fusion()", 0.75, 0.25,
       "jit(step)/jvp(qk_norm_rope.2)/mul:"),
      ("%reshape.8 = bf16[] reshape()", 1.0, 0.5,
       "jit(other)/jvp(rope.12)/reshape:"),
      ("%fusion.5 = f32[] fusion()", 1.5, 0.5,
       "jit(step)/jvp(layer_norm.4)/mul:")],
     50.0),
], ids=["none_of_the_ops", "kernels_only", "three_scopes"])
def test_share_on_small_windows(ops, want):
    run = {"trace": {"busy_s": 2.0},
           "spans": {"host": [], "compiles": None,
                     "ops": [spans.device_op(*op) for op in ops],
                     "modules": [{"program": "step", "start": 0.0,
                                  "dur": 2.0}]}}
    got = cells.load_metric(NAME).read(run)
    assert got is None if want is None else got == pytest.approx(want)


def test_share_is_left_out_of_an_untraced_run():
    assert cells.load_metric(NAME).read({"setup_s": 1.0, "train": {}}) is None


def test_the_entry_names_the_block_diffusion_cell_alone():
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    reader = cells.load_metric(NAME)
    assert entry == {"name": NAME, "unit": reader.UNIT, "better": "lower",
                     "source": reader.SOURCE, "layer": reader.LAYER,
                     "moves": reader.MOVES, "workloads": ["sdar_train_bd4k"]}
