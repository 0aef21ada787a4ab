"""``attn_glue_dev_share_pct`` (PR 25) on the recorded window of
``test_chipbench_spans.py`` (``recorded_spans.json``: two traced steps
of the train cell on a TPU v5e, PR 24), against the fixture's rows by
plain string tests, and on runs that hold nothing for it to read."""

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
NAME = "attn_glue_dev_share_pct"


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
    run = {"trace": tracing.reduce_rows(rows, 1),
           "spans": {"host": fx["host"], "ops": ops,
                     "modules": fx["modules"], "compiles": None},
           "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}
    return fx, run


def test_glue_on_the_recorded_window(recorded):
    """The fixture keeps ops of 100 us or more, so of the glue only
    ``delta``'s broadcast over 128 lanes is in it: 48 ops of 144 us,
    one a layer and step."""
    fx, run = recorded
    glue = [row for row in fx["ops"]
            if "(sp_attention." in (row[3] or "").split(";")[0]
            and "tpu_custom_call" not in row[0]]
    assert len(glue) == 48
    assert all(row[0].startswith("%broadcast_in_dim") for row in glue)
    want = 100 * sum(row[2] for row in glue) / run["trace"]["busy_s"]
    assert cells.load_metric(NAME).read(run) == pytest.approx(want)
    assert want == pytest.approx(1.594009923944162, rel=1e-6)   # by hand


@pytest.mark.parametrize("ops, want", [
    # a traced step with no op scoped sp_attention: the metric is left out
    ([("%fusion.1 = f32[] fusion()", 0.0, 1.0, "jit(step)/mul.3/dot_general")],
     None),
    # kernels alone, all of attention inside them: a reading of 0
    ([('%flash_fwd.1 = custom-call(), custom_call_target="tpu_custom_call"',
       0.0, 1.0, "jit(step)/jvp(sp_attention.13)/flash_fwd/pallas_call:")],
     0.0),
    # a kernel and a copy under the scope, a copy under another program
    ([('%flash_fwd.1 = custom-call(), custom_call_target="tpu_custom_call"',
       0.0, 1.0, "jit(step)/jvp(sp_attention.13)/flash_fwd/pallas_call:"),
      ("%copy.7 = bf16[] copy()", 1.0, 0.5,
       "jit(step)/transpose(jvp(sp_attention.13))/transpose:"),
      ("%copy.8 = bf16[] copy()", 1.5, 0.5,
       "jit(other)/jvp(sp_attention.13)/transpose:")],
     25.0),
], ids=["no_attention", "kernels_only", "kernel_and_copy"])
def test_glue_on_small_windows(ops, want):
    read = cells.load_metric(NAME).read
    run = {"trace": {"busy_s": 2.0},
           "spans": {"host": [], "compiles": None,
                     "ops": [spans.device_op(*op) for op in ops],
                     "modules": [{"program": "step", "start": 0.0,
                                  "dur": 2.0}]}}
    got = read(run)
    assert got is None if want is None else got == pytest.approx(want)


def test_glue_is_left_out_of_an_untraced_run():
    assert cells.load_metric(NAME).read({"setup_s": 1.0, "train": {}}) is None
