"""The reduction from trace rows and run records to metrics, on a
hand-made trace whose answers are known and on a small recorded one
(``recorded_trace.json``: rows of a real TPU v5e trace of the train
step, PR 23)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, peaks, tracing  # noqa: E402
from chipbench.reference import compare  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def _row(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur}


def _hand_made():
    """Two runs of a decode step (3 ms, 2 ms) and one prefill (1 ms) on
    chip 0 between t=10 and t=10.010; 4 ms idle in two gaps."""
    M, O = tracing.MODULE_LINE, tracing.OP_LINE
    return [
        _row(DEV, M, "jit__step_impl(1)", 10.000, 0.003),
        _row(DEV, O, "%fusion.1 = f32[] fusion()", 10.000, 0.002),
        _row(DEV, O, "copy.7", 10.002, 0.001),
        _row(DEV, M, "jit__prefill_impl(2)", 10.004, 0.001),
        _row(DEV, O, "fusion.9", 10.004, 0.001),
        _row(DEV, M, "jit__step_impl(1)", 10.008, 0.002),
        _row(DEV, O, "fusion.1", 10.008, 0.001),
        _row(DEV, O, "all-reduce.3", 10.0085, 0.001),
        _row(DEV, O, "copy.7", 10.0095, 0.0005),
        _row("/host:CPU", "python", "PjitFunction(_step_impl)",
             10.0031, 0.0008),
        _row("/host:CPU", "python", "outer", 10.0030, 0.0009),
        _row("/device:TPU:1", O, "fusion.1", 10.000, 0.010),
    ]


def test_reduce_hand_made_trace():
    red = tracing.reduce_rows(_hand_made(), chips=1)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.006)
    assert red["modules"]["_step_impl"] == pytest.approx([0.003, 0.002])
    assert red["modules"]["_prefill_impl"] == pytest.approx([0.001])
    assert red["ops"]["_step_impl/fusion"] == pytest.approx(0.003)
    assert red["ops"]["_step_impl/copy"] == pytest.approx(0.0015)
    assert red["device_ops"][0] == ["_step_impl/fusion",
                                    pytest.approx(0.003)]
    gaps = dict(red["idle_gaps"])
    assert gaps["host: PjitFunction(_step_impl)"] == pytest.approx(0.001)
    assert gaps["host: no runtime call open (Python code)"] == \
        pytest.approx(0.003)
    two = tracing.reduce_rows(_hand_made(), chips=2)
    assert two["busy_s"] == pytest.approx((0.006 + 0.010) / 2)


def test_no_device_operation_is_refused():
    host_only = [r for r in _hand_made()
                 if r["plane"].startswith("/host")]
    with pytest.raises(SystemExit):
        tracing.reduce_rows(host_only)


@pytest.mark.parametrize("event, want", [
    ("jit__step_impl(1234567)", "_step_impl"),
    ("jit_fn(3)", "fn"),
])
def test_module_name(event, want):
    assert tracing.module_name(event) == want


@pytest.mark.parametrize("event, want", [
    ("%fusion.123 = bf16[8,128]{1,0} fusion(...)", "fusion"),
    ("all-reduce.3", "all-reduce"),
    ("copy", "copy"),
    ("convolution.1.2", "convolution"),
])
def test_op_name(event, want):
    assert tracing.op_name(event) == want


def _serve_run():
    reqs = [{"done": True, "due": float(k), "submit": k + 0.001 * k,
             "admit": k + 0.01, "first": k + 0.1 + 0.01 * k,
             "retire": k + 0.1 + 0.01 * k + 0.02 * 9, "tokens": 10,
             "prompt_len": 100, "max_new": 10} for k in range(10)]
    reqs.append({"done": False, "due": 10.0, "prompt_len": 5,
                 "max_new": 5})
    return {
        "setup_s": 12.5, "requests": reqs, "chips": 1,
        "engine": {"slots": 4, "stats": {
            "decode_steps": 50, "active_slot_steps": 100}},
        "config": cells.load_json(os.path.join(
            ROOT, "chipbench", "configs", "opt-350m-serve-8L.json")),
        "peaks": peaks.peaks_for("TPU v5 lite"),
        "trace": tracing.reduce_rows(_hand_made())}


@pytest.mark.parametrize("name, want", [
    ("setup_s", 12.5),
    ("ttft_p90_ms", 181.0),
    ("itl_mean_ms", 20.0),
    ("gen_late_p99_ms", 8.91),
    ("queue_wait_p90_ms", 9.1),
    ("batch_occupancy_pct", 50.0),
    ("decode_step_dev_ms", 2.5),
    ("prefill_dev_share_pct", 100 * 0.001 / 0.006),
    ("device_idle_pct.serve", 40.0),
])
def test_serving_readers(name, want):
    got = cells.load_metric(name).read(_serve_run())
    assert got == pytest.approx(want, rel=1e-6)


def test_decode_roofline_share_from_shapes():
    run = _serve_run()
    got = cells.load_metric("decode_hbm_roof_pct").read(run)
    # 10 requests x (10 x 100 + 45) K/V tokens over 50 steps, 2 rows
    need = arith.decode_step_bytes(run["config"], 2, 10450 / 50, 2)
    assert got == pytest.approx(100 * need / 819e9 / 0.0025)
    d, f, v = 1024, 4096, 50272
    weights = 8 * (4 * d * d + 2 * d * f + f + 5 * d) + d * v + 2 * 2 * d
    assert need == 2 * (weights + 2 * 8 * d * 209)


def test_train_readers_and_flops():
    cfg = cells.load_json(os.path.join(
        ROOT, "chipbench", "configs", "opt-350m-train.json"))
    per_token = arith.train_flops_per_token(cfg, 2048)
    d, f, v = 1024, 4096, 50272
    assert per_token == 3 * (24 * (8 * d * d + 4 * d * f + 2 * 2048 * d)
                             + 2 * d * v)
    run = {"train": {"steps": 12, "tokens_per_step": 4096,
                     "window_s": 4.0, "traced_steps": 2, "traced_s": 1.5,
                     "seq_len": 2048, "batch": 2,
                     "dispatch_ms": [5.0, 4.0, 6.0]},
           "config": cfg, "chips": 1, "setup_s": 30.0,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    assert cells.load_metric("tokens_per_s").read(run) == 12288.0
    assert cells.load_metric("step_host_ms.train").read(run) == 5.0
    assert cells.load_metric("train_mfu_pct").read(run) == \
        pytest.approx(100 * 16384.0 * per_token / 197e12)
    assert arith.flash_flops_per_step(cfg, 2, 2048) == \
        7 * 2048 * 2048 * 1024 * 24 * 2


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def test_comparisons():
    import numpy as np
    ref = np.array([[1.0, 4.0, 3.99], [2.0, -8.0, 0.0]], np.float32)
    assert compare.logits_error(ref + 0.08, ref) == pytest.approx(
        0.01, rel=1e-5)
    gaps = compare.tie_gaps(ref, [2, 0])
    assert gaps == pytest.approx([0.0025, 0.0], abs=1e-6)
    assert compare.loss_error(10.01, 10.0) == pytest.approx(1e-3)
    assert compare.NEAR_TIE == 2 ** -6


def test_recorded_trace_reduces():
    path = os.path.join(HERE, "recorded_trace.json")
    with open(path) as f:
        rows = json.load(f)["rows"]
    red = tracing.reduce_rows(rows)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"] and red["modules"]
    assert all("/" in name for name, _ in red["device_ops"])
    assert sum(red["ops"].values()) >= red["busy_s"] * 0.999
