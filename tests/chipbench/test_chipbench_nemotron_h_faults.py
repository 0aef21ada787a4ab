"""The cell ``nemotron3nano_train_T8k`` (ISSUE 62) through the whole
train driver at the rehearsal's size on the CPU: sound it is
``correct``, and each planted fault of the model (``D x`` dropped, the
convolution's bias dropped, the norm before the gate, the routed
scaling dropped, a ReLU that is not squared, the shared expert dropped)
parts the program from the reference by more than a limit. (The decay,
the groups, the norm's groups, the selection bias and the chosen
weights' sum are held by the program's gradients against the
reference's, ``test_chipbench_nemotron_h.py``.)"""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells                                 # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402

CELL = "nemotron3nano_train_T8k"


def _tiny_cell():
    """The cell cut to its rehearsal size, as ``run.load_cell`` cuts
    it."""
    cell = cells.load_cell(ROOT, CELL)
    for part in ("config_file", "traffic_file"):
        cell[part] = {**cell[part], **cell[part].get("rehearse", {})}
    return cell


@pytest.fixture(scope="module", autouse=True)
def one_compilation_of_what_the_runs_share(tmp_path_factory):
    """The runs compile the same start-up program and the same two
    references (a fault changes the train step and the forward alone):
    JAX's persistent cache, in a directory of this module's own, makes
    each once. For the clock; what is compared is unchanged."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = [getattr(jax.config, key) for key in keys]
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("xla")))
    jax.config.update(keys[1], 0.5)
    compilation_cache.reset_cache()
    yield
    for key, value in zip(keys, was):
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def _plant(monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu import layers
    from paddle_tpu.models import nemotron_h as model
    from paddle_tpu.ops import selective_scan, ssd_scan
    from paddle_tpu.parallel import moe
    if fault == "d_dropped":
        scan = ssd_scan.ssd_scan_flat
        monkeypatch.setattr(
            ssd_scan, "ssd_scan_flat", lambda x, dt, a, b, c, d, *rest, **kw:
            scan(x, dt, a, b, c, 0 * d, *rest, **kw))
    if fault == "conv_bias_dropped":
        conv = selective_scan.causal_conv_silu
        monkeypatch.setattr(selective_scan, "causal_conv_silu",
                            lambda x, w, bias: conv(x, w, None))
    if fault == "norm_before_gate":
        def wrong(x, gate, scale, groups, epsilon=1e-5):
            parts = x.astype(jnp.float32).reshape(
                x.shape[:-1] + (groups, -1))
            normed = parts * lax.rsqrt(jnp.mean(
                parts * parts, -1, keepdims=True) + epsilon)
            return (normed.reshape(x.shape) * scale * jax.nn.silu(
                gate.astype(jnp.float32))).astype(x.dtype)
        monkeypatch.setattr(ssd_scan, "gated_group_norm", wrong)
    if fault == "scaling_dropped":
        whole = model.nemotron_h_lm
        monkeypatch.setattr(model, "nemotron_h_lm", lambda *a, **kw: whole(
            *a, **{**kw, "routed_scaling_factor": 1.0}))
    if fault == "relu_not_squared":
        monkeypatch.setitem(moe._UNGATED, "relu2", jax.nn.relu)
    if fault == "shared_expert_dropped":
        ffn = model.relu2_ffn
        monkeypatch.setattr(model, "relu2_ffn", lambda x, width, name:
                            layers.scale(ffn(x, width, name), 0.0))


@pytest.mark.parametrize("fault", [
    "sound", "d_dropped", "conv_bias_dropped", "norm_before_gate",
    "scaling_dropped", "relu_not_squared", "shared_expert_dropped"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. Each fault parts the
    program's logits from the reference's by more than
    ``TRAIN_LOGITS_RTOL`` (or its loss by more than ``LOSS_RTOL``), and
    ``correct`` comes out false. The sound run's counters: a step a
    step, every pair counted, a fresh ReLU on for about half its
    units."""
    import jax
    cell = _tiny_cell()
    _plant(monkeypatch, fault)
    said = []
    line = train_steps.run(cell, 11, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    assert line["failed"] == 0
    assert line["correct"] is (fault == "sound"), said
    if fault == "sound":
        counters = line["train"]["counters"]
        steps = counters["steps"][0]
        assert steps == line["train"]["steps"] + 2          # the warm-up
        # two expert layers, 8 x 128 rows, top-2 of 16
        assert sum(counters["expert_rows"]) == steps * 2 * 1024 * 2
        held = sum(counters["expert_rows"][:4])
        assert counters["expert_gate_units"][0] == held * 32
        assert 0.3 < counters["expert_gate_active"][0] \
            / counters["expert_gate_units"][0] < 0.7
        assert len(counters["selection_bias_abs_max"]) == 2
