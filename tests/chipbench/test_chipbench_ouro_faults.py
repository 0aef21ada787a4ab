"""The cell ``ouro_train_T8k`` (ISSUE 59) through the whole train
driver at the rehearsal's size on the CPU: sound it is ``correct``, and
each planted fault of the model (no final norm between the visits, a
pre-norm layer for the sandwich, the rotary embedding dropped, the
entropy term dropped, the exit distribution's remainder forgotten)
parts the program from the reference by more than a limit."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells                                 # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402

CELL = "ouro_train_T8k"


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
    from paddle_tpu.core import registry
    from paddle_tpu.models import looped_lm as model
    sound_norm = model._norm           # (a skipped norm's weight is made
    skipping = lambda tail: monkeypatch.setattr(     # and not used)
        model, "_norm", lambda x, name, eps: (
            sound_norm(x, name, eps), x)[name.endswith(tail)])
    if fault == "no_final_norm_between_visits":
        skipping("_final_norm")
    if fault == "pre_norm_for_sandwich":
        skipping("_post")
    if fault == "rope_dropped":
        monkeypatch.setattr(model.layers, "rope",
                            lambda x, n_head, theta: x)
    if fault == "entropy_term_dropped":
        whole = model.looped_lm
        monkeypatch.setattr(model, "looped_lm", lambda *a, **kw: whole(
            *a, **{**kw, "entropy_weight": 0.0}))
    if fault == "remainder_forgotten":
        # p_R = lambda_R S_{R-1} like every other visit's: the p of a
        # row no longer sum to 1
        def wrong(ctx, op):
            x = ctx.in1(op, "X").astype(jnp.float32)
            stay = jnp.cumsum(jax.nn.log_sigmoid(-x[:-1]), axis=0)
            ctx.set_out(op, "Out", jax.nn.log_sigmoid(x) + jnp.concatenate(
                [jnp.zeros_like(x[:1]), stay]))
        monkeypatch.setattr(registry.lookup("exit_distribution"), "lower",
                            wrong)


@pytest.mark.parametrize("fault", [
    "sound", "no_final_norm_between_visits", "pre_norm_for_sandwich",
    "rope_dropped", "entropy_term_dropped", "remainder_forgotten"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. Each fault parts the
    program from the reference by more than a limit, the loss's (the
    entropy term is 0.1 x 1.2 nats of 5.5) or the logits' (the four
    ``log p_t`` are among those compared), and ``correct`` comes out
    false. The sound run's counters: a step a step, each visit's loss
    about ln 256, the expected exit step about 1.875."""
    import jax
    cell = _tiny_cell()
    _plant(monkeypatch, fault)
    said = []
    line = train_steps.run(cell, 11, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    assert line["failed"] == 0
    assert line["correct"] is (fault == "sound"), said
    counters = line["train"]["counters"]
    steps = counters["steps"][0]
    if fault != "remainder_forgotten":     # (its p do not sum to 1)
        assert steps == pytest.approx(line["train"]["steps"] + 2)  # warm-up
    assert len(counters["visit_loss"]) == 4
    if fault == "sound":
        for total in counters["visit_loss"]:
            assert 4.5 < total / steps < 6.5
        assert 1.7 < counters["exit_step"][0] / steps < 2.0
        assert 1.0 < counters["entropy"][0] / steps < 1.3
