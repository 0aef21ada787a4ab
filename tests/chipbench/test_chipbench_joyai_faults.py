"""The cell ``joyai_train_T8k`` (ISSUE 55) through the whole train
driver at the rehearsal's size on the CPU: sound it is ``correct``, and
each planted fault of the model (the module's target, its embedding's
norm, the order of ``eh_proj``'s input, the loss's weight, the rotary
part of the score, the rotary columns' order) parts the program from
the reference by more than a limit."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells                                 # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402

CELL = "joyai_train_T8k"


def _tiny_cell():
    """The cell cut to its rehearsal size, as ``run.load_cell`` cuts it
    (without steering the kernels: the dense path on the CPU), and
    further for the clock: one dense and one routed layer before the
    module. The embedding's deviation is 2: at the configuration's 1.0
    an RMSNorm of weight 1 hardly moves a fresh embedding row, and
    ``no_enorm`` would be seen by the gradients' test alone
    (``tests/test_latent_moe_mtp.py``, norm weights drawn off 1), not
    by a limit set for bf16."""
    cell = cells.load_cell(ROOT, CELL)
    for part in ("config_file", "traffic_file"):
        cell[part] = {**cell[part], **cell[part].get("rehearse", {})}
    cell["config_file"].update(num_hidden_layers=2, embedding_init_std=2.0)
    return cell


@pytest.fixture(scope="module", autouse=True)
def one_compilation_of_what_the_runs_share(tmp_path_factory):
    """The seven runs compile the same start-up program and the same two
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
    from paddle_tpu.models import latent_moe as model
    arch = cells.load_arch("joyai")
    if fault == "target_shifted_by_one":
        # the module's loss against the fed label, x_{t+1}, and not the
        # label shifted once more
        sound, labels = model.lm_cost, []

        def cost(logits, label, mask, vocab):
            labels.append(label)
            return sound(logits, labels[0], mask, vocab)
        monkeypatch.setattr(model, "lm_cost", cost)
    if fault == "no_enorm":
        sound = model._norm            # its weight is made and not used
        monkeypatch.setattr(model, "_norm", lambda x, name, eps: (
            sound(x, name, eps), x)[name.endswith("_enorm")])
    if fault == "eh_proj_halves_swapped":
        sound = model.layers.concat
        monkeypatch.setattr(
            model.layers, "concat", lambda xs, axis=0, name=None: sound(
                xs[::-1] if any("norm" in x.name for x in xs) else xs,
                axis=axis, name=name))
    if fault == "no_mtp_loss_weight":
        whole = model.latent_moe_lm
        monkeypatch.setattr(model, "latent_moe_lm", lambda **kw: whole(
            **{**kw, "nextn_weight": 1.0}))
    if fault == "rotary_part_dropped":
        from paddle_tpu.ops import latent_attention as la
        two_parts = la.flash_bthd
        monkeypatch.setattr(
            la, "flash_bthd", lambda *a, q2=None, k2=None, **kw:
            two_parts(*a, **kw))
    if fault == "rotate_half_on_published_columns":
        # the program's columns taken for the published order: its
        # kernels then turn column i with column i + 32, the source 2i
        # with 2i + 1
        monkeypatch.setattr(arch, "_published_order", lambda w, heads: w)


@pytest.mark.parametrize("fault", [
    "sound", "target_shifted_by_one", "no_enorm", "eh_proj_halves_swapped",
    "no_mtp_loss_weight", "rotary_part_dropped",
    "rotate_half_on_published_columns"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. Each fault parts the
    program from the reference by more than a limit, the loss's or the
    logits' (the module's logits are among those compared), and
    ``correct`` comes out false. ``target_shifted_by_one`` moves a
    fresh model's loss by noise alone (either target costs about ln V):
    1.3e-5, 6.4e-4 and 1.5e-3 of it on seeds 10, 11 and 12 at this
    size, 1,024 rows (CPU readings, against 2.0e-5 to 4.8e-5 sound;
    with the rehearsal's own three layers 2.2e-4 to 3.8e-3 on six
    seeds), over ``LOSS_RTOL`` on two of the three, this one among
    them, and some sqrt(8) less at the cell's 8,192 rows: on the chip
    ``correct`` cannot be counted on to see it, and ``tests/test_latent_moe_mtp.py`` holds the head's gradient,
    which the target decides, to the reference."""
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
    assert steps == line["train"]["steps"] + 2      # and the warm-up's
    # one routed layer's router and the module's, 8 x 128 rows, top-2
    assert sum(counters["expert_rows"]) == steps * 2 * 1024 * 2
    assert len(counters["selection_bias_abs_max"]) == 2
    assert max(counters["selection_bias_abs_max"]) <= steps * 0.01 + 1e-9
    # both terms about ln 256 a step
    for term in ("main_loss", "mtp_loss"):
        assert 4.5 < counters[term][0] / steps < 7.5, term
