"""The model scripts under benchmarks/ (the repo's counterpart of the
reference's benchmark/fluid scripts) and the harness they share.

Each script's own ``main()`` runs in process on the CPU at the smallest
size its arguments allow, for two or three iterations: what it returns
is finite and positive, and a training script's last loss is finite.
Nothing here asserts a speed: a CPU timing of a toy model says whether
the script still runs, never how fast the framework is (PERF.md has the
chip's numbers). ``benchmarks/common.py`` is pinned on a fake clock.
"""

import importlib
import math
import os
import re
import sys
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as fluid

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")

RUN = ["--device", "CPU", "--iterations", "2", "--skip_batch_num", "1"]
TINY_LM = ["--n_layer", "1", "--n_head", "2", "--d_model", "32",
           "--vocab", "64"]

# script -> its arguments; the scripts that print "loss <x>" in their
# sync train, the others time inference or the input path
SCRIPTS = {
    "mnist": RUN + ["--batch_size", "8"],
    "resnet": RUN + ["--batch_size", "2", "--model", "resnet_cifar10",
                     "--depth", "8"],
    "vgg": RUN + ["--batch_size", "2", "--image_size", "32"],
    "transformer": RUN + TINY_LM + ["--batch_size", "2", "--max_len", "16",
                                    "--d_inner", "64"],
    "machine_translation": RUN + ["--batch_size", "2", "--max_len", "8",
                                  "--n_layer", "1", "--d_model", "32",
                                  "--dict_size", "64"],
    "stacked_dynamic_lstm": RUN + ["--batch_size", "4", "--hidden_dim", "16",
                                   "--stacked_num", "1", "--seq_len", "8",
                                   "--vocab", "50"],
    "lm_decode": RUN + TINY_LM + ["--batch_size", "2", "--max_len", "16",
                                  "--out_len", "8"],
    "resnet_infer": RUN + ["--batch_size", "2", "--depth", "18",
                           "--image_size", "32"],
    "translate_infer": RUN + TINY_LM + ["--batch_size", "2", "--max_len", "8",
                                        "--out_len", "4", "--beam", "2"],
    "input_pipeline": ["--device", "CPU", "--batch_size", "4", "--n_files",
                       "2", "--per_file", "8", "--image_size", "8",
                       "--thread_num", "2"],
}
TRAINS = {"resnet", "vgg", "transformer", "machine_translation",
          "stacked_dynamic_lstm"}


@pytest.fixture
def bench_env(monkeypatch, tmp_path):
    """A script is an entry point: it reads ``sys.argv``, imports
    ``common`` from its own directory, places the compile cache and may
    leave temporary files. Here the cache stays as the suite has it
    (with the variable set ``compile_cache.configure`` sets nothing in
    code) and temporary files land under the test's own directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.syspath_prepend(BENCH_DIR)
    yield
    # the scripts import as top-level names (``common``, ``resnet``):
    # the next test file of this worker does not find them
    for name, mod in list(sys.modules.items()):
        if (getattr(mod, "__file__", None) or "").startswith(BENCH_DIR):
            del sys.modules[name]


def _pipeline_bench(monkeypatch):
    """Its sizes are ``main``'s parameters, not a command line: two
    stages on two of the suite's virtual CPU devices."""
    return importlib.import_module("pipeline_bench").main(
        pp=2, d=16, d_inner=32, t=4, mb=1, layers_per_stage=2, ms=(1, 2))


def _dcn_bench(monkeypatch):
    """It has no arguments: its sizes are module constants (a 52 MB
    dense parameter, a 51 MB table), cut here to a few KB. The pservers
    and their sockets are real."""
    mod = importlib.import_module("dcn_bench")
    for name, small in [("D_IN", 16), ("D_OUT", 8), ("VOCAB", 64),
                        ("EDIM", 4), ("BATCH", 4), ("STEPS", 2)]:
        monkeypatch.setattr(mod, name, small)
    return mod.main()


# these two return a dict of rates, one for each arm they time
SIZED_IN_CODE = {"pipeline_bench": _pipeline_bench, "dcn_bench": _dcn_bench}


@pytest.mark.parametrize("script", sorted(SCRIPTS) + sorted(SIZED_IN_CODE))
def test_script_runs_on_cpu(script, bench_env, monkeypatch, capsys):
    if script in SIZED_IN_CODE:
        rates = list(SIZED_IN_CODE[script](monkeypatch).values())
        assert len(rates) >= 2
    else:
        monkeypatch.setattr(sys, "argv", [script + ".py"] + SCRIPTS[script])
        rates = [importlib.reload(importlib.import_module(script)).main()]
    assert all(math.isfinite(r) and r > 0 for r in rates), rates
    if script in TRAINS:
        losses = re.findall(r"^loss (\S+)$", capsys.readouterr().out, re.M)
        assert losses and math.isfinite(float(losses[-1])), losses


# -- benchmarks/common.py --------------------------------------------------

@pytest.fixture
def common(bench_env):
    return importlib.import_module("common")


def _timed(common, monkeypatch, skip, iterations, with_sync):
    """``time_loop`` on a clock that moves only when a step runs."""
    now, log = [100.0], []
    monkeypatch.setattr(common.time, "perf_counter", lambda: now[0])

    def step(i):
        log.append(("step", i))
        now[0] += 0.5 if i >= skip else 7.0      # warm-up steps are slow

    def sync():
        log.append(("sync",))

    args = SimpleNamespace(skip_batch_num=skip, iterations=iterations)
    rate = common.time_loop(step, args, 10, "things",
                            sync=sync if with_sync else None)
    return rate, log


def test_time_loop_syncs_once_per_window_not_per_step(common, monkeypatch):
    _, log = _timed(common, monkeypatch, skip=2, iterations=4,
                    with_sync=True)
    assert log == ([("step", 0), ("step", 1), ("sync",)]
                   + [("step", i) for i in range(2, 6)] + [("sync",)])


def test_time_loop_skips_warmup_before_the_clock_starts(common, monkeypatch):
    """The two warm-up steps take 7 s each on the fake clock and leave
    no trace in the rate: 10 items a step of 0.5 s."""
    rate, _ = _timed(common, monkeypatch, skip=2, iterations=4,
                     with_sync=True)
    assert rate == pytest.approx(20.0)


def test_time_loop_returns_items_over_the_windows_time(common, monkeypatch,
                                                        capsys):
    rate, log = _timed(common, monkeypatch, skip=0, iterations=3,
                       with_sync=False)
    assert rate == pytest.approx(10 / 0.5)
    assert ("sync",) not in log and len(log) == 3
    assert "500.0000 ms/batch, 20.0 things/sec" in capsys.readouterr().out


@pytest.mark.parametrize("dtype,hi", [("int64", 7), ("float32", 3.0)])
def test_synthetic_feeds_builds_in_graph_feeds_in_range(common, dtype, hi):
    shape = (6, 5)
    var = common.synthetic_feeds({"x": (shape, dtype, hi)})["x"]
    exe = fluid.Executor(fluid.CPUPlace())
    got, = exe.run(feed={}, fetch_list=[var])
    # without x64 JAX keeps an int64 variable in 32 bits: the kind holds
    assert got.shape == shape and got.dtype.kind == np.dtype(dtype).kind
    if dtype.startswith("int"):
        assert got.min() >= 0 and got.max() < hi
        assert len(np.unique(got)) > 1
    else:
        assert got.min() >= 0.0 and got.max() <= hi
        assert got.max() > 1.0          # the range is [0, hi], not [0, 1]
