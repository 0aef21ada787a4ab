"""A model that CHOOSES through the train driver and ``control.py``:
``twin_choosing_arch.py`` (a top-2 of four dense experts), its cell
added to a copy of ``chipbench/`` as files and entries alone and
rehearsed on the CPU. One run of the forward gives the logits and the
router's choices, the reference is handed the choices and takes them
only at near-ties, the control routes as float32 does, and what the
program counted on the device reaches a reader. For an architecture
that chooses nothing (``opt``) the comparison is what it was: the
reference first, then the forward fetching its logits alone."""

import functools
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells, run as entry, traffic     # noqa: E402
from chipbench.drivers import train_steps              # noqa: E402
from chipbench.reference import compare                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TWIN = os.path.join(HERE, "twin_choosing_arch.py")
SIZES = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 4, "num_experts": 4,
         "num_experts_per_tok": 2, "norm_topk_prob": False,
         "vocab_size": 512}
MIX = {"driver": "train_steps", "seq_len": 64, "batch": 2, "n_batches": 2,
       "warmup_steps": 2, "trace_steps": 2, "check_rows": 16}
# a reader of ``train.counters``, dropped into the copy
READER = '''"""Tokens the program's routers sent to experts, all layers:
the sum of what the program counted on the device over the run."""
UNIT, SOURCE, LAYER, MOVES = "1", "host_clock", None, None


def read(run):
    return sum(sum(load) for load in run["train"]["counters"].values())
'''
# the twin with a router that is WRONG: it takes the k LEAST probable
WRONG_ROUTER = ("layers.topk(probs, k)",
                "layers.topk(layers.scale(probs, scale=-1.0), k)")
# the twin with a control that is no control: the reference itself
SOUND_CONTROL = ("\n# -- the arithmetic",
                 "\ncontrol_logits_at = logits_at\n\n# -- the arithmetic")


def _copy_with_the_twin(tmp, swap=None):
    """``chipbench/`` copied under ``tmp`` with the twin's architecture
    (``swap``: one (old, new) edit of its source), configuration, mix,
    the reader and the cell's entries; returns the copy's files as they
    were before anything was added."""
    here = tmp / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), here,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    with open(TWIN) as f:
        source = f.read()
    if swap:
        assert source.count(swap[0]) == 1
        source = source.replace(*swap)
    (here / "archs" / "moelm.py").write_text(source)
    cfg = {"source": "tests/chipbench/twin_choosing_arch.py",
           "arch": "moelm", "published": SIZES, **SIZES,
           "num_hidden_layers": 2, "reduced": ["num_hidden_layers"]}
    (here / "configs" / "moelm-2L.json").write_text(json.dumps(cfg))
    (here / "traffic" / "pretrain_short.json").write_text(json.dumps(MIX))
    (here / "metrics" / "tokens_routed.py").write_text(READER)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "moelm-2L", "source": "x", "why": "x",
         "file": "chipbench/configs/moelm-2L.json",
         "reduced": ["num_hidden_layers"]})
    bench["workloads"].append(
        {"name": "moelm_train", "config": "moelm-2L", "chips": 1,
         "traffic": "pretrain_short", "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"] = m["workloads"] + ["moelm_train"]
    # among the end-to-end metrics, which an untraced run reads (a CPU
    # rehearsal has no device trace to read per-layer metrics from)
    bench["end_to_end"].append(
        {"name": "tokens_routed", "unit": "1", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["moelm_train"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def _python(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), ROOT]))
    return subprocess.run([sys.executable, *args], cwd=root, env=env,
                          text=True, capture_output=True, timeout=600)


def _rehearse(root, seed):
    return _python(root, "chipbench/run.py", "--workload", "moelm_train",
                   "--seed", str(seed), "--seconds", "1", "--trace", "0",
                   "--rehearse")


def _control(root, *seeds):
    p = _python(root, "chipbench/control.py", "--workload", "moelm_train",
                "--seeds", ",".join(map(str, seeds)), "--rehearse")
    return p, json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("choosing")
    return tmp, _copy_with_the_twin(tmp)


@pytest.fixture(scope="module")
def rehearsed(copy):
    # seed 19: two rows of the first sequence choose otherwise than
    # float32 would (my CPU runs, PR 28)
    return _rehearse(copy[0], 19)


@pytest.fixture(scope="module")
def controlled(copy):
    return _control(copy[0], 19, 3000000019)


def test_a_choosing_cell_comes_as_files_alone(copy, rehearsed):
    tmp, before = copy
    p = rehearsed
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert "the reference handed the program's choices" in p.stdout
    assert "tolerance 2e-02" in p.stdout and "tolerance 2e-03" in p.stdout
    # what the program counted on the device reached the reader: every
    # token of every run of the program, the forward's one sequence
    # among them, was sent to k experts in each layer
    runs = MIX["warmup_steps"] + last["attempted"]
    tokens = MIX["batch"] * MIX["seq_len"] * runs + MIX["seq_len"]
    assert last["metrics"]["tokens_routed"]["value"] == \
        2 * SIZES["num_experts_per_tok"] * tokens
    assert all(p.read_bytes() == data for p, data in before.items())


def test_set_up_is_stamped_phase_by_phase(rehearsed):
    (line,) = [l for l in rehearsed.stdout.splitlines()
               if "set-up, seconds after the process began" in l]
    names = re.findall(r"(\w+) \d+\.\d", line)
    assert names == ["entered", "built", "started", "params_read",
                     "compared", "warmed_up"]
    at = [float(x) for x in re.findall(r"\w+ (\d+\.\d)", line)]
    assert at == sorted(at) and at[0] > 0


def test_a_router_that_is_wrong_comes_out_not_correct(tmp_path):
    """The forward reports choices that are no near-ties (its router
    takes the LEAST probable experts): the reference refuses them,
    routes by itself, and the logits part."""
    _copy_with_the_twin(tmp_path, swap=WRONG_ROUTER)
    p = _rehearse(tmp_path, 5)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert "the reference handed the program's choices" in p.stdout


@pytest.fixture(scope="module")
def twin():
    return cells.load_module(TWIN, "chipbench_test_moelm")


@pytest.mark.parametrize("proposed, taken", [
    ([0, 1], [0, 1]),        # its own
    ([1, 0], [1, 0]),        # its own, in the program's order
    ([0, 2], [0, 2]),        # 0.295 against a cut of 0.30: a near-tie
    ([2, 0], [2, 0]),
    ([0, 3], [0, 1]),        # 0.005: no near-tie, refused
    ([2, 3], [0, 1]),        # one near and one far: refused
], ids=str)
def test_the_reference_takes_a_proposal_only_at_a_near_tie(twin, proposed,
                                                           taken):
    import jax.numpy as jnp
    assert (1 - twin.NEAR_TIE) * 0.30 <= 0.295
    probs = jnp.asarray([[0.40, 0.30, 0.295, 0.005],
                         [0.10, 0.60, 0.05, 0.25]])
    got = np.asarray(twin.routed(probs, 2, jnp.asarray([proposed, [1, 3]])))
    assert got.tolist() == [taken, [1, 3]]
    assert np.asarray(twin.routed(probs, 2, None)).tolist() == [[0, 1],
                                                                [1, 3]]


def test_the_control_routes_as_float32_and_still_fails(controlled):
    p, last = controlled
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert last["routed"] is True and last["seeds"] == 2
    assert last["program_max"] < last["limit"] < last["control_min"]
    assert last["separates"] is True


def test_a_choosing_twin_whose_control_is_sound_fails_the_tool(tmp_path):
    _copy_with_the_twin(tmp_path, swap=SOUND_CONTROL)
    p, last = _control(tmp_path, 5)
    assert p.returncode == 1
    assert last["routed"] is True
    assert last["control_min"] == 0.0 and last["separates"] is False


def test_the_tool_reads_what_the_driver_reads(rehearsed, controlled):
    """One function: the same seed gives the same program reading in
    the driver's log and in ``control.py``'s, to the printed digits."""
    (driver,) = re.findall(r"error (\d\.\d+e-\d+) of the largest logit",
                           rehearsed.stdout)
    (tool,) = re.findall(r"seed 19: the program's logits error "
                         r"(\d\.\d+e-\d+)", controlled[0].stdout)
    assert driver == tool


def test_an_architecture_that_chooses_nothing_is_compared_as_before(
        monkeypatch):
    """``opt``: the reference's ``logits_at`` runs first, with no
    ``choices``, then the forward's one run fetches its logits alone;
    the log line says nothing of choices."""
    cell = entry.load_cell("opt350m_train", rehearse=True)
    cfg, mix = cell["config_file"], cell["traffic_file"]
    seq, rows = int(mix["seq_len"]), int(mix["check_rows"])
    calls = []
    with train_steps.trainer(cell, 5, on_tpu=False) as t:
        assert not hasattr(t.arch, "router_choices")
        assert not hasattr(t.arch, "program_counters")
        (one,) = train_steps.declared_feeds(t.main, traffic.lm_batches(
            5, 1, 1, seq, cfg["vocab_size"]))
        params = t.arch.params_of_program(t.main, t.scope, cfg)
        logits_at, run = t.arch.logits_at, t.exe.run

        @functools.wraps(logits_at)
        def spied_reference(*args, **kwargs):
            calls.append(("reference", sorted(kwargs)))
            return logits_at(*args, **kwargs)

        def spied_run(program, **kwargs):
            calls.append(("forward", program is t.forward,
                          kwargs["fetch_list"]))
            return run(program, **kwargs)

        monkeypatch.setattr(t.arch, "logits_at", spied_reference)
        monkeypatch.setattr(t.exe, "run", spied_run)
        got, ref, choices = train_steps.forward_against_reference(
            t, params, one, rows)
    assert calls == [("reference", ["cfg"]), ("forward", True, [t.logits])]
    assert choices is None and got.shape == ref.shape == (
        rows, cfg["vocab_size"])
    assert compare.logits_error(got, ref) < t.arch.TRAIN_LOGITS_RTOL
