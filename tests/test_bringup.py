"""Bring-up contracts (ISSUE 21): nothing on the main paths hides the
device. A place names a device this process must have, an unknown TPU
kind has no peak, the compile cache goes where the environment says or
to one fixed path, chip_smoke.py refuses to run without a TPU, and the
README names only files that exist."""

import json
import os
import re
import subprocess
import sys
import types

import jax
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.monitor import runtime as monrt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("place", [fluid.TPUPlace(0), fluid.CUDAPlace(1)],
                         ids=["TPUPlace", "CUDAPlace"])
def test_accelerator_place_raises_on_a_cpu_only_host(place):
    """This suite runs with JAX_PLATFORMS=cpu: there is no accelerator,
    and the place says so by name instead of resolving to the CPU."""
    with pytest.raises(RuntimeError, match=repr(place).replace(
            "(", r"\(").replace(")", r"\)")):
        place.jax_device()
    with pytest.raises(RuntimeError, match="no accelerator device"):
        fluid.Executor(place)


def test_cpu_place_resolves_through_the_cpu_backend():
    dev = fluid.CPUPlace().jax_device()
    assert dev.platform == "cpu"
    assert dev in jax.local_devices(backend="cpu")
    assert fluid.Executor(fluid.CPUPlace()).place == fluid.CPUPlace()


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v4", 275e12),
                                       ("TPU v9 hypothetical", None)])
def test_auto_peak_flops_knows_a_kind_or_says_none(monkeypatch, kind,
                                                   peak):
    """An unknown TPU device_kind gets NO peak — never the v5e's."""
    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [fake])
    assert monrt._auto_peak_flops() == peak


def test_auto_peak_flops_is_none_on_cpu():
    assert monrt._auto_peak_flops() is None


@pytest.fixture
def cache_config():
    """Restore jax's cache directory after a test that places it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins_and_sets_nothing(monkeypatch,
                                                     tmp_path,
                                                     cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path(monkeypatch,
                                                 cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == compile_cache.DEFAULT_DIR


def test_compile_cache_entries_counts_executables(tmp_path):
    assert compile_cache.entries(str(tmp_path / "absent")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert compile_cache.entries(str(tmp_path)) == 1


def test_import_paddle_tpu_places_no_cache_and_touches_no_backend():
    """The library import neither decides where a process writes nor
    takes the chip: a parent may import it and still start children
    that need the device."""
    code = ("import paddle_tpu, jax\n"
            "from paddle_tpu import serving\n"
            "from jax._src import xla_bridge\n"
            "assert jax.config.jax_compilation_cache_dir is None\n"
            "assert not xla_bridge._backends\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
    assert "[train]" not in out.stdout          # it did not carry on


@pytest.mark.slow
@pytest.mark.parametrize("chips", ["1", "4"])
def test_chip_smoke_rehearsal_passes_and_reports_cpu(chips, tmp_path):
    """The CPU rehearsal (tiny size, kernels in interpret mode) walks
    every phase and can never be read as a chip pass."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse", "--chips", chips],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 4}}
    assert ("[chips4]" in out.stdout) == (chips == "4")
    assert ("[train]" in out.stdout) == (chips == "1")


# a backquoted word that looks like a file of this repo ...
_README_PATH = re.compile(r"[A-Za-z0-9_./-]+\.(?:py|json|md)")
# ... unless it is the reference's tree, a user's own file or what a
# command writes at run time
_README_NOT_OURS = ("paddle/", "go/", "benchmark/fluid/", "python/paddle/",
                    "/tmp/", "chiprun_out/", "__manifest__.json",
                    "incident.json", "calib.json", "spec.json", "slo.json",
                    "serving_slo.json", "fleet.json", "m.json",
                    "timeline.json", "train.py")


def _readme_paths(text):
    parts = text.split("```")            # odd parts are fenced blocks
    spans = parts[1::2] + [s for p in parts[0::2]
                           for s in re.findall(r"`([^`\n]+)`", p)]
    for span in spans:
        for word in span.split():
            word = word.strip("()[],;:'\"").split("::")[0]   # file::test
            if _README_PATH.fullmatch(word) and \
                    not word.startswith(_README_NOT_OURS):
                yield word


def _repo_files():
    skip = {".git", "__pycache__", "chiprun_out", "_export", ".jax_cache",
            ".cache", ".pytest_cache"}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for f in files:
            yield os.path.relpath(os.path.join(root, f), REPO)


def test_readme_names_only_files_that_exist():
    """A path is given from the root or from ``paddle_tpu/``; a bare
    file name may be any file of the tree (``rpc.py``)."""
    with open(os.path.join(REPO, "README.md")) as f:
        words = sorted(set(_readme_paths(f.read())))
    assert "chipbench/run.py" in words and "chip_smoke.py" in words
    files = set(_repo_files())
    names = {os.path.basename(p) for p in files}
    gone = [w for w in words
            if not (w in files or "paddle_tpu/" + w in files
                    or ("/" not in w and w in names))]
    assert not gone, gone
