"""Test config: force an 8-virtual-device CPU platform BEFORE jax import so
multi-chip sharding tests run without TPU hardware (SURVEY.md §7 strategy;
the driver's dryrun_multichip uses the same mechanism)."""

import os

# The TPU-place sweep (tests_tpu/run_sweep.py; SURVEY §4.1 "TPUPlace added
# to the place list") runs SELECTED single-chip op-level files against the
# real accelerator: in that mode the platform is left alone and
# fluid.CPUPlace is aliased to the accelerator place so hardcoded
# Executor(fluid.CPUPlace()) tests execute on the chip.
_TPU_SWEEP = os.environ.get("PADDLE_TPU_OPTEST_PLACE", "").lower() == "tpu"

if not _TPU_SWEEP:
    # override, don't setdefault: on a machine with a chip the
    # environment may name it, and the suite must run on the virtual
    # 8-device CPU platform per the multi-chip test strategy.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The persistent compile cache is left as the environment sets it (off
# unless JAX_COMPILATION_CACHE_DIR is exported). It works on this
# platform — re-tested on jaxlib 0.9.0 with the 8 virtual devices
# (tests/test_parallel_integration.py twice over one cache directory:
# second run hits, no abort) — entry points place it through
# paddle_tpu.compile_cache; the suite does not.
if _TPU_SWEEP:
    import paddle_tpu as _fluid
    _fluid.CPUPlace = _fluid.TPUPlace

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _tpu_sweep_matmul_precision(request):
    """TPU-sweep mode, non-sweep op files only: these files compare
    against torch/numpy references at f32 tolerances of their own, so
    they run under highest-precision matmuls (still the real MXU, via
    the f32 multi-pass path). The two sweep files are excluded — their
    op_test tolerance policy deliberately exercises the DEFAULT bf16
    matmul numerics the training path uses."""
    if not _TPU_SWEEP or \
            request.module.__name__.startswith("test_ops_sweep"):
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs + scope + name generator."""
    import paddle_tpu as fluid
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core import unique_name

    main = fluid.Program()
    startup = fluid.Program()
    old_main = fluid.switch_main_program(main)
    old_startup = fluid.switch_startup_program(startup)
    old_scope = scope_mod._global_scope
    scope_mod._global_scope = scope_mod.Scope()
    with unique_name.guard():
        yield
    fluid.switch_main_program(old_main)
    fluid.switch_startup_program(old_startup)
    scope_mod._global_scope = old_scope


@pytest.fixture(autouse=True, scope="module")
def _scan_lowerings_from_zero():
    """The selective scan counts its lowerings by path in the process
    (``ptpu_scan_lowerings_total``), and two files hold a model's scans
    to "never the step loop" by the absolute count
    (tests/test_hybrid_ssm.py, tests/chipbench/test_chipbench_sambay.py)
    while tests/test_selective_scan.py runs the step loop on purpose:
    under ``--dist loadfile`` a worker that was handed that file first
    failed the other two. The gated delta rule's counter is held the
    same way (tests/chipbench/test_chipbench_olmo_hybrid.py: no
    ``steps`` path; tests/test_delta_rule.py runs it on purpose), and
    the state-space-dual scan's (tests/test_nemotron_h.py: the chunk
    walk alone; tests/test_ssd_scan.py runs every path), and
    which files share a worker moves with every test file a PR adds
    or takes away. Every file starts the three counts from zero."""
    from paddle_tpu.monitor import metrics
    for name in ("ptpu_scan_lowerings_total",
                 "ptpu_delta_rule_lowerings_total",
                 "ptpu_ssd_lowerings_total"):
        counter = metrics.registry().get(name)
        if counter is not None:
            counter.clear()
    yield


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# tests/chipbench/ is the benchmark's own (BENCHMARK.json, "paths"): a PR
# that is no `benchmark` PR adds files there and edits none. A test
# there that asserts that ITS PR's entries are the last of
# BENCHMARK.json's lists, or that a list names its first cell alone,
# ends with the next cell appended. Until a `benchmark` PR repairs the
# pin (the test should read the cells off BENCHMARK.json) it is expected
# to fail, and what else it asserts is run by the test named beside it,
# against the lists as that PR left them.
_PINS_THE_BENCHMARKS_END = {
    "test_chipbench_smallthinker.py::"
    "test_the_configuration_holds_to_its_source":
        "pins PR 46's entries as BENCHMARK.json's last; PR 49 appended a "
        "cell. Its other assertions run in test_chipbench_lfm2.py::"
        "test_smallthinkers_files_hold_to_their_source_as_pr_46_left_them",
    "test_chipbench_oplog.py::test_the_entries_in_benchmark_json":
        "pins PR 51's four metrics as per_layer's last and their lists as "
        "every cell's; PR 53 appended a cell and two metrics. Its "
        "assertions run in test_chipbench_olmo_hybrid.py::"
        "test_a_pinned_entry_is_as_its_pr_left_it",
    "test_chipbench_norm_rope.py::"
    "test_the_entry_names_the_block_diffusion_cell_alone":
        "pins norm_rope_dev_share_pct's list to PR 33's cell; PR 53 "
        "appended the cell whose eleven rms_norm ops no other reader "
        "reads. Its assertion runs in test_chipbench_olmo_hybrid.py::"
        "test_a_pinned_entry_is_as_its_pr_left_it",
    "test_chipbench_olmo_hybrid.py::test_a_pinned_entry_is_as_its_pr_left_it"
    "[test_chipbench_oplog-test_the_entries_in_benchmark_json]":
        "runs PR 51's pin against the benchmark less what PR 53 appended; "
        "PR 55 appended a cell and two metrics more. The pin's assertions "
        "run in test_chipbench_joyai.py::"
        "test_pr_51s_pinned_entries_are_as_their_pr_left_them",
    "test_chipbench_joyai.py::"
    "test_pr_51s_pinned_entries_are_as_their_pr_left_them":
        "runs PR 51's pin against the benchmark less what PRs 53 and 55 "
        "appended; PR 59 appended a cell and four metrics more. The pin's "
        "assertions run in test_chipbench_ouro.py::"
        "test_pr_51s_pinned_entries_are_as_their_pr_left_them",
    "test_chipbench_lfm2.py::"
    "test_smallthinkers_files_hold_to_their_source_as_pr_46_left_them":
        "runs PR 46's pin against the benchmark cut back to PR 46's last "
        "entries, and the pin holds expert_gate_active_pct's list to PR "
        "46's cell alone; PR 62 appended the second cell whose experts "
        "count what their ReLU leaves on. Its assertions run in "
        "test_chipbench_nemotron_h.py::"
        "test_smallthinkers_files_hold_to_their_source_as_pr_46_left_them",
    "test_chipbench_nemotron_h.py::test_the_entries_in_benchmark_json":
        "pins ssd_roof_pct's and ssd_glue_dev_share_pct's lists to PR 62's "
        "cell alone; PR 64 appended the cell whose nine Mamba-2 mixers the "
        "same two readers read. Its assertions run in "
        "test_chipbench_granite_hybrid.py::"
        "test_pr_62s_pin_of_the_scan_readers_lists_as_pr_62_left_them",
    "test_chipbench_ouro.py::test_the_entries_in_benchmark_json":
        "pins the set of metrics PR 59's cell is listed on; PR 66 listed "
        "every cell on the kernel ledger's four readers. Its assertions "
        "run in test_chipbench_kernels.py::"
        "test_a_cells_pinned_lists_are_as_its_pr_left_them",
    "test_chipbench_granite_hybrid.py::test_the_entries_in_benchmark_json":
        "pins the set of metrics PR 64's cell is listed on; PR 66 listed "
        "every cell on the kernel ledger's four readers. Its assertions "
        "run in test_chipbench_kernels.py::"
        "test_a_cells_pinned_lists_are_as_its_pr_left_them",
    "test_chipbench_granite_hybrid.py::"
    "test_pr_62s_pin_of_the_scan_readers_lists_as_pr_62_left_them":
        "runs PR 62's pin, which holds the set of metrics Nemotron's cell "
        "is listed on, against the benchmark less the later CELLS; PR 66 "
        "appended four METRICS that list every cell. Its assertions run in "
        "test_chipbench_kernels.py::"
        "test_a_cells_pinned_lists_are_as_its_pr_left_them",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        for tail, why in _PINS_THE_BENCHMARKS_END.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=False))
