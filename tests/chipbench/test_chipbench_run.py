"""run.py end to end at tiny size on the CPU, under its explicit
rehearse option: the last line is the contract's one JSON object and
says ``"platform": "cpu"``, so it can never pass for a chip run; and
without the option a chipless host is a failure, not a fallback."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _run(workload, *extra, seed=3000000019, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = ROOT
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", workload, "--seed", str(seed), "--seconds", "2",
        "--trace", "0", *extra]
    return subprocess.run(cmd, cwd=root, env=env, text=True,
                          capture_output=True, timeout=600)


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_the_contract_line(workload):
    p = _run(workload, "--rehearse")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["device"]["platform"] == "cpu"      # never a chip run
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == workload]
    assert last["device"]["count"] == cell["chips"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(last["metrics"]) == want and "setup_s" in want
    for name, m in last["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str)


def test_the_chat_cell_is_entries_away(tmp_path):
    """`opt350m_chat` (left out of BENCHMARK.json: at its honest size
    it is under the memory floor; PERF.md, section 7) needs no code: its
    configuration, its mix, the open-loop driver and the readers are
    there, so entries alone make it run, here at tiny size."""
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache",
                                                  "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    name = "opt350m_chat"
    bench["configs"].append(
        {"name": "opt-350m-serve-8L", "source": "x", "why": "x",
         "file": "chipbench/configs/opt-350m-serve-8L.json",
         "reduced": ["num_hidden_layers"]})
    bench["workloads"].append(
        {"name": name, "config": "opt-350m-serve-8L", "chips": 1,
         "traffic": "chat", "why": "x"})
    bench["end_to_end"] += [
        {"name": metric, "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": [name]}
        for metric in ("ttft_p90_ms", "itl_mean_ms")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    lines = []
    for seed in (3000000019, 5):
        p = _run(name, "--rehearse", seed=seed, root=str(tmp_path))
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        last = json.loads(p.stdout.strip().splitlines()[-1])
        assert last["device"] == {"platform": "cpu", "kind": "cpu",
                                  "count": 1, "memory_peak_bytes": 0}
        assert last["correct"] is True and last["failed"] == 0
        assert set(last["metrics"]) == {"ttft_p90_ms", "itl_mean_ms",
                                        "setup_s"}
        lines.append(last)
    # every seed offers the same number of requests
    assert lines[0]["attempted"] == lines[1]["attempted"] > 0


def test_no_tpu_is_a_failure_not_a_fallback():
    p = _run(BENCH["workloads"][0]["name"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in
                   p.stdout.splitlines())


def test_unknown_workload_fails():
    p = _run("no_such_cell", "--rehearse")
    assert p.returncode != 0 and "no workload" in p.stderr
