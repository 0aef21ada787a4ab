"""Tier-1 monitored-training smoke: a few benchmarks/mnist.py-style
train steps on CPU with the full monitor armed (flight recorder +
metrics + cost model), asserting the expected counters/gauges are
emitted, the JSONL log parses, and the CLI summarizes it — the
end-to-end contract production runs rely on."""

import json
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor


@pytest.fixture(autouse=True)
def _fresh_monitor():
    monitor.reset_for_tests()
    yield
    monitor.reset_for_tests()


def _build_mnist():
    # benchmarks/mnist.py build(), shrunk
    img = fluid.layers.data("img", [784])
    label = fluid.layers.data("label", [1], dtype="int64")
    hidden = fluid.layers.fc(img, 64, act="relu")
    prediction = fluid.layers.fc(hidden, 10, act="softmax")
    cost = fluid.layers.cross_entropy(prediction, label)
    avg_cost = fluid.layers.mean(cost)
    fluid.optimizer.Adam(learning_rate=0.001).minimize(avg_cost)
    return avg_cost


def test_monitored_mnist_steps_end_to_end(tmp_path):
    log = str(tmp_path / "mnist.jsonl")
    monitor.enable(log_path=log, peak_flops=1e12)
    avg_cost = _build_mnist()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    xs = rng.rand(32, 784).astype(np.float32)
    ys = rng.randint(0, 10, (32, 1)).astype(np.int64)
    N = 4
    for _ in range(N):
        loss, = exe.run(feed={"img": xs, "label": ys},
                        fetch_list=[avg_cost])
        assert np.isfinite(np.asarray(loss)).all()
    monitor.disable()

    # -- counters / gauges ------------------------------------------------
    reg = monitor.registry()
    steps = reg.get("ptpu_steps_total").snapshot()
    assert sum(steps.values()) == N + 1          # + startup program
    assert reg.get("ptpu_step_seconds").count(executor="exe") == N + 1
    assert reg.get("ptpu_compile_cache_misses_total").value() == 2
    assert reg.get("ptpu_compile_cache_hits_total").value() == N - 1
    assert reg.get("ptpu_recompiles_total").value() == 0
    assert reg.get("ptpu_feed_bytes_total").value() \
        == N * (xs.nbytes + ys.nbytes)
    assert reg.get("ptpu_step_flops").value() > 0    # cost model priced
    assert reg.get("ptpu_mfu").value() > 0           # peak given -> MFU
    assert reg.get("ptpu_tokens_per_sec").value() > 0
    prom = monitor.prometheus_text()
    assert 'ptpu_steps_total{executor="exe"}' in prom

    # -- flight-recorder log parses with the expected shape ---------------
    events = monitor.read_jsonl(log)
    kinds = [e["ev"] for e in events]
    assert kinds[0] == "run_meta"
    assert kinds.count("step") == N + 1
    assert kinds.count("compile") == 2               # startup + main
    step_ev = [e for e in events if e["ev"] == "step"][-1]
    for field in ("dt", "feed_bytes", "tokens", "mfu", "n"):
        assert field in step_ev

    # -- CLI summary over the produced log --------------------------------
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.monitor", log, "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    s = json.loads(out.stdout)
    assert s["steps"] == N + 1
    assert s["p50_s"] > 0 and s["p95_s"] >= s["p50_s"]
    assert s["recompiles"] == 0
    assert s["mean_mfu"] > 0


def test_harness_monitored_run():
    from paddle_tpu.models.harness import monitored_run

    def build():
        x = fluid.layers.data("x", [16])
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return loss

    def feed(rng):
        return {"x": rng.rand(8, 16).astype(np.float32)}

    s = monitored_run(build, feed, steps=3, peak_flops=1e12)
    assert s["steps"] == 4                   # startup + 3 train steps
    assert s["recompiles"] == 0
    assert s["p50_s"] > 0
    assert s["mfu"] is not None


def test_env_armed_import_leaves_jax_backend_uninitialized(tmp_path):
    """PADDLE_TPU_MONITOR=1 + log at import must NOT initialize the jax
    backend: launcher code (jax.distributed.initialize, device-count
    updates) runs after `import paddle_tpu` and needs the config still
    mutable. Device metadata is deferred to a lazy `devices` event."""
    import os
    log = str(tmp_path / "envarmed.jsonl")
    code = (
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "import paddle_tpu  # env-armed monitor enables here\n"
        "from jax._src import xla_bridge as xb\n"
        "assert not xb._backends, 'backend initialized at import: %%s'"
        " %% list(xb._backends)\n"
        "print('BACKEND-MUTABLE-OK')\n"
        % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, PADDLE_TPU_MONITOR="1",
               PADDLE_TPU_MONITOR_LOG=log, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "BACKEND-MUTABLE-OK" in out.stdout
    events = monitor.read_jsonl(log)
    assert events[0]["ev"] == "run_meta"
    assert "platform" not in events[0]   # no device query at import


def test_flag_driven_enable(tmp_path, monkeypatch):
    from paddle_tpu import flags
    log = str(tmp_path / "flagged.jsonl")
    flags.set_flag("monitor", True)
    flags.set_flag("monitor_log", log)
    try:
        monitor.maybe_enable_from_flags()
        assert monitor.enabled()
        assert monitor.recorder() is not None
    finally:
        flags.set_flag("monitor", False)
        flags.set_flag("monitor_log", "")
        monitor.disable()
    events = monitor.read_jsonl(log)
    assert events and events[0]["ev"] == "run_meta"
