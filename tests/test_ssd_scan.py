"""The state-space-dual scan of a Mamba-2 mixer (ops/ssd_scan.py, ISSUE
62): the three paths against each other, values and all six gradients,
with T no multiple of the chunk and heads in groups that share B_t and
C_t; ONE group of 16 and of 64 heads, walked in head blocks of 8 that
sum its dB and dC between them (ISSUE 64); the kernels'
operands in bfloat16 with a float32 state; the gate-then-norm over
groups; the Program ops; the lowerings' counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.monitor import metrics
from paddle_tpu.ops import ssd_scan as S

NAMES = ("x", "dt", "a", "b", "c", "d")


def _operands(seed, bsz=2, t=40, h=4, g=2, p=8, n=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(keys[0], (bsz, t, h, p), dtype),
            jax.nn.softplus(jax.random.normal(keys[1], (bsz, t, h)) - 1.0),
            -jnp.exp(jax.random.normal(keys[2], (h,))),
            jax.random.normal(keys[3], (bsz, t, g, n), dtype),
            jax.random.normal(keys[4], (bsz, t, g, n), dtype),
            jax.random.normal(keys[5], (h,))), \
        jax.random.normal(keys[6], (bsz, t, h, p))


def _grads(args, weight, force, chunk):
    loss = lambda *a: jnp.sum(S.ssd_scan(*a, chunk=chunk, force=force)
                              .astype(jnp.float32) * weight)
    return jax.grad(loss, argnums=range(6))(*args)


@pytest.fixture(scope="module")
def truth():
    args, weight = _operands(0)
    return args, weight, S.ssd_scan(*args, force="steps"), _grads(
        args, weight, "steps", None)


@pytest.mark.parametrize("force,chunk", [
    ("chunked", 16), ("interpret", 16), ("interpret", 8)])
def test_a_walk_gives_the_recurrence_and_all_its_gradients(truth, force,
                                                           chunk):
    """40 rows in chunks of 16 (padded to 48) and of 8 (whole); 4 heads
    in 2 groups. Row by row is the truth."""
    args, weight, y, grads = truth
    np.testing.assert_allclose(S.ssd_scan(*args, chunk=chunk, force=force),
                               y, atol=2e-5)
    for name, got, want in zip(NAMES, _grads(args, weight, force, chunk),
                               grads):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=5e-6 * scale,
                                   err_msg="d" + name)


@pytest.fixture(scope="module", params=[(16, 1), (64, 1), (32, 2)],
                ids=lambda hg: "%d_heads_in_%d" % hg)
def wide(request):
    """Heads of 64 columns, so that a grid step walks 8 of them: 2, 8
    and 2 head blocks to a group."""
    h, g = request.param
    args, weight = _operands(5, bsz=1, t=40, h=h, g=g, p=64, n=16)
    assert S._block_heads(h // g, 64) == 8
    return args, weight, S.ssd_scan(*args, force="steps"), _grads(
        args, weight, "steps", None)


def test_a_groups_head_blocks_give_the_recurrence_and_all_its_gradients(
        wide):
    """40 rows in chunks of 16 (padded to 48), several head blocks to a
    group: y, and dB and dC summed over ALL the group's heads, as the
    row-by-row form gives them."""
    args, weight, y, grads = wide
    np.testing.assert_allclose(
        S.ssd_scan(*args, chunk=16, force="interpret"), y, atol=2e-5 * float(
            jnp.max(jnp.abs(y))))
    for name, got, want in zip(NAMES, _grads(args, weight, "interpret", 16),
                               grads):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=5e-6 * scale,
                                   err_msg="d" + name)


@pytest.mark.parametrize("per_group,p,want", [
    (8, 64, 8), (64, 64, 8), (16, 64, 8), (4, 8, 4), (64, 8, 64),
    (6, 128, 3), (2, 512, 1), (3, 1024, 1)])
def test_a_grid_steps_heads_follow_from_the_shapes(per_group, p, want):
    """The most heads that divide the group and fill 512 lanes at most;
    a group of 8 heads of 64 is ONE block, as before ISSUE 64."""
    assert S._block_heads(per_group, p) == want


def test_the_terms_each_matter(truth):
    """D, the decay and the groups: the recurrence without each differs
    (so the agreement above is no agreement of zeros)."""
    args, _, y, _ = truth
    x, dt, a, b, c, d = args
    far = lambda other: float(jnp.max(jnp.abs(other - y)))
    assert far(S.ssd_scan(x, dt, a, b, c, 0 * d, force="steps")) > 0.1
    assert far(S.ssd_scan(x, dt, 0 * a, b, c, d, force="steps")) > 0.1
    # head 1 reads group 0, not a group of its own
    turned = jnp.roll(b, 1, axis=2)
    assert far(S.ssd_scan(x, dt, a, turned, c, d, force="steps")) > 0.1
    # a head's state is its own: head 0's x reaches no other head's y
    moved = S.ssd_scan(x.at[:, :, 0].add(1.0), dt, a, b, c, d,
                       force="interpret", chunk=16)
    assert float(jnp.max(jnp.abs((moved - y)[:, :, 1:]))) < 1e-5
    assert float(jnp.max(jnp.abs((moved - y)[:, :, 0]))) > 0.1


def test_bfloat16_operands_keep_a_float32_state():
    """bfloat16 x, B_t, C_t through the kernels against the float32
    recurrence on the same (rounded) operands: the error is the
    products' rounding, not a bfloat16 state's (which over 256 rows of
    slow decay reads ten times it)."""
    args, _ = _operands(3, bsz=1, t=256, h=2, g=1, p=8, n=16,
                        dtype=jnp.bfloat16)
    x, dt, a, b, c, d = args
    dt, a = dt * 0.05, a * 0.1                 # a long memory
    want = S.ssd_scan(x.astype(jnp.float32), dt, a, b.astype(jnp.float32),
                      c.astype(jnp.float32), d, force="steps")
    scale = float(jnp.max(jnp.abs(want)))
    for force in ("interpret", "chunked"):
        got = S.ssd_scan(x, dt, a, b, c, d, chunk=32, force=force)
        assert got.dtype == jnp.bfloat16
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
            < 2e-2 * scale


def test_the_lowerings_count_themselves():
    counter = metrics.registry().get("ptpu_ssd_lowerings_total")
    counter.clear()
    args, weight = _operands(1, bsz=1, t=16, h=2, g=1, p=4, n=8)
    _grads(args, weight, "interpret", 8)
    S.ssd_scan(*args, force="steps")
    S.ssd_scan(*args, chunk=8, force="chunked")
    got = {key: v for key, v in counter.snapshot().items()}
    # (path, direction, chunk, d_state, a group's heads, a grid step's,
    # the Gram products a group's chunk takes)
    assert got == {("interpret", "fwd", "8", "8", "2", "2", "1"): 1,
                   ("interpret", "bwd", "8", "8", "2", "2", "1"): 1,
                   ("steps", "fwd", "0", "8", "2", "0", "0"): 1,
                   ("chunked", "fwd", "8", "8", "2", "2", "1"): 1}
    counter.clear()
    args, weight = _operands(1, bsz=1, t=16, h=16, g=1, p=64, n=8)
    _grads(args, weight, "interpret", 8)
    # two head blocks to the group, a Gram product each
    assert set(counter.snapshot()) == {
        ("interpret", d, "8", "8", "16", "8", "2") for d in ("fwd", "bwd")}


def test_what_is_refused():
    (x, dt, a, b, c, d), _ = _operands(2, bsz=1, t=8, h=4, g=2, p=4, n=8)
    with pytest.raises(ValueError, match="force is None"):
        S.ssd_scan(x, dt, a, b, c, d, force="fast")
    with pytest.raises(ValueError, match="H a multiple of G"):
        S.ssd_scan(x[:, :, :3], dt[:, :, :3], a[:3], b, c, d[:3])
    with pytest.raises(ValueError, match="whole lane tiles"):
        S.ssd_scan(x, dt, a, b, c, d, chunk=64, force="pallas")


def test_the_gate_comes_before_the_norm_and_the_norm_is_a_groups():
    rng = np.random.RandomState(0)
    x, gate = rng.randn(3, 5, 12).astype("f"), rng.randn(3, 5, 12).astype("f")
    scale = rng.rand(12).astype("f") + 0.5
    got = np.asarray(S.gated_group_norm(x, gate, scale, 3, 1e-5))
    gated = x * gate / (1.0 + np.exp(-gate))
    parts = gated.reshape(3, 5, 3, 4)
    want = (parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 5, 12) * scale
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # neither the norm first, nor one norm over all the channels
    first = (x.reshape(3, 5, 3, 4) / np.sqrt(
        (x.reshape(3, 5, 3, 4) ** 2).mean(-1, keepdims=True) + 1e-5)
        ).reshape(3, 5, 12) * scale * gate / (1.0 + np.exp(-gate))
    whole = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5) \
        * scale
    assert np.abs(got - first).max() > 0.1 and np.abs(got - whole).max() > 0.1


def test_the_program_ops_train():
    """``layers.ssd_scan`` and ``layers.gated_group_norm`` in a Program:
    the parameters' shapes and initial values, the op's result against
    the function's, and a train step that moves A_log, D and the norm's
    weight."""
    h, g, p, n, t = 4, 2, 4, 8, 24
    x = layers.data("x", [t, h * p])
    dt = layers.data("dt", [t, h])
    b = layers.data("b", [t, g * n])
    c = layers.data("c", [t, g * n])
    z = layers.data("z", [t, h * p])
    y = layers.ssd_scan(x, dt, b, c, h, g, chunk=8, name="m_scan")
    out = layers.gated_group_norm(y, z, g, 1e-5, name="m_gnorm")
    cost = layers.reduce_mean(layers.square(out))
    fluid.optimizer.SGD(learning_rate=0.5).minimize(cost)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    read = lambda name: np.array(scope.find_var(name))
    a_log, d, w = read("m_scan_a_log"), read("m_scan_d"), read("m_gnorm")
    np.testing.assert_allclose(np.exp(a_log), 1.0 + 15.0 * (
        np.arange(h) + 0.5) / h, rtol=1e-6)
    assert d.tolist() == [1.0] * h and w.tolist() == [1.0] * (h * p)
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(2, t, h * p).astype("f"),
            "dt": np.abs(rng.randn(2, t, h)).astype("f") * 0.1,
            "b": rng.randn(2, t, g * n).astype("f"),
            "c": rng.randn(2, t, g * n).astype("f"),
            "z": rng.randn(2, t, h * p).astype("f")}
    got, = exe.run(feed=feed, fetch_list=[y])
    heads = lambda v, k: jnp.asarray(v).reshape(2, t, k, -1)
    want = S.ssd_scan(heads(feed["x"], h), jnp.asarray(feed["dt"]),
                      -jnp.exp(a_log), heads(feed["b"], g),
                      heads(feed["c"], g), jnp.asarray(d), force="steps")
    np.testing.assert_allclose(got, np.asarray(want).reshape(2, t, h * p),
                               atol=1e-5)
    for name, before in (("m_scan_a_log", a_log), ("m_scan_d", d),
                         ("m_gnorm", w)):
        assert np.abs(read(name) - before).max() > 1e-6, name


def test_chip_smoke_rehearses_the_ssd_phase():
    """``chip_smoke.py --phases ssd`` is the chip's own check of the
    pair at both cells' shapes; here its rehearsal, kernels in interpret
    mode, two groups of 8 heads and ONE of 16 in two head blocks."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse", "--phases", "ssd"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("[ssd]")]
    assert len(lines) == 2 and "1 group(s) of 16 heads, 8 a grid step" \
        in lines[1], lines
