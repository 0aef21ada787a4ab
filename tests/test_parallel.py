"""SPMD parallel tests on the 8-virtual-device CPU mesh (conftest sets
xla_force_host_platform_device_count=8): dp ParallelExecutor parity with the
single-device Executor, tp sharding hints, ring/ulysses attention vs dense
reference."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import parallel


def _mlp_with_loss():
    x = fluid.layers.data("x", [16])
    label = fluid.layers.data("label", [1], dtype="int64")
    h = fluid.layers.fc(x, 32, act="relu")
    pred = fluid.layers.fc(h, 4, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    return loss


def test_parallel_executor_matches_single_device():
    loss = _mlp_with_loss()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    xv = rng.rand(16, 16).astype(np.float32)
    yv = rng.randint(0, 4, (16, 1)).astype(np.int64)

    # snapshot initial params, run single-device baseline
    scope = fluid.global_scope()
    names = [p.name for p in fluid.default_main_program().all_parameters()]
    init = {n: np.asarray(scope.find_var(n)).copy() for n in names}
    single = [float(np.asarray(exe.run(feed={"x": xv, "label": yv},
                                       fetch_list=[loss])[0]))
              for _ in range(3)]

    # restore, run the same steps under an 8-way dp mesh
    for n, v in init.items():
        scope.set(n, v)
    mesh = parallel.make_mesh({"dp": 8})
    pexe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)
    assert pexe.device_count == 8
    par = [float(np.asarray(pexe.run([loss],
                                     feed={"x": xv, "label": yv})[0]))
           for _ in range(3)]
    np.testing.assert_allclose(single, par, rtol=1e-5, atol=1e-6)


def test_parallel_executor_rejects_indivisible_batch():
    loss = _mlp_with_loss()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mesh = parallel.make_mesh({"dp": 8})
    pexe = fluid.ParallelExecutor(mesh=mesh)
    with pytest.raises(ValueError, match="divisible"):
        pexe.run([loss], feed={"x": np.ones((6, 16), np.float32),
                               "label": np.zeros((6, 1), np.int64)})


def test_tensor_parallel_sharding_hint():
    x = fluid.layers.data("x", [32])
    w_attr = fluid.ParamAttr(name="tp_w")
    h = fluid.layers.fc(x, 64, param_attr=w_attr, bias_attr=False)
    out = fluid.layers.reduce_sum(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    parallel.shard("tp_w", None, "tp")
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    pexe = fluid.ParallelExecutor(mesh=mesh)
    xv = np.random.RandomState(1).rand(8, 32).astype(np.float32)
    got, = pexe.run([out], feed={"x": xv})
    w = np.asarray(fluid.global_scope().find_var("tp_w"))
    np.testing.assert_allclose(float(np.asarray(got)), (xv @ w).sum(),
                               rtol=1e-4)
    # the committed state must actually be laid out tp-sharded
    wv = fluid.global_scope().find_var("tp_w")
    assert isinstance(wv, jax.Array)
    spec = wv.sharding.spec
    assert tuple(spec) in ((None, "tp"), ("tp",)) or "tp" in str(spec)


def _dense_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t = s.shape[-1]
        mask = np.tril(np.ones((t, t), bool))
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = parallel.make_mesh({"sp": 8})
    rng = np.random.RandomState(0)
    b, h, t, d = 2, 4, 64, 16
    q = rng.randn(b, h, t, d).astype(np.float32)
    k = rng.randn(b, h, t, d).astype(np.float32)
    v = rng.randn(b, h, t, d).astype(np.float32)
    with mesh:
        got = np.asarray(parallel.ring_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            axis_name="sp", causal=causal))
    want = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    from paddle_tpu.parallel.ring import ulysses_attention
    mesh = parallel.make_mesh({"sp": 8})
    rng = np.random.RandomState(1)
    b, h, t, d = 2, 8, 32, 8
    q = rng.randn(b, h, t, d).astype(np.float32)
    k = rng.randn(b, h, t, d).astype(np.float32)
    v = rng.randn(b, h, t, d).astype(np.float32)
    with mesh:
        got = np.asarray(ulysses_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            axis_name="sp", causal=causal))
    want = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ring_attention_differentiable():
    mesh = parallel.make_mesh({"sp": 4})
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))

    def loss_fn(q, k, v):
        with mesh:
            return jnp.sum(parallel.ring_attention(q, k, v, mesh,
                                                   axis_name="sp") ** 2)

    g = jax.jit(jax.grad(loss_fn))(q, k, v)    # ONE program, not an op at a time
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).max()) > 0


def test_collective_ops_identity_outside_mesh():
    x = fluid.layers.data("x", [4])
    blk = fluid.default_main_program().current_block()
    out = blk.create_var(name="ar_out", dtype="float32")
    blk.append_op(type="c_allreduce_sum", inputs={"X": [x]},
                  outputs={"Out": [out]})
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((2, 4), np.float32)
    got, = exe.run(feed={"x": xv}, fetch_list=[out])
    np.testing.assert_allclose(got, xv)
