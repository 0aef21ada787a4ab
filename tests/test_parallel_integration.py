"""SP/PP/EP integration through the Program IR + ParallelExecutor (round-1
review): the same fluid-built flagship program must produce the same loss
single-device (dense fallbacks) and sharded on a mesh (ring attention /
GPipe / MoE all-to-all), proving the parallel subsystem is a framework
feature, not a library."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import parallel
from paddle_tpu.models import transformer as T

BATCH, MAX_LEN, VOCAB, D_MODEL, N_LAYER, N_HEAD = 8, 16, 50, 32, 2, 4


def _feeds(rng):
    f = T.make_lm_batch(rng, BATCH, MAX_LEN, VOCAB)
    return {k: np.asarray(v) for k, v in f.items()}


def _build(strategy=None, num_experts=0):
    avg_cost, _ = T.transformer_lm_parallel(
        vocab_size=VOCAB, max_len=MAX_LEN, n_layer=N_LAYER, n_head=N_HEAD,
        d_model=D_MODEL, d_inner=64, strategy=strategy,
        num_experts=num_experts)
    return avg_cost


def _copy_scope(src_scope, names):
    dst = fluid.Scope()
    for n in names:
        v = src_scope.find_var(n)
        if v is not None:
            dst.set(n, np.array(np.asarray(v)))
    return dst


def _parity(strategy, mesh_axes, num_experts=0, rtol=2e-4, n_steps=3):
    """N>=3 optimizer steps on both paths: per-step loss parity plus
    final-weight parity — multi-step catches RNG-stream, accumulator-
    sharding and LR-counter drift that a single step cannot see
    (round-3 review)."""
    batches = [_feeds(np.random.RandomState(7 + 31 * i))
               for i in range(n_steps)]
    avg_cost = _build(strategy, num_experts)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(avg_cost)

    names = [v.name for v in
             fluid.default_main_program().global_block().vars.values()
             if v.persistable]
    # init once, clone the params, run the SAME steps single-device and
    # sharded from identical state
    scope2 = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope2):
        exe.run(fluid.default_startup_program())
    scope1b = _copy_scope(scope2, names)
    singles = []
    with fluid.scope_guard(scope1b):
        for feeds in batches:
            l, = exe.run(feed=feeds, fetch_list=[avg_cost])
            singles.append(float(np.asarray(l)))

    mesh = parallel.make_mesh(mesh_axes)
    pexe = parallel.ParallelExecutor(loss_name=avg_cost.name, mesh=mesh,
                                     scope=scope2)
    for i, feeds in enumerate(batches):
        l, = pexe.run(fetch_list=[avg_cost], feed=feeds)
        loss2 = float(np.asarray(l))
        assert np.isfinite(loss2)
        np.testing.assert_allclose(loss2, singles[i], rtol=rtol,
                                   atol=1e-5,
                                   err_msg="step %d of %d" % (i, n_steps))
    # and the updated params match after ALL steps (the optimizer ran
    # sharded with its accumulators/counters sharded alongside)
    for n in names:
        a = np.asarray(scope1b.find_var(n))
        b = np.asarray(scope2.find_var(n))
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=2e-4,
                                   err_msg="weight %s after %d steps"
                                   % (n, n_steps))


def test_flagship_dp_tp_parity():
    _parity(parallel.DistributedStrategy(dp=4, tp=2),
            {"dp": 4, "tp": 2})


def test_flagship_sp_ring_parity():
    _parity(parallel.DistributedStrategy(dp=2, sp=4),
            {"dp": 2, "sp": 4}, rtol=5e-4)


def test_flagship_pp_parity():
    _parity(parallel.DistributedStrategy(dp=2, pp=2),
            {"dp": 2, "pp": 2})


def test_flagship_moe_ep_parity():
    _parity(parallel.DistributedStrategy(dp=2, ep=4),
            {"dp": 2, "ep": 4}, num_experts=4)


def test_flagship_3d_dp_tp_sp_parity():
    # 3-axis composition on one mesh: batch on dp, Megatron weight shards
    # on tp, ring attention over sp — all through the same Program
    _parity(parallel.DistributedStrategy(dp=2, tp=2, sp=2),
            {"dp": 2, "tp": 2, "sp": 2}, rtol=5e-4)


def test_sp_attention_op_matches_dense_numpy(rng):
    b, h, t, d = 2, 2, 8, 4
    qv = rng.randn(b, h, t, d).astype(np.float32)
    kv = rng.randn(b, h, t, d).astype(np.float32)
    vv = rng.randn(b, h, t, d).astype(np.float32)
    q = fluid.layers.data("q", [h, t, d])
    k = fluid.layers.data("k", [h, t, d])
    v = fluid.layers.data("v", [h, t, d])
    out = fluid.layers.sequence_parallel_attention(q, k, v, causal=True)
    exe = fluid.Executor(fluid.CPUPlace())
    got, = exe.run(feed={"q": qv, "k": kv, "v": vv}, fetch_list=[out])

    s = np.einsum("bhqd,bhkd->bhqk", qv, kv) * (d ** -0.5)
    mask = np.tril(np.ones((t, t), bool))
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, vv)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-5)


def test_moe_layer_trains_single_device(rng):
    x = fluid.layers.data("x", [6, 16])
    out, aux = fluid.layers.sparse_moe(x, num_experts=4, d_inner=32)
    loss = fluid.layers.mean(out) + fluid.layers.scale(aux, 0.01)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = rng.randn(4, 6, 16).astype(np.float32)
    l1, = exe.run(feed={"x": xv}, fetch_list=[loss])
    assert np.isfinite(np.asarray(l1)).all()
