"""Pipeline (GPipe over pp axis) and MoE (ep axis) tests on the virtual
8-device CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import parallel


def test_gpipe_matches_sequential():
    mesh = parallel.make_mesh({"pp": 4})
    rng = np.random.RandomState(0)
    s, d = 4, 8
    ws = rng.randn(s, d, d).astype(np.float32) * 0.3
    bs = rng.randn(s, d).astype(np.float32) * 0.1
    params = {"w": jnp.asarray(ws), "b": jnp.asarray(bs)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    m, mb = 6, 4
    xs = rng.randn(m, mb, d).astype(np.float32)
    got = np.asarray(parallel.gpipe(stage_fn, params, jnp.asarray(xs),
                                    mesh, axis_name="pp"))
    # sequential reference
    want = xs.copy()
    for i in range(s):
        want = np.tanh(want @ ws[i] + bs[i])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_gpipe_differentiable():
    mesh = parallel.make_mesh({"pp": 2})
    rng = np.random.RandomState(1)
    s, d = 2, 4
    params = {"w": jnp.asarray(rng.randn(s, d, d).astype(np.float32) * 0.3)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    xs = jnp.asarray(rng.randn(3, 2, d).astype(np.float32))

    def loss(params):
        return jnp.sum(parallel.gpipe(stage_fn, params, xs, mesh) ** 2)

    g = jax.jit(jax.grad(loss))(params)    # ONE program, not an op at a time
    arr = np.asarray(g["w"])
    assert np.isfinite(arr).all()
    assert np.abs(arr).max() > 0
    # both stages' params must receive gradient
    assert np.abs(arr[0]).max() > 0 and np.abs(arr[1]).max() > 0


def test_moe_routing_and_shapes():
    rng = np.random.RandomState(2)
    t, d, e, h = 32, 8, 4, 16
    x = jnp.asarray(rng.randn(t, d).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(d, e).astype(np.float32))
    w_up = jnp.asarray(rng.randn(e, d, h).astype(np.float32) * 0.2)
    w_down = jnp.asarray(rng.randn(e, h, d).astype(np.float32) * 0.2)
    out, aux = parallel.moe_ffn(x, gate_w, w_up, w_down,
                                capacity_factor=2.0)
    assert out.shape == (t, d)
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0

    # with generous capacity, each kept token must equal its top-1 expert's
    # FFN output scaled by the gate prob
    probs = np.asarray(jax.nn.softmax(x @ gate_w, axis=-1))
    top = probs.argmax(-1)
    xn = np.asarray(x)
    for i in range(5):
        ei = int(top[i])
        hi = np.maximum(xn[i] @ np.asarray(w_up)[ei], 0)
        want = (hi @ np.asarray(w_down)[ei]) * probs[i, ei]
        np.testing.assert_allclose(np.asarray(out)[i], want, rtol=1e-3,
                                   atol=1e-4)


def test_moe_capacity_drops_overflow():
    rng = np.random.RandomState(3)
    t, e = 16, 2
    # force all tokens to expert 0
    logits = jnp.asarray(np.tile([10.0, -10.0], (t, 1)).astype(np.float32))
    dispatch, combine, aux = parallel.top1_gating(logits, capacity=4)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == 4          # only capacity tokens kept
    assert d[:, 1].sum() == 0


def test_moe_under_ep_mesh():
    mesh = parallel.make_mesh({"ep": 4})
    rng = np.random.RandomState(4)
    t, d, e, h = 16, 8, 4, 8
    x = jnp.asarray(rng.randn(t, d).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(d, e).astype(np.float32))
    w_up = jnp.asarray(rng.randn(e, d, h).astype(np.float32) * 0.2)
    w_down = jnp.asarray(rng.randn(e, h, d).astype(np.float32) * 0.2)

    with mesh:
        jit_moe = jax.jit(lambda *a: parallel.moe_ffn(*a, mesh=mesh))
        out, aux = jit_moe(x, gate_w, w_up, w_down)
    base, _ = parallel.moe_ffn(x, gate_w, w_up, w_down)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-4, atol=2e-5)


def test_moe_top2_identical_experts_equals_dense():
    # with normalized top-2 combine weights and all experts equal, the MoE
    # output must equal the single dense FFN exactly (weights sum to 1)
    rng = np.random.RandomState(5)
    t, d, e, h = 16, 8, 4, 16
    x = jnp.asarray(rng.randn(t, d).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(d, e).astype(np.float32))
    wu = rng.randn(d, h).astype(np.float32) * 0.2
    wd = rng.randn(h, d).astype(np.float32) * 0.2
    w_up = jnp.asarray(np.tile(wu, (e, 1, 1)))
    w_down = jnp.asarray(np.tile(wd, (e, 1, 1)))
    out, aux = parallel.moe_ffn(x, gate_w, w_up, w_down, top_k=2,
                                capacity_factor=4.0)
    want = np.maximum(np.asarray(x) @ wu, 0) @ wd
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_moe_top2_overflow_metric():
    t, e, cap = 16, 2, 4
    # all tokens pick expert 0 first (logit 10), expert 1 second (logit 5):
    # rank-0 keeps cap of 16, rank-1 keeps cap of 16 → dropped 24/32
    logits = jnp.asarray(np.tile([10.0, 5.0], (t, 1)).astype(np.float32))
    dispatch, combine, aux, overflow = parallel.topk_gating(
        logits, capacity=cap, k=2)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == cap and d[:, 1].sum() == cap
    np.testing.assert_allclose(float(overflow), 24.0 / 32.0)
    # kept combine weights normalized over the two selected gates
    c = np.asarray(combine)
    probs = np.asarray(jax.nn.softmax(logits, -1))[0]
    np.testing.assert_allclose(c[0, 0].sum(),
                               probs[0] / (probs[0] + probs[1]), rtol=1e-5)


def test_moe_top2_under_ep_mesh_matches_local():
    mesh = parallel.make_mesh({"ep": 4})
    rng = np.random.RandomState(6)
    t, d, e, h = 32, 8, 4, 8
    x = jnp.asarray(rng.randn(t, d).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(d, e).astype(np.float32))
    w_up = jnp.asarray(rng.randn(e, d, h).astype(np.float32) * 0.2)
    w_down = jnp.asarray(rng.randn(e, h, d).astype(np.float32) * 0.2)
    with mesh:
        jit_moe = jax.jit(lambda *a: parallel.moe_ffn(*a, mesh=mesh,
                                                      top_k=2))
        out, aux = jit_moe(x, gate_w, w_up, w_down)
    base, _ = parallel.moe_ffn(x, gate_w, w_up, w_down, top_k=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-4, atol=2e-5)


def test_moe_top2_grads_reach_gate_and_experts():
    rng = np.random.RandomState(7)
    t, d, e, h = 16, 8, 4, 8
    x = jnp.asarray(rng.randn(t, d).astype(np.float32))
    params = {
        "g": jnp.asarray(rng.randn(d, e).astype(np.float32)),
        "u": jnp.asarray(rng.randn(e, d, h).astype(np.float32) * 0.2),
        "d": jnp.asarray(rng.randn(e, h, d).astype(np.float32) * 0.2),
    }

    def loss(p):
        out, aux = parallel.moe_ffn(x, p["g"], p["u"], p["d"], top_k=2)
        return jnp.sum(out ** 2) + 0.01 * aux

    g = jax.jit(jax.grad(loss))(params)    # ONE program, not an op at a time
    for k in ("g", "u", "d"):
        arr = np.asarray(g[k])
        assert np.isfinite(arr).all() and np.abs(arr).max() > 0, k


def test_sparse_moe_layer_top2_overflow_fetchable():
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6, 16])
        out, aux, ovf = fluid.layers.sparse_moe(
            x, num_experts=4, d_inner=32, top_k=2, return_overflow=True)
        loss = fluid.layers.mean(out) + fluid.layers.scale(aux, 0.01)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.random.RandomState(8).randn(4, 6, 16).astype(np.float32)
        l1, o1 = exe.run(feed={"x": xv}, fetch_list=[loss, ovf])
    assert np.isfinite(np.asarray(l1)).all()
    o1 = float(np.asarray(o1))
    assert 0.0 <= o1 <= 1.0


def test_gpipe_heterogeneous_stage_params():
    """Per-stage parameter SHAPES differ (list-of-pytrees form): stage 0
    is a dense tanh layer, stage 1 an affine scale — same activation
    shape, different param shapes, selected by stage index."""
    mesh = parallel.make_mesh({"pp": 2})
    rng = np.random.RandomState(9)
    d = 6
    w = rng.randn(d, d).astype(np.float32) * 0.4
    s = rng.rand(d).astype(np.float32) + 0.5
    b = rng.randn(d).astype(np.float32) * 0.1
    params = [{"w": jnp.asarray(w)},
              {"s": jnp.asarray(s), "b": jnp.asarray(b)}]

    def stage_fn(p, x):
        if "w" in p:
            return jnp.tanh(x @ p["w"])
        return x * p["s"] + p["b"]

    xs = rng.randn(4, 3, d).astype(np.float32)
    got = np.asarray(parallel.gpipe(stage_fn, params, jnp.asarray(xs),
                                    mesh, axis_name="pp"))
    want = np.tanh(xs @ w) * s + b
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    # differentiable through both heterogeneous stages
    def loss(ps):
        return jnp.sum(parallel.gpipe(stage_fn, ps, jnp.asarray(xs),
                                      mesh, axis_name="pp") ** 2)

    g = jax.jit(jax.grad(loss))(params)    # ONE program, not an op at a time
    assert np.abs(np.asarray(g[0]["w"])).max() > 0
    assert np.abs(np.asarray(g[1]["s"])).max() > 0


def test_gpipe_interleaved_matches_sequential():
    """Interleaved virtual stages (V chunks per device, Megatron
    assignment {d, d+S, ...}): same math as the sequential stack, with
    the bubble cut to (S-1)/V chunk-times (pipeline.gpipe_interleaved)."""
    mesh = parallel.make_mesh({"pp": 4})
    rng = np.random.RandomState(0)
    s, v, d = 4, 2, 8
    L = s * v                              # one layer per chunk
    ws = rng.randn(L, d, d).astype(np.float32) * 0.3
    bs = rng.randn(L, d).astype(np.float32) * 0.1
    # device dd holds global chunks {dd, dd+S}: [L,...] -> [V,S,...] ->
    # [S,V,...] (the op lowering's interleave reshape, per=1 folded in)
    params = {
        "w": jnp.asarray(ws).reshape(v, s, d, d).swapaxes(0, 1),
        "b": jnp.asarray(bs).reshape(v, s, d).swapaxes(0, 1)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    m, mb = 4, 2                           # M <= S regime
    xs = rng.randn(m, mb, d).astype(np.float32)
    got = np.asarray(parallel.gpipe_interleaved(
        stage_fn, params, jnp.asarray(xs), mesh, n_chunks=v,
        axis_name="pp"))
    want = xs.copy()
    for i in range(L):
        want = np.tanh(want @ ws[i] + bs[i])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    # differentiable, every chunk's params receive gradient
    def loss(ps):
        return jnp.sum(parallel.gpipe_interleaved(
            stage_fn, ps, jnp.asarray(xs), mesh, n_chunks=v,
            axis_name="pp") ** 2)

    g = np.asarray(jax.jit(jax.grad(loss))(params)["w"])
    assert np.isfinite(g).all()
    assert (np.abs(g).reshape(s * v, -1).max(axis=1) > 0).all()

    # M > S is a different schedule regime: refused loudly
    with pytest.raises(ValueError, match="interleaved"):
        parallel.gpipe_interleaved(
            stage_fn, params, jnp.asarray(rng.randn(6, 2, d)), mesh,
            n_chunks=v, axis_name="pp")


def _lm_parallel_loss(strategy, mesh_axes, prefix, num_experts=0):
    """Build transformer_lm_parallel under `strategy`, run ONE step on
    the given mesh, return (loss, updated first pipeline weight)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.models import transformer as T

    mesh = parallel.make_mesh(mesh_axes) if mesh_axes else None
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        avg, _ = T.transformer_lm_parallel(
            vocab_size=64, max_len=16, n_layer=4, n_head=4, d_model=32,
            d_inner=64, strategy=strategy, num_experts=num_experts)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(avg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(5)
        feeds = T.make_lm_batch(rng, 8, 16, 64)
        if mesh is None:
            l, = exe.run(feed=feeds, fetch_list=[avg])
        else:
            pexe = fluid.ParallelExecutor(loss_name=avg.name,
                                          main_program=main, mesh=mesh,
                                          scope=scope)
            l, = pexe.run([avg], feed=feeds)
        wname = prefix + "pipeline_stack_0.wq"
        w = scope.find_var(wname)
        return float(np.asarray(l)), (np.asarray(w) if w is not None
                                      else None)


@pytest.mark.slow  # ISSUE-11 durations audit: >10 s on tier-1
def test_pipeline_composes_with_tp_and_sp():
    """pp x tp (Megatron shards + psum inside the stage) and pp x sp
    (ring attention inside the stage) match the pp-only run, which
    matches dense single-device math (lifting the round-3 refusal at
    models/transformer.py)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    st_pp = parallel.DistributedStrategy(dp=1, pp=2)
    l_pp, w_pp = _lm_parallel_loss(st_pp, {"dp": 1, "pp": 2}, "pa_")
    st_tp = parallel.DistributedStrategy(dp=1, pp=2, tp=2)
    l_tp, w_tp = _lm_parallel_loss(st_tp, {"dp": 1, "pp": 2, "tp": 2},
                                   "pb_")
    st_sp = parallel.DistributedStrategy(dp=1, pp=2, sp=2)
    l_sp, w_sp = _lm_parallel_loss(st_sp, {"dp": 1, "pp": 2, "sp": 2},
                                   "pc_")
    np.testing.assert_allclose(l_tp, l_pp, rtol=2e-4)
    np.testing.assert_allclose(l_sp, l_pp, rtol=2e-4)
    # updated WEIGHTS match too, not just the loss
    np.testing.assert_allclose(w_tp, w_pp, rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(w_sp, w_pp, rtol=2e-3, atol=2e-5)


def test_pipeline_interleaved_schedule_parity():
    """The interleaved schedule through the layer DSL trains the same
    model as gpipe (same loss + updated weights)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    st_g = parallel.DistributedStrategy(dp=2, pp=2)
    l_g, w_g = _lm_parallel_loss(st_g, {"dp": 2, "pp": 2}, "qa_")
    st_i = parallel.DistributedStrategy(dp=2, pp=2,
                                        pp_schedule="interleaved")
    l_i, w_i = _lm_parallel_loss(st_i, {"dp": 2, "pp": 2}, "qb_")
    np.testing.assert_allclose(l_i, l_g, rtol=2e-4)
    np.testing.assert_allclose(w_i, w_g, rtol=2e-3, atol=2e-5)


@pytest.mark.slow  # ISSUE-11 durations audit: >10 s on tier-1
def test_pipeline_full_composition_pp_tp_sp():
    """pp x tp x sp in ONE stage body: Megatron-sharded weights with
    per-sublayer psum AND ring attention over the sequence shard, inside
    the pipeline shard_map — the deepest composition the stage supports."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    st_pp = parallel.DistributedStrategy(dp=1, pp=2)
    l_pp, w_pp = _lm_parallel_loss(st_pp, {"dp": 1, "pp": 2}, "fa_")
    st_all = parallel.DistributedStrategy(dp=1, pp=2, tp=2, sp=2)
    l_all, w_all = _lm_parallel_loss(
        st_all, {"dp": 1, "pp": 2, "tp": 2, "sp": 2}, "fb_")
    np.testing.assert_allclose(l_all, l_pp, rtol=2e-4)
    np.testing.assert_allclose(w_all, w_pp, rtol=2e-3, atol=2e-5)


def test_pipeline_interleaved_with_recompute():
    """Interleaved virtual stages compose with per-layer recompute
    (jax.checkpoint inside the chunk body): same trained model as plain
    gpipe."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name

    def run(schedule, recompute, prefix):
        mesh = parallel.make_mesh({"dp": 2, "pp": 2})
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 23
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), unique_name.guard(prefix):
            x = fluid.layers.data("x", [8, 16])
            y = fluid.layers.pipelined_decoder_stack(
                x, n_layer=4, n_head=2, d_inner=32,
                schedule=schedule, recompute=recompute)
            loss = fluid.layers.mean(fluid.layers.square(y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            pexe = fluid.ParallelExecutor(loss_name=loss.name,
                                          main_program=main, mesh=mesh,
                                          scope=scope)
            xv = np.random.RandomState(6).rand(8, 8, 16).astype(
                np.float32)
            l, = pexe.run([loss], feed={"x": xv})
            wname = prefix + "pipeline_stack_0.wq"
            return (float(np.asarray(l)),
                    np.asarray(scope.find_var(wname)))

    l_g, w_g = run("gpipe", False, "ra_")
    l_ir, w_ir = run("interleaved", True, "rb_")
    np.testing.assert_allclose(l_ir, l_g, rtol=1e-5)
    np.testing.assert_allclose(w_ir, w_g, rtol=1e-4, atol=1e-6)


@pytest.mark.slow  # ISSUE-11 durations audit: >10 s on tier-1
def test_pipeline_composes_with_ep_moe():
    """pp x ep — the last composition refusal, lifted: MoE FFN inside
    the pipeline stage body, expert stacks sharded over ep with the
    dispatch all-to-all nested in the stage (moe_ffn_pp_sharded), must
    match the dense fallback's group-wise routing exactly (the
    moe_gate_groups static-granularity contract)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    st = parallel.DistributedStrategy(dp=2, pp=2, ep=2)
    l_dense, w_dense = _lm_parallel_loss(st, None, "pe_", num_experts=4)
    l_ep, w_ep = _lm_parallel_loss(st, {"dp": 2, "pp": 2, "ep": 2},
                                   "pe_", num_experts=4)
    np.testing.assert_allclose(l_ep, l_dense, rtol=2e-4)
    np.testing.assert_allclose(w_ep, w_dense, rtol=2e-3, atol=2e-5)


@pytest.mark.slow  # ISSUE-11 durations audit: >10 s on tier-1
def test_pipeline_moe_interleaved_schedule():
    """pp x ep under the interleaved virtual-stage schedule (aux loss
    rides the live-tick mask through the V-lap tick loop)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    st = parallel.DistributedStrategy(dp=2, pp=2, ep=2,
                                      pp_schedule="interleaved",
                                      pp_virtual_stages=2)
    l_dense, w_dense = _lm_parallel_loss(st, None, "pi_", num_experts=4)
    l_ep, w_ep = _lm_parallel_loss(st, {"dp": 2, "pp": 2, "ep": 2},
                                   "pi_", num_experts=4)
    np.testing.assert_allclose(l_ep, l_dense, rtol=2e-4)
    np.testing.assert_allclose(w_ep, w_dense, rtol=2e-3, atol=2e-5)


def test_pipeline_moe_rejects_sp():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    st = parallel.DistributedStrategy(dp=1, pp=2, sp=2, ep=2)
    with pytest.raises(Exception, match="sequence"):
        _lm_parallel_loss(st, {"dp": 1, "pp": 2, "sp": 2, "ep": 2},
                          "ps_", num_experts=4)


def test_pipeline_moe_gate_groups_must_match_mesh():
    """The static routing granularity (dp*ep baked into the program)
    must equal the mesh's actual token split — a mismatched mesh would
    silently route differently than the program's fallback."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    st = parallel.DistributedStrategy(dp=2, pp=2, ep=2)  # groups = 4
    with pytest.raises(Exception, match="moe_gate_groups"):
        # run on a mesh whose dp*ep = 2
        _lm_parallel_loss(st, {"dp": 1, "pp": 2, "ep": 2}, "pg_",
                          num_experts=4)


def test_pipeline_moe_top2_parity():
    """pp x ep with GShard top-2 routing (normalized combine weights)
    through the pipelined stage body matches the dense fallback — the
    layer-level knob (moe_top_k) the flagship builder defaults away."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name

    def run(mesh_axes, prefix):
        mesh = parallel.make_mesh(mesh_axes) if mesh_axes else None
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 23
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), unique_name.guard(prefix):
            x = fluid.layers.data("x", [16, 32])
            y = fluid.layers.data("y", [16, 32])
            out, aux = fluid.layers.pipelined_decoder_stack(
                x, n_layer=2, n_head=4, d_inner=64, num_experts=4,
                moe_top_k=2, num_microbatches=2, moe_gate_groups=4)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(out, y)) \
                + fluid.layers.scale(aux, 0.01)
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(9)
            feeds = {"x": rng.rand(8, 16, 32).astype(np.float32),
                     "y": rng.rand(8, 16, 32).astype(np.float32)}
            if mesh is None:
                l, = exe.run(feed=feeds, fetch_list=[loss])
            else:
                pexe = fluid.ParallelExecutor(loss_name=loss.name,
                                              main_program=main,
                                              mesh=mesh, scope=scope)
                l, = pexe.run([loss], feed=feeds)
            # POST-step expert weight: proves the top-2 combine's
            # cotangent split survives the sharded stage body, not just
            # the (pre-update) loss value
            w = np.asarray(scope.find_var(
                prefix + "pipeline_stack_0.w_up"))
        return float(np.asarray(l)), w

    dense, w_dense = run(None, "t2_")
    sharded, w_sharded = run({"dp": 2, "pp": 2, "ep": 2}, "t2_")
    np.testing.assert_allclose(sharded, dense, rtol=2e-4)
    np.testing.assert_allclose(w_sharded, w_dense, rtol=2e-3, atol=2e-5)
