"""Rematerialization (layers.recompute regions + append_backward
checkpoint=True) — ops/control_flow.py recompute_block,
core/executor.py _lower_with_grad.

Parity contract: wrapping layers in recompute regions (or checkpointing
the whole forward) changes WHEN activations are computed, never what —
loss and gradients must match the plain run bit-for-bit at test
tolerances. Measured effect on the real chip (PERF.md): at T=8192 the
flagship LM trains at 2x the plain batch in the same HBM.

What a region keeps (ISSUE 42): the output and the lse rows of a flash
forward kernel, by the names the kernels' fwd rules give them, so the
kernel runs once a layer; everything else is recomputed, and a region
with no kernel in it lowers as under a bare jax.checkpoint. Since
ISSUE 48 also the results of the regions' `mul` ops, for as many as a
byte budget reckoned from the device's limit admits: nothing on the
CPU, which states no limit. Since ISSUE 52 the plan prices every kind
of value a region could keep (a `mul` result, a short convolution's, an
expert layer's output, its router's results, its weights as it computes
with them) and charges the last region's values to the head alone: its
backward follows at once, and the values of the regions before it are
what that backward holds besides (tests/test_recompute_kinds.py has the
new kinds' cases).
"""

import collections

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import _gather_state, _normalize_feeds
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.ops import flash_attention as FA


def _run_lm(recompute, checkpoint=False, dropout=0.0, prefix="x_"):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        cost, _ = T.transformer_lm(vocab_size=64, max_len=16, n_layer=2,
                                   n_head=4, d_model=32, d_inner=64,
                                   packed=True, recompute=recompute,
                                   dropout_rate=dropout)
        pg = fluid.append_backward(cost, checkpoint=checkpoint)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feeds = {k: np.asarray(v) for k, v in
                 T.make_lm_batch(rng, 4, 16, 64).items()}
        fetch = [cost] + [g.name for _, g in pg[:2]]
        vals = exe.run(main, feed=feeds, fetch_list=fetch)
    return float(np.asarray(vals[0])), [np.asarray(v) for v in vals[1:]]


def test_recompute_region_matches_plain():
    l0, g0 = _run_lm(False, prefix="p_")
    l1, g1 = _run_lm(True, prefix="r_")
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_marker_checkpoint_matches_plain():
    l0, g0 = _run_lm(False, prefix="p2_")
    l2, g2 = _run_lm(False, checkpoint=True, prefix="c_")
    np.testing.assert_allclose(l2, l0, rtol=1e-5)
    for a, b in zip(g2, g0):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_recompute_with_dropout_trains():
    # rng-consuming ops inside a region must replay the SAME mask in the
    # recomputed backward (a mismatch would corrupt grads -> NaN/garbage
    # training); prove several steps of training stay finite and improve
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        cost, _ = T.transformer_lm(vocab_size=32, max_len=8, n_layer=2,
                                   n_head=2, d_model=16, d_inner=32,
                                   packed=True, recompute=True,
                                   dropout_rate=0.3)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(1)
        losses = []
        for _ in range(20):
            feeds = {k: np.asarray(v) for k, v in
                     T.make_lm_batch(rng, 4, 8, 32).items()}
            l, = exe.run(main, feed=feeds, fetch_list=[cost])
            losses.append(float(np.asarray(l)))
    assert all(np.isfinite(losses)), losses
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_recompute_region_preserves_lod():
    # a sequence op inside the region changes the LoD; the region must
    # export the NEW lengths so a later sequence op segments correctly
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", [2], lod_level=1)
        with fluid.layers.recompute():
            r = fluid.layers.lod_reset(x, target_lod=[0, 3, 6])
            s = fluid.layers.scale(r, 1.0)
        pooled = fluid.layers.sequence_pool(s, "sum")
        exe = fluid.Executor(fluid.CPUPlace())
        data = np.arange(12, dtype=np.float32).reshape(6, 2)
        out, = exe.run(feed={"x": fluid.LoDTensor(data, [[0, 2, 6]])},
                       fetch_list=[pooled])
    want = np.stack([data[:3].sum(0), data[3:].sum(0)])
    np.testing.assert_allclose(np.asarray(out), want)


def test_recompute_region_nan_guard(monkeypatch):
    # per-op NaN guards must fire for ops INSIDE a region, naming the
    # real op — even when the NaN is masked out of the region's output
    from paddle_tpu import flags
    monkeypatch.setenv("PADDLE_TPU_CHECK_NAN_INF", "1")
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", [3])
        with fluid.layers.recompute():
            bad = fluid.layers.log(x)          # log(-1) -> NaN inside
            masked = fluid.layers.elementwise_mul(
                bad, fluid.layers.fill_constant([1], "float32", 0.0))
        out = fluid.layers.mean(masked)        # NaN*0 -> masked output
        exe = fluid.Executor(fluid.CPUPlace())
        xv = -np.ones((2, 3), np.float32)
        with pytest.raises(FloatingPointError, match="log"):
            exe.run(feed={"x": xv}, fetch_list=[out])


def test_checkpoint_composes_with_accumulation():
    from paddle_tpu import parallel

    def train(accum, ckpt, prefix):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 9
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), unique_name.guard(prefix):
            x = fluid.layers.data("x", [8])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.fc(x, 16, act="tanh")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.append_backward(loss, checkpoint=ckpt)
            sgd_in = [(p.name, p.name + "@GRAD") for p in
                      main.global_block().all_parameters()]
            blk = main.global_block()
            lr = fluid.layers.fill_constant([1], "float32", 0.1)
            for p, g in sgd_in:
                blk.append_op("sgd", {"Param": [p], "Grad": [g],
                                      "LearningRate": [lr.name]},
                              {"ParamOut": [p]}, {})
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            pexe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, scope=scope,
                strategy=parallel.DistributedStrategy(
                    gradient_accumulation_steps=accum))
            rng = np.random.RandomState(0)
            xv = rng.rand(16, 8).astype(np.float32)
            yv = rng.rand(16, 1).astype(np.float32)
            ls = [float(np.asarray(
                pexe.run([loss], feed={"x": xv, "y": yv})[0]))
                for _ in range(3)]
            params = {n: np.asarray(scope.find_var(n)).copy()
                      for n, _ in sgd_in}
        return ls, params

    l_plain, p_plain = train(4, False, "a_")
    l_ckpt, p_ckpt = train(4, True, "b_")
    np.testing.assert_allclose(l_ckpt, l_plain, rtol=1e-5)
    # match params across the two builds by prefix-stripped name
    def strip(d, pre):
        def s(k):
            while k.startswith(pre):
                k = k[len(pre):]
            return k
        return {s(k): v for k, v in d.items()}
    a, b = strip(p_plain, "a_"), strip(p_ckpt, "b_")
    assert a.keys() == b.keys(), (sorted(a), sorted(b))
    for n in a:
        np.testing.assert_allclose(b[n], a[n], rtol=1e-5, atol=1e-6)


def test_recompute_output_readable_by_while_body():
    # a later control-flow op reads the region output only inside ITS
    # sub-block — the export scan must look through sub-blocks
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", [4])
        with fluid.layers.recompute():
            h = fluid.layers.fc(x, 4, bias_attr=False,
                                param_attr=fluid.ParamAttr(
                                    name="w_whl",
                                    initializer=fluid.initializer.Constant(
                                        0.5)))
        i = fluid.layers.fill_constant([1], "int64", 0)
        acc = fluid.layers.fill_constant([4, 4], "float32", 0.0)
        n = fluid.layers.fill_constant([1], "int64", 3)
        cond = fluid.layers.less_than(i, n)
        w = fluid.layers.While(cond, loop_vars=[i, acc])
        with w.block():
            acc2 = fluid.layers.elementwise_add(acc, h)   # h read in body
            fluid.layers.assign(acc2, acc)
            i2 = fluid.layers.increment(i)
            fluid.layers.assign(fluid.layers.less_than(i2, n), cond)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.ones((4, 4), np.float32)
        out, = exe.run(feed={"x": xv}, fetch_list=[acc])
    np.testing.assert_allclose(np.asarray(out), 3 * (xv @ np.full(
        (4, 4), 0.5, np.float32)), rtol=1e-6)


def test_recompute_terminal_output_fetchable():
    # a region output with no later consumer must still be fetchable
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", [4])
        with fluid.layers.recompute():
            h = fluid.layers.fc(x, 2, bias_attr=False,
                                param_attr=fluid.ParamAttr(
                                    name="w_tf",
                                    initializer=fluid.initializer.Constant(
                                        1.0)))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.arange(8, dtype=np.float32).reshape(2, 4)
        out, = exe.run(feed={"x": xv}, fetch_list=[h])
    np.testing.assert_allclose(np.asarray(out), xv @ np.ones((4, 2),
                                                             np.float32))


def test_pipeline_stack_recompute_gpipe_mesh_parity():
    # the GPipe branch (pp mesh) with recompute on: parity vs the same
    # program without recompute on the same mesh
    import jax
    from jax.sharding import Mesh
    from paddle_tpu import parallel
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")

    def run(recompute, prefix):
        mesh = parallel.make_mesh({"dp": 2, "pp": 2})
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 17
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), unique_name.guard(prefix):
            x = fluid.layers.data("x", [8, 16])
            y = fluid.layers.pipelined_decoder_stack(
                x, n_layer=2, n_head=2, d_inner=32, recompute=recompute)
            loss = fluid.layers.mean(fluid.layers.square(y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            pexe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, mesh=mesh,
                scope=scope)
            xv = np.random.RandomState(4).rand(16, 8, 16).astype(
                np.float32)
            l, = pexe.run([loss], feed={"x": xv})
        return float(np.asarray(l))

    l0 = run(False, "gp_")
    l1 = run(True, "gr_")
    np.testing.assert_allclose(l1, l0, rtol=1e-5)


def test_pipeline_stack_recompute_matches_plain():
    def run(recompute, prefix):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 13
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), unique_name.guard(prefix):
            x = fluid.layers.data("x", [8, 16])
            y = fluid.layers.pipelined_decoder_stack(
                x, n_layer=2, n_head=2, d_inner=32, recompute=recompute)
            loss = fluid.layers.mean(fluid.layers.square(y))
            pg = fluid.append_backward(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            xv = np.random.RandomState(3).rand(2, 8, 16).astype(np.float32)
            vals = exe.run(main, feed={"x": xv},
                           fetch_list=[loss, pg[0][1].name])
        return float(np.asarray(vals[0])), np.asarray(vals[1])

    l0, g0 = run(False, "pp_")
    l1, g1 = run(True, "pr_")
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_allclose(g1, g0, rtol=1e-4, atol=1e-6)


def test_recompute_region_general_graph():
    # non-transformer usage: arbitrary ops in a region, grads through two
    # chained regions
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", [6])
        with fluid.layers.recompute():
            h = fluid.layers.fc(x, 12, act="tanh")
        with fluid.layers.recompute():
            h2 = fluid.layers.fc(h, 6, act="relu")
        loss = fluid.layers.mean(fluid.layers.square(h2))
        pg = fluid.append_backward(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.random.RandomState(2).rand(4, 6).astype(np.float32)
        l, g = exe.run(main, feed={"x": xv},
                       fetch_list=[loss, pg[0][1].name])
        assert np.isfinite(float(np.asarray(l)))
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() > 0


# -- what a region keeps by name (ISSUE 42) ---------------------------------
_LAYERS, _B, _T, _H, _D = 2, 2, 128, 2, 128


def _through(monkeypatch, rule):
    """sp_attention's call of flash_bthd sent to the kernels in interpret
    mode through ONE of the three custom_vjps, whose fwd rule names what
    a region keeps: `_flash` (the call as it is), `_flash_lse` (ring
    attention's entry: the lse rows are a result too, and weigh the
    output here so that their cotangent is not zero) or `_flash2` (a
    score of two parts: q again against head 0's key)."""
    flash, flash_lse = FA.flash_bthd, FA.flash_bthd_lse

    def entry(q, k, v, n_head, **kw):
        if rule == "_flash":
            return flash(q, k, v, n_head, force="interpret", **kw)
        if rule == "_flash2":
            return flash(q, k, v, n_head, force="interpret", q2=q,
                         k2=k[..., :_D], **kw)
        out, lse = flash_lse(q, k, v, n_head, force="interpret", **kw)
        weight = jax.nn.sigmoid(lse).transpose(0, 2, 1)[..., None]
        return (out.reshape(_B, _T, n_head, _D) * weight).reshape(out.shape)

    monkeypatch.setattr(FA, "flash_bthd", entry)


def _region_lm(recompute, prefix):
    """A two-layer LM of 2 heads of 128 at T 128 (a kernel block is one
    head and holds all of T), float32: (program, scope, feeds, fetch
    names: the loss and the first two gradients)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        cost, _ = T.transformer_lm(vocab_size=64, max_len=_T,
                                   n_layer=_LAYERS, n_head=_H,
                                   d_model=_H * _D, d_inner=64,
                                   packed=True, recompute=recompute)
        pg = fluid.append_backward(cost)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    feeds = {k: np.asarray(v) for k, v in T.make_lm_batch(
        np.random.RandomState(0), _B, _T, 64).items()}
    return main, scope, feeds, (cost.name,) + tuple(
        g.name for _, g in pg[:2])


def _run(main, scope, feeds, fetch):
    with fluid.scope_guard(scope):
        return [np.asarray(v) for v in fluid.Executor(fluid.CPUPlace()).run(
            main, feed=feeds, fetch_list=list(fetch))]


def _step_jaxpr(main, scope, feeds, fetch):
    """The jaxpr of the whole step, gradient included, as the Executor
    builds it for these feeds and fetches."""
    state, keys = _gather_state(main, scope)
    feed_arrays, static_info = _normalize_feeds(feeds)
    step = fluid.Executor(fluid.CPUPlace())._build(
        main, tuple(sorted(feed_arrays)), fetch, keys, static_info)
    return jax.make_jaxpr(step)(state, feed_arrays, jax.random.key(0)).jaxpr


def _eqns(jaxpr):
    """Every equation, sub-jaxprs included, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _equations(jaxpr):
    """(primitive, operand avals, result avals) of every equation."""
    return [(e.primitive.name, tuple(str(v.aval) for v in e.invars),
             tuple(str(v.aval) for v in e.outvars)) for e in _eqns(jaxpr)]


def _kernels(jaxpr):
    """{name: count} of the jaxpr's pallas_call equations."""
    return dict(collections.Counter(
        e.params["name"] for e in _eqns(jaxpr)
        if e.primitive.name == "pallas_call"))


_RULES = ["_flash", "_flash_lse", "_flash2"]


@pytest.mark.parametrize("rule", _RULES)
def test_region_that_keeps_flash_results_matches_plain_to_the_bit(
        monkeypatch, rule):
    """Regions on against regions off with the kernels in the program:
    the loss and the first gradients are the same bits (what is kept is
    what the first run made; what is recomputed is the same ops on the
    same operands), and the counters say what the regions kept: out
    [B, T, H*D] and lse [B, H, T], float32 here, a layer."""
    _through(monkeypatch, rule)
    plain = _run(*_region_lm(False, "kp_"))
    regions = CF._REGIONS.value()
    kept = {n: CF._KEPT_BYTES.value(name=n) for n in FA.KEPT_IN_REGIONS}
    got = _run(*_region_lm(True, "kr_"))
    for a, b in zip(got, plain):
        np.testing.assert_array_equal(a, b)
    assert np.abs(got[2]).sum() > 0
    assert CF._REGIONS.value() - regions == _LAYERS
    assert {n: CF._KEPT_BYTES.value(name=n) - kept[n] for n in kept} == {
        "flash_out": _LAYERS * _B * _T * _H * _D * 4,
        "flash_lse": _LAYERS * _B * _H * _T * 4}


@pytest.mark.parametrize("rule", _RULES)
def test_region_runs_each_flash_forward_once(monkeypatch, rule):
    """The step's jaxpr, gradient included, holds each layer's forward
    kernel ONCE with regions on, as with regions off (under a bare
    jax.checkpoint it held it twice: once forward, once again before
    the backward kernels), and the ONE backward kernel once (a score of
    two parts too, since ISSUE 56)."""
    _through(monkeypatch, rule)
    for recompute in (False, True):
        kernels = _kernels(_step_jaxpr(*_region_lm(recompute, "kj_")))
        assert kernels == dict(flash_fwd=_LAYERS, flash_bwd=_LAYERS), \
            recompute


def test_region_with_no_kernel_lowers_as_a_bare_checkpoint(monkeypatch):
    """The dense path (no kernel, so no named value) under the region's
    policy: the step's jaxpr is, equation for equation, what a bare
    jax.checkpoint gives, and nothing is counted as kept."""
    kept = CF._KEPT_BYTES.snapshot()
    got = _equations(_step_jaxpr(*_region_lm(True, "kd_")))
    assert CF._KEPT_BYTES.snapshot() == kept
    monkeypatch.setattr(CF, "_region_policy", None)
    bare = _equations(_step_jaxpr(*_region_lm(True, "kd_")))
    assert not [e for e in got if e[0] == "pallas_call"]
    assert len([e for e in got if "remat" in e[0]]) == _LAYERS
    assert got == bare


# -- the `mul` results a region keeps while they fit (ISSUE 48) --------------
_ROWS = 4 * 8            # batch 4 x 8 rows a sequence


def _two_regions(prefix):
    """Two regions of three products each on a float32 stream [4, 8, 16]:
    region A 16 -> 64 -> 32 -> 16, region B 16 -> 128 -> 16 -> 16, each
    `x + last(tanh(second(tanh(first(x)))))`. Four results a backward
    rule reads, of four widths (so a shape says which): A1 [.., 64] K
    16, A2 [.., 32] K 64, B1 [.., 128] K 16, B2 [.., 16] K 128; the two
    last products go into the stream and nowhere else. By FLOPs a byte
    (2 K / 4): B2, A2, then A1 before B1 (program order among equals).
    Returns (program, scope, feeds, fetch names: the loss and the six
    weights' gradients)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        x = fluid.layers.data("x", [8, 16])
        for widths in ((64, 32), (128, 16)):
            with fluid.layers.recompute():
                h = x
                for width in widths:
                    h = fluid.layers.fc(h, width, num_flatten_dims=2,
                                        act="tanh", bias_attr=False)
                x = fluid.layers.elementwise_add(x, fluid.layers.fc(
                    h, 16, num_flatten_dims=2, bias_attr=False))
        loss = fluid.layers.mean(fluid.layers.square(x))
        pg = fluid.append_backward(loss)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    feeds = {"x": np.random.RandomState(1).rand(4, 8, 16).astype(np.float32)}
    return main, scope, feeds, (loss.name,) + tuple(g.name for _, g in pg)


_KEPT_WIDTH = {"A1": 64, "A2": 32, "B1": 128, "B2": 16}


def _named_mul_out(jaxpr):
    """The widths of the values the step's jaxpr names `mul_out`."""
    return sorted(e.outvars[0].aval.shape[-1] for e in _eqns(jaxpr)
                  if e.primitive.name == "name"
                  and e.params["name"] == CF.MUL_OUT)


def _products(jaxpr):
    return sum(e.primitive.name == "dot_general" for e in _eqns(jaxpr))


def _limit_for(monkeypatch, budget, prefix, program=None):
    """Hand the plan a device limit that leaves `budget` bytes for what
    the regions BEFORE THE LAST keep in the program of _two_regions
    (or `program`, a builder like it): what a limit of 2**40 leaves
    says what the state and the reserve take. The room at the head is
    wider by what the largest region's term stands over the head's,
    which is returned; a negative `budget` leaves nothing before the
    last region and that much less at the head."""
    program = program or _two_regions
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
    _step_jaxpr(*program(prefix))
    room = int(CF._MUL_PLAN.value(what="budget_bytes"))
    wider = int(CF._PLAN.value(kind="all", what="head_budget_bytes")) - room
    monkeypatch.setattr(CF, "_device_limit",
                        lambda ctx: 2 ** 40 - room + budget)
    return wider


# (the head's room in _two_regions' program stands 77,820 bytes over
# the room before the last region: 2 x 40,960 of region B against the
# head's 2,052 + 2,048)
@pytest.mark.parametrize("budget, kept", [
    (-77820, []), (-77820 + 4 * _ROWS * 16, ["B2"]),
    # A2 and A1 do not fit before the last region; B1, after A2 in the
    # order, is the last region's and fits at the head
    (0, ["B2", "B1"]), (4 * _ROWS * 32 - 1, ["B2", "B1"]),
    (4 * _ROWS * 32, ["B2", "A2", "B1"]),
    (4 * _ROWS * (32 + 64) - 1, ["B2", "A2", "B1"]),
    (4 * _ROWS * (32 + 64), ["B2", "A2", "B1", "A1"])],
    ids=["nothing", "one_at_the_head", "the_last_regions_alone",
         "a_byte_short", "one_before_the_last", "stops_at_the_room",
         "all"])
def test_regions_keep_the_mul_results_the_budget_admits(monkeypatch, budget,
                                                        kept):
    """With a device limit handed in, the two regions keep exactly the
    results the room admits, the costliest a byte first and the last
    region's first among equals (B2, A2, B1, A1): B's are charged to
    the head alone, A's also to the last region's backward, and one
    that does not fit is passed over. The step's jaxpr names those and
    no other, runs one product fewer for each (a kept result is not
    made again), the plan's gauges and the kept-bytes counter say so;
    the last product of a region is never a candidate."""
    assert _limit_for(monkeypatch, budget, "mb_") == 77820
    before = CF._KEPT_BYTES.value(name=CF.MUL_OUT)
    jaxpr = _step_jaxpr(*_two_regions("mk_"))
    assert _named_mul_out(jaxpr) == sorted(_KEPT_WIDTH[k] for k in kept)
    nbytes = 4 * _ROWS * sum(_KEPT_WIDTH[k] for k in kept)
    assert {w: CF._MUL_PLAN.value(what=w) for w in (
        "candidates", "admitted", "admitted_bytes", "budget_bytes")} == {
        "candidates": 4, "admitted": len(kept), "admitted_bytes": nbytes,
        "budget_bytes": max(budget, 0)}
    assert {w: CF._PLAN.value(kind=CF.MUL_OUT, what=w) for w in (
        "candidates", "admitted", "admitted_bytes")} == {
        "candidates": 4, "admitted": len(kept), "admitted_bytes": nbytes}
    assert CF._PLAN.value(kind="all", what="head_budget_bytes") \
        == 77820 + budget
    assert CF._KEPT_BYTES.value(name=CF.MUL_OUT) - before == nbytes
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 0)
    assert _products(jaxpr) == _products(
        _step_jaxpr(*_two_regions("mk_"))) - len(kept)


@pytest.mark.parametrize("limit", [None, 0], ids=["cpu", "limit_0"])
def test_no_limit_to_read_lowers_a_region_as_before(monkeypatch, limit):
    """On a backend that states no limit (the CPU, as it is) and with a
    limit of 0 handed in, the step's jaxpr is, equation for equation,
    what PR 42's policy gives (flash_out, flash_lse and nothing else):
    no value carries the new name and nothing is counted as kept."""
    if limit is not None:
        monkeypatch.setattr(CF, "_device_limit", lambda ctx: limit)
    kept = CF._KEPT_BYTES.snapshot()
    got = _step_jaxpr(*_two_regions("mz_"))
    assert CF._KEPT_BYTES.snapshot() == kept
    assert _named_mul_out(got) == []
    monkeypatch.setattr(CF, "_region_policy",
                        jax.checkpoint_policies.save_only_these_names(
                            *FA.KEPT_IN_REGIONS))
    assert _equations(got) == _equations(
        _step_jaxpr(*_two_regions("mz_")))


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
def test_kept_mul_results_change_no_bit(monkeypatch, amp):
    """The loss and all six gradients with every result kept against
    none kept: the same bits, in float32 and under bf16 AMP (a kept
    result is the value the second forward would have made again, at
    the precision the first made it)."""
    with fluid.amp.amp_guard(amp):
        none = _run(*_two_regions("me_"))
        monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
        kept = _run(*_two_regions("me_"))
    assert CF._MUL_PLAN.value(what="admitted") == 4
    assert all(np.abs(g).sum() > 0 for g in kept[1:])
    for a, b in zip(kept, none):
        np.testing.assert_array_equal(a, b)
