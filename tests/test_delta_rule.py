"""The gated delta rule (ISSUE 53, ``ops/delta_rule.py``) at small sizes
on the CPU: the chunk walk against the row-by-row recurrence
(``force="steps"``), float32 and bfloat16 operands, one chunk and
several, T not a multiple of the chunk, ``beta`` near 2 and ``g`` near
0 and very negative; the gradients of all five inputs against
``jax.grad`` of the steps; a row never sees a later one; the steps
against ``transformers``' own ``torch_recurrent_gated_delta_rule``; the
lowering's count; the small ops round the rule against numpy; and
``ssm_conv`` with and without its bias.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.monitor import metrics
from paddle_tpu.ops import delta_rule as DR
from paddle_tpu.ops import selective_scan as SS

H, DK, DV = 3, 8, 16


def _draw(seed, t, b=2, g_scale=1.0, g_shift=0.0, beta_low=0.0,
          dtype=jnp.float32):
    """Operands as a mixer hands them on: unit keys, unit queries
    times ``d_k^-0.5``, ``g`` not positive, ``beta`` in (beta_low, 2)."""
    r = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(b, t, H, DK)) * DK ** -0.5
    k = unit(r.randn(b, t, H, DK))
    v = r.randn(b, t, H, DV)
    g = -np.abs(r.randn(b, t, H)) * g_scale - g_shift
    beta = beta_low + (2.0 - beta_low) / (1.0 + np.exp(-2 * r.randn(b, t, H)))
    return tuple(jnp.asarray(x, d) for x, d in zip(
        (q, k, v, g, beta), (dtype, dtype, dtype, jnp.float32, jnp.float32)))


def _both(xs, chunk):
    """(the chunk walk, the steps) in ONE program."""
    return jax.jit(lambda *a: (DR.gated_delta_rule(*a, chunk=chunk),
                               DR.gated_delta_rule(*a, force="steps")))(*xs)


@pytest.mark.parametrize("t, chunk", [(16, 16), (48, 16), (37, 16),
                                      (130, 64)],
                         ids=["one_chunk", "three_chunks", "ragged",
                              "chunk_64_ragged"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 5e-6),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_chunk_walk_is_the_steps(t, chunk, dtype, tol):
    """Float32 inside whatever the operands are, v's dtype out; a T
    that is no multiple of the chunk is padded with rows that decay
    nothing and add nothing."""
    xs = _draw(1, t, dtype=dtype)
    got, want = _both(xs, chunk)
    assert got.dtype == dtype and got.shape == (2, t, H, DV)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("kind, kw", [
    ("beta_near_2", dict(beta_low=1.9)),
    ("g_near_0", dict(g_scale=1e-4)),
    ("g_very_negative", dict(g_scale=5.0, g_shift=30.0)),
    ("g_mixed", dict(g_scale=20.0))])
def test_the_walk_holds_at_the_edges_of_its_gates(kind, kw):
    """``beta`` near 2: the transition's eigenvalue along a key near -1,
    the triangular system's entries at their largest. ``g`` near 0:
    nothing is forgotten in 100 rows. ``g`` very negative: a decay
    underflows to 0 and the difference of running sums above the
    diagonal overflows if it is not masked before the exp."""
    xs = _draw(2, 100, **kw)
    got, want = _both(xs, 16)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_the_gradients_of_all_five_inputs_are_the_steps():
    xs = _draw(3, 40, b=1)
    loss = lambda force, chunk: lambda *a: jnp.sum(jnp.sin(
        DR.gated_delta_rule(*a, chunk=chunk, force=force)))
    every = (0, 1, 2, 3, 4)
    got, want = jax.jit(lambda *a: (
        jax.grad(loss(None, 16), every)(*a),
        jax.grad(loss("steps", None), every)(*a)))(*xs)
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=1e-4, err_msg=name)
        assert float(jnp.max(jnp.abs(b))) > 1e-3, name


def test_a_row_does_not_move_when_later_rows_do():
    xs = _draw(4, 48)
    later = tuple(x.at[:, 20:].set(x[:, 20:] * 0.5 + 0.25) for x in xs)
    rule = jax.jit(lambda *a: DR.gated_delta_rule(*a, chunk=16))
    np.testing.assert_array_equal(np.asarray(rule(*later)[:, :20]),
                                  np.asarray(rule(*xs)[:, :20]))


def test_the_steps_are_transformers_recurrence():
    """``torch_recurrent_gated_delta_rule`` of ``qwen3_next``, line for
    line: it scales the query by ``d_k^-0.5`` itself, so it is handed
    the unit query; the doubled ``beta`` is the caller's, here as
    there."""
    torch = pytest.importorskip("torch")
    theirs = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next"
    ).torch_recurrent_gated_delta_rule
    q, k, v, g, beta = _draw(5, 24)
    as_torch = lambda x: torch.from_numpy(np.array(x))
    want, _ = theirs(as_torch(q * DK ** 0.5), as_torch(k), as_torch(v),
                     as_torch(g), as_torch(beta), None, False)
    got = DR.gated_delta_rule(q, k, v, g, beta, force="steps")
    np.testing.assert_allclose(np.asarray(got), want.numpy(), atol=2e-6)


def test_a_lowering_counts_itself_by_path_and_shape():
    counter = metrics.registry().get("ptpu_delta_rule_lowerings_total")
    xs = _draw(6, 16, b=1)
    labels = lambda path, chunk: dict(path=path, chunk=chunk, heads=str(H),
                                      d_k=str(DK), d_v=str(DV))
    before = [counter.value(**labels("chunked", "16")),
              counter.value(**labels("steps", "0"))]
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a, chunk=16), *xs)
    jax.eval_shape(lambda *a: DR.gated_delta_rule(*a, force="steps"), *xs)
    assert [counter.value(**labels("chunked", "16")),
            counter.value(**labels("steps", "0"))] == [
                before[0] + 1, before[1] + 1]
    with pytest.raises(ValueError, match="force"):
        DR.gated_delta_rule(*xs, force="pallas")
    with pytest.raises(ValueError, match=r"\[B, T, H\]"):
        DR.gated_delta_rule(*xs[:4], xs[4][..., :2])


def test_the_triangular_systems_inverse_is_exact():
    """``(I - n)^-1`` as the product ``(I + n)(I + n^2)(I + n^4) ..``
    against numpy's inverse, at a chunk that is no power of two too."""
    for c in (16, 24, 64):
        n = np.tril(np.random.RandomState(c).randn(2, c, c) * 0.3, -1)
        got = DR.unit_lower_inverse(jnp.asarray(n, jnp.float32))
        np.testing.assert_allclose(np.asarray(got),
                                   np.linalg.inv(np.eye(c) - n),
                                   atol=1e-4, rtol=1e-4)


# -- the ops round the rule ---------------------------------------------------

def _r(*shape, seed=0):
    return np.asarray(np.random.RandomState(seed).randn(*shape), np.float32)


def test_the_small_ops_are_their_equations():
    x = _r(2, 5, 3 * 8, seed=1)
    heads = x.reshape(2, 5, 3, 8)
    want = heads / np.sqrt((heads ** 2).sum(-1, keepdims=True) + 1e-6) * 0.25
    np.testing.assert_allclose(
        np.asarray(DR.l2_norm_scale(jnp.asarray(x), 3, 0.25)),
        want.reshape(x.shape), atol=1e-6)
    low = DR.l2_norm_scale(jnp.asarray(x, jnp.bfloat16), 3, 0.25)
    assert low.dtype == jnp.bfloat16

    xa, xb, a_log, dt = _r(2, 5, 3, seed=2), _r(2, 5, 3, seed=3), \
        _r(3, seed=4), _r(3, seed=5)
    g, beta = DR.delta_gates(jnp.asarray(xa, jnp.bfloat16), jnp.asarray(xb),
                             jnp.asarray(a_log), jnp.asarray(dt), 2.0)
    assert g.dtype == beta.dtype == jnp.float32
    xa = np.asarray(jnp.asarray(xa, jnp.bfloat16), np.float32)
    np.testing.assert_allclose(
        np.asarray(g), -np.exp(a_log) * np.log1p(np.exp(xa + dt)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(beta), 2 / (1 + np.exp(-xb)),
                               rtol=1e-6)
    assert (np.asarray(g) < 0).all() and (np.asarray(beta) < 2).all()

    o, gate, w = _r(2, 5, 3 * 8, seed=6), _r(2, 5, 3 * 8, seed=7), \
        _r(8, seed=8)
    per = o.reshape(2, 5, 3, 8)
    normed = per / np.sqrt((per ** 2).mean(-1, keepdims=True) + 1e-6) * w
    want = normed.reshape(o.shape) * gate / (1 + np.exp(-gate))
    np.testing.assert_allclose(
        np.asarray(DR.gated_rms_norm(jnp.asarray(o), jnp.asarray(gate),
                                     jnp.asarray(w))), want, atol=1e-5)


def _taps_loop(x, w, bias=None):
    out = np.zeros(x.shape, np.float64)
    k = w.shape[0]
    for t in range(x.shape[1]):
        for i in range(k):
            if t - k + 1 + i >= 0:
                out[:, t] += w[i] * x[:, t - k + 1 + i]
    out = out if bias is None else out + bias
    return out / (1 + np.exp(-out))


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_ssm_conv_runs_with_and_without_a_bias(bias):
    """The layer declares ``<name>_b`` only where `bias`; the op without
    it is the taps and the SiLU alone, and with a bias of zeros it is
    bit for bit the op without one."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    x = _r(2, 9, 6, seed=9)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        data = layers.data("x", [9, 6], dtype="float32")
        out = layers.ssm_conv(data, width=4, bias=bias, name="c")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        names = {p.name for p in main.global_block().all_parameters()}
        assert names == ({"c_w", "c_b"} if bias else {"c_w"})
        (op,) = [o for o in main.global_block().ops if o.type == "ssm_conv"]
        assert bool(op.input("Bias")) is bias
        w = np.asarray(scope.find_var("c_w"))
        b = np.asarray(scope.find_var("c_b")) if bias else None
        got = exe.run(main, feed={"x": x}, fetch_list=[out])[0]
    np.testing.assert_allclose(got, _taps_loop(x, w, b), atol=1e-6)
    x, w = jnp.asarray(x), jnp.asarray(w)
    np.testing.assert_array_equal(
        np.asarray(SS.causal_conv_silu(x, w)),
        np.asarray(SS.causal_conv_silu(x, w, jnp.zeros(6))))
