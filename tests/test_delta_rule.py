"""The gated delta rule (ISSUEs 53 and 54, ``ops/delta_rule.py``) at
small sizes on the CPU: the chunk walk, as ``jax.numpy`` and as the
Pallas kernel pair in interpret mode, against the row-by-row recurrence
(``force="steps"``), float32 and bfloat16 operands, one chunk and
several, T not a multiple of the chunk, ``beta`` near 2 and ``g`` near
0 and very negative; the gradients of all five inputs against
``jax.grad`` of the steps; a row never sees a later one; the kernels
against the ``jax.numpy`` walk to float32 rounding, with the three
faults that test exists to notice planted; the steps against
``transformers``' own ``torch_recurrent_gated_delta_rule``; the
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
# the kernels' cases: two heads of the cell's widths (keys of 96, values
# of 192: neither a whole lane tile) and a narrower pair
CELL, NARROW = (2, 96, 192), (2, 32, 64)


def _draw(seed, t, b=2, g_scale=1.0, g_shift=0.0, beta_low=0.0,
          dtype=jnp.float32, heads=(H, DK, DV)):
    """Operands as a mixer hands them on: unit keys, unit queries
    times ``d_k^-0.5``, ``g`` not positive, ``beta`` in (beta_low, 2)."""
    r = np.random.RandomState(seed)
    h, dk, dv = heads
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(b, t, h, dk)) * dk ** -0.5
    k = unit(r.randn(b, t, h, dk))
    v = r.randn(b, t, h, dv)
    g = -np.abs(r.randn(b, t, h)) * g_scale - g_shift
    beta = beta_low + (2.0 - beta_low) / (1.0 + np.exp(-2 * r.randn(b, t, h)))
    return tuple(jnp.asarray(x, d) for x, d in zip(
        (q, k, v, g, beta), (dtype, dtype, dtype, jnp.float32, jnp.float32)))


def _both(xs, chunk, path=None):
    """(the chunk walk on `path`, the steps) in ONE program."""
    return jax.jit(lambda *a: (
        DR.gated_delta_rule(*a, chunk=chunk, force=path),
        DR.gated_delta_rule(*a, force="steps")))(*xs)


def _grads(path, chunk):
    """The gradients of all five inputs of ``sum(sin(o))``."""
    return jax.grad(lambda *a: jnp.sum(jnp.sin(DR.gated_delta_rule(
        *a, chunk=chunk, force=path).astype(jnp.float32))), (0, 1, 2, 3, 4))


@pytest.mark.parametrize("t, chunk, path, heads", [
    (16, 16, None, None), (48, 16, None, None), (37, 16, None, None),
    (130, 64, None, None),
    (64, 64, "interpret", CELL), (256, 64, "interpret", CELL),
    (200, 64, "interpret", CELL), (256, 128, "interpret", CELL),
    (90, 16, "interpret", NARROW)],
    ids=["one_chunk", "three_chunks", "ragged", "chunk_64_ragged",
         "kernels_one_chunk", "kernels_four_chunks", "kernels_ragged",
         "kernels_chunk_128", "kernels_narrow_ragged"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 5e-6),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_chunk_walk_is_the_steps(t, chunk, path, heads, dtype, tol):
    """Float32 inside whatever the operands are, v's dtype out; a T
    that is no multiple of the chunk is padded with rows that decay
    nothing and add nothing. The CPU's own path is the jax.numpy walk;
    the kernels run in interpret mode, one sequence of two heads."""
    heads = heads or (H, DK, DV)
    xs = _draw(1, t, b=1 if path else 2, dtype=dtype, heads=heads)
    got, want = _both(xs, chunk, path)
    assert got.dtype == dtype and got.shape == xs[2].shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("path", [None, "interpret"],
                         ids=["chunked", "kernels"])
@pytest.mark.parametrize("kind, kw", [
    ("beta_near_2", dict(beta_low=1.9)),
    ("g_near_0", dict(g_scale=1e-4)),
    ("g_very_negative", dict(g_scale=5.0, g_shift=30.0)),
    ("g_mixed", dict(g_scale=20.0))])
def test_the_walk_holds_at_the_edges_of_its_gates(kind, kw, path):
    """``beta`` near 2: the transition's eigenvalue along a key near -1,
    the triangular system's entries at their largest. ``g`` near 0:
    nothing is forgotten in 100 rows. ``g`` very negative: a decay
    underflows to 0 and the difference of running sums above the
    diagonal overflows if it is not masked before the exp."""
    xs = _draw(2, 100, **kw)
    got, want = _both(xs, 16, path)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path, t, chunk, heads, dtype, atol, rtol", [
    (None, 40, 16, (H, DK, DV), jnp.float32, 2e-5, 1e-4),
    ("interpret", 256, None, CELL, jnp.float32, 5e-5, 2e-4),
    ("interpret", 150, 64, CELL, jnp.bfloat16, 0.25, 5e-2),
    ("interpret", 90, 16, NARROW, jnp.float32, 2e-5, 1e-4),
    ("interpret", 100, 16, NARROW, "edges", 1e-4, 2e-4)],
    ids=["chunked", "kernels", "kernels_bfloat16_ragged",
         "kernels_narrow_ragged", "kernels_gates_at_their_edges"])
def test_the_gradients_of_all_five_inputs_are_the_steps(
        path, t, chunk, heads, dtype, atol, rtol):
    """The jax.numpy walk's are autodiff's; the kernels' are the
    written backward (``delta_rule_bwd``), which reads the chunks'
    starting states the forward kernel wrote (the first case at the
    kernels' own chunk, `KERNEL_CHUNK`: two chunks). With bfloat16 operands
    dq, dk and dv are rounded to bfloat16 once, at 2^-9 of their
    value. At the gates' edges: beta from 1.9, g near 0 in one head's
    rows and very negative in another's."""
    if dtype == "edges":
        xs = list(_draw(3, t, b=1, beta_low=1.9, heads=heads))
        xs[3] = xs[3] * jnp.asarray([1e-4, 30.0])
    else:
        xs = _draw(3, t, b=1, dtype=dtype, heads=heads)
    got, want = jax.jit(lambda *a: (_grads(path, chunk)(*a),
                                    _grads("steps", None)(*a)))(*xs)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol,
            rtol=rtol, err_msg=name)
        assert float(jnp.max(jnp.abs(b))) > 1e-3, name


@pytest.mark.parametrize("path", [None, "interpret"],
                         ids=["chunked", "kernels"])
def test_a_row_does_not_move_when_later_rows_do(path):
    xs = _draw(4, 48)
    later = tuple(x.at[:, 20:].set(x[:, 20:] * 0.5 + 0.25) for x in xs)
    rule = jax.jit(lambda *a: DR.gated_delta_rule(*a, chunk=16, force=path))
    np.testing.assert_array_equal(np.asarray(rule(*later)[:, :20]),
                                  np.asarray(rule(*xs)[:, :20]))


# -- the state's precision is the configuration's ----------------------------

def _low(x):
    return x.astype(jnp.bfloat16)


def _plant_default_precision(monkeypatch):
    """Every float32 product of the kernels as a TPU makes it at the
    DEFAULT precision: the operands rounded to bfloat16."""
    dot = DR._dot
    monkeypatch.setattr(DR, "_dot", lambda a, b, dims: dot(
        _low(a).astype(jnp.float32), _low(b).astype(jnp.float32), dims))


_FAULTS = {
    "none": lambda monkeypatch: None,
    "a_bfloat16_state": lambda monkeypatch: monkeypatch.setattr(
        DR, "_CARRIED", jnp.bfloat16),
    "bfloat16_saved_states": lambda monkeypatch: monkeypatch.setattr(
        DR, "_SAVED", jnp.bfloat16),
    "products_at_the_default_precision": _plant_default_precision,
}


@pytest.mark.parametrize("fault", list(_FAULTS))
def test_the_kernels_are_the_walk_to_float32_rounding(fault, monkeypatch):
    """The configuration states the rule's state, its triangular solve
    and its decays float32, and the cell's `correct` cannot see them
    (a bfloat16 state moves its logits error by less than the seeds
    do). With float32 operands the kernels' result and their five
    gradients are the jax.numpy walk's, at the same chunk (the
    kernels' own, two of them), to float32
    rounding: within 2e-6 of each value's largest entry (3.2e-7 is the
    most a sound tree reads here), under decays slow enough (g about
    -0.04 a row) that a state is the sum of a hundred rows. The other
    three cases plant what would be faster and a different result (the
    carried state and its cotangent in bfloat16, the saved states in
    bfloat16, a float32 product with its operands rounded to bfloat16
    as a TPU's default precision rounds them) and see the same
    comparison fail by two orders of magnitude or more in every value
    the fault can reach: what this test guards, it notices."""
    _FAULTS[fault](monkeypatch)
    jax.clear_caches()      # the kernels' wrappers are jitted
    xs = _draw(7, 256, b=1, heads=CELL, g_scale=0.05)

    def both(path):
        def run(*a):
            o, vjp = jax.vjp(lambda *b: DR.gated_delta_rule(
                *b, chunk=DR.KERNEL_CHUNK, force=path), *a)
            return (o,) + vjp(jnp.cos(o))
        return run

    try:
        got, want = jax.jit(lambda *a: (both("interpret")(*a),
                                        both("chunked")(*a)))(*xs)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    far = dict(zip("o dq dk dv dg dbeta".split(), (
        float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        for a, b in zip(got, want))))
    # (the forward does not read what it saves, and dv reads no state)
    sound = ("o", "dv") if fault == "bfloat16_saved_states" else () \
        if fault != "none" else tuple(far)
    for name, distance in far.items():
        if name in sound:
            assert distance <= 2e-6, (fault, far)
        else:
            assert distance >= 2e-4, (fault, far)


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


@pytest.mark.parametrize("path, chunk, said", [
    (None, 16, ("chunked", "16")), ("chunked", None, ("chunked", "64")),
    ("steps", None, ("steps", "0")), ("interpret", 32, ("interpret", "32")),
    ("pallas", None, ("pallas", str(DR.KERNEL_CHUNK)))])
def test_a_lowering_counts_itself_by_path_and_shape(path, chunk, said):
    """One count a lowering under the path it took: on a CPU the
    jax.numpy walk unless a test pins another (``"pallas"`` is only
    traced here: nothing compiles under ``eval_shape``)."""
    counter = metrics.registry().get("ptpu_delta_rule_lowerings_total")
    xs = _draw(6, 16, b=1)
    labels = dict(path=said[0], chunk=said[1], heads=str(H), d_k=str(DK),
                  d_v=str(DV))
    before = counter.value(**labels)
    jax.eval_shape(lambda *a: DR.gated_delta_rule(
        *a, chunk=chunk, force=path), *xs)
    assert counter.value(**labels) == before + 1


def test_a_call_the_rule_cannot_take_says_so():
    xs = _draw(6, 16, b=1)
    with pytest.raises(ValueError, match="force"):
        DR.gated_delta_rule(*xs, force="kernels")
    with pytest.raises(ValueError, match=r"\[B, T, H\]"):
        DR.gated_delta_rule(*xs[:4], xs[4][..., :2])
    with pytest.raises(ValueError, match="multiple of 16"):
        DR.gated_delta_rule(*xs, chunk=24, force="interpret")
    wide = _draw(6, 16, b=1, heads=(1, 160, 16))
    with pytest.raises(ValueError, match="at most 128"):
        DR.gated_delta_rule(*wide, force="interpret")
    # with no force such a head takes the jax.numpy walk, on any device
    assert DR._resolve_path(160, 16, True) == "chunked"
    assert DR._resolve_path(96, 192, True) == "pallas"
    assert DR._resolve_path(96, 192, False) == "chunked"


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
