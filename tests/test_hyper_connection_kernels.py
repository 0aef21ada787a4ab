"""The hyper-connections' kernels (ISSUE 43), in interpret mode on the
CPU: "mix" and "merge" of ops/hyper_connection.py and their written
backward, through a whole sublayer (mix -> a stand-in F -> merge),
against the jax.numpy form of the same file and against the benchmark's
plain reference, `chipbench/reference/xing_lm.hyper_connection`.

What interpret mode cannot see (block shapes, VMEM) is compiled for a
described v5e in tests/test_tpu_compile_streams.py; times are chip_smoke.py's
(`--phases hc`)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import xing_lm
from paddle_tpu.ops import hyper_connection as HC

ITERS, EPS, CLAMP = 4, 1e-6, (-30.0, 30.0)


def _operands(n, d, rows, seed=0):
    rng = np.random.RandomState(seed)
    c = n * (n + 2)
    r = lambda *shape, scale=1.0: jnp.asarray(rng.randn(*shape) * scale,
                                              jnp.float32)
    return {"x": r(rows, n * d), "proj": r(n * d, c, scale=0.05),
            "alpha": jnp.asarray([0.5, 0.7, 0.9], jnp.float32),
            "bias": r(c, scale=0.3), "w": r(d, d, scale=d ** -0.5),
            "dout": r(rows, n * d), "dh": r(rows, d)}


def _sublayer(path, n, y_dtype, x, proj, alpha, bias, w):
    """(X', H_pre X) round F(h) = tanh(h w), handed on in `y_dtype`."""
    h, post, res, through = HC.mix_stage(x, proj, alpha, bias, n, ITERS, EPS,
                                         CLAMP, force=path)
    y = jnp.tanh(h @ w).astype(y_dtype)
    return HC.merge_stage(through, post, res, y, n, force=path), h


def _reference(n, d, y_dtype, x, proj, alpha, bias, w):
    cfg = {"hc_mult": n, "rms_norm_eps": 1e-6, "hc_sinkhorn_iters": ITERS,
           "hc_eps": EPS, "mhc_h_res_clamp_min": CLAMP[0],
           "mhc_h_res_clamp_max": CLAMP[1]}
    kept = []

    def f(h):
        kept.append(h)
        return jnp.tanh(h @ w).astype(y_dtype).astype(jnp.float32)

    out = xing_lm.hyper_connection(
        x.reshape(x.shape[0], n, d),
        {"proj": proj, "alpha": alpha, "bias": bias}, f, cfg)
    return out.reshape(x.shape), kept[0]


@functools.partial(jax.jit, static_argnums=0)
def _out_and_pulled(fn, args, cotangents):
    """fn(*args) and the cotangents pulled back, as ONE program:
    dispatched eagerly, the Sinkhorn iterations and their transpose are
    some 300 small compilations a call."""
    out, pull = jax.vjp(fn, *args)
    return out, pull(cotangents)


def _values_and_gradients(fn, o):
    out, pulled = _out_and_pulled(
        fn, (o["x"], o["proj"], o["alpha"], o["bias"], o["w"]),
        (o["dout"], o["dh"]))
    return dict(zip(("out", "h", "dx", "dproj", "dalpha", "dbias", "dw"),
                    out + pulled))


def _apart(got, want):
    """Largest difference over the largest reference value, a result;
    on the host, where comparing compiles nothing."""
    got, want = ({k: np.asarray(v, np.float32) for k, v in side.items()}
                 for side in (got, want))
    return {k: float(np.max(np.abs(got[k] - want[k]))
                     / (np.max(np.abs(want[k])) + 1e-30)) for k in want}


@pytest.fixture()
def blocks_of_8_rows(monkeypatch):
    """A grid step holds 8 rows, so that 24 rows are three blocks and
    20 rows end inside the third."""
    monkeypatch.setattr(HC, "_STREAM_BYTES", 1)


@pytest.mark.parametrize("y_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["y_f32", "y_bf16"])
@pytest.mark.parametrize("rows", [24, 20], ids=["whole_blocks", "ragged"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("n", [2, 4])
def test_a_sublayer_through_the_kernels(blocks_of_8_rows, n, d, rows,
                                        y_dtype):
    """Values of both stages and the gradients to X, proj, alpha, bias
    and (through y) F's weight: the kernels against the jax.numpy form
    and against the reference's einsums, to float32 rounding where y is
    float32; where y is bfloat16 a last-bit difference in h turns a
    rounding of y here and there (2^-9 of one value), and the
    reference's backward does not round at all."""
    assert HC._block_rows(rows, n * d, 3) == 8
    o = _operands(n, d, rows)
    with jax.default_matmul_precision("highest"):
        got = _values_and_gradients(
            lambda *a: _sublayer("interpret", n, y_dtype, *a), o)
        plain = _values_and_gradients(
            lambda *a: _sublayer("xla", n, y_dtype, *a), o)
        ref = _values_and_gradients(
            lambda *a: _reference(n, d, y_dtype, *a), o)
    exact = y_dtype == jnp.float32
    assert max(_apart(got, plain).values()) <= (5e-6 if exact else 2e-3), \
        _apart(got, plain)
    assert max(_apart(got, ref).values()) <= (5e-6 if exact else 2e-2), \
        _apart(got, ref)


@pytest.mark.parametrize("rows", [24, 20], ids=["whole_blocks", "ragged"])
@pytest.mark.parametrize("n", [2, 4])
def test_two_sublayers_inside_a_recompute_region(blocks_of_8_rows, n, rows):
    """As a layer of the model has them: mix, F, merge, mix, F, merge
    under `jax.checkpoint` with the region's policy. The second
    forward re-runs the kernels' forward rules and the stream's two
    cotangents a sublayer meet in "mix"'s backward."""
    from paddle_tpu.ops import control_flow as CF
    d = 128
    o = _operands(n, d, rows, seed=3)

    def layer(path, x, proj, alpha, bias, w):
        x, h1 = _sublayer(path, n, jnp.float32, x, proj, alpha, bias, w)
        x, h2 = _sublayer(path, n, jnp.float32, x, proj * 0.5, alpha, bias,
                          w)
        return x, h1 + h2

    def run(path):
        region = jax.checkpoint(lambda *a: layer(path, *a),
                                policy=CF._region_policy)
        return _values_and_gradients(region, o)

    with jax.default_matmul_precision("highest"):
        apart = _apart(run("interpret"), run("xla"))
    assert max(apart.values()) <= 1e-5, apart


@pytest.mark.parametrize("stage", ["mix", "merge"])
def test_each_stage_alone_and_its_gradients(stage):
    """One stage with every cotangent given, one block holding all the
    rows: "mix" with its stream handed through (the cotangent of that
    result is added to the gradient of X in the kernel), "merge" with
    its coefficients' gradients in the [n, n, N] layout."""
    n, d, rows = 4, 128, 16
    o = _operands(n, d, rows, seed=5)
    rng = np.random.RandomState(6)
    r = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)

    def mix(path):
        return lambda *a: HC.mix_stage(*a, n, ITERS, EPS, CLAMP, force=path)

    def merge(path):
        return lambda *a: HC.merge_stage(*a, n, force=path)

    with jax.default_matmul_precision("highest"):
        args = (o["x"], o["proj"], o["alpha"], o["bias"])
        if stage == "mix":
            cot = (o["dh"], r(rows, n), r(n, n, rows), o["dout"])
            got, want = (_out_and_pulled(mix(path), args, cot)
                         for path in ("interpret", "xla"))
        else:
            _, post, res, _ = jax.jit(mix("xla"))(*args)
            y = r(rows, d).astype(jnp.bfloat16)
            got, want = (_out_and_pulled(merge(path), (o["x"], post, res, y),
                                       o["dout"])
                         for path in ("interpret", "xla"))
        leaves = jax.tree_util.tree_leaves
        for a, b in zip(leaves(got), leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5 * float(jnp.max(jnp.abs(b.astype(jnp.float32)))))


def test_the_packed_projection_keeps_highests_six_products():
    """[P_hi | P_mid | P_lo] against x_hi, x_mid, x_lo: the kernel's
    three passes give x P to float32 rounding of the float64 product, as
    `Precision.HIGHEST` does, and three orders of magnitude nearer than
    ONE bfloat16 pass."""
    n, d, rows = 4, 128, 16
    o = _operands(n, d, rows, seed=9)
    c = n * (n + 2)
    _, zs, _ = HC._mix(o["x"], o["proj"], o["alpha"][0], o["bias"][:n], n,
                       1e-6, True)
    x64, p64 = np.asarray(o["x"], np.float64), np.asarray(o["proj"],
                                                          np.float64)
    want = x64 @ p64 / np.sqrt(np.mean(x64 * x64, -1, keepdims=True) + 1e-6)
    one_pass = np.asarray(jnp.dot(o["x"].astype(jnp.bfloat16),
                                  o["proj"].astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)
                          ) * np.asarray(zs[:, c:c + 1])
    apart = np.abs(np.asarray(zs[:, :c]) - want).max()
    assert apart <= 2e-6 * np.abs(want).max()
    assert np.abs(one_pass - want).max() > 1e3 * apart
    assert not np.asarray(zs[:, c + 1:]).any()


@pytest.mark.parametrize("case", ["d_96", "cpu", "bf16_stream", "six_lanes"])
def test_the_rule_sends_what_the_kernels_cannot_hold_to_xla(case):
    """Auto takes the kernels only on a TPU, for a float32 stream whose
    lanes are whole 128-lane tiles and whose packed projection fits one
    MXU tile; anything else is the jax.numpy form, and forcing a kernel
    there raises."""
    n, d, dtype = {"d_96": (4, 96, jnp.float32),
                   "cpu": (4, 128, jnp.float32),
                   "bf16_stream": (4, 128, jnp.bfloat16),
                   "six_lanes": (6, 128, jnp.float32)}[case]
    x = jnp.zeros((8, n * d), dtype)
    assert HC._resolve_path(x, n, None) == "xla"
    assert HC._resolve_path(x, n, "xla") == "xla"
    if case == "cpu":
        assert HC._resolve_path(x, n, "interpret") == "interpret"
    else:
        with pytest.raises(ValueError, match="whole 128-lane tiles"):
            HC._resolve_path(x, n, "interpret")


@pytest.mark.parametrize("path", ["interpret", "xla"])
def test_the_counter_says_which_path_each_stage_took(path):
    n, d, rows = 2, 128, 8
    o = _operands(n, d, rows)
    labels = [dict(lanes="2", sinkhorn_iters=str(ITERS), path=path,
                   stage="mix"),
              dict(lanes="2", sinkhorn_iters="", path=path, stage="merge")]
    was = [HC._LOWERINGS.value(**l) for l in labels]
    _sublayer(path, n, jnp.float32, o["x"], o["proj"], o["alpha"], o["bias"],
              o["w"])
    assert [HC._LOWERINGS.value(**l) for l in labels] \
        == [was[0] + 1, was[1] + 1]


def test_the_program_op_hands_the_stream_through():
    """`layers.hyper_connection`: "mix" returns the stream among what
    "merge" is handed, and "merge" reads it from there."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 2 * 8], dtype="float32")
        h, mixes = layers.hyper_connection(x, 2, "mix", name="hc")
        out = layers.hyper_connection(x, 2, "merge", y=h, coefficients=mixes)
    ops = [op for op in main.global_block().ops
           if op.type == "hyper_connection"]
    assert len(mixes) == 3 and tuple(mixes[2].shape) == tuple(x.shape)
    assert ops[0].output("Through") == [mixes[2].name]
    assert ops[1].input("X") == [mixes[2].name]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = np.random.RandomState(0).randn(3, 4, 16).astype(np.float32)
    got, = exe.run(main, feed={"x": feed}, fetch_list=[out])
    assert got.shape == feed.shape and np.isfinite(got).all()
