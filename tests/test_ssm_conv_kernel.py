"""The Program op ``ssm_conv``'s kernel pair (``ops/ssm_conv.py``, ISSUE
65) in interpret mode against ``causal_conv_silu``'s ``jax.numpy`` path,
the CPU's own: y and the gradients of x, w and the bias, with and
without a bias, under 4 and 3 taps, float32 and bfloat16, two sequences
of three tiles of three groups (so both carries, the rows before a
group and the ``dpre`` rows after it, cross groups and tiles with
non-zero rows) over two channel blocks; a T that is not whole tiles
and a C that is not whole lane tiles (one block, and blocks whose last
is partly outside the array); causality bit for bit; the
dispatch and the counter's labels. One program a side, inputs drawn
and results compared on the host (ROADMAP D1). The chip's compiler
judges the same kernels in ``tests/test_tpu_compile_streams.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.monitor import metrics
from paddle_tpu.ops import selective_scan as SS
from paddle_tpu.ops import ssm_conv


def _drawn(seed, bsz, t, c, k, biased, dtype):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(bsz, t, c), dtype)
    dy = jnp.asarray(rng.randn(bsz, t, c), dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (k, c)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.5, 0.5, (c,)), jnp.float32)
    return dy, (x, w) + ((bias,) if biased else ())


def _both_ways(rows, dy, args):
    """(y, dx, dw[, dbias]) of the kernels in interpret mode and of the
    ``jax.numpy`` path, each ONE jitted program, as float32 numpy."""
    def with_grads(fn):
        def run(dy, *args):
            y, pull = jax.vjp(fn, *args)
            return (y,) + pull(dy)
        return [np.asarray(v, np.float32) for v in jax.jit(run)(dy, *args)]

    return (with_grads(lambda *a: ssm_conv.conv_silu(
                *a, rows=rows, interpret=True)),
            with_grads(SS.causal_conv_silu))


def _close(got, want, dtype):
    # a bfloat16 result may round the other way: 2^-8 of a value
    for g, w_, name in zip(got, want, ("y", "dx", "dw", "dbias")):
        narrow = dtype == jnp.bfloat16 and name in ("y", "dx")
        np.testing.assert_allclose(
            g, w_, rtol=2 ** -7 if narrow else 2e-5,
            atol=(2 ** -7 if narrow else 2e-5) * np.abs(w_).max(),
            err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("k", [4, 3], ids=["4_taps", "3_taps"])
def test_the_pair_against_the_taps(k, biased, dtype):
    """B 2, T 144 as three tiles of 48 rows, each three groups of 16;
    768 channels as two blocks of three lane tiles."""
    assert ssm_conv.kernel_tiles(144, 768, k, rows=48) == (48, 384, 16)
    dy, args = _drawn(k + biased, 2, 144, 768, k, biased, dtype)
    got, want = _both_ways(48, dy, args)
    assert len(got) == 3 + biased and got[0].shape == (2, 144, 768)
    _close(got, want, dtype)


@pytest.mark.parametrize("t,c", [(100, 256), (40, 200), (7, 96),
                                 (40, 600)],
                         ids=["T_100_of_48", "C_200", "T_7", "C_600"])
def test_a_t_that_is_not_whole_tiles_and_a_c_that_is_not_lane_tiles(t, c):
    """T is padded with zero rows after the sequence inside the
    wrapper; a width that is not whole lane tiles is one block of all
    of C up to 512 channels, its last lane tile partly filled, and
    blocks of 512 past that, the last partly outside the array (600: 88
    of its 512 lanes hold channels, and dw's and dbias's sums there are
    not written back)."""
    tile, cb, _ = ssm_conv.kernel_tiles(t, c, 4, rows=48)
    assert t % tile and cb == min(c, 512)
    dy, args = _drawn(t, 2, t, c, 4, True, jnp.float32)
    got, want = _both_ways(48, dy, args)
    assert got[0].shape == (2, t, c)
    _close(got, want, jnp.float32)


def test_a_later_row_leaves_the_earlier_rows_as_they_were():
    """Causal, bit for bit: rows before the one that changed are the
    same bytes, in the changed row's own tile and in the tiles before
    it; the rows from it on differ."""
    dy, (x, w, bias) = _drawn(11, 1, 96, 128, 4, True, jnp.bfloat16)
    run = jax.jit(lambda x: ssm_conv.conv_silu(x, w, bias, rows=32,
                                               interpret=True))
    at = 70                     # in the third tile of 32 rows
    other = x.at[0, at].set(x[0, at] + 1.0)
    was, now = (np.asarray(run(v), np.float32) for v in (x, other))
    np.testing.assert_array_equal(was[:, :at], now[:, :at])
    assert (was[0, at:at + 4] != now[0, at:at + 4]).any(axis=-1).all()
    np.testing.assert_array_equal(was[:, at + 4:], now[:, at + 4:])


def _counted(labels):
    counter = metrics.registry().get("ptpu_ssm_conv_lowerings_total")
    return counter.snapshot().get(
        tuple(labels[name] for name in counter.label_names), 0)


def test_a_cpu_takes_the_taps_and_counts_them():
    labels = {"path": "taps", "direction": "fwd", "taps": "4",
              "channels": "24"}
    before = _counted(labels)
    dy, (x, w, bias) = _drawn(5, 1, 9, 24, 4, True, jnp.float32)
    SS.causal_conv_silu(x, w, bias)
    assert _counted(labels) == before + 1
    assert _counted(dict(labels, path="pallas")) == 0


@pytest.mark.parametrize("c,k,tiles", [
    (4096, 4, (1024, 512, 128)), (128, 4, (1024, 128, 128)),
    (5120, 4, (1024, 512, 128)), (1440, 4, (1024, 512, 128)),
    (2880, 3, (1024, 512, 128)), (4096, 10, None)],
    ids=["4096", "128", "5120", "1440_ends_in_a_partial_block",
         "2880_ends_in_a_partial_block", "10_taps_keep_the_taps"])
def test_on_a_tpu_the_shape_alone_chooses(monkeypatch, c, k, tiles):
    """What a TPU would take at a cell's shape, traced and not run: the
    kernel pair, forward and written backward, counted under the path
    ``pallas``, at every width (1,440 and 2,880 in blocks of 512 lanes,
    the last partly outside the array); more than 9 taps keep the
    ``jax.numpy`` path."""
    assert ssm_conv.kernel_tiles(8192, c, k) == tiles
    monkeypatch.setattr(SS, "_on_tpu", lambda x: True)
    path = "pallas" if tiles else "taps"
    labels = {"path": path, "taps": str(k), "channels": str(c)}
    before = [_counted(dict(labels, direction=d)) for d in ("fwd", "bwd")]
    sds = jax.ShapeDtypeStruct
    grads = jax.eval_shape(
        jax.grad(lambda x, w: SS.causal_conv_silu(x, w).astype(
            jnp.float32).sum(), argnums=(0, 1)),
        sds((1, 8192, c), jnp.bfloat16), sds((k, c), jnp.float32))
    assert [(g.shape, g.dtype) for g in grads] == [
        ((1, 8192, c), jnp.bfloat16), ((k, c), jnp.float32)]
    after = [_counted(dict(labels, direction=d)) for d in ("fwd", "bwd")]
    assert after == [before[0] + 1, before[1] + bool(tiles)]
