"""What the flash kernels' interpret-mode test files share
(tests/test_flash_walk.py, test_flash_backward.py,
test_flash_backward_streamed.py, test_flash_masks.py,
test_flash_entries.py, test_flash_window.py, test_flash_diff.py): inputs
in the entries' layouts, the dense float32 references with a block mask
or a band written out, the error measure and a jaxpr's kernels. As
tests/op_test.py is: a module the files import, no test of its own.

What a case compiles is its kernels and its reference, ONE program a
side (`_with_grads` under `jax.jit` or `_traced_once`: results and
gradients from one forward, the jaxpr read from the same trace; the
scan's, the latent and the block-diffusion files take `_with_grads`
from here too). Dispatched an op at a time, a dense reference and its
gradient are some hundred small compilations for every new shape, more
seconds than the kernels under test take; so inputs are drawn and
rounded on the host (`_draw`) and results are compared there (`_np32`,
`_assert_close`, `_assert_within`)."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA


def _qkv(b=2, h=3, t=256, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d).astype(np.float32)
                             * np.float32(0.3))
    return mk(), mk(), mk()


def _f32(a):
    return a.astype(jnp.float32)


def _np32(a):
    """A device value as float32 on the host: what is compared there
    compiles nothing."""
    return np.asarray(a).astype(np.float32)


def _assert_close(name, got, want, tol):
    """Largest error over the largest reference value."""
    got, want = _np32(got), _np32(want)
    err = float(np.max(np.abs(got - want))) / (
        float(np.max(np.abs(want))) + 1e-9)
    assert err < tol, (name, err)


def _draw(rng, shape, dtype=jnp.float32, scale=0.5):
    """randn * scale, rounded to float32 and then to `dtype` on the
    host (the device's rounding, and no program compiled for it)."""
    return jnp.asarray((rng.randn(*shape) * scale).astype(np.float32)
                       .astype(jnp.dtype(dtype)))


def _assert_within(name, got, want, tol, least=0.1):
    """Every element within tol times the largest reference value (at
    least `least`)."""
    got, want = _np32(got), _np32(want)
    np.testing.assert_allclose(
        got, want, atol=tol * max(float(np.max(np.abs(want))), least),
        err_msg=name)


def _bthd_inputs(h, d, dtype, t=256, b=1, seed=7):
    rng = np.random.RandomState(seed)
    q, k, v, dy = (_draw(rng, (b, t, h * d), dtype) for _ in range(4))
    return q, k, v, dy, _draw(rng, (b, h, t))


# the dense forms of ops/flash_attention.py as ONE program a shape
_dense = jax.jit(FA._dense, static_argnums=(3, 4))
_dense_lse = jax.jit(FA._dense_lse, static_argnums=(3, 4))


def _grads_of(loss, *operands):
    """d loss / d (q, k, v) of a dense float32 reference, compiled as
    one program."""
    return jax.jit(jax.grad(loss, (0, 1, 2)))(*operands)


def _host32(*operands):
    """The operands as float32 device values, converted on the host."""
    return tuple(jnp.asarray(_np32(x)) for x in operands)


def _pallas_eqns(jaxpr):
    """The pallas_call equations of a jaxpr, sub-jaxprs included, in
    order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def _pallas_names(jaxpr):
    return [eqn.params["name"] for eqn in _pallas_eqns(jaxpr)]


def _traced_once(fn, *operands):
    """(the pallas_call equations of fn's jaxpr, fn's results): fn is
    traced ONCE and runs as one compiled program, whatever it does round
    the kernels (a loss's weights, its sums) inside it, as a step
    program holds them."""
    traced = jax.jit(fn).trace(*operands)
    return (list(_pallas_eqns(traced.jaxpr.jaxpr)),
            traced.lower().compile()(*operands))


def _with_grads(fn, weigh):
    """operands -> (fn's results, d weigh(results) / d each operand),
    the forward run once: a kernel entry's results are its forward
    rule's."""
    def both(*operands):
        def loss(*operands):
            outs = fn(*operands)
            return weigh(outs), outs
        grads, outs = jax.grad(loss, tuple(range(len(operands))),
                               has_aux=True)(*operands)
        return outs, grads
    return both


def _kernels_and_grads(loss, *operands):
    """(the kernels' names in d loss / d (q, k, v), the gradients)."""
    eqns, grads = _traced_once(jax.grad(loss, (0, 1, 2)), *operands)
    return [eqn.params["name"] for eqn in eqns], grads


@pytest.fixture
def two_kernels(monkeypatch):
    """No shape is within the ONE streamed kernel's byte bound: what is
    traced under this fixture streams through flash_bwd_dq and
    flash_bwd_dkv, as every streamed shape did before ISSUE 39 and as a
    T too long for the bound still does."""
    monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)


def _gqa_inputs(h, hkv, d, t, dtype, seed=11):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: _draw(rng, shape, dtype)
    return (mk(1, t, h * d), mk(1, t, hkv * d), mk(1, t, hkv * d),
            mk(1, t, h * d), _draw(rng, (1, h, t)))


@functools.partial(jax.jit, static_argnames=("h", "hkv", "mask_block",
                                             "strict", "own"))
def _dense_block_causal(q, k, v, h, hkv, mask_block, strict, own=False):
    """(out [B, T, H*D], lse [B, H, T], seen [T]) by dense float32 math
    with the mask written out: query i sees key j iff
    j // m + strict <= i // m. `own` (ISSUE 37): the T rows are two
    halves at the same positions, [noised; clean]; a clean key is seen
    from its block on by the clean queries and from the block after by
    the noised ones, a noised key by the noised queries of its block."""
    b, t, hd = q.shape
    d = hd // h
    qh = FA.heads_first(_f32(q), h)
    kh, vh = (jnp.repeat(FA.heads_first(_f32(x), hkv), h // hkv, 1)
              for x in (k, v))
    at = jnp.arange(t) // mask_block
    seen = at[None, :] + int(strict) <= at[:, None]
    if own:
        at = np.arange(t // 2) // mask_block
        ahead, same = at[None, :] < at[:, None], at[None, :] == at[:, None]
        seen = jnp.asarray(np.block([[same, ahead],
                                     [np.zeros_like(same), ahead | same]]))
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * d ** -0.5
    s = jnp.where(seen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(lse), lse,
                                              0.0)[..., None]), 0.0)
    return (FA.heads_last(jnp.einsum("bhqk,bhkd->bhqd", p, vh)), lse,
            seen.any(1))


def _band_inputs(t, h, hkv, d, dtype, seed):
    """q and dy [1, T, H*D], k and v [1, T, Hkv*D] for the window form."""
    mk = lambda n, s: _draw(np.random.RandomState(s), (1, t, n * d), dtype)
    return mk(h, seed), mk(hkv, seed + 1), mk(hkv, seed + 2), mk(h, seed + 3)


def _band_written_out(q, k, v, h, hkv, window):
    """softmax(q k^T / sqrt(D)) v with `i - window < j <= i` written
    out, float32, query head a reading key/value head a // (h / hkv)."""
    b, t, hd = q.shape
    f32 = lambda x: x.astype(jnp.float32)
    qh = FA.heads_first(f32(q), h)
    kh, vh = (jnp.repeat(FA.heads_first(f32(x), hkv), h // hkv, 1)
              for x in (k, v))
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * (hd // h) ** -0.5
    s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
    return FA.heads_last(jnp.einsum("bhqk,bhkd->bhqd",
                                    jax.nn.softmax(s, -1), vh))
