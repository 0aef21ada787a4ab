"""The embedding's gradient (ops/embedding_grad.py, ISSUE 58) in
interpret mode on the CPU: the kernel `embedding_grad_rows` against
float32 additions in sorted stable order, bit for bit; the `custom_vjp`
alone, under a tied table and through a Program's `minimize`; the
counter; and the dispatch, which leaves XLA's scatter-add where it was
on every device that is not a TPU.

What interpret mode cannot see (the dynamic sublane of a row's read,
add and write, block shapes, VMEM) `tests/test_tpu_compile_streams.py -k
embedding` compiles for a described v5e, and `chip_smoke.py --phases
embed` runs on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.ops import embedding_grad as EG

R, C = EG._blocks(128)      # table rows a block, sorted places a chunk


def _sorted_stable_sums(ids, dy, vocab):
    """float32 additions, a table row's in token order: numpy's, on the
    host."""
    order = np.argsort(ids, kind="stable")
    want = np.zeros((vocab, dy.shape[1]), np.float32)
    np.add.at(want, ids[order], dy[order])
    return want


def _ids(kind, rng, t, vocab):
    if kind == "unique":
        return rng.permutation(vocab)[:t]
    if kind == "all_the_same":
        return np.full(t, vocab // 3)
    if kind == "ends_of_the_table":
        return np.where(rng.rand(t) < 0.5, 0, vocab - 1)
    if kind == "runs_across_grid_steps":
        # sorted, the second run lies across the first chunk's end, and
        # with it the first table block's places; the next block's one
        # place follows
        return rng.permutation(np.repeat(
            [3, R - 1, R, 2 * R + 5], [C - 24, 60, 1, t - C - 37]))
    return rng.randint(0, vocab, t)     # "uniform"


CASES = [
    # kind, T, V, d
    ("unique", 100, 300, 1024),
    ("all_the_same", 150, 200, 128),
    ("ends_of_the_table", 90, 3 * R + 7, 256),
    ("runs_across_grid_steps", 3 * C, 4 * R, 128),
    ("uniform", 2 * C + 2, R + 6, 128),     # neither T nor V whole blocks
    ("uniform", 40, 1000, 128),             # most blocks hold no place
    ("uniform", 200, 150, 1024),            # the slab's widths and not:
    ("uniform", 200, 150, 2560),
    ("uniform", 200, 150, 3840),
    ("uniform", 200, 150, 8192),            # a chunk of 64 places
]


@pytest.mark.parametrize(
    "kind,t,vocab,d", CASES, ids=["%s_%d_%d_%d" % c for c in CASES])
def test_the_kernel_is_the_sorted_stable_sum_bit_for_bit(kind, t, vocab, d):
    rng = np.random.RandomState(t + vocab + d)
    ids = _ids(kind, rng, t, vocab).astype(np.int32)
    dy = rng.randn(t, d).astype(np.float32)
    got = EG.embedding_grad(jnp.asarray(ids), jnp.asarray(dy), vocab,
                            "interpret")
    assert got.dtype == jnp.float32 and got.shape == (vocab, d)
    np.testing.assert_array_equal(np.asarray(got),
                                  _sorted_stable_sums(ids, dy, vocab))


def test_the_items_cover_every_place_once_and_every_block():
    """The grid's items, read on the host: a block's items are one
    after the other, every block has one, and the places of the live
    items are 0 .. T - 1 in order, each inside its item's chunk and its
    block's rows."""
    rng = np.random.RandomState(5)
    t, vocab = 5 * C - 9, 7 * R + 3
    blocks, chunks = -(-vocab // R), -(-t // C)
    ids = np.sort(np.concatenate([rng.randint(0, 2 * R, t - 30),
                                  rng.randint(5 * R, vocab, 30)]))
    padded = np.concatenate([ids, np.full(chunks * C - t, blocks * R)])
    block, chunk, lo, hi = (np.asarray(a) for a in EG._items(
        jnp.asarray(padded, jnp.int32), R, C, blocks, chunks))
    assert len(block) == blocks + chunks
    assert (np.diff(block) >= 0).all() and set(block) == set(range(blocks))
    places = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])
    np.testing.assert_array_equal(places, np.arange(t))
    for b, c, a, z in zip(block, chunk, lo, hi):
        assert a <= z and (a == z or (c * C <= a and z <= (c + 1) * C))
        assert (ids[a:z] // R == b).all()
    # the items past the last pair stay on its block and chunk, empty:
    # nothing moves for them; every pair before it comes once
    pairs = list(zip(block, chunk))
    last = pairs.index(pairs[-1])
    assert len(set(pairs[:last])) == last and last < len(pairs) - 1
    assert set(pairs[last:]) == {pairs[-1]} and (lo[last + 1:]
                                                 == hi[last + 1:]).all()


def _lookup_loss(force, ids, padding_idx=None):
    """A lookup as `_lookup_table` lowers it, weighed so that every
    row's gradient differs."""
    def loss(w, weight):
        out = EG.take_rows(w, ids, force)
        if padding_idx is not None:
            out = out * (ids != padding_idx)[..., None].astype(out.dtype)
        return (out * weight).sum()
    return loss


@pytest.mark.parametrize("padding_idx", [None, 7], ids=["plain", "padding"])
def test_the_custom_vjp_is_the_scatter_adds_gradient(padding_idx):
    """[B, T] ids through `take_rows`: the table's gradient on the
    kernel's path is XLA's, and a `padding_idx` row's is zero."""
    rng = np.random.RandomState(11)
    vocab, d = 90, 256
    ids = jnp.asarray(rng.randint(0, vocab, (3, 50)), jnp.int32)
    ids = ids.at[:, ::5].set(7)
    w = jnp.asarray(rng.randn(vocab, d), jnp.float32)
    weight = jnp.asarray(rng.randn(3, 50, d), jnp.float32)
    got, want = (jax.grad(_lookup_loss(force, ids, padding_idx))(w, weight)
                 for force in ("interpret", "xla"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[7] == 0).all() == (padding_idx is not None)


def test_the_custom_vjp_inside_a_jitted_recompute_region():
    """Forward and backward traced apart (`jax.checkpoint` under
    `jax.jit`): the table's size reaches the backward as a number."""
    rng = np.random.RandomState(13)
    ids = jnp.asarray(rng.randint(0, 70, 100), jnp.int32)
    w = jnp.asarray(rng.randn(70, 128), jnp.float32)
    weight = jnp.asarray(rng.randn(100, 128), jnp.float32)
    got, want = (jax.jit(jax.grad(jax.checkpoint(_lookup_loss(force, ids))))(
        w, weight) for force in ("interpret", "xla"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_tied_tables_two_gradients_sum_as_before():
    """The head reads the table too: dW is the lookup's gradient plus
    the product's, on either path."""
    rng = np.random.RandomState(12)
    vocab, d, t = 70, 128, 96
    ids = jnp.asarray(rng.randint(0, vocab, t), jnp.int32)
    w = jnp.asarray(rng.randn(vocab, d), jnp.float32)
    weight = jnp.asarray(rng.randn(t, vocab), jnp.float32)

    def tied(force):
        def loss(w):
            x = EG.take_rows(w, ids, force)
            return (jnp.dot(x, w.T, precision="highest") * weight).sum()
        return jax.grad(loss)(w)

    np.testing.assert_array_equal(np.asarray(tied("interpret")),
                                  np.asarray(tied("xla")))


def _two_layer_step(prefix, padding_idx):
    """An embedding (128 wide, so the kernel can take it) under an fc,
    one Adam step: {parameter: value after it}."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        ids = fluid.layers.data("ids", [1], dtype="int64")
        label = fluid.layers.data("label", [1], dtype="int64")
        emb = fluid.layers.embedding(ids, [50, 128], padding_idx=padding_idx)
        logits = fluid.layers.fc(fluid.layers.fc(emb, 32, act="relu"), 10)
        cost = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(2)
        exe.run(main, feed={
            "ids": rng.randint(0, 50, (150, 1)).astype(np.int64),
            "label": rng.randint(0, 10, (150, 1)).astype(np.int64)},
            fetch_list=[cost])
        return {p.name.replace(prefix, ""): np.asarray(scope.find_var(p.name))
                for p in main.global_block().all_parameters()}


@pytest.mark.parametrize("padding_idx", [None, 4], ids=["plain", "padding"])
def test_a_programs_adam_step_is_the_xla_paths(padding_idx, monkeypatch):
    """`layers.embedding` -> `minimize` with the dispatch steered to
    the kernel (in the test: the program has no such option) against
    the CPU's default: the same parameters after the step, the table's
    among them, and the counter says which path each build took."""
    want = _two_layer_step("x_", padding_idx)
    label = dict(rows="150", vocab="50", width="128")
    before = EG._LOWERINGS.value(path="interpret", **label)
    assert EG._LOWERINGS.value(path="xla", **label) >= 1
    monkeypatch.setattr(EG, "_resolve_path",
                        lambda ids, shape, dtype, like, force: "interpret")
    got = _two_layer_step("k_", padding_idx)
    assert EG._LOWERINGS.value(path="interpret", **label) == before + 1
    assert sorted(got) == sorted(want) and len(got) == 5
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("how", ["is_sparse", "is_distributed", "tied",
                                 "mesh"])
def test_sparse_distributed_and_tied_tables_and_meshes_keep_xlas_form(
        how, monkeypatch):
    """`_lookup_table` asks for XLA's form outright for a sparse or a
    distributed table, for one a head reads too (XLA accumulates into
    the head's gradient in place; the optimizer's reading the table
    ties nothing) and under a mesh of more than one device (GSPMD
    cannot partition a Mosaic kernel); a plain table on one device,
    looked up twice or once, is the dispatch's to decide."""
    from paddle_tpu import parallel
    asked = []
    take_rows = EG.take_rows
    monkeypatch.setattr(EG, "take_rows", lambda w, ids, force=None: (
        asked.append(force), take_rows(w, ids, force))[1])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.scope_guard(
            fluid.Scope()), unique_name.guard(how + "_"):
        ids = fluid.layers.data("ids", [1], dtype="int64")
        shared = fluid.ParamAttr(name=how + "_shared")
        plain = (fluid.layers.embedding(ids, [50, 128], param_attr=shared)
                 + fluid.layers.embedding(ids, [50, 128], param_attr=shared))
        other = fluid.layers.embedding(
            ids, [50, 128], param_attr=fluid.ParamAttr(name=how + "_other"),
            **({how: True} if how.startswith("is_") else {}))
        if how == "tied":
            other = fluid.layers.tied_head(
                other, main.global_block().var(how + "_other"))
        cost = fluid.layers.mean(plain) + fluid.layers.mean(other)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(cost)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        feed = {"ids": np.arange(16).reshape(16, 1).astype(np.int64)}
        if how == "mesh":
            exe = fluid.ParallelExecutor(
                main_program=main, mesh=parallel.make_mesh({"dp": 8}))
            exe.run([cost], feed=feed)
        else:
            fluid.Executor(fluid.CPUPlace()).run(main, feed=feed,
                                                 fetch_list=[cost])
    assert asked == (["xla"] * 3 if how == "mesh" else [None, None, "xla"])


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


@pytest.mark.parametrize("force", [None, "xla", "interpret"],
                         ids=["cpu_default", "xla", "interpret"])
def test_the_dispatch_leaves_xlas_lowering_where_it_was(force):
    """On the CPU, and under force="xla", the gradient's jaxpr holds
    `scatter-add` and no kernel, as before this op existed; the
    kernel's path holds the kernel and no scatter."""
    ids = jnp.arange(64, dtype=jnp.int32) % 40
    w = jnp.ones((40, 128), jnp.float32)
    grad = jax.grad(lambda w: EG.take_rows(w, ids, force).sum())
    found = set(_primitives(jax.make_jaxpr(grad)(w).jaxpr))
    assert ("scatter-add" in found) == (force != "interpret")
    assert ("pallas_call" in found) == (force == "interpret")
    assert "custom_vjp_call" not in found or force == "interpret"


def test_what_the_kernel_cannot_take_stays_with_xla():
    """Rows that are not whole lane tiles, tables that are not float32
    and more ids than SMEM holds resolve to XLA's form, and forcing the
    kernel on them is an error, not a wrong sum."""
    like, f32 = jnp.zeros(()), jnp.float32
    not_usable = [(64, (40, 96), f32), (64, (40, 128), jnp.bfloat16),
                  (EG._SMEM_WORDS, (40, 128), f32),
                  (64, (64 * EG._SMEM_WORDS // 4, 128), f32)]
    for ids, shape, dtype in not_usable:
        assert EG._resolve_path(ids, shape, dtype, like, None) == "xla"
        with pytest.raises(ValueError, match="whole lane tiles"):
            EG._resolve_path(ids, shape, dtype, like, "pallas")
    assert EG._resolve_path(64, (40, 128), f32, like, None) == "xla"  # a CPU
    assert EG._resolve_path(64, (40, 128), f32, like,
                            "interpret") == "interpret"


def test_a_block_and_a_chunk_are_whole_tiles_inside_their_bytes():
    assert [EG._blocks(d) for d in (128, 2560, 4096, 8192, 65536)] == [
        (64, 128), (64, 128), (64, 128), (64, 64), (8, 8)]
