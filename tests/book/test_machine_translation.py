"""Book test: machine_translation (reference
python/paddle/fluid/tests/book/test_machine_translation.py) — the
attention seq2seq (here: the transformer the benchmarks use) trained on
wmt14-style triples to a loss threshold, then BEAM-SEARCH decode of the
trained weights (the decode path round 1 lacked entirely)."""

import numpy as np

import paddle_tpu as paddle
import paddle_tpu as fluid
from paddle_tpu.models import transformer as T


DICT = 64
LEN = 16


_P = 0.82 ** np.arange(DICT - 3)
_P /= _P.sum()


def _feeds(rng, batch):
    # skewed (geometric) token distribution: the model provably learns by
    # fitting the unigram prior (loss drops well below the uniform ln|V|)
    # plus the deterministic trg = src+1 structure
    src = (rng.choice(DICT - 3, size=(batch, LEN), p=_P) + 3).astype(
        np.int64)
    pos = np.tile(np.arange(LEN, dtype=np.int64), (batch, 1))
    mask = np.ones((batch, LEN), np.float32)
    trg = (src + 1) % DICT
    lbl = np.roll(trg, -1, axis=1)
    return {"src_word": src, "src_pos": pos, "src_mask": mask,
            "trg_word": trg, "trg_pos": pos, "trg_mask": mask,
            "lbl_word": lbl}


def test_machine_translation_train_and_beam_decode():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        avg_cost, _ = T.transformer(
            src_vocab_size=DICT, trg_vocab_size=DICT, max_len=LEN,
            n_layer=1, n_head=2, d_model=32, d_inner=64)
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(avg_cost)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        first = last = None
        for i in range(100):
            lv, = exe.run(main, feed=_feeds(rng, 8),
                          fetch_list=[avg_cost])
            if first is None:
                first = float(lv)
            last = float(lv)
    assert last < first * 0.75, (first, last)
    # ABSOLUTE: uniform CE over DICT=64 is ln(64)=4.16; converged runs
    # sit far below 3.2 (round-4 review: absolute threshold)
    assert last < 3.2, (first, last)

    # beam-search decode with the TRAINED weights (book decode path)
    import jax.numpy as jnp
    from paddle_tpu.models.transformer_infer import TransformerInfer
    infer = TransformerInfer(main, scope, n_layer=1, n_head=2, d_model=32,
                             max_len=LEN)
    feeds = _feeds(rng, 4)
    src = jnp.asarray(feeds["src_word"], jnp.int32)
    mask = jnp.asarray(feeds["src_mask"])
    sents, scores = infer.translate(src, mask, beam_size=2, max_out_len=8)
    sents = np.asarray(sents)
    scores = np.asarray(scores)
    assert sents.shape == (4, 2, 8)
    assert np.isfinite(scores).all()
    assert (sents >= 0).all() and (sents < DICT).all()
    # beams sorted best-first
    assert (np.diff(scores, axis=1) <= 1e-5).all()
