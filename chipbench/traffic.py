"""The one traffic generator: a traffic file's distributions and rate
become a schedule of requests that offers the same work under every
seed.

What makes two runs agree (PR 22 was refused for lacking it): with
``n = floor(rate x seconds)`` requests in a segment, prompt lengths are
the (i+1/2)/n quantiles of the prompt distribution and output lengths
the same of theirs: a fixed multiset, whatever the seed. ``--seed``
decides how the two lists are paired, in what order the requests arrive,
where each arrival falls inside its own 1/rate slot, and the token ids
(and, in the drivers, the weights). Every run offers the same number of
requests, the same prompt tokens and the same output tokens; a cell
needs enough requests in its window for the order to average out
(PERF.md, PR 23: with 33 requests the order moved the token gap by 7%).

Three segments share the schedule: ``pre`` (load before the window, so
the window opens in steady state), ``window`` (the requests the metrics
cover: those DUE inside it) and ``post`` (load kept up while the
window's requests drain). Times are seconds relative to the window's
start.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile(dist, p):
    """The p-quantile of one length distribution of a traffic file."""
    kind = dist["kind"]
    if kind == "constant":
        return float(dist["value"])
    if kind == "uniform":
        return dist["lo"] + p * (dist["hi"] - dist["lo"])
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * _NORMAL.inv_cdf(p))
        return min(max(x, dist["lo"]), dist["hi"])
    raise ValueError("unknown distribution kind %r" % (kind,))


def stratified(dist, n):
    """n integer lengths: the (i+1/2)/n quantiles, in rising order."""
    return [int(round(quantile(dist, (i + 0.5) / n))) for i in range(n)]


def _segment(name, t0, seconds, traffic, rng):
    rate = float(traffic["rate_per_s"])
    n = int(rate * seconds + 1e-9)     # whole slots of 1/rate only
    prompts = rng.permutation(stratified(traffic["prompt_tokens"], n))
    outputs = rng.permutation(stratified(traffic["output_tokens"], n))
    jitter = rng.random(n)
    return [{"segment": name, "due": t0 + (k + jitter[k]) / rate,
             "prompt_len": int(prompts[k]), "max_new": int(outputs[k])}
            for k in range(n)]


def schedule(traffic, seconds, seed):
    """Every request of one run, in due order: dicts of ``k``,
    ``segment``, ``due`` (s from the window's start), ``prompt_len``,
    ``max_new``."""
    rng = np.random.default_rng([int(seed), 0])
    pre, post = float(traffic["pre_roll_s"]), float(traffic["drain_s"])
    reqs = (_segment("pre", -pre, pre, traffic, rng)
            + _segment("window", 0.0, float(seconds), traffic, rng)
            + _segment("post", float(seconds), post, traffic, rng))
    for k, r in enumerate(reqs):
        r["k"] = k
    return reqs


def prompt_ids(seed, k, prompt_len, vocab, bos_id=1):
    """Request k's token ids: BOS, then seeded ids in [3, vocab).
    Negative k names the requests of the correctness sample."""
    rng = np.random.default_rng([int(seed), 1 if k >= 0 else 3, abs(k)])
    return [bos_id] + rng.integers(3, vocab, prompt_len - 1).tolist()


def totals(reqs, segment="window"):
    """(requests, prompt tokens, output tokens) offered in a segment."""
    sel = [r for r in reqs if r["segment"] == segment]
    return (len(sel), sum(r["prompt_len"] for r in sel),
            sum(r["max_new"] for r in sel))


def lm_batches(seed, n_batches, batch, seq_len, vocab):
    """Seeded packed-sequence training batches as the trainer's numpy
    feeds (src/pos/mask/label [batch, seq_len]; label = next token)."""
    rng = np.random.default_rng([int(seed), 2])
    pos = np.tile(np.arange(seq_len, dtype=np.int64), (batch, 1))
    out = []
    for _ in range(n_batches):
        src = rng.integers(3, vocab, (batch, seq_len), dtype=np.int64)
        label = np.roll(src, -1, axis=1)
        label[:, -1] = 0
        out.append({"src": src, "pos": pos, "label": label,
                    "mask": np.ones((batch, seq_len), np.float32)})
    return out
