"""The stratified generator: the same number of requests, the same
prompt tokens and the same output tokens in every run, whatever the
seed (the property PR 22's benchmark lacked)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import traffic  # noqa: E402

MIXES = [n[:-5] for n in sorted(os.listdir(
    os.path.join(ROOT, "chipbench", "traffic"))) if n.endswith(".json")]


def _mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           name + ".json")) as f:
        return json.load(f)


OPEN_LOOP = [m for m in MIXES if _mix(m)["driver"] == "open_loop"]
TRAIN = [m for m in MIXES if _mix(m)["driver"] == "train_steps"]


@pytest.mark.parametrize("mix", OPEN_LOOP)
@pytest.mark.parametrize("seconds", [10, 51])
def test_every_seed_offers_the_same_requests(mix, seconds):
    """Under two seeds the request count, the prompt tokens and the
    output tokens are identical: the lengths are a fixed multiset, and
    the run's seed draws the pairing, the order and the token ids."""
    m = _mix(mix)
    reqs = traffic.schedule(m, seconds, 7)
    assert reqs == traffic.schedule(m, seconds, 7)
    n, ptok, otok = traffic.totals(reqs)
    assert n == int(m["rate_per_s"] * seconds + 1e-9)
    # stratified: the lengths ARE the distribution's quantiles
    window = [r for r in reqs if r["segment"] == "window"]
    assert sorted(r["prompt_len"] for r in window) == \
        traffic.stratified(m["prompt_tokens"], n)
    assert sorted(r["max_new"] for r in window) == \
        traffic.stratified(m["output_tokens"], n)
    # another seed, past 2**31: same totals in every segment, another
    # pairing and order
    other = traffic.schedule(m, seconds, 3000000019)
    for segment in ("pre", "window", "post"):
        assert traffic.totals(other, segment) == \
            traffic.totals(reqs, segment)
    assert [(r["prompt_len"], r["max_new"]) for r in other] != \
        [(r["prompt_len"], r["max_new"]) for r in reqs]
    ids = [traffic.prompt_ids(3000000019, r["k"], r["prompt_len"], 50272)
           for r in window[:3]]
    assert [len(x) for x in ids] == [r["prompt_len"] for r in window[:3]]


def test_seed_draws_the_token_ids():
    assert traffic.prompt_ids(42, 3, 50, 50272) == \
        traffic.prompt_ids(42, 3, 50, 50272)
    assert traffic.prompt_ids(42, 3, 50, 50272) != \
        traffic.prompt_ids(43, 3, 50, 50272)
    assert traffic.prompt_ids(42, 3, 50, 50272) != \
        traffic.prompt_ids(42, -3, 50, 50272)


@pytest.mark.parametrize("mix", OPEN_LOOP)
def test_arrivals_keep_their_slots_and_lengths_fit(mix):
    m = _mix(mix)
    reqs = traffic.schedule(m, 20, 11)
    rate = m["rate_per_s"]
    window = [r for r in reqs if r["segment"] == "window"]
    for k, r in enumerate(window):          # request k in slot k
        assert k / rate <= r["due"] < (k + 1) / rate
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    assert all(r["due"] < 0 for r in reqs if r["segment"] == "pre")
    assert all(r["due"] >= 20 for r in reqs if r["segment"] == "post")
    # no operation may fail: every request fits the model's context
    assert all(r["prompt_len"] + r["max_new"] - 1 <= 2048
               and r["prompt_len"] >= 2 and r["max_new"] >= 1
               for r in reqs)
    ids = traffic.prompt_ids(7, 0, reqs[0]["prompt_len"], 50272)
    assert len(ids) == reqs[0]["prompt_len"] and ids[0] == 1
    assert all(3 <= t < 50272 for t in ids[1:])


@pytest.mark.parametrize("dist, n, want", [
    ({"kind": "constant", "value": 7}, 3, [7, 7, 7]),
    ({"kind": "uniform", "lo": 0, "hi": 100}, 4, [12, 38, 62, 88]),
])
def test_stratified_quantiles(dist, n, want):
    assert traffic.stratified(dist, n) == want


def test_lognormal_is_clipped_and_centred():
    dist = {"kind": "lognormal", "median": 256, "sigma": 0.9,
            "lo": 32, "hi": 1024}
    xs = traffic.stratified(dist, 1001)
    assert xs == sorted(xs) and xs[0] >= 32 and xs[-1] == 1024
    assert xs[500] == 256
    with pytest.raises(ValueError):
        traffic.quantile({"kind": "zipf"}, 0.5)


@pytest.mark.parametrize("mix", TRAIN)
def test_training_batches_come_from_the_seed(mix):
    m = _mix(mix)
    a = traffic.lm_batches(5, 2, 2, 16, 100)
    b = traffic.lm_batches(5, 2, 2, 16, 100)
    c = traffic.lm_batches(6, 2, 2, 16, 100)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, b)
               for k in x)
    assert not np.array_equal(a[0]["src"], c[0]["src"])
    assert np.array_equal(a[0]["label"][:, :-1], a[0]["src"][:, 1:])
    assert a[0]["mask"].all() and m["batch"] >= 1
