"""The traffic generators: the same seed gives the same traffic, every seed
gives the same work in another order, and the lengths are the stated ones."""

import os

import numpy as np
import pytest

from benchmarks.lib import manifest as mf

GEN = mf.load_module(os.path.join(mf.BENCH, "generators", "open_loop_lognormal.py"))
BATCHES = mf.load_module(os.path.join(mf.BENCH, "generators", "fixed_batches.py"))
SERVE_MIXES = ["chat-steady", "chat-overload", "chat-capacity"]


def _params(mix):
    return mf.load_json(os.path.join(mf.BENCH, "traffic", f"{mix}.json"))["params"]


def _gen(mix, seed, seconds=40, salt=0):
    return GEN.generate(_params(mix), seed, seconds, {"vocab_size": 32000, "salt": salt})["requests"]


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_same_seed_same_traffic(mix):
    assert _gen(mix, 3000000011) == _gen(mix, 3000000011)
    assert _gen(mix, 1) != _gen(mix, 2)  # other tokens


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_every_seed_offers_the_same_work(mix):
    """With ``order_seed`` in the mix the schedule is the mix's own and the seed
    draws only the tokens; without it the seed also permutes the order."""
    sizes = lambda rs: [(len(r["prompt"]), r["max_new_tokens"], r["arrival_s"]) for r in rs]
    a, b = _gen(mix, 1), _gen(mix, 2**31 + 7)
    assert sizes(a) == sizes(b) and a[0]["prompt"] != b[0]["prompt"]
    free = {k: v for k, v in _params(mix).items() if k != "order_seed"}
    c, d = (GEN.generate(free, s, 40, {"vocab_size": 32000})["requests"] for s in (1, 2**31 + 7))
    for pick in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(pick, c)) == sorted(map(pick, d)) == sorted(map(pick, a))
        assert list(map(pick, c)) != list(map(pick, d))
    gaps = lambda rs: np.sort(np.diff([0.0] + [r["arrival_s"] for r in rs]))
    assert np.allclose(gaps(c), gaps(d))


@pytest.mark.parametrize("mix", ["chat-steady", "chat-overload"])
def test_lengths_hit_their_stated_medians_and_clips(mix):
    p = _params(mix)
    rs = _gen(mix, 5, seconds=200)
    assert len(rs) == round(p["rate_rps"] * 200)
    for key, size in (("prompt", lambda r: len(r["prompt"])), ("output", lambda r: r["max_new_tokens"])):
        xs = np.asarray([size(r) for r in rs])
        assert abs(np.median(xs) - p[key]["median"]) <= 0.03 * p[key]["median"]
        assert abs(np.percentile(xs, 95) - p[key]["p95"]) <= 0.06 * p[key]["p95"]
        assert xs.min() >= p[key]["min"] and xs.max() <= p[key]["max"]
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in rs) <= 2560  # the engine's max_context
    arrivals = [r["arrival_s"] for r in rs]
    assert arrivals == sorted(arrivals) and 0 < arrivals[0] and arrivals[-1] < 200


def test_salt_changes_the_tokens_and_nothing_else():
    a, b = _gen("chat-steady", 9, salt=0), _gen("chat-steady", 9, salt=1)
    assert [(r["arrival_s"], len(r["prompt"]), r["max_new_tokens"]) for r in a] == \
           [(r["arrival_s"], len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all(0 <= t < 32000 for r in a for t in r["prompt"])


def test_capacity_mix_is_front_loaded():
    rs = _gen("chat-capacity", 1)
    assert rs[-1]["arrival_s"] < _params("chat-capacity")["front_load_s"]


def test_fixed_batches_are_seeded_whole_sequences():
    p = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-z3.json"))["params"]
    ctx = {"vocab_size": 50304, "global_batch": 8}
    a = BATCHES.generate(p, 2**31 + 3, 40, ctx)["batches"]
    b = BATCHES.generate(p, 2**31 + 3, 40, ctx)["batches"]
    assert len(a) == p["n_batches"] and a[0]["input_ids"].shape == (8, p["seq_len"])
    assert all(np.array_equal(x["input_ids"], y["input_ids"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])
    assert a[0]["input_ids"].dtype == np.int32 and int(a[0]["input_ids"].max()) < 50304
