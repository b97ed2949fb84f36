"""The one traffic generator: seeded, the same work for every seed, and
an open-loop schedule inside the window."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

BENCH = Path(__file__).resolve().parents[2] / "bench"
RATE = json.loads((BENCH / "cells" / "qwen3-0.6b.chat-poisson.json")
                  .read_text())["rate_per_s"]


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def gen(name, seed, seconds=51.0, slots=16):
    rate = RATE if mix(name)["loop"] == "open" else None
    return T.generate(mix(name), seed=seed, seconds=seconds, vocab=151_936,
                      slots=slots, rate=rate)


def schedule(items):
    return [(len(i.prompt), i.max_new, i.due, i.client) for i in items]


@pytest.mark.parametrize("name", ["chat-poisson", "decode-long"])
def test_same_seed_same_requests(name):
    a, b = gen(name, 2**31 + 5), gen(name, 2**31 + 5)
    assert [(i.prompt, i.max_new, i.due, i.client) for i in a] == \
        [(i.prompt, i.max_new, i.due, i.client) for i in b]


@pytest.mark.parametrize("name", ["chat-poisson", "decode-long"])
def test_every_seed_offers_the_same_work(name):
    a, b = gen(name, 1), gen(name, 2**31 + 77)
    assert schedule(a) == schedule(b)
    assert a[0].prompt != b[0].prompt


@pytest.mark.parametrize("name", ["chat-poisson", "decode-long"])
def test_reorder_moves_the_same_requests(name):
    items = gen(name, 3)
    assert T.reorder(items, 0) is items
    other = T.reorder(items, 2)
    assert Counter((len(i.prompt), i.max_new) for i in other) == \
        Counter((len(i.prompt), i.max_new) for i in items)
    assert [(i.idx, i.due, i.client) for i in other] == \
        [(i.idx, i.due, i.client) for i in items]
    assert [len(i.prompt) for i in other] != [len(i.prompt) for i in items]


@pytest.mark.parametrize("name", ["chat-poisson", "decode-long"])
def test_lengths_follow_the_mix(name):
    m = mix(name)
    items = gen(name, 3)
    for key, lens in (("prompt", [len(i.prompt) for i in items]),
                      ("output", [i.max_new for i in items])):
        d = m[key]
        assert d["min"] <= min(lens) and max(lens) <= d["max"]
        assert abs(np.median(lens) - d["median"]) <= 0.1 * d["median"]


def test_open_loop_schedule():
    rate = RATE
    a, b = gen("chat-poisson", 11), gen("chat-poisson", 12)
    assert len(a) == len(b) == round(rate * 51)
    for items in (a, b):
        due = [i.due for i in items]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 51.0
        assert all(i.client is None for i in items)
    gaps = np.diff([i.due for i in a] + [51.0])
    # mid-quantiles of the exponential, scaled so the mean gap is the
    # window over n
    assert np.mean(gaps) == pytest.approx(51.0 / len(a))
    assert sorted(gaps) == pytest.approx(sorted(
        T.exp_gaps(len(a), rate) * 51.0 / T.exp_gaps(len(a), rate).sum()))


def test_closed_loop_deals_requests_to_clients():
    items = gen("decode-long", 5, slots=32)
    per = mix("decode-long")["requests_per_client"]
    assert len(items) == 32 * per
    assert Counter(i.client for i in items) == {c: per for c in range(32)}
    assert all(i.due is None for i in items)


def test_unknown_rate_is_an_error():
    with pytest.raises(ValueError):
        T.generate(mix("chat-poisson"), seed=1, seconds=51.0, vocab=100,
                   slots=16)
