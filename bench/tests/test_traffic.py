"""The generator: seeded, within its clips, at its rate, and the same
work for every seed."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench.lib import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 12345


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "docs", "longctx"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), BIG_SEED, 30, 1000)
    b = traffic.generate(mix(name), BIG_SEED, 30, 1000)
    assert [(r.due_s, r.max_new) for r in a] == \
        [(r.due_s, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.generate(mix(name), BIG_SEED + 1, 30, 1000)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))
    # Another seed: other tokens, the same lengths, gaps and order.
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s) for r in c]


@pytest.mark.parametrize("name", ["chat", "docs", "longctx"])
def test_lengths_within_clips(name):
    m = mix(name)
    reqs = traffic.generate(m, 7, 30, 1000)
    plens = np.array([len(r.prompt) for r in reqs])
    assert plens.min() >= m["prompt"]["min"]
    assert plens.max() <= m["prompt"]["max"]
    out = np.array([r.max_new for r in reqs])
    if m["output"]["dist"] == "fill_to":
        assert np.all(plens + out == m["output"]["total"])
    else:
        assert out.min() >= m["output"]["min"]
        assert out.max() <= m["output"]["max"]
    assert max(plens + out) <= m["engine"]["max_len"]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_poisson_mean_matches_rate():
    m = mix("chat")
    rate = m["arrivals"]["rate_per_s"]
    lead = m["arrivals"]["lead_s"]
    reqs = traffic.generate(m, 3, 600, 100)
    due = np.array([r.due_s for r in reqs])
    gaps = np.diff(due)
    # The lead's arrivals come first, due before the window opens.
    assert len(reqs) == round(rate * (600 + lead))
    assert due[0] == -lead and due[1] > -lead
    assert abs(np.sum(due < 0) - rate * lead) <= 2
    assert abs(gaps.mean() * rate - 1) < 0.005
    # Exponential: the spread of the gaps is about their mean (a little
    # less, as each stratum stands at its mean).
    assert 0.85 < gaps.std() / gaps.mean() < 1.0


@pytest.mark.parametrize("name", ["chat", "docs", "longctx"])
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    block = m["block"]
    runs = [traffic.generate(m, s, 30, 1000) for s in (1, 2, BIG_SEED)]
    n = len(runs[0]) // block * block
    for reqs in runs[1:]:
        for k in range(block, n + 1, block):
            assert Counter(len(r.prompt) for r in reqs[:k]) == \
                Counter(len(r.prompt) for r in runs[0][:k])
            assert Counter(r.max_new for r in reqs[:k]) == \
                Counter(r.max_new for r in runs[0][:k])
    if m["arrivals"]["kind"] == "poisson":
        # A block's first request is due once the blocks before it have
        # passed, whatever the order inside them.
        for k in range(block, n, block):
            due = [reqs[k].due_s for reqs in runs]
            assert max(due) - min(due) < 1e-9


def test_chunk_widths_cover_the_mix():
    m = mix("chat")
    chunk, page = m["engine"]["prefill_chunk"], m["engine"]["page_size"]
    widths = set(traffic.chunk_widths(m))
    for r in traffic.generate(m, 11, 30, 100):
        plen = len(r.prompt)
        tail = (plen - 1) % chunk + 1
        assert -(-tail // page) * page in widths
        if plen > chunk:
            assert chunk in widths
