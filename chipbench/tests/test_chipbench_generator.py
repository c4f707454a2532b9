"""The traffic generator: one seed, one trace; every seed the same sizes."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import generator

ROOT = Path(__file__).resolve().parents[2]
BIG_SEED = 2**31 + 12345  # past 32 signed bits, as the check draws them


def mix(name):
    return json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json")
                      .read_text())


def summary(reqs):
    return [(r.rid, r.prompt.tolist(), r.max_new_tokens, r.due_s)
            for r in reqs]


@pytest.mark.parametrize("name", ["offline-backlog", "chat-poisson"])
def test_same_seed_same_trace(name):
    a = generator.make_requests(mix(name), vocab=1000, seed=BIG_SEED,
                                seconds=30)
    b = generator.make_requests(mix(name), vocab=1000, seed=BIG_SEED,
                                seconds=30)
    assert summary(a) == summary(b)
    c = generator.make_requests(mix(name), vocab=1000, seed=BIG_SEED + 1,
                                seconds=30)
    assert summary(a) != summary(c)


@pytest.mark.parametrize("name", ["offline-backlog", "chat-poisson"])
def test_every_seed_the_same_sizes_in_a_window(name):
    """A block is what one window takes: the backlog's first ``block``
    requests, every request of a Poisson window."""
    m = mix(name)
    runs = [generator.make_requests(m, vocab=1000, seed=s, seconds=51)
            for s in (1, 2, BIG_SEED)]
    assert len({len(reqs) for reqs in runs}) == 1
    block = m.get("block", len(runs[0]))
    first = [(Counter(len(r.prompt) for r in reqs[:block]),
              Counter(r.max_new_tokens for r in reqs[:block]))
             for reqs in runs]
    assert first[0] == first[1] == first[2]
    assert [len(r.prompt) for r in runs[0][:block]] != \
        [len(r.prompt) for r in runs[1][:block]]


@pytest.mark.parametrize("name,key", [
    ("offline-backlog", "prompt_tokens"), ("offline-backlog", "output_tokens"),
    ("chat-poisson", "prompt_tokens"), ("chat-poisson", "output_tokens")])
def test_medians_and_clips(name, key):
    d = mix(name)[key]
    vals = generator.quantile_block(d, 32)
    assert vals.min() >= d["min"] and vals.max() <= d["max"]
    # the stratified block hits the clip at both tails it reaches
    assert np.median(vals) == pytest.approx(d["median"], rel=0.05)


def test_backlog_all_due_at_zero():
    m = mix("offline-backlog")
    reqs = generator.make_requests(m, vocab=1000, seed=3, seconds=10)
    assert len(reqs) == m["arrival"]["requests"]
    assert all(r.due_s == 0.0 for r in reqs)
    assert max(len(r.prompt) for r in reqs) == m["prompt_tokens"]["max"]
    assert all(1 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_poisson_rate_and_window():
    m = mix("chat-poisson")
    rate = m["arrival"]["rate_per_s"]
    reqs = generator.make_requests(m, vocab=1000, seed=5, seconds=40)
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0 and (np.diff(due) > 0).all() and due[-1] < 40
    assert len(reqs) == round(rate * 40)
    gaps = {tuple(sorted(np.diff([r.due_s for r in generator.make_requests(
        m, vocab=1000, seed=s, seconds=40)]).round(9))) for s in range(3)}
    assert len({len(g) for g in gaps}) == 1
