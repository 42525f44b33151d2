"""The traffic generator: deterministic per seed, the same sizes for every
seed, and the file's parameters."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import traffic as T  # noqa: E402

VOCAB, MAX_LEN = 151936, 8192


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def reqs(p):
    return p.initial + p.queue


@pytest.mark.parametrize("name", ["chat", "conv", "code"])
def test_same_seed_same_plan(name):
    a = T.plan(mix(name), VOCAB, MAX_LEN, 2**31 + 12345, 45)
    b = T.plan(mix(name), VOCAB, MAX_LEN, 2**31 + 12345, 45)
    for x, y in zip(reqs(a), reqs(b)):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_tokens, x.temperature, x.top_k, x.top_p, x.lane_seed,
                x.due) == (y.max_tokens, y.temperature, y.top_k, y.top_p,
                           y.lane_seed, y.due)


@pytest.mark.parametrize("name", ["chat", "conv", "code"])
def test_lengths_fit_the_engine(name):
    for r in reqs(T.plan(mix(name), VOCAB, MAX_LEN, 3, 45)):
        assert 1 <= len(r.prompt) <= MAX_LEN // 2
        assert r.max_tokens >= 1
        assert len(r.prompt) + r.max_tokens <= MAX_LEN - 1
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < VOCAB


def test_open_loop_has_the_same_arrivals_and_sizes_for_every_seed():
    m = mix("code")
    plans = [T.plan(m, VOCAB, MAX_LEN, s, 45) for s in (1, 2, 2**31 + 7)]
    n = round(m["rate_per_s"] * 45)
    warm = round(m["rate_per_s"] * m["warm_s"])
    for p in plans:
        assert len(p.queue) == n + warm and not p.initial
        due = [r.due for r in p.queue]
        assert due[0] == -m["warm_s"] and all(d < 45 for d in due)
        assert sum(d >= 0 for d in due) == n
        assert np.all(np.diff(due) > 0)
    keys = [sorted((len(r.prompt), r.max_tokens) for r in p.queue)
            for p in plans]
    gaps = [np.sort(np.diff([r.due for r in p.queue])) for p in plans]
    assert sorted(k[0] for k in keys[0]) == sorted(k[0] for k in keys[1])
    assert sorted(k[1] for k in keys[0]) == sorted(k[1] for k in keys[2])
    assert [len(r.prompt) for r in plans[0].queue] != \
        [len(r.prompt) for r in plans[1].queue]
    assert abs(np.mean(gaps[0][gaps[0] > 0]) * (n + warm)
               / (45 + m["warm_s"]) - 1) < 0.1


def test_lengths_follow_the_lognormal_and_the_clip():
    m = mix("code")
    L = T.lognormal_quantiles(m["prompt"], 1001, m["prompt"]["min"], 4096)
    assert L[500] == int(np.exp(m["prompt"]["mu"]))
    assert L.min() >= m["prompt"]["min"] and L.max() == 4096
    z16 = np.exp(m["prompt"]["mu"] - m["prompt"]["sigma"])
    assert abs(np.sort(L)[int(1001 * 0.1587)] / z16 - 1) < 0.02


@pytest.mark.parametrize("name", ["chat", "conv"])
def test_closed_loop_starts_in_steady_state(name):
    m = mix(name)
    p = T.plan(m, VOCAB, MAX_LEN, 5, 45)
    assert len(p.initial) == m["clients"] == p.clients
    assert len(p.queue) == m["pool"]
    assert len({r.idx for r in reqs(p)}) == m["pool"] + m["clients"]
    # the same in-progress sizes for every seed, in another order
    q = T.plan(m, VOCAB, MAX_LEN, 2**31 + 99, 45)
    sizes = [sorted((len(r.prompt), r.max_tokens) for r in x.initial)
             for x in (p, q)]
    assert sizes[0] == sizes[1]
    assert [len(r.prompt) for r in p.initial] != \
        [len(r.prompt) for r in q.initial]
    assert max(len(r.prompt) for r in p.initial) == \
        min(m["prompt"]["max"], MAX_LEN // 2)
    # length-biased by output: in-progress requests are the longer ones
    full = [r.max_tokens for r in p.queue]
    assert np.mean([len(r.prompt) for r in p.initial]) > \
        np.mean([len(r.prompt) for r in p.queue])
    assert min(full) >= 1


@pytest.mark.parametrize("name", ["chat", "conv", "code"])
def test_sampling_modes_in_the_file_shares(name):
    m = mix(name)
    p = T.plan(m, VOCAB, MAX_LEN, 9, 45)
    rs = reqs(p)
    for mode in m["sampling"]:
        n = sum(1 for r in rs
                if (r.greedy if mode.get("greedy") else
                    (not r.greedy and r.top_k == mode.get("top_k", 0)
                     and r.top_p == mode.get("top_p", 1.0))))
        assert abs(n - mode["share"] * len(rs)) <= 1
    for r in rs:
        assert (r.lane_seed is None) == r.greedy
        assert r.lane_seed is None or 0 <= r.lane_seed < 2**31
