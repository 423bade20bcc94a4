"""The generator: the same seed gives the same schedule, and the amount
of work does not depend on the seed."""

import collections
import json
import os

import numpy as np
import pytest

from chipbench import run as bench_run
from chipbench import traffic

# every committed serving mix, and the first of them with nothing shared
MIXES = sorted(
    f[:-5] for f in os.listdir(os.path.join(bench_run.HERE, "traffic"))
    if f.endswith(".json") and '"serve"' in open(
        os.path.join(bench_run.HERE, "traffic", f)).read())
MIXES.append(MIXES[0] + ":unshared")
BIG = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits


def _mix(name):
    name, _, variant = name.partition(":")
    with open(os.path.join(bench_run.HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if variant == "unshared":
        mix["shared_heads"] = {"count": 0, "tokens": 0, "zipf_s": 1.0}
    return mix


def _work(sched):
    heads = sched["heads"]
    return collections.Counter(
        (int(h), len(p) - (len(heads[h]) if h >= 0 else 0), int(m))
        for h, p, m in zip(sched["head"], sched["prompts"],
                           sched["max_new"]))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.serve_schedule(_mix(name), 50257, BIG, 45.0)
    b = traffic.serve_schedule(_mix(name), 50257, BIG, 45.0)
    assert np.array_equal(a["due"], b["due"])
    assert all(np.array_equal(x, y)
               for x, y in zip(a["prompts"], b["prompts"]))
    assert np.array_equal(a["max_new"], b["max_new"])


@pytest.mark.parametrize("name", MIXES)
def test_work_and_timing_do_not_depend_on_the_seed(name):
    mix = _mix(name)
    a = traffic.serve_schedule(mix, 50257, 1, 45.0)
    b = traffic.serve_schedule(mix, 50257, BIG, 45.0)
    assert _work(a) == _work(b)
    assert np.array_equal(a["due"], b["due"])
    assert [len(p) for p in a["prompts"]] == [len(p) for p in b["prompts"]]
    assert np.array_equal(a["max_new"], b["max_new"])
    # what the seed does change: every token id
    assert not any(np.array_equal(x, y)
                   for x, y in zip(a["prompts"], b["prompts"]))
    n = round(mix["rate_per_s"] * 45.0)
    assert len(a["prompts"]) == len(b["prompts"]) == n
    assert (np.diff(a["due"]) >= 0).all() and 0 <= a["due"][0]
    assert a["due"][-1] < 45.0


def test_another_schedule_seed_is_the_same_work_at_other_times():
    mix = _mix(MIXES[0])
    a = traffic.serve_schedule(mix, 50257, 1, 45.0)
    b = traffic.serve_schedule(dict(mix, schedule_seed=99), 50257, 1, 45.0)
    assert not np.array_equal(a["due"], b["due"])
    assert sorted(len(p) for p in a["prompts"]) == sorted(
        len(p) for p in b["prompts"])
    assert sorted(a["max_new"]) == sorted(b["max_new"])
    assert sorted(a["head"]) == sorted(b["head"])


@pytest.mark.parametrize("name", MIXES)
def test_every_request_fits_its_slot_and_its_vocabulary(name):
    mix = _mix(name)
    s = traffic.serve_schedule(mix, 50257, 7, 30.0)
    tail, out = mix["prompt_tail"], mix["output"]
    head = (mix.get("shared_heads") or {}).get("tokens", 0)
    for p, m, h in zip(s["prompts"], s["max_new"], s["head"]):
        assert len(p) + m <= mix["engine"]["max_len"]
        assert out["min"] <= m <= out["max"]
        assert tail["min"] <= len(p) - (head if h >= 0 else 0) <= tail["max"]
        assert 0 <= p.min() and p.max() < 50257
    if head == 0:  # unshared: no two prompts start alike
        firsts = [int(p[0]) for p in s["prompts"]]
        assert len(set(firsts)) == len(firsts)
        assert not set(firsts) & set(int(x) for x in s["spare_first"])


def test_length_grid_and_quota():
    spec = {"dist": "lognormal", "median": 32, "sigma": 0.8, "min": 8,
            "max": 256}
    g = traffic.length_grid(spec, 1001)
    assert g[500] == 32 and g.min() >= 8 and g.max() <= 256
    assert (np.diff(g) >= 0).all()
    q = traffic.head_quota(4, 1.0, 100)
    assert np.bincount(q).tolist() == [48, 24, 16, 12]


def test_arrivals_are_poisson_given_their_count():
    rng = np.random.default_rng(0)
    t = np.concatenate([traffic.arrival_times(rng, 50, 10.0)
                        for _ in range(200)])
    # uniform order statistics: flat over the window
    hist, _ = np.histogram(t, bins=10, range=(0, 10))
    assert hist.min() > 0.85 * hist.mean()
    gaps = np.diff(traffic.arrival_times(rng, 2000, 10.0))
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


def test_bigram_chain_batches():
    chain = traffic.BigramChain(50257, BIG, branching=4, zipf_s=1.0)
    tok, lab = chain.batch(3, 8, 2048)
    tok2, lab2 = traffic.BigramChain(50257, BIG).batch(3, 8, 2048)
    assert np.array_equal(tok, tok2) and np.array_equal(lab, lab2)
    assert tok.shape == lab.shape == (8, 2048) and tok.dtype == np.int32
    assert np.array_equal(tok[:, 1:], lab[:, :-1])
    assert 0 <= tok.min() and max(tok.max(), lab.max()) < 50257
    # every label is one of its token's four successors
    assert (chain.succ[tok] == lab[..., None]).any(-1).all()
    assert not np.array_equal(tok, chain.batch(4, 8, 2048)[0])
