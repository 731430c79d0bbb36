"""The traffic generator: deterministic per seed, the same work for every
seed (a permutation of one multiset), Poisson at the mix's rate."""
import json
import os

import numpy as np

from bench.traffic import generate

HERE = os.path.join(os.path.dirname(__file__), "..", "traffic")


def mix(name):
    return json.load(open(os.path.join(HERE, name + ".json")))


def test_same_seed_same_requests():
    a = generate.requests(mix("steady_large"), 60, 300, 2**33 + 5)
    b = generate.requests(mix("steady_large"), 60, 300, 2**33 + 5)
    assert a == b


def test_every_seed_gets_the_same_work():
    m = mix("steady_large")
    n = 59 * 41 * 64   # whole cycles of nodes, iteration counts and gaps
    a = generate.requests(m, 60, n, 1)
    b = generate.requests(m, 60, n, 2**31 + 17)
    for key in ("load_x", "n_iter"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert [r[key] for r in a[:50] for key in ("load_x",)] != \
        [r[key] for r in b[:50] for key in ("load_x",)]
    ga, gb = np.diff([r["due_s"] for r in a]), np.diff([r["due_s"] for r in b])
    assert abs(ga.sum() - gb.sum()) / ga.sum() < 1e-3


def test_ranges_and_rate():
    m = mix("steady_small")
    reqs = generate.requests(m, 30, 4096, 7)
    xs = [r["load_x"] for r in reqs]
    assert min(xs) == 0 and max(xs) == 28
    assert all(-1.5 <= r["fy"] <= -0.5 for r in reqs)
    assert {r["n_iter"] for r in reqs} == set(range(20, 61))
    rate = (len(reqs) - 1) / reqs[-1]["due_s"]
    assert abs(rate / m["rate_per_s"] - 1) < 0.02


def test_closed_mix_iterations():
    reqs = generate.requests(mix("backlog16"), 60, 99, 3)
    assert {r["n_iter"] for r in reqs} == set(range(36, 45))
    assert "due_s" not in reqs[0]
    fixed = generate.requests(dict(mix("backlog16"), n_iter=40), 60, 10, 3)
    assert {r["n_iter"] for r in fixed} == {40}
