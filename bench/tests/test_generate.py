"""The traffic generator: the same seed gives the same inputs, every
seed gets the same sizes in another order, and the drawn lengths and
popularity follow the mix's parameters."""

import numpy as np
import pytest

from bench import generate as G
from conftest import ROOT, load


def tenants_mix():
    import json

    with open(ROOT / "bench" / "traffic" / "tenants.json") as f:
        return json.load(f)


def test_same_seed_same_requests():
    mix = tenants_mix()
    a = G.serve_requests(mix, 1000, 2 ** 35 + 3, 64, 600.0)
    b = G.serve_requests(mix, 1000, 2 ** 35 + 3, 64, 600.0)
    assert [(r.arrival, r.max_new, r.tenant, r.latency_class)
            for r in a] == [(r.arrival, r.max_new, r.tenant, r.latency_class)
                            for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_sizes_and_gaps():
    mix = tenants_mix()
    a = G.serve_requests(mix, 1000, 1, 64, 600.0)
    b = G.serve_requests(mix, 1000, 2, 64, 600.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.arrival for r in rs]), 9))
    assert gaps(a) == gaps(b)
    assert a[-1].arrival == pytest.approx(b[-1].arrival) and a[-1].arrival <= 600
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_offline_queue_is_the_same_work_for_every_seed():
    import json

    with open(ROOT / "bench" / "traffic" / "tenants-sat.json") as f:
        mix = json.load(f)
    a = G.serve_requests(mix, 1000, 2 ** 40 + 1, 64, 40.0)
    b = G.serve_requests(mix, 1000, 7, 64, 40.0)
    assert len(a) == len(b) == mix["requests"]
    assert all(r.arrival == 0.0 for r in a)
    assert [(len(r.prompt), r.max_new, r.latency_class) for r in a] == \
        [(len(r.prompt), r.max_new, r.latency_class) for r in b]
    assert [r.tenant for r in a] != [r.tenant for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_follow_the_mix():
    mix = tenants_mix()
    rs = G.serve_requests(mix, 1000, 5, 64, 4000.0)
    p = np.array([len(r.prompt) for r in rs])
    o = np.array([r.max_new for r in rs])
    assert p.min() >= 64 and p.max() <= 2048
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) / 512 - 1) < 0.1
    assert abs(np.median(o) / 128 - 1) < 0.1
    rate = len(rs) / 4000.0
    assert abs(rate / mix["arrivals"]["rate"] - 1) < 0.1


def test_zipf_ranks_and_base_share():
    rng = np.random.default_rng(0)
    ranks = G.zipf_ranks(64, 1.1, 200_000, rng)
    freq = np.bincount(ranks, minlength=64) / len(ranks)
    p = np.arange(1, 65) ** -1.1
    np.testing.assert_allclose(freq[:8], (p / p.sum())[:8], rtol=0.05)
    rs = G.serve_requests(tenants_mix(), 1000, 9, 64, 4000.0)
    base = np.mean([r.tenant is None for r in rs])
    assert abs(base - 0.1) < 0.04


def test_train_rows_differ_by_step_and_repeat_by_seed():
    mix = load("smoke-train.json")
    a0 = G.train_rows(mix, 500, 7, 0)
    assert a0.shape == (4, 65)
    assert np.array_equal(a0, G.train_rows(mix, 500, 7, 0))
    assert not np.array_equal(a0, G.train_rows(mix, 500, 7, 1))
    assert not np.array_equal(a0, G.train_rows(mix, 500, 8, 0))
