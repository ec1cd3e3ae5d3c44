"""The arrival generator: the same requests and gaps for every seed, in
the order the seed shuffles them into; and the percentile arithmetic."""

import json
import math
import os

import numpy as np
import pytest

import arrivals
import datagen

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = {"rate_rps": 50,
           "mix": [{"rows": 1, "weight": 60}, {"rows": 8, "weight": 25},
                   {"rows": 64, "weight": 12}, {"rows": 512, "weight": 3}]}


def plan(traffic=TRAFFIC, seconds=20.0, seed=2**31 + 5):
    return arrivals.schedule(
        traffic, seconds, datagen.rng_for(seed, datagen.STREAM_TRAFFIC))


def test_the_same_seed_gives_the_same_schedule():
    assert plan() == plan() and len(plan()) == 1000


def test_another_seed_is_the_same_work_in_another_order():
    a, b = plan(), plan(seed=2**31 + 6)
    assert sorted(r for _, r in a) == sorted(r for _, r in b)
    assert [r for _, r in a] != [r for _, r in b]
    gaps = lambda p: np.sort(np.diff([t for t, _ in p] + [20.0]))
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
    assert [t for t, _ in a] != [t for t, _ in b]


def test_the_seed_moves_the_order_the_queries_and_the_sample():
    import harness

    driver = harness._module("openloop", "drivers")
    tr = dict(TRAFFIC, check_requests=8, check_rows_per_request=4)
    pa, oa, sa, _ = driver.plan_and_sample(tr, 2**31 + 5, 20.0, 4096)
    pb, ob, sb, _ = driver.plan_and_sample(tr, 2**31 + 6, 20.0, 4096)
    pc, oc, sc, _ = driver.plan_and_sample(tr, 2**31 + 5, 20.0, 4096)
    assert pa == pc and pa != pb
    assert (oa == oc).all() and sa == sc
    assert (oa != ob).any() and sa != sb
    longest = max(r for _, r in pa)
    assert any(pa[i][1] == longest for i in sa)


def test_the_mix_is_kept_exactly():
    counts = np.bincount([r for _, r in plan()], minlength=513)
    assert [counts[r] for r in (1, 8, 64, 512)] == [600, 250, 120, 30]


def test_due_times_rise_and_stay_inside_the_window():
    t = np.array([d for d, _ in plan()])
    assert (np.diff(t) >= 0).all() and t[0] >= 0 and t[-1] < 20.0


def test_gaps_look_exponential():
    t = np.array([d for d, _ in plan(seconds=200.0)])
    g = np.diff(t)
    assert abs(g.mean() - 1 / 50) < 1e-3
    assert abs(g.std() / g.mean() - 1.0) < 0.15  # CV of an exponential


@pytest.mark.parametrize("samples,p,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    ([5], 95, 5.0), ([1, 2, 3, 4, math.inf], 50, 3.0),
    ([1, 2, 3, 4, math.inf], 95, math.inf), ([math.inf, math.inf], 50, math.inf),
])
def test_percentile(samples, p, want):
    assert arrivals.percentile(samples, p) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        arrivals.percentile([], 50)


def test_every_openloop_traffic_file_gives_a_schedule():
    found = 0
    for name in sorted(os.listdir(os.path.join(BENCH_DIR, "traffic"))):
        with open(os.path.join(BENCH_DIR, "traffic", name)) as f:
            tr = json.load(f)
        if tr["kind"] != "openloop":
            continue
        found += 1
        p = plan(tr, 20.0)
        assert len(p) == round(tr["rate_rps"] * 20.0)
        assert max(r for _, r in p) <= max(tr["buckets"])
    assert found
