"""The generator's schedule and the world are functions of the seed, and
every seed carries the same work in another order."""

import json
import os

import numpy as np
import pytest

import traffic
import world

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel):
    with open(os.path.join(BENCH, rel), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["drain", "quota-bound"])
def test_schedule_repeats_and_work_is_seed_free(mix):
    m = load(f"traffic/{mix}.json")
    big = 2 ** 31 + 12345                       # the driver's seeds are large
    a = traffic.make_schedule(big, m, ["default"], 40.0)
    b = traffic.make_schedule(big, m, ["default"], 40.0)
    c = traffic.make_schedule(7, m, ["default"], 40.0)
    assert a == b
    assert a != c
    assert len(a) == len(c) == round(m["requests_per_s"] * 40.0)
    sizes = lambda s: sorted(len(r["jobs"]) for r in s)
    cpus = lambda s: sorted(j["cpus"] for r in s for j in r["jobs"])
    assert sizes(a) == sizes(c) and cpus(a) == cpus(c)

    # the same gaps in another order: a Poisson process's histogram of
    # gaps, and its bursts and lulls from second to second
    gaps = lambda s: np.sort(np.diff([0.0] + [r["due"] for r in s]))
    assert np.allclose(gaps(a), gaps(c))
    for s in (a, c):
        per_second = np.bincount([int(r["due"]) for r in s], minlength=40)
        assert 0.5 < per_second.var() / per_second.mean() < 2.0
    gaps = np.diff([r["due"] for r in a])
    assert np.std(gaps) > 0.7 * np.mean(gaps)   # Poisson-like, not a comb
    assert 0 < a[0]["due"] and a[-1]["due"] < 40.0
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
    uuids = [j["uuid"] for r in a for j in r["jobs"]]
    assert len(set(uuids)) == len(uuids)


def test_backlog_is_zipf_and_the_same_multiset_for_every_seed():
    w = load("configs/cook-1pool-100kx5k.json")["world"]
    w = dict(w, jobs_per_pool=20000)
    a = world.make_backlog(2 ** 31 + 5, w)
    b = world.make_backlog(9, w)
    assert (a.uuid == world.make_backlog(2 ** 31 + 5, w).uuid).all()
    assert len(set(a.uuid.tolist())) == 20000
    count = lambda t: np.sort(np.unique(t.user, return_counts=True)[1])
    assert (count(a) == count(b)).all()
    assert (np.sort(a.cpus) == np.sort(b.cpus)).all()
    assert (np.sort(a.priority) == np.sort(b.priority)).all()
    top = count(a)[-1] / 20000
    assert 0.15 < top < 0.19                    # about a sixth (Zipf 1.0)
    assert world.user_share("user010", w)["cpus"] == 2 * w["share_cpus"]
    assert world.user_share("light010", w)["cpus"] == w["share_cpus"]
