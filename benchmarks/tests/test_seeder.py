"""Every seed carries the same set of timers, kinds, placements and
group sizes, in another order; the live ids follow the membership
rule."""

import collections

import numpy as np

import pytest

import seeder
from test_reference import small_fleet


def shape(fleet):
    return (collections.Counter(fleet.timers),
            collections.Counter(fleet.kinds.tolist()),
            collections.Counter((fleet.group_of >= 0).tolist()),
            collections.Counter(fleet.excluded.tolist()),
            sorted(len(g) for g in fleet.groups))


def test_same_set_in_another_order():
    a, b = small_fleet(7), small_fleet(2_999_999_929)
    assert shape(a) == shape(b)
    assert a.timers != b.timers
    assert shape(a) == shape(small_fleet(7))
    assert a.timers == small_fleet(7).timers


def test_exact_shares():
    """Exact within each placement bucket, so within one job per bucket
    (8 groups and the single-node bucket here) over the fleet."""
    f = small_fleet(11, n_jobs=1000)
    kinds = collections.Counter(f.kinds.tolist())
    for kind, want in ((seeder.KIND_COMMON, 450), (seeder.KIND_INTERVAL, 450),
                       (seeder.KIND_ALONE, 100)):
        assert abs(kinds[kind] - want) <= 9
    assert int((f.group_of >= 0).sum()) == 200
    assert abs(int(f.excluded.sum()) - 100) <= 12
    single = f.group_of < 0
    every = sum(t.startswith("@every") for t in f.timers)
    assert abs(every - 600) <= 33
    pinned = collections.Counter(f.node_of[single].tolist())
    assert set(pinned.values()) <= {12, 13}       # 800 over 64 nodes
    # every live node pins the same jobs, to the letter
    mine = [sorted((f.timers[i], int(f.kinds[i]))
                   for i in np.flatnonzero(single & (f.node_of == n)))
            for n in f.live]
    assert mine[0] == mine[1] and len(mine[0]) == 12


@pytest.mark.parametrize("seed", [3, 295250098, 3_000_000_019])
def test_live_nodes_join_the_groups_the_rule_says(seed):
    f = small_fleet(seed, n_jobs=100)
    assert len(set(f.live)) == 2 and f.live == small_fleet(seed, 100).live
    counts = [sum(n in g for g in f.groups) for n in f.live]
    assert counts == [1, 2]
    # the rungs they join are the same under every seed
    rungs = sorted(len(g) for g in f.groups if set(g) & set(f.live))
    g0 = small_fleet(3, n_jobs=100)
    assert rungs == sorted(len(g) for g in g0.groups
                           if set(g) & set(g0.live))
    assert all(len(set(g)) == len(g) for g in f.groups)


def test_every_group_holds_the_same_design():
    f = small_fleet(9, n_jobs=4000)
    per_group = collections.defaultdict(collections.Counter)
    for i in range(f.n_jobs):
        if f.group_of[i] >= 0:
            per_group[int(f.group_of[i])][(f.timers[i], int(f.kinds[i]),
                                           bool(f.excluded[i]))] += 1
    assert len(per_group) == 8
    assert len({frozenset(c.items()) for c in per_group.values()}) == 1
