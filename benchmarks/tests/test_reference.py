"""The plain reference against the program's scalar cron engine
(``cronsun_tpu/cron``) on a few hundred seeded timers — the one place
the two meet; the reference itself imports nothing of the program."""

import datetime
import json
import os

import numpy as np
import pytest

import reference
import seeder

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UTC = datetime.timezone.utc


def scalar_due(timer, anchor, s0, s1):
    """chip_smoke.py ``due_seconds``: the program's engine."""
    from cronsun_tpu.cron import EverySpec, Schedule, parse
    sch = Schedule(parse(timer))
    t = s0 - 1
    if isinstance(sch.spec, EverySpec):
        p = max(1, sch.spec.period_s)
        t = anchor + (s0 - 1 - anchor) // p * p
    out = set()
    at = datetime.datetime.fromtimestamp(t, UTC)
    while True:
        at = sch.next(at)
        if at is None or at.timestamp() >= s1:
            return out
        out.add(int(at.timestamp()))


def small_fleet(seed, n_jobs=400, now=1_790_000_000):
    with open(os.path.join(BENCH, "traffic", "minute.json")) as f:
        traffic = json.load(f)
    mix = {**traffic, "groups": {"count": 8, "min_members": 4,
                                 "max_members": 32}}
    return seeder.draw(mix, n_jobs, 64, seed, now, [1, 2])


@pytest.mark.parametrize("seed", [1, 2_147_483_659])
def test_due_matches_the_scalar_engine(seed):
    fleet = small_fleet(seed)
    # a stretch that holds a minute boundary and second m of minute m
    s0 = 1_790_000_000 // 3600 * 3600 + 3600 - 20
    s1 = s0 + 150
    due = reference.due_matrix(fleet, s0, s1)
    for i in range(fleet.n_jobs):
        want = scalar_due(fleet.timers[i], int(fleet.anchors[i]), s0, s1)
        got = {s0 + int(c) for c in np.flatnonzero(due[i])}
        assert got == want, (fleet.timers[i], int(fleet.anchors[i]))
    assert due.any()


@pytest.mark.parametrize("spec", [
    "*/7 * * * * *", "5 5 * * * *", "0 */2 * * * *", "1,31 * * * * *",
    "0-10/5 * * * * *", "0 0 0 1 * *", "30 59 23 * * 0", "0 0 12 15 * 1"])
def test_general_cron_fields(spec):
    s0 = 1_790_000_000 // 86400 * 86400 - 60     # two days, from 23:59
    s1 = s0 + 2 * 86400
    c = reference.parse_cron(spec)
    assert {s for s in range(s0, s1) if c.matches(s)} \
        == scalar_due(spec, 0, s0, s1)
