"""The control has to come out as not correct: the reference put in the
program's place keeps every guarantee and passes; with one guarantee
broken the comparison fails it.  Small size; ``run.py --control`` runs
the same at the cell's own size on the chip."""

import pytest

import reference
from test_reference import small_fleet

S0 = 1_790_000_000 // 60 * 60 + 50      # holds second :00


def judged(control, seed=5):
    fleet = small_fleet(seed, n_jobs=2000)
    live = fleet.live
    obs = reference.reference_outcome(fleet, live, S0, S0 + 40, control)
    return reference.judge(fleet, live, S0, S0 + 40, obs)


def test_the_reference_itself_is_correct():
    v = judged("")
    assert v.correct and v.attempted > 1000 and len(v.lags)


@pytest.mark.parametrize("control,kind", [
    ("at_least_once", "spurious"), ("any_node", "spurious"),
    ("early_by_one", "spurious"), ("drop_herd_tail", "lost"),
    ("unclaimed_on_live", "lost"), ("alone_drop_after_first", "lost")])
def test_a_broken_guarantee_is_not_correct(control, kind):
    v = judged(control)
    assert not v.correct
    assert getattr(v, kind) > 0, v.detail


def test_every_control_has_a_test():
    assert len(reference.CONTROLS) == 6


def alone_case(lag_of_first, run_s):
    """One Alone job on a live node, due every 2 s; it ran at S0 and
    nothing is left of its fire at S0 + 2."""
    fleet = small_fleet(5, n_jobs=2000)
    live = fleet.live
    job = next(i for i in range(fleet.n_jobs)
               if fleet.kinds[i] == reference.KIND_ALONE
               and fleet.group_of[i] < 0)
    fleet.timers[job] = "*/2 * * * * *"
    fleet.node_of[job] = live[0]
    s0 = S0 // 2 * 2
    obs = reference.reference_outcome(fleet, live, s0, s0 + 4)
    obs.records = [r for r in obs.records if r[1:3] != (job, s0 + 2)]
    obs.fences = [f for f in obs.fences if f[1:] != (job, s0 + 2)]
    obs.alone_runs[job] = [(s0, s0 + lag_of_first,
                            s0 + lag_of_first + run_s)]
    return reference.judge(fleet, live, s0, s0 + 4, obs)


def test_an_alone_fire_is_excused_only_behind_a_run_that_was_live():
    spans = alone_case(0.1, 2.3)     # still running when :02 came due
    assert spans.correct
    assert spans.alone_gaps == [pytest.approx((-0.4, 0.1))]
    just = alone_case(0.1, 1.4)      # ended 0.5 s before: lock not yet let go
    assert just.correct
    late = alone_case(0.9, 0.01)     # began 0.9 s late, ended 1.09 s before
    assert late.correct
    long_gone = alone_case(0.1, 0.1)     # prompt, and ended 1.8 s before
    assert not long_gone.correct
    assert long_gone.detail == {"alone_missing": 1}
    assert long_gone.alone_lost_gaps == [pytest.approx((1.8, 0.1))]


def test_a_fence_without_a_run_on_a_live_node_is_lost():
    fleet = small_fleet(5, n_jobs=2000)
    live = fleet.live
    obs = reference.reference_outcome(fleet, live, S0, S0 + 40)
    node, job, sec = obs.fences[0]
    obs.records = [r for r in obs.records if r[:3] != (node, job, sec)]
    v = reference.judge(fleet, live, S0, S0 + 40, obs)
    assert not v.correct and v.detail == {"claimed_not_run": 1}


def test_nothing_due_is_not_correct():
    fleet = small_fleet(5, n_jobs=10)
    v = reference.judge(fleet, [0], 10, 10, reference.Observed(
        [], [], [], [], {}))
    assert v.attempted == 0 and not v.correct
