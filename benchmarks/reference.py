"""The plain reference and the comparison that decides ``correct``.

A straightforward evaluation of the same semantics from the seeded
documents alone: which (job, second) pairs are due (a six-field cron
matcher and the ``@every`` chain along its anchor), which nodes each
job's rule makes eligible, and what the guarantees of the
configuration then require of the orders in the store and of the
executions recorded on the live agents.  It imports nothing of the
program and reads nothing the program made.
"""

import dataclasses
import datetime

import numpy as np

from seeder import KIND_ALONE, KIND_COMMON, SeededFleet

UTC = datetime.timezone.utc
_FIELDS = ((0, 59), (0, 59), (0, 23), (1, 31), (1, 12), (0, 6))


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------

def _field(text: str, lo: int, hi: int) -> frozenset:
    out = set()
    for part in text.split(","):
        if not part:
            continue
        rng, _, step = part.partition("/")
        step = int(step) if step else 1
        if step < 1:
            raise ValueError(f"bad step in {text!r}")
        if rng in ("*", "?"):
            a, b = lo, hi
        elif "-" in rng:
            a, b = (int(x) for x in rng.split("-"))
        else:
            a = int(rng)
            b = hi if "/" in part else a
        if not lo <= a <= b <= hi:
            raise ValueError(f"{text!r} outside {lo}..{hi}")
        out.update(range(a, b + 1, step))
    return frozenset(out)


@dataclasses.dataclass(frozen=True)
class CronSpec:
    """sec min hour dom month dow, UTC; dom and dow are OR-ed when both
    are restricted (standard cron)."""
    sets: tuple
    dom_star: bool
    dow_star: bool

    def matches(self, s: int) -> bool:
        t = datetime.datetime.fromtimestamp(s, UTC)
        sec, mi, hour, dom, month, dow = self.sets
        if t.second not in sec or t.minute not in mi \
                or t.hour not in hour or t.month not in month:
            return False
        d_ok = t.day in dom
        w_ok = (t.weekday() + 1) % 7 in dow       # 0 = Sunday
        if self.dom_star or self.dow_star:
            return d_ok and w_ok
        return d_ok or w_ok


def parse_cron(spec: str) -> CronSpec:
    parts = spec.split()
    if len(parts) != 6:
        raise ValueError(f"want 6 fields, got {spec!r}")
    sets = tuple(_field(p, lo, hi) for p, (lo, hi) in zip(parts, _FIELDS))
    return CronSpec(sets, parts[3][0] in "*?", parts[5][0] in "*?")


def every_period(spec: str) -> int:
    """``@every <n>s`` only — the one duration form the mixes use."""
    body = spec[len("@every "):].strip()
    if not body.endswith("s") or not body[:-1].isdigit():
        raise ValueError(f"unsupported duration {spec!r}")
    return max(1, int(body[:-1]))


def due_matrix(fleet: SeededFleet, s0: int, s1: int) -> np.ndarray:
    """bool [n_jobs, s1 - s0]: job i is due at second s0 + c."""
    secs = np.arange(s0, s1, dtype=np.int64)
    due = np.zeros((fleet.n_jobs, len(secs)), bool)
    timers = np.asarray(fleet.timers, dtype=object)
    is_every = np.fromiter((t.startswith("@every") for t in timers),
                           bool, len(timers))
    ev = np.flatnonzero(is_every)
    if len(ev):
        periods = np.fromiter((every_period(timers[i]) for i in ev),
                              np.int64, len(ev))
        rel = secs[None, :] - fleet.anchors[ev][:, None]
        due[ev] = (rel % periods[:, None] == 0) & (rel > 0)
    cron_rows = np.flatnonzero(~is_every)
    by_spec = {}
    for i in cron_rows:
        by_spec.setdefault(timers[i], []).append(i)
    for spec, rows in by_spec.items():
        c = parse_cron(spec)
        col = np.fromiter((c.matches(int(s)) for s in secs), bool,
                          len(secs))
        due[np.asarray(rows)] = col[None, :]
    return due


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------

def group_matrix(fleet: SeededFleet) -> np.ndarray:
    """bool [n_groups, n_nodes]."""
    m = np.zeros((len(fleet.groups), fleet.n_nodes), bool)
    for gi, members in enumerate(fleet.groups):
        m[gi, members] = True
    return m


def eligible(fleet: SeededFleet, gm: np.ndarray, job: int,
             node: int) -> bool:
    g = int(fleet.group_of[job])
    if g < 0:
        return node == int(fleet.node_of[job])
    if fleet.excluded[job] and node == int(fleet.node_of[job]):
        return False
    return bool(gm[g, node])


# ---------------------------------------------------------------------------
# what was observed, and the comparison
# ---------------------------------------------------------------------------

# An Alone fire may be skipped only behind a live previous run of the
# same job: a record of that job, of an earlier second, that ended no
# longer before the skipped second came due than this plus that run's
# own lag.  The agent lets go of the job's lock after the run ends, and
# an agent that started the run late lets go late.  Set between two
# readings (PERF.md section 2): sound runs and the control.
ALONE_RELEASE_S = 1.0


@dataclasses.dataclass
class Observed:
    """What the run left behind, already reduced to indexes.  Only the
    judged seconds [s0, s1) are in it, but for ``alone_runs``."""
    broadcasts: list          # (job, second) Common orders in the store
    orders: list              # (node, job, second) exclusive orders: what
    #                           the placeholders consumed, and whatever was
    #                           still in the store a minute past the close
    records: list             # (node, job, second, ok, begin_ts) live runs
    fences: list              # (node, job, second) exclusive fences
    alone_runs: dict          # Alone job -> [(second, begin_ts, end_ts)] of
    #                           its records on live nodes, any second of the
    #                           run


@dataclasses.dataclass
class Verdict:
    attempted: int
    lost: int
    spurious: int
    detail: dict              # kind of fault -> count
    examples: list            # a few (fault, job id, second, node)
    lags: np.ndarray          # begin_ts - second of every judged run
    alone_gaps: list          # (skipped second - end of the previous run it
    #                           was skipped behind, that run's lag), s; a
    #                           negative gap: the run spans the second
    alone_lost_gaps: list     # the same of the Alone fires counted lost
    #                           (inf: the job has no earlier run)

    @property
    def failed(self) -> int:
        return self.lost + self.spurious

    @property
    def alone_excess_s(self):
        """The widest (gap - lag) of an Alone fire that left nothing
        behind, held to ALONE_RELEASE_S (over it: the fire is in
        ``lost``); None where every Alone fire left its trace."""
        both = self.alone_gaps + self.alone_lost_gaps
        return max(g - lag for g, lag in both) if both else None

    @property
    def correct(self) -> bool:
        return self.attempted > 0 and self.failed == 0


def judge(fleet: SeededFleet, live: list, s0: int, s1: int,
          obs: Observed) -> Verdict:
    """Hold what was observed to the guarantees.

    lost: a due Common fire with no broadcast order, or not run on an
    eligible live node; a due exclusive fire that neither ran on a live
    node nor was ordered to a placeholder (an order that a live node
    never claimed, a fence a live node took without a run, a run that
    failed: all lost) — but for an Alone fire skipped behind a previous
    run of its job whose lock may still have been held when it came due
    (ALONE_RELEASE_S).
    spurious: an order, fence or run for a (job, second) that was not
    due, on a node that is not eligible, or more than once.
    """
    due = due_matrix(fleet, s0, s1)
    gm = group_matrix(fleet)
    live_set = set(live)
    detail = {}
    examples = []

    def fault(name, job, sec, node=None):
        detail[name] = detail.get(name, 0) + 1
        if len(examples) < 12:
            examples.append((name, fleet.job_id(job), sec,
                             None if node is None
                             else fleet.node_ids[node]))

    def is_due(job, sec):
        return 0 <= job < fleet.n_jobs and s0 <= sec < s1 \
            and bool(due[job, sec - s0])

    kinds = fleet.kinds
    # ---- Common: broadcast present, run once on every eligible live node
    bcast = {}
    for job, sec in obs.broadcasts:
        bcast[(job, sec)] = bcast.get((job, sec), 0) + 1
    for (job, sec), n in bcast.items():
        if not is_due(job, sec) or kinds[job] != KIND_COMMON:
            fault("broadcast_not_due", job, sec)
        elif n > 1:
            fault("broadcast_repeated", job, sec)
    runs = {}
    for node, job, sec, ok, _begin in obs.records:
        runs.setdefault((job, sec), []).append((node, ok))
    common_rows = np.flatnonzero(kinds == KIND_COMMON)
    live_elig = {}
    for job in common_rows:
        nodes = [n for n in live if eligible(fleet, gm, int(job), n)]
        if nodes:
            live_elig[int(job)] = nodes
    n_common_due = n_common_live_due = 0
    for job in common_rows:
        job = int(job)
        for c in np.flatnonzero(due[job]):
            sec = s0 + int(c)
            n_common_due += 1
            if (job, sec) not in bcast:
                fault("broadcast_missing", job, sec)
            got = runs.get((job, sec), ())
            for node in live_elig.get(job, ()):
                n_common_live_due += 1
                mine = [ok for n, ok in got if n == node]
                if not mine:
                    fault("common_run_missing", job, sec, node)
                elif not all(mine):
                    fault("run_failed", job, sec, node)
                if len(mine) > 1:
                    fault("run_repeated", job, sec, node)
    # ---- exclusive: placed at most once, on an eligible node; a live
    # node's order counts only once it has run
    placed, unclaimed = {}, {}
    for node, job, sec in obs.orders:
        (unclaimed if node in live_set else placed).setdefault(
            (job, sec), []).append(node)
    for (job, sec), got in runs.items():
        if not 0 <= job < fleet.n_jobs:
            fault("run_not_due", -1, sec, got[0][0])
            continue
        if kinds[job] == KIND_COMMON:
            if not is_due(job, sec):
                fault("run_not_due", job, sec, got[0][0])
            for node, _ok in got:
                if is_due(job, sec) and node not in live_elig.get(job, ()):
                    fault("run_ineligible", job, sec, node)
            continue
        for node, ok in got:
            placed.setdefault((job, sec), []).append(node)
            if not ok:
                fault("run_failed", job, sec, node)
    fenced = {}
    for node, job, sec in obs.fences:
        fenced.setdefault((job, sec), []).append(node)
    everywhere = {k: list(v) for k, v in placed.items()}
    for key, nodes in unclaimed.items():
        # the same order, left behind by the node that ran it, is one
        # placement; on any other node it is a second one
        everywhere.setdefault(key, []).extend(
            n for n in nodes if n not in placed.get(key, ()))
    for (job, sec), nodes in everywhere.items():
        if not is_due(job, sec) or kinds[job] == KIND_COMMON:
            fault("order_not_due", job, sec, nodes[0])
            continue
        if len(nodes) > 1:
            fault("placed_repeated", job, sec, nodes[0])
        for node in nodes:
            if not eligible(fleet, gm, job, node):
                fault("placed_ineligible", job, sec, node)
    for (job, sec), nodes in fenced.items():
        if not is_due(job, sec) or kinds[job] == KIND_COMMON:
            fault("fence_not_due", job, sec, nodes[0])
        elif len(nodes) > 1 or not eligible(fleet, gm, job, nodes[0]):
            fault("fence_ineligible", job, sec, nodes[0])
    n_excl_due = 0
    alone_gaps, alone_lost_gaps = [], []
    for job in np.flatnonzero(kinds != KIND_COMMON):
        job = int(job)
        for c in np.flatnonzero(due[job]):
            sec = s0 + int(c)
            n_excl_due += 1
            if (job, sec) in placed:
                continue
            if (job, sec) in unclaimed:
                fault("order_unclaimed", job, sec, unclaimed[(job, sec)][0])
            elif (job, sec) in fenced:
                # the lock comes before the fence: a skip leaves none
                fault("claimed_not_run", job, sec, fenced[(job, sec)][0])
            elif kinds[job] == KIND_ALONE:
                gap, lag = min(((sec - end, begin - s) for s, begin, end
                                in obs.alone_runs.get(job, ()) if s < sec),
                               default=(float("inf"), 0.0))
                if gap <= ALONE_RELEASE_S + lag:
                    alone_gaps.append((gap, lag))
                else:
                    alone_lost_gaps.append((gap, lag))
                    fault("alone_missing", job, sec)
            else:
                fault("interval_missing", job, sec)
    lost_kinds = ("broadcast_missing", "common_run_missing",
                  "interval_missing", "alone_missing", "order_unclaimed",
                  "claimed_not_run", "run_failed")
    lost = sum(v for k, v in detail.items() if k in lost_kinds)
    spurious = sum(v for k, v in detail.items() if k not in lost_kinds)
    lags = np.asarray([begin - sec for _n, _j, sec, _ok, begin
                       in obs.records], float)
    return Verdict(n_common_due + n_common_live_due + n_excl_due,
                   lost, spurious, detail, examples, lags,
                   sorted(alone_gaps), sorted(alone_lost_gaps))


# ---------------------------------------------------------------------------
# the control: the reference in the program's place, one guarantee broken
# ---------------------------------------------------------------------------

CONTROLS = ("at_least_once", "any_node", "early_by_one", "drop_herd_tail",
            "unclaimed_on_live", "alone_drop_after_first")


def reference_outcome(fleet: SeededFleet, live: list, s0: int, s1: int,
                      control: str = "") -> Observed:
    """What a run would leave behind if the reference planned, placed
    and ran it — every guarantee kept, or with ``control`` one of them
    broken the way a cheaper system would break it:

    at_least_once   an exclusive fire is re-delivered after a retry and
                    runs twice (at-most-once given up for at-least-once)
    any_node        an exclusive group fire goes to the least-loaded node
                    of the fleet, not of its rule (eligibility given up)
    early_by_one    @every chains are evaluated from the wrong anchor
                    for one job in a thousand (fires one second early)
    drop_herd_tail  the orders of a herd second past a fixed bucket are
                    dropped instead of replanned (fires lost)
    unclaimed_on_live  every fifth exclusive order of a live node stays
                    in the store: its agent never claims it (fires lost
                    where no record shows it — late orders dropped)
    alone_drop_after_first  on a live node an Alone job runs once and
                    every later fire is skipped as "behind a live run",
                    though that run ended seconds before (fires lost)
    """
    if control and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    due = due_matrix(fleet, s0, s1)
    gm = group_matrix(fleet)
    live_set = set(live)
    if control == "early_by_one":
        ev = np.flatnonzero([t.startswith("@every") for t in fleet.timers])
        hit = ev[due[ev].any(axis=1)]
        hit = hit[:max(1, len(hit) // 1000)]
        due = due.copy()
        due[hit] = np.roll(due[hit], -1, axis=1)
    obs = Observed([], [], [], [], {})
    per_second = np.zeros(s1 - s0, np.int64)
    bucket = None
    if control == "drop_herd_tail":
        bucket = max(1, int(due.sum(axis=0).max() * 0.98))
    load = np.zeros(fleet.n_nodes, np.int64)
    n_excl = n_live_excl = 0
    jobs, cols = np.nonzero(due)
    for job, c in zip(jobs.tolist(), cols.tolist()):
        sec = s0 + c
        per_second[c] += 1
        if bucket is not None and per_second[c] > bucket:
            continue
        if fleet.kinds[job] == KIND_COMMON:
            obs.broadcasts.append((job, sec))
            for n in live:
                if eligible(fleet, gm, job, n):
                    obs.records.append((n, job, sec, True, sec + 0.1))
            continue
        g = int(fleet.group_of[job])
        if g < 0:
            node = int(fleet.node_of[job])
        else:
            pool = np.flatnonzero(gm[g])
            if fleet.excluded[job]:
                pool = pool[pool != int(fleet.node_of[job])]
            if control == "any_node":
                pool = np.arange(fleet.n_nodes)
            node = int(pool[np.argmin(load[pool])])
        load[node] += 1
        n_excl += 1
        copies = 2 if control == "at_least_once" and n_excl % 1000 == 1 \
            else 1
        if node in live_set:
            n_live_excl += 1
            alone = fleet.kinds[job] == KIND_ALONE
            if control == "unclaimed_on_live" and n_live_excl % 5 == 0:
                obs.orders.append((node, job, sec))
                continue
            if control == "alone_drop_after_first" and alone \
                    and job in obs.alone_runs:
                continue
            if alone:
                obs.alone_runs.setdefault(job, []).append(
                    (sec, sec + 0.1, sec + 0.11))
            obs.records.extend([(node, job, sec, True, sec + 0.1)] * copies)
            obs.fences.append((node, job, sec))
        else:
            obs.orders.extend([(node, job, sec)] * copies)
    return obs
