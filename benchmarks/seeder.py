"""The one seeder: a mix (data, ``traffic/<mix>.json``) -> the fleet's
documents.

Copied in shape from ``scripts/bench_sched.py`` ``seed()`` /
``schedule_mix()`` (the yardstick may not move with the program), with
one difference that matters for steadiness: nothing is drawn with
replacement.  Class counts are exact shares, every placement bucket
(each group, and the single-node jobs) holds the same nested design of
kinds and timers, group sizes are a fixed ladder, and the live nodes
join a fixed number of groups at fixed rungs of it — so every seed
carries the same fleet and the same work for the live agents, in
another order (job ids, node ids, members, phases).  Nothing here
imports the program.
"""

import dataclasses
import json

import numpy as np

KIND_COMMON, KIND_ALONE, KIND_INTERVAL = 0, 1, 2
KIND_IDS = {"common": KIND_COMMON, "alone": KIND_ALONE,
            "interval": KIND_INTERVAL}
JOB_GROUP = "bench"
RULE_ID = "r"


@dataclasses.dataclass
class SeededFleet:
    """Everything the reference needs, as drawn — never read back from
    the store."""
    n_nodes: int
    node_ids: list            # [n_nodes] str
    groups: list              # [n_groups] np.ndarray of node indexes
    timers: list              # [n_jobs] str
    anchors: np.ndarray       # [n_jobs] int64, 0 for cron rows
    kinds: np.ndarray         # [n_jobs] int64
    node_of: np.ndarray       # [n_jobs] pinned / excluded node index
    group_of: np.ndarray      # [n_jobs] group index, -1 = single node
    excluded: np.ndarray      # [n_jobs] bool: group rule with exclude_nids
    command: str
    live: list = dataclasses.field(default_factory=list)
    #                         node indexes the real agents run under

    @property
    def n_jobs(self) -> int:
        return len(self.timers)

    def job_id(self, i: int) -> str:
        return f"bj{i}"


def exact_counts(shares: dict, n: int) -> dict:
    """Largest-remainder split of n over the shares (which need not sum
    to exactly 1: they are normalised)."""
    total = float(sum(shares.values()))
    raw = {k: v / total * n for k, v in shares.items()}
    out = {k: int(v) for k, v in raw.items()}
    for k in sorted(raw, key=lambda k: raw[k] - out[k],
                    reverse=True)[:n - sum(out.values())]:
        out[k] += 1
    return out


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n integers covering [lo, hi] evenly (each value as often as any
    other, to within one; a few values sit at the middles of their
    shares of the range, not at its low end)."""
    return lo + ((2 * np.arange(n, dtype=np.int64) + 1) * (hi - lo + 1)
                 ) // max(2 * n, 1)


def interleave(counts: dict) -> np.ndarray:
    """The keys of ``counts``, each as often as its count, spread evenly
    through one sequence (a class with a third of the total comes up
    about every third place)."""
    keys = np.concatenate([np.full(n, i) for i, n in
                           enumerate(counts.values())] or [[]]).astype(int)
    at = np.concatenate([(np.arange(n) + 0.5) / n for n in
                         counts.values() if n] or [[]])
    names = np.asarray(list(counts), dtype=object)
    return names[keys[np.argsort(at, kind="stable")]]


def design(mix: dict, m: int):
    """The (kind, timer, excluded) of the m jobs of one placement
    bucket, nested: kinds in exact shares spread evenly through the
    bucket, timer forms in exact shares within each kind, each form's
    parameter spread evenly over its range within that, exclusions
    alternating innermost.  The same m gives the same bucket whatever
    the seed: the seed only deals job ids, nodes and phases."""
    kinds = np.empty(m, np.int64)
    timers = np.empty(m, dtype=object)
    periods = np.zeros(m, np.int64)          # @every rows only
    excluded = np.zeros(m, bool)
    kind_seq = interleave(exact_counts(mix["kinds"], m))
    tshares = {i: t["share"] for i, t in enumerate(mix["timers"])}
    gshares = {k: v for k, v in mix["placement"].items()
               if k != "single_node"}
    for kname in mix["kinds"]:
        idx = np.flatnonzero(kind_seq == kname)
        kinds[idx] = KIND_IDS[kname]
        t_seq = interleave(exact_counts(tshares, len(idx)))
        for ti, t in enumerate(mix["timers"]):
            cell = idx[t_seq == ti]
            if t["form"] == "every":
                p = spread(t["min_s"], t["max_s"], len(cell))
                periods[cell] = p
                timers[cell] = [f"@every {x}s" for x in p]
            elif t["form"] == "step_seconds":
                timers[cell] = [f"*/{k} * * * * *" for k in
                                spread(t["min_k"], t["max_k"], len(cell))]
            elif t["form"] == "minute_of_hour":
                timers[cell] = [f"{x} {x} * * * *" for x in
                                spread(0, 59, len(cell))]
            else:
                raise ValueError(f"unknown timer form {t['form']!r}")
            if gshares:
                # an odd cell's extra job goes to either side in turn
                turn = dict(reversed(gshares.items())) \
                    if (ti + KIND_IDS[kname]) % 2 else gshares
                excluded[cell] = interleave(exact_counts(
                    turn, len(cell))) == "group_with_exclusion"
    return kinds, timers, periods, excluded


def draw(mix: dict, n_jobs: int, n_nodes: int, seed: int, now: int,
         live_memberships: list) -> SeededFleet:
    """Every seed carries the same fleet in another order.

    Groups: sizes are a fixed log-spaced ladder.  The live nodes are
    drawn first; live node i then joins as many groups as
    ``live_memberships[i]`` says, taken from consecutive rungs around
    the middle of the ladder, and every other member is drawn from the
    rest of the fleet — so the live nodes' share of the group-placed
    work is the same under every seed.  Jobs: each group gets the same
    number of jobs (to within one) and the same ``design``; each live
    node pins the fleet's mean number of single-node jobs, a ``design``
    of its own; the other single-node jobs are one bucket, dealt to the
    other nodes round-robin in seeded order."""
    rng = np.random.default_rng(seed)
    node_ids = [f"bn{i:05d}" for i in range(n_nodes)]
    live = [int(n) for n in rng.choice(n_nodes, len(live_memberships),
                                       replace=False)]
    rest = np.setdiff1d(np.arange(n_nodes), live)

    g = mix["groups"]
    top = min(g["max_members"], len(rest))
    sizes = np.round(np.logspace(np.log10(min(g["min_members"], top)),
                                 np.log10(top), g["count"])).astype(int)
    rung = max(0, (g["count"] - sum(live_memberships)) // 2)
    joins = [[] for _ in sizes]              # rung -> live nodes in it
    for node, n in zip(live, live_memberships):
        for _ in range(n):
            joins[rung % len(sizes)].append(node)
            rung += 1
    groups = [np.concatenate([
        rng.choice(rest, size=max(0, int(s) - len(j)), replace=False),
        np.asarray(j, np.int64)]).astype(np.int64)
        for s, j in zip(sizes, joins)]
    groups = [groups[i] for i in rng.permutation(len(groups))]

    pc = exact_counts(mix["placement"], n_jobs)
    n_single = pc.get("single_node", 0)
    order = rng.permutation(n_jobs)          # design place -> job
    kinds = np.empty(n_jobs, np.int64)
    timers = np.empty(n_jobs, dtype=object)
    periods = np.zeros(n_jobs, np.int64)
    excluded = np.zeros(n_jobs, bool)
    group_of = np.full(n_jobs, -1, np.int64)
    node_of = rng.integers(0, n_nodes, n_jobs)   # a group rule's
    #                                    excluded node is any node
    single_mix = {**mix, "placement": {"single_node": 1}}
    per_node = n_single // n_nodes
    buckets, at = [], 0
    for node in live:                    # a bucket of its own each
        buckets.append((order[at:at + per_node], single_mix, -1))
        node_of[order[at:at + per_node]] = node
        at += per_node
    others = order[at:n_single]          # dealt round-robin to the rest
    buckets.append((others, single_mix, -1))
    node_of[others] = rest[rng.permutation(len(others)) % len(rest)]
    per_group = exact_counts({i: 1 for i in range(len(groups))},
                             n_jobs - n_single)
    at = n_single
    for gi, m in per_group.items():
        buckets.append((order[at:at + m], mix, gi))
        at += m
    for jobs, bmix, gi in buckets:
        k, t, p, x = design(bmix, len(jobs))
        kinds[jobs], timers[jobs], periods[jobs], excluded[jobs] = k, t, p, x
        group_of[jobs] = gi
    ev = periods > 0
    # anchors back-dated uniformly over the job's own period: a
    # long-lived fleet's anchors are spread, the rate is steady
    anchors = np.where(ev, now - rng.integers(0, 1 << 30, n_jobs)
                       % np.maximum(periods, 1), 0)
    fleet = SeededFleet(n_nodes, node_ids, groups, list(timers), anchors,
                        kinds, node_of, group_of, excluded, mix["command"])
    fleet.live = live
    return fleet


def documents(fleet: SeededFleet, ks_prefix: str = "/cronsun"):
    """(node items, group items, job items, phase items) as the program
    stores them (core/keyspace.py layout, upstream's)."""
    ids = fleet.node_ids
    node_items = [(f"{ks_prefix}/node/{n}", "bench:1") for n in ids]
    group_items = []
    for gi, members in enumerate(fleet.groups):
        gid = f"bg{gi:02d}"
        group_items.append((f"{ks_prefix}/group/{gid}", json.dumps(
            {"id": gid, "name": gid, "nids": [ids[m] for m in members]},
            separators=(",", ":"))))
    job_items, phase_items = [], []
    for i in range(fleet.n_jobs):
        timer, node = fleet.timers[i], ids[int(fleet.node_of[i])]
        if fleet.group_of[i] < 0:
            place = f'"nids":["{node}"]'
        else:
            place = f'"gids":["bg{int(fleet.group_of[i]):02d}"]'
            if fleet.excluded[i]:
                place += f',"exclude_nids":["{node}"]'
        jid = fleet.job_id(i)
        job_items.append((
            f"{ks_prefix}/cmd/{JOB_GROUP}/{jid}",
            f'{{"name":"b{i}","command":"{fleet.command}",'
            f'"kind":{int(fleet.kinds[i])},"rules":[{{"id":"{RULE_ID}",'
            f'"timer":"{timer}",{place}}}]}}'))
        if timer.startswith("@every"):
            phase_items.append((
                f"{ks_prefix}/phase/{JOB_GROUP}/{jid}/{RULE_ID}",
                f"{timer}|{int(fleet.anchors[i])}"))
    return node_items, group_items, job_items, phase_items
