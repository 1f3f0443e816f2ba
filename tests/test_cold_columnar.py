"""The cold load's columnar path against the job-at-a-time path.

``_load_jobs`` writes the plain jobs of a page a column at a time and
hands every other document to ``_apply_job``.  Here the same store is
cold-loaded twice — as the scheduler does it, and with every document
sent through ``_apply_job`` in listing order — and every piece of state
the load leaves behind must come out the same, shape by shape.  The
vectorized helpers the columnar path calls are pinned to their scalar
forms below.
"""

import json
import random

import jax
import numpy as np
import pytest

from cronsun_tpu import trace
from cronsun_tpu.core import Keyspace
from cronsun_tpu.ops.eligibility import EligibilityBuilder, NodeUniverse
from cronsun_tpu.ops.schedule_table import FRAMEWORK_EPOCH, make_row, make_rows
from cronsun_tpu.sched import SchedulerService
from cronsun_tpu.sched.partition import job_partition
from cronsun_tpu.store import MemStore

KS = Keyspace()
T0 = 1_760_000_000          # the fixed clock both loads read
NODES = [f"n{i}" for i in range(8)]
GROUPS = {"g1": NODES[:4], "g2": NODES[4:]}


def job_doc(name, rules, **extra):
    return json.dumps({"name": name, "command": "true", "kind": 0,
                       "rules": rules, **extra}, separators=(",", ":"))


def rule(rid, timer, nids=(), gids=(), ex=()):
    return {"id": rid, "timer": timer, "nids": list(nids),
            "gids": list(gids), "exclude_nids": list(ex)}


# the plain jobs around every shape, so its rows interleave with the
# columnar ones: (job id, document)
BASE = [
    ("p0", job_doc("p0", [rule("r", "*/5 * * * * *", nids=["n0"])])),
    ("p1", job_doc("p1", [rule("r", "@every 45s", nids=["n1"])],
                   kind=2)),
    ("p2", job_doc("p2", [rule("r", "@every 120s", gids=["g2"])])),
    ("p3", job_doc("p3", [rule("r", "0 * * * * *", gids=["g1"],
                               ex=["n2"])], kind=2, avg_time=0.5)),
]

# shape -> [(key tail after cmd/, value, plain)]: plain documents take
# the columnar path; every other one goes through _apply_job
SHAPES = {
    "single_node": [
        ("default/s1", job_doc("s1", [rule("r", "*/3 * * * * *",
                                           nids=["n5", "nx"])]), True)],
    "group": [
        ("default/g1j", job_doc("g1j", [rule("r", "@every 30s",
                                             gids=["g1"])], kind=2), True)],
    "group_exclusion": [
        ("other/gx", job_doc("gx", [rule("r", "0 */2 * * * *",
                                         gids=["g1", "g2", "gone"],
                                         ex=["n2", "n6"])]), True)],
    "multi_rule": [
        ("default/mr", job_doc("mr", [
            rule("a", "*/7 * * * * *", nids=["n0"]),
            rule("b", "@every 90s", gids=["g2"]),
            rule("c", "0 0 * * * *", nids=["n3"], ex=["n3"])],
            kind=2), True)],
    "paused": [
        ("default/pz", job_doc("pz", [rule("r", "@every 60s",
                                           nids=["n4"])], pause=True),
         True)],
    "jitter_trace": [
        ("default/jt", job_doc("jt", [rule("r", "*/10 * * * * *",
                                           nids=["n7"])],
                               jitter=7, trace=True), True),
        ("default/jf", job_doc("jf", [rule("r", "@every 40s",
                                           nids=["n1"])], jitter=3.0),
         False)],
    "tenant": [
        ("default/tn", job_doc("tn", [rule("r", "*/2 * * * * *",
                                           nids=["n2"])],
                               tenant="acme"), False)],
    "dep_chain": [
        # dependents listed before their upstream: the upstream's
        # columnar write refreshes them
        ("default/da", job_doc("da", [rule("r", "@dep", nids=["n0"])],
                               deps={"on": ["zu"]}), False),
        ("default/db", job_doc("db", [rule("r", "@dep", gids=["g1"])],
                               deps={"on": ["da"], "misfire": "fire"}),
         False),
        ("default/zu", job_doc("zu", [rule("r", "*/4 * * * * *",
                                           nids=["n0"])]), True)],
    "malformed": [
        ("default/m1", "not-json", False),
        ("default/m2", "[1, 2]", False),
        ("default/m3", '{"name":"m3","rules":5}', False),
        ("default/m4", "  " + job_doc("m4", [rule(
            "r", "* * * * * *", nids=["n1"])]) + "\n", False),
        ("default/m5", job_doc("m5", [rule("r", "*/6 * * * * *",
                                           nids=["n1"])]) + " x", False)],
    "no_job_id": [
        ("lonely", job_doc("lonely", [rule("r", "* * * * * *",
                                           nids=["n0"])]), False)],
    "bad_timer": [
        ("default/bt", job_doc("bt", [
            rule("a", "not a timer", nids=["n0"]),
            rule("b", "*/9 * * * * *", nids=["n1"])]), False),
        ("default/dup", job_doc("dup", [
            rule("a", "*/8 * * * * *", nids=["n0"]),
            rule("a", "@every 50s", nids=["n1"])]), False)],
}
SHAPES["every_shape"] = [d for docs in SHAPES.values() for d in docs]


def seeded_store(docs, partitions=1):
    st = MemStore()
    if partitions > 1:
        from cronsun_tpu.sched.partition import pin_partition_map
        pin_partition_map(st, KS, partitions)
    for n in NODES:
        st.put(KS.node_key(n), "x")
    for gid, members in GROUPS.items():
        st.put(KS.group + gid, json.dumps(
            {"id": gid, "name": gid, "nids": members}))
    for jid, value in BASE:
        st.put(KS.job_key("default", jid), value)
    for tail, value, _plain in docs:
        st.put(KS.cmd + tail, value)
    # an anchor from before, a stale one (timer changed), and a
    # completed upstream round the dep plane seeds rows from
    st.put(KS.phase_key("default", "p1", "r"), f"@every 45s|{T0 - 1000}")
    st.put(KS.phase_key("default", "p2", "r"), f"@every 99s|{T0 - 77}")
    st.put(KS.dep_key("default", "zu"), f"{T0 - 8}|ok")
    return st


class Probe(SchedulerService):
    """Keeps what the cold load hands its first flush and its phase
    write-backs, both of which the load then clears."""

    def _flush_device(self):
        if not hasattr(self, "pending"):
            self.pending = {k: dict(getattr(self, k)) for k in (
                "_table_updates", "_meta_updates", "_tenant_row_updates",
                "_dep_resets", "_dep_epoch_updates")}
        super()._flush_device()


class OneAtATime(Probe):
    def _load_jobs(self, kvs):
        for kv in kvs:
            self._apply_job(kv.key, kv.value)


def cold_load(cls, docs, partitions):
    st = seeded_store(docs, partitions)
    puts = []
    put_many = st.put_many
    st.put_many = lambda items, lease=0: (puts.append(list(items)),
                                          put_many(items, lease))[1]
    svc = cls(st, job_capacity=64, node_capacity=32, window_s=2,
              partitions=partitions, clock=lambda: T0)
    return svc, puts


STATE = ("jobs", "_row_phase", "_row_dispatch", "_rd_payload",
         "_rd_suffix", "_rd_bentry", "_rd_job", "_dep_rows", "_dep_jobs",
         "_dep_rdeps", "_jitter_jobs", "_max_jitter_seen", "pending")
ARRAYS = ("_rd_flags", "_rd_tbase", "_rd_sbase", "_rd_tflag",
          "_rd_jitter", "_row_tenant")


@pytest.mark.parametrize("partitions", [1, 2], ids=["whole", "partitioned"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_columnar_load_equals_one_at_a_time(shape, partitions):
    docs = SHAPES[shape]
    got, got_puts = cold_load(Probe, docs, partitions)
    want, want_puts = cold_load(OneAtATime, docs, partitions)
    assert got_puts == want_puts
    for name in STATE:
        assert getattr(got, name) == getattr(want, name), name
    for name in ARRAYS:
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), err_msg=name)
    for name in ("by_cmd", "by_row", "by_job"):
        assert getattr(got.rows, name) == getattr(want.rows, name), name
    assert sorted(got.rows._free) == sorted(want.rows._free)
    np.testing.assert_array_equal(got.builder.matrix, want.builder.matrix)
    assert got.builder.job_rules == want.builder.job_rules
    assert got.builder.group_jobs == want.builder.group_jobs
    for name in ("table", "elig", "exclusive", "cost"):
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               jax.device_get(getattr(got.planner, name)),
                               jax.device_get(getattr(want.planner, name)))
    # which path each document took
    owned = [(t, plain) for t, _v, plain in
             [(f"default/{j}", v, True) for j, v in BASE] + docs
             if "/" in t and
             job_partition(t.split("/", 1)[1], partitions) == 0]
    assert got.stats["cold_jobs_columnar_total"] == \
        sum(1 for _t, plain in owned if plain)
    assert got.stats["cold_jobs_per_job_total"] == \
        sum(1 for _t, plain in owned if not plain)
    snap = got.metrics_snapshot()
    assert snap["cold_jobs_columnar_total"] + \
        snap["cold_jobs_per_job_total"] == len(owned)


# ---- the vectorized helpers against their scalar forms -------------------

SIZES = [0, 1, 37]


@pytest.mark.parametrize("n", SIZES)
def test_fnv_partial_vec_equals_fnv_partial(n):
    rnd = random.Random(n)
    strs = ["".join(rnd.choice("ab/|-é9中") for _ in range(
        rnd.randrange(0, 24))) for _ in range(n)]
    got = trace.fnv_partial_vec(strs)
    assert got.dtype == np.uint64 and got.shape == (n,)
    assert [int(h) for h in got] == [trace.fnv_partial(s) for s in strs]


@pytest.mark.parametrize("n", SIZES)
def test_set_jobs_equals_set_job_loop(n):
    rnd = random.Random(100 + n)
    u1, u2 = NodeUniverse(96), NodeUniverse(96)
    one, batch = EligibilityBuilder(u1, 64), EligibilityBuilder(u2, 64)
    for b in (one, batch):
        for i in range(90):
            b.node_added(f"n{i}")
        b.set_group("ga", [f"n{i}" for i in range(0, 90, 3)])
        b.set_group("gb", [f"n{i}" for i in range(40, 70)])
        # a row set before, so the batch must drop its old group link
        b.set_job(5, ["n1"], ["ga"], [])
        b.dirty_rows()
    rows = ([5] + rnd.sample(range(6, 64), n - 1)) if n else []
    inputs = []
    for _ in rows:
        nids = [f"n{rnd.randrange(100)}" for _ in range(rnd.randrange(3))]
        gids = rnd.sample(["ga", "gb", "gone"], rnd.randrange(3))
        ex = [f"n{rnd.randrange(100)}" for _ in range(rnd.randrange(3))]
        inputs.append((nids, gids, ex))
    for row, (nids, gids, ex) in zip(rows, inputs):
        one.set_job(row, nids, gids, ex)
    batch.set_jobs(rows, *[[inp[k] for inp in inputs] for k in range(3)])
    np.testing.assert_array_equal(batch.matrix, one.matrix)
    assert batch.job_rules == one.job_rules
    assert batch.group_jobs == one.group_jobs
    r1, m1 = one.dirty_rows()
    r2, m2 = batch.dirty_rows()
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(m1, m2)


def test_set_jobs_refuses_a_row_twice():
    b = EligibilityBuilder(NodeUniverse(32), 8)
    with pytest.raises(ValueError):
        b.set_jobs([1, 1], [[], []], [[], []], [[], []])


@pytest.mark.parametrize("timer", ["*/5 * * * * *", "@every 45s",
                                   "@every 0s", "0 30 9 * * 1-5"])
@pytest.mark.parametrize("n", SIZES)
def test_make_rows_equals_make_row(timer, n):
    rnd = random.Random(n)
    phases = [rnd.randrange(T0 - 10**6, T0 + 10**6) for _ in range(n)]
    kw = dict(paused=bool(n % 2), tenant=3, jitter=n % 5)
    rows = make_rows(timer, phases, **kw)
    assert rows == [make_row(timer, phase_epoch_s=p, **kw) for p in phases]
    period = max(1, int(timer[7:-1])) if timer.startswith("@every") else 1
    assert [r["phase_mod"] for r in rows] == \
        [(p - FRAMEWORK_EPOCH) % period if period > 1 else 0
         for p in phases]
