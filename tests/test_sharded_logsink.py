"""Sharded result plane: the ShardedJobLogStore routing client.

The conformance bar mirrors tests/test_sharded_store.py's: routing
known-vectors pin cross-process agreement, a randomized differential
pins the merged read path (ordering ties included) against an unsharded
sink fed the same record stream, stats must sum exactly, the per-shard
whole-batch retry must stay idempotent, and mismatched topologies must
refuse to start."""

import random
import sys

import pytest

from cronsun_tpu.logsink import (JobLogStore, LogRecord, LogSinkServer,
                                 RemoteJobLogStore)
from cronsun_tpu.logsink.sharded import (LOG_HASH_SCHEME,
                                         ShardedJobLogStore,
                                         advance_cursor,
                                         connect_sharded_sink,
                                         decode_log_id, encode_log_id,
                                         log_shard_index)
from cronsun_tpu.store.sharded import fnv1a


def _rec(job="j1", node="n1", ok=True, begin=1000.0, **kw):
    d = dict(job_id=job, job_group="g", name=f"name-{job}", node=node,
             user="", command="echo hi", output="out", success=ok,
             begin_ts=begin, end_ts=begin + 2)
    d.update(kw)
    return LogRecord(**d)


# ---------------------------------------------------------------- routing


def test_routing_known_vectors():
    """The routing hash is 64-bit FNV-1a of the RAW job_id — pinned
    against precomputed constants so no process's client can drift
    without a test going red (Python's own hash() is salted per
    process).  A one-bit divergence strands a job's history on the
    wrong shard."""
    assert fnv1a("") == 0xcbf29ce484222325
    assert fnv1a("a") == 0xaf63dc4c8601ec8c
    assert fnv1a("bj0") == 0x5df4191357f597
    assert fnv1a("group/job-42") == 0x9bca17e986e9f241
    assert log_shard_index("bj0", 2) == 0x5df4191357f597 % 2
    assert log_shard_index("bj0", 4) == 0x5df4191357f597 % 4
    assert log_shard_index("anything", 1) == 0


def test_encoded_ids_roundtrip():
    """Encoded ids (raw * N + shard) stay globally unique, decodable,
    and monotone per shard."""
    for n in (2, 3, 5):
        seen = set()
        for raw in (1, 2, 7, 10**9):
            for si in range(n):
                gid = encode_log_id(raw, si, n)
                assert decode_log_id(gid, n) == (raw, si)
                assert gid not in seen
                seen.add(gid)


def test_writes_colocate_by_job():
    """Every record of one job — its log rows AND its latest entry —
    lands on the one shard its job_id hashes to."""
    shards = [JobLogStore() for _ in range(3)]
    ss = ShardedJobLogStore(shards)
    jobs = [f"cj{i}" for i in range(12)]
    ss.create_job_logs([_rec(job=j, node=f"n{k}", begin=1000.0 + k)
                        for j in jobs for k in range(2)])
    for j in jobs:
        want = log_shard_index(j, 3)
        for si, sh in enumerate(shards):
            _, hist = sh.query_logs(job_ids=[j])
            _, lat = sh.query_logs(job_ids=[j], latest=True)
            if si == want:
                assert hist == 2 and lat == 2
            else:
                assert hist == 0 and lat == 0
    ss.close()


def test_node_and_account_tables_pin_to_shard_zero():
    shards = [JobLogStore() for _ in range(3)]
    ss = ShardedJobLogStore(shards)
    ss.upsert_node("nx", '{"id": "nx"}', alived=True)
    ss.upsert_account("a@b.c", '{"email": "a@b.c"}')
    assert shards[0].get_node("nx") is not None
    assert shards[0].get_account("a@b.c") is not None
    for sh in shards[1:]:
        assert sh.get_nodes() == [] and sh.list_accounts() == []
    assert ss.get_node("nx")["alived"] and len(ss.list_accounts()) == 1
    assert ss.delete_account("a@b.c") is True
    ss.close()


# ------------------------------------------------- randomized differential


def _strip(recs):
    return [{k: v for k, v in r.__dict__.items() if k != "id"}
            for r in recs]


@pytest.mark.parametrize("nshards", [2, 3])
def test_randomized_differential_vs_unsharded(nshards):
    """The heart of the read-path contract: a sharded sink and an
    unsharded sink fed the SAME record stream must answer every query
    identically — stats exactly, the latest view byte-identical
    (both backends pin its (begin_ts DESC, job_id, node) order, which
    the merge reproduces), and history queries content-identical in
    the DOCUMENTED merge order (begin_ts DESC, shard ASC, id ASC) —
    verified against per-record provenance, ordering ties included
    (begin_ts values collide on purpose)."""
    rng = random.Random(20260803)
    shards = [JobLogStore() for _ in range(nshards)]
    ss = ShardedJobLogStore(shards)
    un = JobLogStore()
    jobs = [f"dj{i}" for i in range(10)]
    nodes = [f"n{i}" for i in range(3)]
    serial = 0
    prov = []        # (doc, shard, per-shard insertion seq) in order
    per_shard_seq = {}

    def mkdoc():
        nonlocal serial
        serial += 1
        return dict(job_id=rng.choice(jobs), job_group="g",
                    name=f"nm{rng.randrange(4)}", node=rng.choice(nodes),
                    user="", command="c", output=f"o{serial}",
                    success=rng.random() < 0.7,
                    # few distinct begins: ties MUST happen
                    begin_ts=1000.0 + rng.randrange(6) * 10,
                    end_ts=2000.0)

    for b in range(30):
        docs = [mkdoc() for _ in range(rng.randrange(1, 6))]
        tok = f"dt{b}"
        if len(docs) == 1 and rng.random() < 0.5:
            ss.create_job_log(LogRecord(**docs[0]), idem=tok)
            un.create_job_log(LogRecord(**docs[0]), idem=tok)
        else:
            ss.create_job_logs([LogRecord(**d) for d in docs], idem=tok)
            un.create_job_logs([LogRecord(**d) for d in docs], idem=tok)
        for d in docs:
            si = log_shard_index(d["job_id"], nshards)
            seq = per_shard_seq[si] = per_shard_seq.get(si, 0) + 1
            prov.append((d, si, seq))

    # stats: exact summation
    assert ss.stat_overall() == un.stat_overall()
    assert ss.stat_days(10) == un.stat_days(10)
    for day in {d["day"] for d in un.stat_days(10)}:
        assert ss.stat_day(day) == un.stat_day(day)

    # latest view: byte-identical (order included)
    ls, lts = ss.query_logs(latest=True, page_size=500)
    lu, ltu = un.query_logs(latest=True, page_size=500)
    assert lts == ltu and _strip(ls) == _strip(lu)

    def expected(filt):
        rows = [((-d["begin_ts"], si, seq), d)
                for d, si, seq in prov if filt(d)]
        rows.sort(key=lambda t: t[0])
        return [d for _k, d in rows]

    filters = [
        (dict(), lambda d: True),
        (dict(node="n1"), lambda d: d["node"] == "n1"),
        (dict(failed_only=True), lambda d: not d["success"]),
        (dict(job_ids=jobs[:3]), lambda d: d["job_id"] in jobs[:3]),
        (dict(begin=1010.0, end=1040.0),
         lambda d: 1010.0 <= d["begin_ts"] < 1040.0),
        (dict(name_like="nm2"), lambda d: "nm2" in d["name"]),
    ]
    for kw, filt in filters:
        exp = expected(filt)
        got, total = ss.query_logs(page_size=500, **kw)
        _gu, tu = un.query_logs(page_size=500, **kw)
        assert total == tu == len(exp)
        # content equality in the DOCUMENTED merge order
        strip = _strip(got)
        assert strip == exp, f"order diverged for {kw}"
        # paging windows are slices of that order (deterministic paging)
        for page, psz in ((1, 5), (2, 5), (3, 4)):
            w, wt = ss.query_logs(page=page, page_size=psz, **kw)
            assert wt == len(exp)
            assert _strip(w) == exp[(page - 1) * psz: page * psz]

    # cursor sweep: drains everything exactly once, total pinned -1,
    # ids encoded and decodable
    vec = [0] * nshards
    seen = []
    while True:
        rows, t = ss.query_logs(after_id=vec, page_size=7)
        assert t == -1
        if not rows:
            break
        seen.extend(rows)
        vec = advance_cursor(vec, rows, nshards)
    assert len(seen) == len(prov)
    assert len({r.id for r in seen}) == len(prov)
    by_out = {d["output"]: (si, seq) for d, si, seq in prov}
    for r in seen:
        raw, si = decode_log_id(r.id, nshards)
        assert si == by_out[r.output][0] == log_shard_index(r.job_id,
                                                            nshards)
        assert ss.get_log(r.id).output == r.output
    ss.close()
    un.close()


def test_cursor_vector_never_skips_a_slow_shard():
    """The reason the cursor is a VECTOR: shard raw-id spaces advance
    independently, so after draining a fast shard to raw id R a scalar
    cursor would skip a slower shard's ids <= R.  The vector resumes
    each shard exactly where the consumer left it."""
    shards = [JobLogStore(), JobLogStore()]
    ss = ShardedJobLogStore(shards)
    # find job ids that land on distinct shards
    j0 = next(j for j in (f"a{i}" for i in range(99))
              if log_shard_index(j, 2) == 0)
    j1 = next(j for j in (f"b{i}" for i in range(99))
              if log_shard_index(j, 2) == 1)
    # shard 0 races ahead
    ss.create_job_logs([_rec(job=j0, begin=1.0 + i) for i in range(20)])
    rows, _ = ss.query_logs(after_id=[0, 0], page_size=500)
    vec = advance_cursor([0, 0], rows, 2)
    assert vec[0] == 20 and vec[1] == 0
    # the slow shard now produces LOW raw ids — a scalar max would
    # have skipped them
    ss.create_job_logs([_rec(job=j1, begin=100.0 + i) for i in range(3)])
    rows, _ = ss.query_logs(after_id=vec, page_size=500)
    assert [r.job_id for r in rows] == [j1] * 3
    # and a scalar (nonzero) cursor is refused loudly
    with pytest.raises(ValueError, match="vector"):
        ss.query_logs(after_id=7)
    with pytest.raises(ValueError, match="entries"):
        ss.query_logs(after_id=[1, 2, 3])
    ss.close()


# --------------------------------------------- idempotent per-shard retry


class _FlakyOnce:
    """Wraps one shard's client: the FIRST bulk create raises after
    applying nothing (wire down), later calls pass through."""

    def __init__(self, inner):
        self._inner = inner
        self.fail_next = True

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def create_job_logs(self, recs, idem=""):
        if self.fail_next:
            self.fail_next = False
            raise ConnectionError("injected shard outage")
        return self._inner.create_job_logs(recs, idem=idem)


def test_whole_batch_retry_is_idempotent_per_shard():
    """The agents' retry contract, sharded edition: a batch whose
    flush failed on ONE shard is re-sent WHOLE with the same batch
    token; the shard that already applied dedups via its derived
    per-shard token (idem + '.s<i>') — the dedup lives SERVER-side, so
    this runs over real LogSinkServers — the failed shard applies: no
    double inserts, no double-counted stats."""
    srvs = [LogSinkServer().start() for _ in range(2)]
    clients = [RemoteJobLogStore(s.host, s.port) for s in srvs]
    flaky = _FlakyOnce(clients[1])
    ss = ShardedJobLogStore([clients[0], flaky], verify_map=False)
    jobs = [f"r{i}" for i in range(40)]
    batch = [_rec(job=j, begin=1000.0 + i) for i, j in enumerate(jobs)]
    on0 = sum(1 for j in jobs if log_shard_index(j, 2) == 0)
    assert 0 < on0 < len(jobs), "need both shards in the batch"
    with pytest.raises(ConnectionError):
        ss.create_job_logs([LogRecord(**r.__dict__) for r in batch],
                           idem="retry-tok")
    # shard 0 applied, shard 1 did not — the indeterminate state the
    # flusher's retry slot holds
    assert clients[0].stat_overall()["total"] == on0
    assert clients[1].stat_overall()["total"] == 0
    # whole-batch retry, SAME token
    recs2 = [LogRecord(**r.__dict__) for r in batch]
    ss.create_job_logs(recs2, idem="retry-tok")
    assert ss.stat_overall()["total"] == len(jobs), \
        "retry dropped or duplicated records"
    _, total = ss.query_logs(page_size=500)
    assert total == len(jobs)
    assert all(r.id is not None for r in recs2)
    ss.close()
    for s in srvs:
        s.stop()


def test_bulk_retry_over_the_wire_dedups():
    """Same contract against real LogSinkServers: two identical
    create_job_logs calls with one batch token double-insert nothing,
    and the replay returns the original encoded ids."""
    srvs = [LogSinkServer().start() for _ in range(2)]
    ss = connect_sharded_sink([f"{s.host}:{s.port}" for s in srvs])
    batch = [_rec(job=f"w{i}", begin=1000.0 + i) for i in range(10)]
    r1 = [LogRecord(**r.__dict__) for r in batch]
    r2 = [LogRecord(**r.__dict__) for r in batch]
    ss.create_job_logs(r1, idem="wire-tok")
    ss.create_job_logs(r2, idem="wire-tok")       # the retry
    assert [r.id for r in r1] == [r.id for r in r2]
    assert ss.stat_overall()["total"] == 10
    _, total = ss.query_logs(page_size=500)
    assert total == 10
    ss.close()
    for s in srvs:
        s.stop()


# ------------------------------------------------------- topology pinning


def test_logmap_refuses_mismatched_topologies():
    srvs = [LogSinkServer().start() for _ in range(2)]
    addrs = [f"{s.host}:{s.port}" for s in srvs]
    ss = connect_sharded_sink(addrs)             # pins n=2
    assert ss.logmap() == {"n": 2, "hash": LOG_HASH_SCHEME}
    # a 3-"shard" client over the same set refuses
    with pytest.raises(RuntimeError, match="logmap"):
        connect_sharded_sink(addrs + addrs[:1])
    # a stale single-sink config pointed at shard 0 refuses too
    with pytest.raises(RuntimeError, match="logmap"):
        connect_sharded_sink(addrs[:1])
    ss.close()
    for s in srvs:
        s.stop()


def test_single_address_without_pin_is_plain_client():
    """An un-sharded deployment never writes the pin: one address
    connects as a plain RemoteJobLogStore, behavior unchanged."""
    srv = LogSinkServer().start()
    c = connect_sharded_sink([f"{srv.host}:{srv.port}"])
    assert isinstance(c, RemoteJobLogStore)
    r = _rec()
    c.create_job_log(r)
    assert r.id == 1                     # no id encoding on one shard
    c.close()
    srv.stop()


# ------------------------------------------------------------ stat shapes


def test_stat_days_sum_is_exact_across_uneven_shards():
    """A day present on one shard but absent on another (or past
    another's horizon) still sums exactly: day order is global, so each
    shard's top-n contains all of ITS days in the global top-n."""
    shards = [JobLogStore(), JobLogStore()]
    ss = ShardedJobLogStore(shards)
    un = JobLogStore()
    j0 = next(j for j in (f"a{i}" for i in range(99))
              if log_shard_index(j, 2) == 0)
    j1 = next(j for j in (f"b{i}" for i in range(99))
              if log_shard_index(j, 2) == 1)
    day = 86400.0
    recs = [_rec(job=j0, begin=0.5), _rec(job=j0, begin=2 * day),
            _rec(job=j1, begin=day), _rec(job=j1, begin=3 * day),
            _rec(job=j1, begin=3 * day + 5, ok=False)]
    ss.create_job_logs([LogRecord(**r.__dict__) for r in recs])
    un.create_job_logs([LogRecord(**r.__dict__) for r in recs])
    for n in (1, 2, 3, 10):
        assert ss.stat_days(n) == un.stat_days(n)
    assert ss.stat_overall() == un.stat_overall()
    ss.close()
    un.close()
