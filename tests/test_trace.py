"""Trace plane: deterministic ids, order-wire stamping + back-compat,
agent span stamping, logd trace stores (py and native), the web
waterfall, Prometheus exposition correctness, and health endpoints.
"""

import json
import os
import pathlib
import time

import pytest

from cronsun_tpu import trace
from cronsun_tpu.core import Job, JobRule, Keyspace, KIND_INTERVAL
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.metrics import parse_exposition
from cronsun_tpu.node.agent import NodeAgent
from cronsun_tpu.store import MemStore

KS = Keyspace()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# ids + sampling
# ---------------------------------------------------------------------------

def test_fnv_parity_with_store_hash():
    """One FNV-1a implementation fleet-wide: trace ids must agree with
    the store's routing hash bit for bit (native/logd.cc carries the
    same function for its trace-id lookup)."""
    from cronsun_tpu.store.sharded import fnv1a
    for s in ("", "a", "jobid|1700000000", "grp/job|123", "日本語"):
        assert trace.fnv1a64(s) == fnv1a(s)


def test_fnv_continue_matches_full_hash():
    import numpy as np
    ids = ["abc", "9f3b2c10", "x"]
    epoch = 1_754_300_000
    bases = np.array([trace.fnv_partial(j + "|") for j in ids],
                     dtype=np.uint64)
    tids = trace.fnv_continue_vec(bases, str(epoch))
    for j, t in zip(ids, tids.tolist()):
        assert t == trace.trace_id(j, epoch)
        assert t == trace.fnv_continue(trace.fnv_partial(j + "|"),
                                       str(epoch))


def test_head_sampling_shift_semantics():
    assert trace.head_sampled(0x100, 8)
    assert not trace.head_sampled(0x101, 8)
    assert trace.head_sampled(12345, 0)      # shift 0 = sample all
    assert not trace.head_sampled(0, -1)     # negative = never


def test_stage_durations_clamped_and_partial():
    sec = 1000
    full = {"b": 999.5, "recv": 1000.2, "claim": 1000.3,
            "start": 1000.4, "end": 1001.0, "flush": 1001.1}
    st = trace.stage_durations(sec, full)
    assert set(st) == set(trace.STAGES)
    assert all(v >= 0 for v in st.values())
    assert st["sched"] == 0.0            # planned ahead -> clamped
    assert st["run"] == pytest.approx(600.0, abs=0.01)
    # spanless legacy order: no b/recv -> those stages simply absent
    st = trace.stage_durations(sec, {"claim": 1000.1, "start": 1000.2,
                                     "end": 1000.5, "flush": 1000.6})
    assert "sched" not in st and "publish" not in st
    assert set(st) == {"claim", "queue", "run", "record"}


# ---------------------------------------------------------------------------
# scheduler order-wire stamping
# ---------------------------------------------------------------------------

def _mini_sched(trace_shift, n_jobs=3):
    from cronsun_tpu.sched import SchedulerService
    st = MemStore()
    st.put(KS.node_key("n1"), "x:1")
    jobs = []
    for i in range(n_jobs):
        j = Job(name=f"a{i}", command="true", kind=KIND_INTERVAL,
                rules=[JobRule(timer="* * * * * *", nids=["n1"])])
        j.check()
        jobs.append(j)
        st.put(KS.job_key(j.group, j.id), j.to_json())
    svc = SchedulerService(st, job_capacity=16, node_capacity=4,
                           trace_shift=trace_shift)
    return st, svc, jobs


def _build(svc, ep):
    secs, acct = [], []
    for p in svc.planner.plan_window(ep, 1):
        svc._build_plan_orders(p, secs, acct)
    return secs


def test_order_wire_byte_identical_when_disabled():
    """trace_shift < 0 (the default for direct constructions) must
    keep the coalesced order value byte-identical to the pre-trace
    format: a plain JSON array of "group/job" strings."""
    st, svc, jobs = _mini_sched(trace_shift=-1)
    ep = (int(time.time()) // 60 + 2) * 60
    secs = _build(svc, ep)
    (sec, orders), = secs
    (key, value), = orders
    entries = json.loads(value)
    assert all(isinstance(e, str) for e in entries)
    expect = sorted(f"{j.group}/{j.id}" for j in jobs)
    assert sorted(entries) == expect
    assert value == json.dumps(entries, separators=(",", ":")) \
        .replace('","', '","')          # no trailing object, plain array
    svc.stop()
    st.close()


def test_order_wire_stamped_and_ref_identical():
    """shift 0 (sample everything): ONE trailing {"tb": ...} element,
    and the vectorized build stays byte-identical to the reference
    loop (the _tb_stamp cache pins the wall stamp per second)."""
    st, svc, jobs = _mini_sched(trace_shift=0)
    ep = (int(time.time()) // 60 + 2) * 60
    plans = svc.planner.plan_window(ep, 1)
    secs, secs2 = [], []
    svc._build_plan_orders(plans[0], secs, [])
    svc._build_plan_orders_ref(plans[0], secs2, [])
    assert secs == secs2
    (_, orders), = secs
    (key, value), = orders
    entries = json.loads(value)
    assert isinstance(entries[-1], dict) and "tb" in entries[-1]
    assert all(isinstance(e, str) for e in entries[:-1])
    # anti-entropy mirror accounting skips the header (slot counts
    # come out right against the stamped value)
    st.put(key, value)
    built = svc._build_mirrors(st)
    orders_mirror = built[1]
    node, cost, slots = orders_mirror[key]
    assert node == "n1" and slots == len(jobs)
    svc.stop()
    st.close()


def test_scheduler_trace_arrays_survive_restore(tmp_path):
    """Pre-trace checkpoints keep restoring (the trace row caches are
    re-derived, not checkpointed): a restored scheduler stamps the
    exact same bundle values as the one it checkpointed."""
    from cronsun_tpu.sched import SchedulerService
    st, svc, jobs = _mini_sched(trace_shift=0)
    path = str(tmp_path / "sched.ckpt")
    svc.checkpoint_save(path=path, kind="full")
    svc2 = SchedulerService(st, job_capacity=16, node_capacity=4,
                            trace_shift=0, node_id="warm",
                            checkpoint_dir=str(tmp_path))
    assert svc2.checkpoint_restored
    ep = (int(time.time()) // 60 + 3) * 60
    a = _build(svc, ep)
    b = _build(svc2, ep)
    # normalize the wall stamp (two instances stamp at different
    # times); the job lists and sampling verdicts must agree
    def strip(secs):
        out = []
        for sec, orders in secs:
            for k, v in orders:
                ents = json.loads(v)
                tb = [e for e in ents if isinstance(e, dict)]
                out.append((sec, k, [e for e in ents
                                     if isinstance(e, str)],
                            len(tb)))
        return out
    assert strip(a) == strip(b)
    svc2.stop()
    svc.stop()
    st.close()


# ---------------------------------------------------------------------------
# python agent end-to-end
# ---------------------------------------------------------------------------

def _run_fire(agent, store, sink, job, epoch, tb=None, legacy=False):
    store.put(KS.job_key(job.group, job.id), job.to_json())
    if legacy:
        value = json.dumps([f"{job.group}/{job.id}"])
    else:
        value = json.dumps([f"{job.group}/{job.id}",
                            {"tb": tb if tb is not None else epoch - 1.0}])
    store.put(KS.dispatch_bundle_key(agent.id, epoch), value)
    agent.poll()
    agent.join_running()


def test_e2e_waterfall_py_agent():
    """A sampled exclusive fire through the bundle path stamps all six
    stages; the assembled waterfall has non-negative durations."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0", trace_shift=0)
    agent.register()
    job = Job(name="t", command="echo hi", kind=KIND_INTERVAL,
              rules=[JobRule(timer="* * * * * *", nids=["n0"])])
    job.check()
    epoch = int(time.time()) - 2
    _run_fire(agent, store, sink, job, epoch)
    spans = sink.trace_get(job.id, epoch)
    assert len(spans) == 1
    wf = trace.assemble(job.id, epoch, spans)
    stages = wf["nodes"][0]["stages"]
    assert set(stages) == set(trace.STAGES), stages
    assert all(v >= 0 for v in stages.values())
    assert wf["trace_id"] == str(trace.trace_id(job.id, epoch))
    agent.stop()
    store.close()


def test_legacy_spanless_bundle_still_traces_agent_stages():
    """A spanless legacy bundle value (plain string array) parses and
    executes; the span carries the agent-side stamps only."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0", trace_shift=0)
    agent.register()
    job = Job(name="t", command="echo hi", kind=KIND_INTERVAL,
              rules=[JobRule(timer="* * * * * *", nids=["n0"])])
    job.check()
    epoch = int(time.time()) - 2
    _run_fire(agent, store, sink, job, epoch, legacy=True)
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 1
    spans = sink.trace_get(job.id, epoch)
    assert len(spans) == 1
    ts = spans[0]["ts"]
    assert "b" not in ts and "recv" in ts and "claim" in ts
    agent.stop()
    store.close()


def test_unsampled_fire_ships_no_span_but_failure_does():
    """Head sampling: shift 63 samples (essentially) nothing — but a
    FAILED execution tail-samples regardless."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0", trace_shift=63)
    agent.register()
    ok_job = Job(name="ok", command="echo hi", kind=KIND_INTERVAL,
                 rules=[JobRule(timer="* * * * * *", nids=["n0"])])
    ok_job.check()
    bad_job = Job(name="bad", command="sh -c 'exit 3'",
                  kind=KIND_INTERVAL,
                  rules=[JobRule(timer="* * * * * *", nids=["n0"])])
    bad_job.check()
    epoch = int(time.time()) - 2
    _run_fire(agent, store, sink, ok_job, epoch, legacy=True)
    _run_fire(agent, store, sink, bad_job, epoch + 1, legacy=True)
    if trace.head_sampled(trace.trace_id(ok_job.id, epoch), 63):
        pytest.skip("astronomically unlucky job id")  # pragma: no cover
    assert sink.trace_get(ok_job.id, epoch) == []
    bad = sink.trace_get(bad_job.id, epoch + 1)
    assert len(bad) == 1 and bad[0]["ok"] is False
    # per-job trace: true forces sampling too
    forced = Job(name="forced", command="echo hi", kind=KIND_INTERVAL,
                 trace=True,
                 rules=[JobRule(timer="* * * * * *", nids=["n0"])])
    forced.check()
    _run_fire(agent, store, sink, forced, epoch + 2, legacy=True)
    assert len(sink.trace_get(forced.id, epoch + 2)) == 1
    agent.stop()
    store.close()


def test_trace_off_env_disables_stamping(monkeypatch):
    monkeypatch.setenv("CRONSUN_TRACE", "off")
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0", trace_shift=0)
    assert agent.trace_shift == -1
    agent.stop()
    store.close()


# ---------------------------------------------------------------------------
# logd trace stores (ring, spill, sharded routing)
# ---------------------------------------------------------------------------

def _span(job, sec, node="n0", ok=True):
    tid = str(trace.trace_id(job, sec))
    return {"tid": tid, "job": job, "grp": "g", "sec": sec,
            "node": node, "ok": ok,
            "ts": {"b": sec - 1.0, "recv": sec + 0.1, "claim": sec + 0.2,
                   "start": sec + 0.3, "end": sec + 0.8,
                   "flush": sec + 0.9}}


def test_trace_ring_eviction_and_spill(tmp_path):
    sink = JobLogStore(str(tmp_path / "logs.db"))
    sec = 1_754_200_000
    for i in range(5000):
        sink.trace_ingest([_span(f"j{i}", sec)])
    # oldest evicted from the ring but recovered from the day spill
    assert len(sink.traces._ring) == 4096
    spans = sink.trace_get("j0", sec)
    assert len(spans) == 1 and spans[0]["job"] == "j0"
    # per-day spill file exists beside the tiered store
    day = time.strftime("%Y-%m-%d", time.gmtime(sec))
    assert (tmp_path / "logs.db.traces" / f"{day}.jsonl").exists()
    stats = sink.trace_stats()
    assert stats["spans_total"] == 5000
    assert stats["stages"]["run"]["count"] == 5000
    sink.close()


def test_trace_spill_straddling_midnight_recoverable(tmp_path):
    """One flush batch carrying spans from BOTH sides of a UTC
    midnight must file each span under its own day — get() opens
    exactly one day file, so a span filed under its neighbor's day
    would be unrecoverable once the ring evicts it."""
    sink = JobLogStore(str(tmp_path / "logs.db"))
    midnight = (1_754_200_000 // 86400 + 1) * 86400
    before, after = midnight - 1, midnight + 1
    sink.trace_ingest([_span("late", after), _span("early", before)])
    for d in (before, after):
        day = time.strftime("%Y-%m-%d", time.gmtime(d))
        assert (tmp_path / "logs.db.traces" / f"{day}.jsonl").exists()
    sink.traces._ring.clear()                       # force spill reads
    assert len(sink.trace_get("early", before)) == 1
    assert len(sink.trace_get("late", after)) == 1
    sink.close()


def test_trace_ingest_idempotent_per_node():
    sink = JobLogStore()
    sec = 1_754_200_000
    sink.trace_ingest([_span("j1", sec)])
    sink.trace_ingest([_span("j1", sec)])          # batch retry
    sink.trace_ingest([_span("j1", sec, node="n1")])
    spans = sink.trace_get("j1", sec)
    assert len(spans) == 2                          # one per node
    top = sink.trace_top(10)
    assert len(top) == 1 and len(top[0]["nodes"]) == 2


def test_sharded_span_routing_and_stats_sum():
    from cronsun_tpu.logsink.sharded import ShardedJobLogStore
    from cronsun_tpu.logsink.joblog import LogRecord
    shards = [JobLogStore(), JobLogStore()]
    s = ShardedJobLogStore(shards)
    sec = 1_754_200_000
    recs, spans = [], []
    for i in range(20):
        jid = f"job{i:02d}"
        recs.append(LogRecord(jid, "g", "n", "n0", "", "true", "", True,
                              float(sec), sec + 0.5))
        spans.append(_span(jid, sec))
    s.create_job_logs(recs, idem="tok", spans=spans)
    # spans co-locate with their job's shard and route back on get
    for i in range(20):
        got = s.trace_get(f"job{i:02d}", sec)
        assert len(got) == 1, f"job{i:02d} misrouted"
    per_shard = [sh.trace_stats()["spans_total"] for sh in shards]
    assert sum(per_shard) == 20 and all(n > 0 for n in per_shard), \
        f"expected both shards populated: {per_shard}"
    merged = s.trace_stats()
    assert merged["spans_total"] == 20
    assert merged["stages"]["run"]["count"] == 20
    assert len(s.trace_top(64)) == 20


# ---------------------------------------------------------------------------
# native twin: logd stores spans
# ---------------------------------------------------------------------------

def _native_logd():
    p = pathlib.Path(REPO) / "native" / "cronsun-logd"
    return p if p.exists() else None


def test_native_logd_trace_ops(tmp_path):
    binary = _native_logd()
    if binary is None:
        pytest.skip("native logd unavailable")
    from cronsun_tpu.logsink.native import NativeLogSinkServer
    from cronsun_tpu.logsink import RemoteJobLogStore
    from cronsun_tpu.logsink.joblog import LogRecord
    srv = NativeLogSinkServer(port=0, db=str(tmp_path / "logd.wal")).start()
    try:
        c = RemoteJobLogStore(srv.host, srv.port)
        sec = 1_754_200_000
        rec = LogRecord("jN", "g", "n", "n0", "", "true", "", True,
                        float(sec), sec + 0.5)
        c.create_job_logs([rec], idem="tokN", spans=[_span("jN", sec)])
        # idempotent replay must not double-count the histograms
        rec2 = LogRecord("jN", "g", "n", "n0", "", "true", "", True,
                         float(sec), sec + 0.5)
        c.create_job_logs([rec2], idem="tokN", spans=[_span("jN", sec)])
        spans = c.trace_get("jN", sec)
        assert len(spans) == 1
        assert set(spans[0]["ts"]) == {"b", "recv", "claim", "start",
                                       "end", "flush"}
        stats = c.trace_stats()
        assert stats["spans_total"] == 1, \
            "idempotent batch replay double-ingested spans"
        assert stats["stages"]["run"]["count"] == 1
        top = c.trace_top(10)
        assert len(top) == 1 and top[0]["job"] == "jN"
        assert top[0]["nodes"][0]["stages"]["run"] == \
            pytest.approx(500.0, abs=1.0)
        c.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# web: waterfall route, exposition correctness, health
# ---------------------------------------------------------------------------

def _web(store, sink, slo_engine=None):
    from cronsun_tpu.web.server import ApiServer
    return ApiServer(store, sink, ks=KS, auth_enabled=False,
                     slo_engine=slo_engine)


def test_web_trace_routes():
    store, sink = MemStore(), JobLogStore()
    api = _web(store, sink)
    sec = 1_754_200_000
    sink.trace_ingest([_span("jW", sec)])
    wf, _ = api.handle("GET", f"/v1/trace/jW/{sec}", {}, b"", {})
    assert wf["job"] == "jW" and len(wf["nodes"]) == 1
    assert set(wf["nodes"][0]["stages"]) == set(trace.STAGES)
    top, _ = api.handle("GET", "/v1/trace/top", {"n": "5"}, b"", {})
    assert top["traces"] and top["traces"][0]["job"] == "jW"
    by_run, _ = api.handle("GET", "/v1/trace/top",
                           {"n": "5", "stage": "run"}, b"", {})
    assert by_run["stage"] == "run"
    from cronsun_tpu.web.server import HttpError
    with pytest.raises(HttpError) as ei:
        api.handle("GET", "/v1/trace/nosuch/123", {}, b"", {})
    assert ei.value.status == 404
    store.close()


def test_web_slo_set_rejects_bad_values_with_400():
    """target=0 must 400 via validate() ('in (0, 1)'), not be silently
    masked into the 0.999 default; a non-numeric target is a 400 like
    every sibling route, not an unexplained 500."""
    store, sink = MemStore(), JobLogStore()
    api = _web(store, sink)
    from cronsun_tpu.web.server import HttpError
    for body in ({"name": "x", "target": 0},
                 {"name": "x", "target": "abc"},
                 {"name": "x", "target": None},
                 {"name": "x", "latency_ms": "fast"}):
        with pytest.raises(HttpError) as ei:
            api.handle("PUT", "/v1/slo", {},
                       json.dumps(body).encode(), {})
        assert ei.value.status == 400, body
    ok, _ = api.handle("PUT", "/v1/slo", {},
                       json.dumps({"name": "x", "target": 0.99}).encode(),
                       {})
    assert ok["target"] == 0.99
    store.close()


def test_metrics_exposition_escaping_roundtrip():
    """Label values containing backslash, quote and NEWLINE must emit
    a parseable exposition (the renderer escaped only the first two
    before) — pinned by a full round-trip parse."""
    store, sink = MemStore(), JobLogStore()
    api = _web(store, sink)
    evil = 'ten"ant\\x\nline'
    store.put(KS.metrics_key("tenant", "sched-1"),
              json.dumps({evil: {"admitted_fires": 3}}))
    store.put(KS.metrics_key("node", 'inst"4\n'),
              json.dumps({"execs_total": 7}))
    text, _ = api.handle("GET", "/v1/metrics", {}, b"", {})
    series = parse_exposition(str(text))
    hit = [k for k in series
           if k[0] == "cronsun_tenant_admitted_fires"]
    assert len(hit) == 1
    labels = dict(hit[0][1])
    # unescape and compare: the original value survives the round trip
    raw = labels["tenant"].replace("\\n", "\n").replace('\\"', '"') \
        .replace("\\\\", "\\")
    assert raw == evil
    store.close()


def test_parse_exposition_rejects_label_garbage():
    """The parser the round-trip pin relies on must itself be strict:
    unmatched bytes anywhere in the label section — before the first
    pair, between pairs, or trailing — are an error, not silently
    skipped."""
    assert parse_exposition('m{a="1",b="2"} 3')[
        ("m", frozenset({("a", "1"), ("b", "2")}))] == 3.0
    for bad in ('m{a="1",junk...,b="2"} 3',
                'm{;;a="1"} 3',
                'm{a="1"junk} 3',
                'm{a="1";b="2"} 3'):
        with pytest.raises(ValueError):
            parse_exposition(bad)


def test_metrics_smoke_mini_fleet():
    """Tier-1 smoke (satellite): a live mini-fleet's full /v1/metrics
    output parses line by line, has no duplicate series, and every
    histogram's cumulative bucket counts are monotone with
    count == the +Inf bucket."""
    store, sink = MemStore(), JobLogStore()
    from cronsun_tpu.web.slo import SloEngine
    eng = SloEngine(store, ks=KS)
    api = _web(store, sink, slo_engine=eng)
    agent = NodeAgent(store, sink, node_id="nm", trace_shift=0)
    agent.register()
    job = Job(name="m", command="echo hi", kind=KIND_INTERVAL,
              tenant="acme",
              rules=[JobRule(timer="* * * * * *", nids=["nm"])])
    job.check()
    epoch = int(time.time()) - 2
    _run_fire(agent, store, sink, job, epoch)
    agent.metrics._next_at = 0.0
    agent.metrics.maybe_publish()
    store.put(KS.slo_key("base"), json.dumps(
        {"name": "base", "scope": "", "target": 0.99,
         "latency_ms": 1000}))
    eng.tick()
    text, _ = api.handle("GET", "/v1/metrics", {}, b"", {})
    series = parse_exposition(str(text))   # raises on any bad line/dup
    names = {k[0] for k in series}
    assert "cronsun_node_execs_total" in names
    assert "cronsun_trace_stage_ms_bucket" in names
    assert "cronsun_exec_latency_ms_bucket" in names
    assert "cronsun_slo_burn_rate" in names
    # histogram correctness: per (name, non-le labels) cumulative
    # counts are monotone in le and the +Inf bucket equals _count
    hists = {}
    for (name, labels), val in series.items():
        if not name.endswith("_bucket"):
            continue
        lab = dict(labels)
        le = lab.pop("le")
        hists.setdefault((name, tuple(sorted(lab.items()))),
                         []).append((le, val))
    assert hists, "no histograms rendered"
    for (name, lab), buckets in hists.items():
        def key(le):
            return float("inf") if le == "+Inf" else float(le)
        ordered = sorted(buckets, key=lambda x: key(x[0]))
        vals = [v for _, v in ordered]
        assert vals == sorted(vals), f"{name}{lab} not cumulative"
        assert ordered[-1][0] == "+Inf"
        cname = name[:-len("_bucket")] + "_count"
        cnt = series.get((cname, frozenset(lab)))
        assert cnt == vals[-1], f"{name}{lab}: +Inf != _count"
    agent.stop()
    store.close()


def test_web_readyz_names_failing_check():
    store, sink = MemStore(), JobLogStore()
    api = _web(store, sink)
    body, ctx = api.handle("GET", "/readyz", {}, b"", {})
    assert body["ok"] and ctx.out_status == 200

    class DeadStore:
        def get(self, key):
            raise ConnectionError("store unreachable")
    api.store = DeadStore()   # store outage -> readiness fails, NAMED
    body, ctx = api.handle("GET", "/readyz", {}, b"", {})
    assert not body["ok"] and ctx.out_status == 503
    assert not body["checks"]["store"]["ok"]
    assert "unreachable" in body["checks"]["store"]["detail"]
    assert body["checks"]["logsink"]["ok"]
    store.close()


def test_health_server_endpoints(tmp_path):
    import urllib.request
    from cronsun_tpu.health import (HealthServer, tcp_accept_check,
                                    wal_writable_check)
    flaky = [True]
    hs = HealthServer({
        "wal": wal_writable_check(str(tmp_path / "x.wal")),
        "custom": lambda: (flaky[0], "injected")}).start()
    try:
        base = f"http://127.0.0.1:{hs.port}"
        with urllib.request.urlopen(f"{base}/healthz", timeout=5) as r:
            assert r.status == 200
        with urllib.request.urlopen(f"{base}/readyz", timeout=5) as r:
            assert json.loads(r.read())["ok"]
        flaky[0] = False
        try:
            urllib.request.urlopen(f"{base}/readyz", timeout=5)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            body = json.loads(e.read())
            assert not body["checks"]["custom"]["ok"]
            assert body["checks"]["wal"]["ok"]
        # tcp check against the health server's own port
        assert tcp_accept_check("127.0.0.1", hs.port)()[0]
    finally:
        hs.stop()
