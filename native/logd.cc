// cronsun-logd: the native result-store server.
//
// The rebuild's MongoDB (reference db/mgo.go:24-49, job_log.go:84-133):
// execution logs, latest-status per (job, node), success/fail counters
// (overall + per-day), the node-liveness mirror, and accounts — served
// over the exact line-JSON protocol of cronsun_tpu/logsink/serve.py, so
// the Python RemoteJobLogStore client (agents, web, noticer) runs
// unchanged against it.  tests/test_logsink_remote.py is the
// conformance suite for both backends.
//
// Storage model: in-memory tables + a write-ahead log.  Every mutation
// appends one JSON-array line (flushed to the OS immediately; fdatasync
// rides a sweeper, --fsync-per-commit closes the window); boot replays
// the file and rewrites it as a compacted snapshot.  Execution history
// is bounded by --retain (default 1M records): older rows age out of
// memory and the WAL at compaction, while the stats counters and the
// latest-status table — which summarize all history — are snapshotted
// explicitly and never lose counts.
//
// Build: make -C native   (g++ -O2 -std=c++17 -pthread)

#include <arpa/inet.h>
#include <dirent.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <signal.h>
#include <sys/prctl.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "njson.h"

// ---------------------------------------------------------------------------
// records
// ---------------------------------------------------------------------------

struct Rec {
  long long id = 0;
  std::string job_id, group, name, node, user, command, output;
  bool success = false;
  double begin = 0, end = 0;
};

// 64-bit FNV-1a — the trace plane's deterministic id hash (bit-twin of
// cronsun_tpu/trace.py fnv1a64)
static unsigned long long trace_fnv1a64(const std::string& s) {
  unsigned long long h = 0xcbf29ce484222325ull;
  for (unsigned char c : s) h = (h ^ c) * 0x100000001b3ull;
  return h;
}

// LogRecord wire form: plain dict of the Python dataclass fields.
static void rec_wire(std::string& out, const Rec& r, bool with_id) {
  out += "{\"job_id\":";
  jesc(out, r.job_id);
  out += ",\"job_group\":";
  jesc(out, r.group);
  out += ",\"name\":";
  jesc(out, r.name);
  out += ",\"node\":";
  jesc(out, r.node);
  out += ",\"user\":";
  jesc(out, r.user);
  out += ",\"command\":";
  jesc(out, r.command);
  out += ",\"output\":";
  jesc(out, r.output);
  out += ",\"success\":";
  out += r.success ? "true" : "false";
  out += ",\"begin_ts\":";
  jdbl(out, r.begin);
  out += ",\"end_ts\":";
  jdbl(out, r.end);
  out += ",\"id\":";
  if (with_id) jint(out, r.id);
  else out += "null";
  out += '}';
}

static bool rec_unwire(const JV& o, Rec& r) {
  if (o.t != JV::OBJ) return false;
  auto str_of = [&](const char* k, std::string& dst) {
    const JV* v = o.get(k);
    if (v && v->t == JV::STR) dst = v->s;
  };
  str_of("job_id", r.job_id);
  str_of("job_group", r.group);
  str_of("name", r.name);
  str_of("node", r.node);
  str_of("user", r.user);
  str_of("command", r.command);
  str_of("output", r.output);
  if (const JV* v = o.get("success")) r.success = v->t == JV::BOOL ? v->b : v->as_int() != 0;
  if (const JV* v = o.get("begin_ts")) r.begin = v->as_dbl();
  if (const JV* v = o.get("end_ts")) r.end = v->as_dbl();
  return true;
}

static std::string day_of(double ts) {
  time_t t = (time_t)ts;
  struct tm g;
  gmtime_r(&t, &g);
  char buf[40];
  snprintf(buf, sizeof buf, "%04d-%02d-%02d", g.tm_year + 1900, g.tm_mon + 1,
           g.tm_mday);
  return buf;
}

// epoch seconds of a "YYYY-MM-DD" day's 00:00 UTC (-1 on parse failure)
static double day_start(const std::string& day) {
  struct tm g {};
  if (sscanf(day.c_str(), "%d-%d-%d", &g.tm_year, &g.tm_mon, &g.tm_mday) != 3)
    return -1;
  g.tm_year -= 1900;
  g.tm_mon -= 1;
  return (double)timegm(&g);
}

// start of the hot window: records with begin_ts below this are eligible
// to age cold.  hot_days counts whole UTC days including today —
// hot_days=1 keeps only today hot (logsink/tiering.py pins the same).
static double hot_cutoff_ts(double now, size_t hot_days) {
  double today = day_start(day_of(now));
  return today - 86400.0 * (double)((hot_days ? hot_days : 1) - 1);
}

// ASCII case-insensitive substring — the semantics of SQLite's
// LIKE '%x%' that the Python JobLogStore defines the contract with.
static bool contains_nocase(const std::string& hay, const std::string& needle) {
  if (needle.empty()) return true;
  auto low = [](unsigned char c) {
    return (c >= 'A' && c <= 'Z') ? (char)(c + 32) : (char)c;
  };
  for (size_t i = 0; i + needle.size() <= hay.size(); i++) {
    size_t j = 0;
    while (j < needle.size() && low(hay[i + j]) == low(needle[j])) j++;
    if (j == needle.size()) return true;
  }
  return false;
}

// ---------------------------------------------------------------------------
// per-op server-side timing (stored.cc / memstore.py op_stats parity):
// lets a bench attribute the RESULT plane's ceiling to a named op —
// bulk create vs query vs single create — instead of "logd".
// ---------------------------------------------------------------------------

struct OpStat {
  long long count = 0, total_ns = 0, max_ns = 0;
};
static std::mutex g_op_mu;
static std::map<std::string, OpStat> g_op_stats;

static long long mono_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

static void op_record(const std::string& op, long long t0_ns) {
  long long dt = mono_ns() - t0_ns;
  std::lock_guard<std::mutex> g(g_op_mu);
  OpStat& s = g_op_stats[op];
  s.count++;
  s.total_ns += dt;
  if (dt > s.max_ns) s.max_ns = dt;
}

// count-only stat (no timing): per-record tallies under the bulk op —
// log_records / create_job_logs gives the server-observed batch size
static void op_count(const std::string& op, long long n) {
  std::lock_guard<std::mutex> g(g_op_mu);
  g_op_stats[op].count += n;
}

static void op_stats_json(std::string& out) {
  std::lock_guard<std::mutex> g(g_op_mu);
  out += '{';
  bool first = true;
  for (const auto& [op, s] : g_op_stats) {
    if (!first) out += ',';
    first = false;
    jesc(out, op);
    out += ":{\"count\":";
    jint(out, s.count);
    out += ",\"total_ms\":";
    jdbl(out, (double)s.total_ns / 1e6);
    out += ",\"max_ms\":";
    jdbl(out, (double)s.max_ns / 1e6);
    out += '}';
  }
  out += '}';
}

// ---------------------------------------------------------------------------
// WAL (same design as stored.cc's: append + flush now, fdatasync by
// sweeper or per-commit; boot replay then compacted snapshot rewrite)
// ---------------------------------------------------------------------------

class Wal {
 public:
  bool open_append(const std::string& path, bool sync_per_commit) {
    std::lock_guard<std::mutex> g(mu_);
    f_ = fopen(path.c_str(), "a");
    sync_per_commit_ = sync_per_commit;
    return f_ != nullptr;
  }
  void append(const std::string& line) {
    std::lock_guard<std::mutex> g(mu_);
    if (!f_) return;
    if (fwrite(line.data(), 1, line.size(), f_) != line.size() ||
        fputc('\n', f_) == EOF || fflush(f_) != 0) {
      fprintf(stderr, "FATAL: wal append failed: %s\n", strerror(errno));
      abort();
    }
    if (sync_per_commit_ && fdatasync(fileno(f_)) != 0) {
      fprintf(stderr, "FATAL: wal fdatasync failed: %s\n", strerror(errno));
      abort();
    }
  }

  // N pre-joined '\n'-terminated lines as ONE write + ONE flush (+ one
  // fdatasync under --fsync-per-commit): a bulk create commits a whole
  // batch at the durability cost of a single record — the per-record
  // append was the 4-write pattern's last per-record cost on this path
  void append_block(const std::string& block) {
    if (block.empty()) return;
    std::lock_guard<std::mutex> g(mu_);
    if (!f_) return;
    if (fwrite(block.data(), 1, block.size(), f_) != block.size() ||
        fflush(f_) != 0) {
      fprintf(stderr, "FATAL: wal append failed: %s\n", strerror(errno));
      abort();
    }
    if (sync_per_commit_ && fdatasync(fileno(f_)) != 0) {
      fprintf(stderr, "FATAL: wal fdatasync failed: %s\n", strerror(errno));
      abort();
    }
  }
  void sync() {
    std::lock_guard<std::mutex> g(mu_);
    if (f_) fdatasync(fileno(f_));
  }

 private:
  FILE* f_ = nullptr;
  bool sync_per_commit_ = false;
  std::mutex mu_;
};

// ---------------------------------------------------------------------------
// the store
// ---------------------------------------------------------------------------

struct Stat {
  long long total = 0, ok = 0, fail = 0;
};

// cold-tier segment index entry: one immutable per-day file under
// <wal>.segs/ (format shared byte-compatibly with logsink/tiering.py —
// a ["d", day, count, min, max] header line then ["L", <rec body>]
// lines, id ascending)
struct Seg {
  std::string day, path;
  long long min_id = 0, max_id = 0, count = 0;
};

// ---------------------------------------------------------------------------
// change-stream subscribers (the `subscribe` wire op — the bit-twin of
// logsink/joblog.py's LogSubscription): a bounded lossy per-connection
// queue of pre-serialized event summaries.  Overflow drops EVERYTHING
// and latches `lost` — the store's watch semantics; the consumer
// re-lists and re-subscribes.  Each subscription owns a dup of the
// connection's fd plus a pusher thread that writes frames under the
// connection's shared write mutex, so pushes interleave with replies
// at line granularity.
// ---------------------------------------------------------------------------

struct Subscriber {
  long long sid = 0;                 // the subscribe request's rid
  int fd = -1;                       // dup'd conn fd (pusher closes it)
  std::shared_ptr<std::mutex> wmu;   // the connection's write mutex
  size_t cap = 4096;
  std::mutex mu;
  std::condition_variable cv;
  std::deque<std::string> buf;       // serialized "[id,...]" event bodies
  bool lost = false, closed = false;
};

class LogStore {
 public:
  explicit LogStore(size_t retain, size_t hot_days = 0)
      : retain_(retain), hot_days_(hot_days) {}

  // -- mutations ---------------------------------------------------------

  long long create(Rec r, const std::string& idem) {
    std::lock_guard<std::mutex> g(mu);
    if (!idem.empty()) {
      auto it = idem_.find(idem);
      if (it != idem_.end()) return it->second;  // replayed retry
    }
    r.id = next_id_++;
    apply_create(r);
    if (wal_) {
      std::string line;
      wal_create(line, r);
      wal_->append(line);
    }
    if (!subs_.empty()) {
      std::vector<std::string> evs(1);
      sub_event_json(evs[0], r);
      sub_emit_locked(evs);
    }
    if (!idem.empty()) {
      idem_[idem] = r.id;
      idem_fifo_.push_back(idem);
      while (idem_fifo_.size() > 8192) {
        idem_.erase(idem_fifo_.front());
        idem_fifo_.pop_front();
      }
    }
    return r.id;
  }

  // Bulk create (agent record flushers): one idem token covers the
  // whole batch.  Ids are allocated consecutively under the lock, so a
  // replayed retry reconstructs the full id list from the recorded
  // first id.  The per-record side writes COALESCE per batch — one
  // stat bump per (day) touched, one latest-table upsert per
  // (job, node) (last record in batch order wins, exactly the
  // sequential outcome), one WAL block append — so a 1k-record batch
  // pays ~4 table touches, not 4k.
  bool create_many(const std::vector<Rec>& recs, const std::string& idem,
                   std::string& res, const JV* spans = nullptr) {
    std::lock_guard<std::mutex> g(mu);
    long long first = -1;
    if (!idem.empty()) {
      auto it = idem_.find(idem);
      if (it != idem_.end()) first = it->second;  // replayed retry
    }
    if (first < 0) {
      // the trace-span sidecar ingests only on the NON-replay branch:
      // an idempotent batch retry must not double-count the stage
      // histograms (the Python serve layer's idem thunk, here)
      if (spans != nullptr && spans->t == JV::ARR)
        trace_ingest_locked(*spans);
      first = next_id_;
      std::string block;
      std::map<std::pair<std::string, std::string>, Rec> last;
      std::map<std::string, Stat> deltas;
      std::vector<std::string> evs;
      Stat overall;
      for (Rec r : recs) {
        r.id = next_id_++;
        recs_.push_back(r);
        if (!subs_.empty()) {
          evs.emplace_back();
          sub_event_json(evs.back(), r);
        }
        Stat& d = deltas[day_of(r.begin)];
        d.total++;
        (r.success ? d.ok : d.fail)++;
        overall.total++;
        (r.success ? overall.ok : overall.fail)++;
        if (wal_) {
          wal_create(block, r);
          block += '\n';
        }
        last[{r.job_id, r.node}] = std::move(r);
      }
      while (recs_.size() > retain_) recs_.pop_front();
      for (auto& [key, r] : last) latest_[key] = std::move(r);
      for (const auto& [day, d] : deltas) {
        Stat& s = stats_[day];
        s.total += d.total;
        s.ok += d.ok;
        s.fail += d.fail;
      }
      Stat& o = stats_[std::string()];
      o.total += overall.total;
      o.ok += overall.ok;
      o.fail += overall.fail;
      if (wal_) wal_->append_block(block);
      // counted HERE, not at the handle layer: an idempotent replay of
      // a retried batch must not inflate the records-per-batch ratio
      // (the Python backend counts inside create_job_logs the same
      // way — the serve-layer dedup skips the thunk)
      op_count("log_records", (long long)recs.size());
      sub_emit_locked(evs);
      if (!idem.empty()) {
        idem_[idem] = first;
        idem_fifo_.push_back(idem);
        while (idem_fifo_.size() > 8192) {
          idem_.erase(idem_fifo_.front());
          idem_fifo_.pop_front();
        }
      }
    }
    res += '[';
    for (size_t i = 0; i < recs.size(); i++) {
      if (i) res += ',';
      jint(res, first + (long long)i);
    }
    res += ']';
    return true;
  }

  // -- change stream (the store watch plane, result-plane edition) -------

  // Event summary: the wire twin of joblog.sub_event — 8 fields, the
  // heavy payload (user/command/output) stays behind get_log.
  static void sub_event_json(std::string& out, const Rec& r) {
    out += '[';
    jint(out, r.id);
    out += ',';
    jesc(out, r.job_id);
    out += ',';
    jesc(out, r.group);
    out += ',';
    jesc(out, r.name);
    out += ',';
    jesc(out, r.node);
    out += r.success ? ",true," : ",false,";
    jdbl(out, r.begin);
    out += ',';
    jdbl(out, r.end);
    out += ']';
  }

  // Open a change stream.  Revision snapshot, replay, and registration
  // happen in ONE mu hold, so no record lands between the snapshot and
  // the first pushed event.  Replay comes only from the contiguous hot
  // deque (get_log's id-indexing invariant); a resume below its floor —
  // retention-dropped or cold-aged — acks lost:true and the consumer
  // re-lists.  The ack JSON lands in `res`; the caller must SEND it
  // before starting the pusher (frames never precede the ack).
  std::shared_ptr<Subscriber> subscribe(long long sid, long long after_id,
                                        long long cap, int fd,
                                        std::shared_ptr<std::mutex> wmu,
                                        std::string& res) {
    auto s = std::make_shared<Subscriber>();
    s->sid = sid;
    s->fd = fd;
    s->wmu = std::move(wmu);
    if (cap > 0) s->cap = (size_t)cap;
    std::lock_guard<std::mutex> g(mu);
    long long rev = next_id_ - 1;
    bool gap = false;
    if (after_id > 0 && after_id < rev) {
      if (!recs_.empty() && recs_.front().id <= after_id + 1) {
        size_t start = (size_t)(after_id + 1 - recs_.front().id);
        for (size_t i = start; i < recs_.size(); i++) {
          s->buf.emplace_back();
          sub_event_json(s->buf.back(), recs_[i]);
        }
        if (s->buf.size() > s->cap) {  // replay alone overflows: stream
          s->buf.clear();              // is born lost (python parity)
          s->lost = true;
        }
      } else {
        gap = true;
      }
    }
    subs_.push_back(s);
    res += "{\"rev\":";
    jint(res, rev);
    res += gap ? ",\"lost\":true}" : ",\"lost\":false}";
    return s;
  }

  void unsubscribe_sub(const std::shared_ptr<Subscriber>& s) {
    std::lock_guard<std::mutex> g(mu);
    subs_.erase(std::remove(subs_.begin(), subs_.end(), s), subs_.end());
  }

  // called under mu by the create paths
  void sub_emit_locked(const std::vector<std::string>& evs) {
    if (subs_.empty() || evs.empty()) return;
    op_count("sub_events", (long long)(evs.size() * subs_.size()));
    bool prune = false;
    for (auto& s : subs_) {
      std::lock_guard<std::mutex> lk(s->mu);
      if (s->lost || s->closed) {
        prune = true;
        continue;
      }
      if (s->buf.size() + evs.size() > s->cap) {
        s->buf.clear();  // watch semantics: drop ALL buffered + latch
        s->lost = true;
      } else {
        for (const auto& e : evs) s->buf.push_back(e);
      }
      s->cv.notify_all();
    }
    if (prune)
      subs_.erase(std::remove_if(subs_.begin(), subs_.end(),
                                 [](const std::shared_ptr<Subscriber>& s) {
                                   std::lock_guard<std::mutex> lk(s->mu);
                                   return s->closed;
                                 }),
                  subs_.end());
  }

  // -- trace plane (fire-lifecycle spans) --------------------------------
  // Bounded in-memory ring keyed by trace id (decimal STRINGS on the
  // wire — 64-bit ids overflow a JSON double), per-(trace, node)
  // overwrite so a retried batch re-merges instead of duplicating.
  // Ingest folds stage durations into fixed-bucket histograms (the
  // trace.BUCKETS_MS twin — counters add across shards/replicas).
  // In-memory only: the per-day spill is the Python server's job; a
  // native logd restart starts with an empty ring.

  static constexpr double kTraceBucketsMs[13] = {
      1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000, 5000, 10000};
  static constexpr const char* kTraceStages[6] = {
      "sched", "publish", "claim", "queue", "run", "record"};

  struct NodeSpan {
    bool ok = true;
    double b = 0, recv = 0, claim = 0, start = 0, end = 0, flush = 0;
  };
  struct TraceEnt {
    std::string tid, job, grp;
    long long sec = 0;
    std::map<std::string, NodeSpan> nodes;
  };

  // clamped stage durations (ms): the exact formulas of
  // cronsun_tpu/trace.py stage_durations (0 timestamps = absent)
  static void trace_stage_ms(long long sec, const NodeSpan& s,
                             double out[6], bool present[6]) {
    for (int i = 0; i < 6; i++) present[i] = false;
    auto st = [&](int i, double a, double b2) {
      if (a <= 0 || b2 <= 0) return;
      out[i] = std::max(0.0, (b2 - a) * 1e3);
      present[i] = true;
    };
    st(0, (double)sec, s.b);
    st(1, s.b, s.recv);
    if (s.claim > 0)
      st(2, s.recv > 0 ? std::max((double)sec, s.recv) : (double)sec,
         s.claim);
    st(3, s.claim > 0 ? s.claim : s.recv, s.start);
    st(4, s.start, s.end);
    st(5, s.end, s.flush);
  }

  static double trace_total_ms(long long sec, const NodeSpan& s) {
    double last = (double)sec;
    for (double v : {s.b, s.recv, s.claim, s.start, s.end, s.flush})
      last = std::max(last, v);
    return std::max(0.0, (last - (double)sec) * 1e3);
  }

  void trace_ingest_locked(const JV& arr) {
    for (const JV& sp : arr.arr) {
      if (sp.t != JV::OBJ) continue;
      const JV* tidf = sp.get("tid");
      const JV* jobf = sp.get("job");
      const JV* secf = sp.get("sec");
      const JV* tsf = sp.get("ts");
      if (!tidf || tidf->t != JV::STR || !jobf || jobf->t != JV::STR ||
          !secf || !tsf || tsf->t != JV::OBJ)
        continue;
      auto [it, fresh] = traces_.try_emplace(tidf->s);
      TraceEnt& ent = it->second;
      if (fresh) {
        ent.tid = tidf->s;
        ent.job = jobf->s;
        if (const JV* g2 = sp.get("grp"))
          if (g2->t == JV::STR) ent.grp = g2->s;
        ent.sec = secf->as_int();
        trace_fifo_.push_back(tidf->s);
        while (trace_fifo_.size() > 4096) {
          traces_.erase(trace_fifo_.front());
          trace_fifo_.pop_front();
        }
      }
      std::string node;
      if (const JV* nf = sp.get("node"))
        if (nf->t == JV::STR) node = nf->s;
      NodeSpan& ns = ent.nodes[node];
      if (const JV* f = sp.get("ok")) ns.ok = !(f->t == JV::BOOL && !f->b);
      auto D = [&](const char* k, double& dst) {
        if (const JV* f = tsf->get(k))
          if (f->t == JV::INT || f->t == JV::DBL) dst = f->as_dbl();
      };
      D("b", ns.b);
      D("recv", ns.recv);
      D("claim", ns.claim);
      D("start", ns.start);
      D("end", ns.end);
      D("flush", ns.flush);
      double ms[6];
      bool present[6];
      trace_stage_ms(ent.sec, ns, ms, present);
      for (int i = 0; i < 6; i++) {
        if (!present[i]) continue;
        int bi = 0;
        while (bi < 13 && ms[i] > kTraceBucketsMs[bi]) bi++;
        trace_hist_[i][bi]++;
        trace_sum_[i] += ms[i];
        trace_cnt_[i]++;
      }
      trace_spans_++;
    }
  }

  void span_json(std::string& out, const TraceEnt& ent,
                 const std::string& node, const NodeSpan& s) {
    out += "{\"tid\":\"" + ent.tid + "\",\"job\":";
    jesc(out, ent.job);
    out += ",\"grp\":";
    jesc(out, ent.grp);
    out += ",\"sec\":";
    jint(out, ent.sec);
    out += ",\"node\":";
    jesc(out, node);
    out += ",\"ok\":";
    out += s.ok ? "true" : "false";
    out += ",\"ts\":{";
    bool first = true;
    auto T = [&](const char* k, double v) {
      if (v <= 0) return;
      if (!first) out += ',';
      first = false;
      out += '"';
      out += k;
      out += "\":";
      jdbl(out, v);
    };
    T("b", s.b);
    T("recv", s.recv);
    T("claim", s.claim);
    T("start", s.start);
    T("end", s.end);
    T("flush", s.flush);
    out += "}}";
  }

  void trace_get(const std::string& job, long long sec,
                 std::string& res) {
    std::string tid = std::to_string(
        trace_fnv1a64(job + "|" + std::to_string(sec)));
    std::lock_guard<std::mutex> g(mu);
    res += '[';
    auto it = traces_.find(tid);
    if (it != traces_.end()) {
      bool first = true;
      for (const auto& [node, s] : it->second.nodes) {
        if (!first) res += ',';
        first = false;
        span_json(res, it->second, node, s);
      }
    }
    res += ']';
  }

  void trace_top(long long n, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    if (n < 1) n = 1;
    res += '[';
    bool firstent = true;
    size_t start = trace_fifo_.size() > (size_t)n
                       ? trace_fifo_.size() - (size_t)n
                       : 0;
    for (size_t i = start; i < trace_fifo_.size(); i++) {
      auto it = traces_.find(trace_fifo_[i]);
      if (it == traces_.end() || it->second.nodes.empty()) continue;
      const TraceEnt& ent = it->second;
      if (!firstent) res += ',';
      firstent = false;
      double total = 0;
      std::string nodes = "[";
      bool firstnode = true;
      for (const auto& [node, s] : ent.nodes) {
        if (!firstnode) nodes += ',';
        firstnode = false;
        double nt = trace_total_ms(ent.sec, s);
        total = std::max(total, nt);
        nodes += "{\"node\":";
        jesc(nodes, node);
        nodes += ",\"ok\":";
        nodes += s.ok ? "true" : "false";
        nodes += ",\"stages\":{";
        double ms[6];
        bool present[6];
        trace_stage_ms(ent.sec, s, ms, present);
        bool firststage = true;
        for (int k = 0; k < 6; k++) {
          if (!present[k]) continue;
          if (!firststage) nodes += ',';
          firststage = false;
          nodes += '"';
          nodes += kTraceStages[k];
          nodes += "\":";
          jdbl(nodes, ms[k]);
        }
        nodes += "},\"total_ms\":";
        jdbl(nodes, nt);
        nodes += "}";
      }
      nodes += "]";
      res += "{\"tid\":\"" + ent.tid + "\",\"job\":";
      jesc(res, ent.job);
      res += ",\"grp\":";
      jesc(res, ent.grp);
      res += ",\"sec\":";
      jint(res, ent.sec);
      res += ",\"total_ms\":";
      jdbl(res, total);
      res += ",\"nodes\":";
      res += nodes;
      res += "}";
    }
    res += ']';
  }

  void trace_stats(std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    res += "{\"spans_total\":";
    jint(res, trace_spans_);
    res += ",\"stages\":{";
    bool first = true;
    for (int i = 0; i < 6; i++) {
      if (!trace_cnt_[i]) continue;
      if (!first) res += ',';
      first = false;
      res += '"';
      res += kTraceStages[i];
      res += "\":{\"buckets\":[";
      for (int b = 0; b < 14; b++) {
        if (b) res += ',';
        jint(res, trace_hist_[i][b]);
      }
      res += "],\"sum\":";
      jdbl(res, trace_sum_[i]);
      res += ",\"count\":";
      jint(res, trace_cnt_[i]);
      res += "}";
    }
    res += "}}";
  }

  void upsert_node(const std::string& id, const std::string& doc, bool alived) {
    std::lock_guard<std::mutex> g(mu);
    nodes_[id] = {doc, alived};
    if (wal_) {
      std::string line = "[\"N\",";
      jesc(line, id);
      line += ',';
      jesc(line, doc);
      line += alived ? ",true]" : ",false]";
      wal_->append(line);
    }
  }

  void set_node_alived(const std::string& id, bool alived) {
    std::lock_guard<std::mutex> g(mu);
    auto it = nodes_.find(id);
    if (it != nodes_.end()) it->second.second = alived;
    if (wal_) {
      std::string line = "[\"S\",";
      jesc(line, id);
      line += alived ? ",true]" : ",false]";
      wal_->append(line);
    }
  }

  void upsert_account(const std::string& email, const std::string& doc) {
    std::lock_guard<std::mutex> g(mu);
    accounts_[email] = doc;
    if (wal_) {
      std::string line = "[\"A\",";
      jesc(line, email);
      line += ',';
      jesc(line, doc);
      line += ']';
      wal_->append(line);
    }
  }

  bool delete_account(const std::string& email) {
    std::lock_guard<std::mutex> g(mu);
    bool had = accounts_.erase(email) > 0;
    if (had && wal_) {
      std::string line = "[\"D\",";
      jesc(line, email);
      line += ']';
      wal_->append(line);
    }
    return had;
  }

  // -- queries (reply JSON built under the lock: rows are snapshots) ----

  // filters mirror JobLogStore.query_logs (joblog.py): node, job_ids,
  // name substring, [begin, end), failed_only, latest view, paging
  void query(const JV& kw, std::string& res) {
    std::string node, name_like;
    std::vector<std::string> job_ids;
    bool has_begin = false, has_end = false, failed_only = false,
         latest = false;
    double begin = 0, end = 0;
    long long page = 1, page_size = 50;
    long long after_id = -1;   // >=0 => cursor mode: id>after_id, id ASC
    if (kw.t == JV::OBJ) {
      if (const JV* v = kw.get("node"))
        if (v->t == JV::STR) node = v->s;
      if (const JV* v = kw.get("name_like"))
        if (v->t == JV::STR) name_like = v->s;
      if (const JV* v = kw.get("job_ids"))
        if (v->t == JV::ARR)
          for (const JV& e : v->arr)
            if (e.t == JV::STR) job_ids.push_back(e.s);
      if (const JV* v = kw.get("begin"))
        if (v->t == JV::INT || v->t == JV::DBL) { has_begin = true; begin = v->as_dbl(); }
      if (const JV* v = kw.get("end"))
        if (v->t == JV::INT || v->t == JV::DBL) { has_end = true; end = v->as_dbl(); }
      if (const JV* v = kw.get("failed_only")) failed_only = v->t == JV::BOOL && v->b;
      if (const JV* v = kw.get("latest")) latest = v->t == JV::BOOL && v->b;
      if (const JV* v = kw.get("page")) page = std::max(1LL, v->as_int());
      if (const JV* v = kw.get("page_size"))
        page_size = std::max(1LL, std::min(500LL, v->as_int()));
      if (const JV* v = kw.get("after_id"))
        if (v->t == JV::INT || v->t == JV::DBL)
          after_id = std::max(0LL, v->as_int());
    }
    if (latest) after_id = -1;   // latest rows carry no id (joblog.py)
    auto match = [&](const Rec& r) {
      if (after_id >= 0 && r.id <= after_id) return false;
      if (!node.empty() && r.node != node) return false;
      if (!job_ids.empty() &&
          std::find(job_ids.begin(), job_ids.end(), r.job_id) == job_ids.end())
        return false;
      if (!name_like.empty() && !contains_nocase(r.name, name_like)) return false;
      if (has_begin && r.begin < begin) return false;
      if (has_end && r.begin >= end) return false;
      if (failed_only && r.success) return false;
      return true;
    };

    std::lock_guard<std::mutex> g(mu);
    size_t res_base = res.size();
    std::string memo_key;
    if (latest) {
      // canonical key over every filter the latest view honors: the
      // marshalled reply for an unchanged revision is reusable across
      // a dashboard fleet's polls with zero row copies / re-marshals
      memo_key = node;
      memo_key += '\x1f';
      memo_key += name_like;
      memo_key += '\x1f';
      for (const auto& j : job_ids) {
        memo_key += j;
        memo_key += '\x1e';
      }
      memo_key += '\x1f';
      memo_key += has_begin ? std::to_string(begin) : std::string("-");
      memo_key += '\x1f';
      memo_key += has_end ? std::to_string(end) : std::string("-");
      memo_key += '\x1f';
      memo_key += failed_only ? '1' : '0';
      memo_key += '\x1f';
      memo_key += std::to_string(page);
      memo_key += '\x1f';
      memo_key += std::to_string(page_size);
      auto mit = latest_memo_.find(memo_key);
      if (mit != latest_memo_.end() &&
          mit->second.first == next_id_ - 1) {
        res += mit->second.second;
        op_count("q_latest_memo", 1);
        return;
      }
    }
    auto sort_begin_desc = [](std::vector<const Rec*>& v) {
      // ORDER BY begin_ts DESC, id ASC — the tie order the SQLite
      // backend pins explicitly; both backends must page identically
      std::stable_sort(v.begin(), v.end(), [](const Rec* a, const Rec* b) {
        if (a->begin != b->begin) return a->begin > b->begin;
        return a->id < b->id;
      });
    };
    // clamp before multiplying (UB guard — pinned below too) so the
    // cold keep-bound can't overflow
    page = std::min(page, (long long)1 << 40);
    size_t need = (size_t)page * (size_t)page_size;
    bool no_filter = node.empty() && job_ids.empty() &&
                     name_like.empty() && !failed_only && !has_begin &&
                     !has_end;
    // extra matches the cold tier counted but did not retain (the
    // keep bound) — added back into the reply total
    long long cold_extra = 0;
    // cold_store fully populated BEFORE any pointer into it is taken
    // (a later push_back would reallocate under the hits vector)
    std::vector<Rec> cold_store;
    std::vector<const Rec*> hits;
    if (latest) {
      for (const auto& [k, r] : latest_)
        if (match(r)) hits.push_back(&r);
      // the id-less latest view breaks begin_ts ties by its
      // (job_id, node) primary key — pinned in BOTH backends so the
      // sharded client's scatter-gather merge by the same key
      // reproduces the global order exactly
      std::stable_sort(hits.begin(), hits.end(),
                       [](const Rec* a, const Rec* b) {
                         if (a->begin != b->begin) return a->begin > b->begin;
                         if (a->job_id != b->job_id)
                           return a->job_id < b->job_id;
                         return a->node < b->node;
                       });
      op_count("q_latest_hot", 1);
    } else if (after_id >= 0) {
      // a cursor resuming below the cold watermark merges the cold
      // tier first: every cold id precedes every hot id, so segment
      // matches (sorted by id) followed by the deque scan IS the
      // global id-ascending order
      bool cold = false;
      if (!segs_.empty() && after_id < cold_boundary_) {
        long long ct = 0;
        cold = cold_collect(match, no_filter, has_begin, begin, has_end,
                            end, after_id, need, /*hist=*/false,
                            cold_store, ct) > 0;
      }
      op_count(cold ? "q_cursor_cold" : "q_cursor_hot", 1);
      for (const Rec& r : cold_store) hits.push_back(&r);
      // hot side: ids are contiguous (retention only pops the
      // front — same invariant get_log exploits), so a poller's
      // id > after_id is an index jump, and deque iteration order IS
      // id ASC — a follow poll costs O(new records), not O(store)
      size_t start = 0;
      if (!recs_.empty() && after_id >= recs_.front().id)
        start = (size_t)std::min<long long>(
            after_id - recs_.front().id + 1, (long long)recs_.size());
      for (size_t i = start; i < recs_.size(); i++)
        if (match(recs_[i])) hits.push_back(&recs_[i]);
    } else {
      // history: merge hot + cold under the documented
      // (begin_ts DESC, id ASC) order — byte-identical to an untiered
      // store fed the same stream (total counts both tiers)
      if (!segs_.empty()) {
        long long cold_total = 0;
        if (cold_collect(match, no_filter, has_begin, begin, has_end,
                         end, 0, need, /*hist=*/true, cold_store,
                         cold_total) > 0)
          op_count("q_history_cold", 1);
        cold_extra = cold_total - (long long)cold_store.size();
      }
      for (const Rec& r : cold_store) hits.push_back(&r);
      for (const Rec& r : recs_)
        if (match(r)) hits.push_back(&r);
      sort_begin_desc(hits);
    }
    size_t off = (size_t)((page - 1) * page_size);
    res += "{\"total\":";
    // cursor mode pins total == -1 (the SQLite backend's contract: a
    // follow poller never reads it, and there it cost a full filtered
    // COUNT(*) scan per poll); history totals add back the cold
    // matches the keep bound counted but did not retain
    jint(res, after_id >= 0 ? -1LL
                            : (long long)hits.size() + cold_extra);
    res += ",\"list\":[";
    for (size_t i = off; i < hits.size() && i < off + (size_t)page_size; i++) {
      if (i != off) res += ',';
      rec_wire(res, *hits[i], /*with_id=*/!latest);
    }
    res += "]}";
    if (!memo_key.empty()) {
      latest_memo_[memo_key] = {next_id_ - 1, res.substr(res_base)};
      while (latest_memo_.size() > 64)
        latest_memo_.erase(latest_memo_.begin());
    }
  }

  bool get_log(long long id, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    if (!recs_.empty() && id >= recs_.front().id && id <= recs_.back().id) {
      const Rec& r = recs_[(size_t)(id - recs_.front().id)];
      rec_wire(res, r, true);
      op_count("q_get_hot", 1);
      return true;
    }
    // cold lookup: only at or below the durable watermark (rows above
    // it are authoritatively hot even if a pre-crash segment holds a
    // copy) and above the retention floor (the untiered store would
    // have evicted those rows — same visible set)
    if (id > 0 && id <= cold_boundary_ && !segs_.empty()) {
      long long floor_id = retain_ ? next_id_ - 1 - (long long)retain_ : 0;
      if (id <= floor_id) return false;
      for (const Seg& s : segs_) {
        if (id < s.min_id || id > s.max_id) continue;
        std::vector<Rec> rows;
        // sparse-index seek: O(stride) lines, not the whole day
        read_segment_range(s.path, id, id, rows);
        for (const Rec& r : rows)
          if (r.id == id) {
            rec_wire(res, r, true);
            op_count("q_get_cold", 1);
            return true;
          }
      }
    }
    return false;
  }

  // revision AND the last `limit` records from ONE lock hold — the
  // follow bootstrap needs both atomically (a record landing between
  // two separate reads would be skipped forever by an id > revision
  // poll; logsink/joblog.py pins the same contract)
  void tail_snapshot(long long limit, std::string& res) {
    if (limit < 0) limit = 0;
    if (limit > 500) limit = 500;
    std::lock_guard<std::mutex> g(mu);
    res += "{\"revision\":";
    jint(res, next_id_ - 1);
    res += ",\"list\":[";
    size_t start = recs_.size() > (size_t)limit
                       ? recs_.size() - (size_t)limit : 0;
    for (size_t i = start; i < recs_.size(); i++) {
      if (i != start) res += ',';
      rec_wire(res, recs_[i], true);
    }
    res += "]}";
  }

  // observability: watermark, hot sizes, segment inventory (same shape
  // as JobLogStore.tier_info)
  void tier_info(std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    // native's in-memory tables ARE the hot mirrors; "tiering" here
    // reports whether day AGING is active (the part the rollback
    // switch controls) so the runbook's rollback check tells the truth
    res += hot_days_ > 0 ? "{\"tiering\":true,\"hot_days\":"
                         : "{\"tiering\":false,\"hot_days\":";
    jint(res, (long long)hot_days_);
    res += ",\"cold_boundary\":";
    jint(res, cold_boundary_);
    res += ",\"hot_records\":";
    jint(res, (long long)recs_.size());
    res += ",\"revision\":";
    jint(res, next_id_ - 1);
    res += ",\"segments\":[";
    bool first = true;
    for (const Seg& s : segs_) {
      if (!first) res += ',';
      first = false;
      res += "{\"day\":";
      jesc(res, s.day);
      res += ",\"min\":";
      jint(res, s.min_id);
      res += ",\"max\":";
      jint(res, s.max_id);
      res += ",\"count\":";
      jint(res, s.count);
      res += '}';
    }
    res += "]}";
  }

  // monotone change token for the read plane: the max record id ever
  // assigned (0 when empty).  Creates bump it; retention only pops the
  // front — the web tier's revision-keyed ETag and a follow poller's
  // tail bootstrap read this instead of re-running the query.
  long long revision() {
    std::lock_guard<std::mutex> g(mu);
    return next_id_ - 1;
  }

  // sharded-result-plane topology pin: with n >= 0, publish-if-absent
  // {hash, n}; always replies with the current pin (or null).  The
  // stored text matches the Python backend's json.dumps(sort_keys=True)
  // byte for byte so a differential across backends can't diverge.
  void logmap(long long n, const std::string& hash, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    if (n >= 0 && logmap_.empty()) {
      logmap_ = "{\"hash\": ";
      jesc(logmap_, hash);
      logmap_ += ", \"n\": ";
      jint(logmap_, n);
      logmap_ += '}';
      if (wal_) {
        std::string line = "[\"M\",";
        jesc(line, logmap_);
        line += ']';
        wal_->append(line);
      }
    }
    res += logmap_.empty() ? "null" : logmap_;
  }

  void stat(const std::string& day, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    Stat s;
    auto it = stats_.find(day);
    if (it != stats_.end()) s = it->second;
    stat_wire(res, s, nullptr);
  }

  void stat_days(long long n, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    res += '[';
    long long emitted = 0;
    for (auto it = stats_.rbegin(); it != stats_.rend() && emitted < n; ++it) {
      if (it->first.empty()) continue;            // '' = overall
      if (emitted) res += ',';
      stat_wire(res, it->second, &it->first);
      emitted++;
    }
    res += ']';
  }

  // node docs are stored JSON objects; alived is injected on the way out
  // (the Python server json-decodes and re-encodes — same wire result)
  void get_nodes(std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    res += '[';
    bool first = true;
    for (const auto& [id, dv] : nodes_) {
      if (!first) res += ',';
      first = false;
      node_wire(res, dv.first, dv.second);
    }
    res += ']';
  }

  bool get_node(const std::string& id, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    auto it = nodes_.find(id);
    if (it == nodes_.end()) return false;
    node_wire(res, it->second.first, it->second.second);
    return true;
  }

  bool get_account(const std::string& email, std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    auto it = accounts_.find(email);
    if (it == accounts_.end()) return false;
    jesc(res, it->second);          // doc travels as a STRING
    return true;
  }

  void list_accounts(std::string& res) {
    std::lock_guard<std::mutex> g(mu);
    res += '[';
    bool first = true;
    for (const auto& [email, doc] : accounts_) {
      if (!first) res += ',';
      first = false;
      jesc(res, doc);
    }
    res += ']';
  }

  // -- WAL open/replay/compact ------------------------------------------

  bool open_wal(const std::string& path, std::string& err,
                bool sync_per_commit) {
    std::lock_guard<std::mutex> g(mu);
    seg_dir_ = path + ".segs";
    FILE* f = fopen(path.c_str(), "r");
    if (f) {
      char* lineptr = nullptr;
      size_t cap = 0;
      ssize_t n;
      std::string line;
      bool bad = false;
      while ((n = getline(&lineptr, &cap, f)) != -1) {
        line.assign(lineptr, (size_t)n);
        while (!line.empty() && (line.back() == '\n' || line.back() == '\r'))
          line.pop_back();
        if (!line.empty() && !replay_line(line)) {
          bad = true;   // torn final record (crash mid-append) is fine
          break;
        }
      }
      if (bad && getline(&lineptr, &cap, f) != -1) {
        err = "corrupt wal record: " + line.substr(0, 200);
        free(lineptr);
        fclose(f);
        return false;
      }
      free(lineptr);
      fclose(f);
    }
    // compacted snapshot -> temp file -> atomic rename.  Stats and the
    // latest table summarize ALL history, so they snapshot explicitly;
    // only the retained record window re-emits as "L" lines.  Lines
    // stream one at a time (never the whole snapshot in memory) and
    // every write is CHECKED — an ENOSPC mid-snapshot must abort before
    // the rename, not silently truncate the only copy of history.
    std::string tmp = path + ".tmp";
    FILE* out = fopen(tmp.c_str(), "w");
    if (!out) {
      err = "cannot write " + tmp;
      return false;
    }
    std::string line;
    bool wok = true;
    auto emit = [&]() {
      line += '\n';
      wok = wok && fwrite(line.data(), 1, line.size(), out) == line.size();
      line.clear();
    };
    line = "[\"v\",";
    jint(line, next_id_);
    line += ']';
    emit();
    for (const auto& [day, s] : stats_) {
      line = "[\"C\",";
      jesc(line, day);
      line += ',';
      jint(line, s.total);
      line += ',';
      jint(line, s.ok);
      line += ',';
      jint(line, s.fail);
      line += ']';
      emit();
    }
    for (const auto& [key, r] : latest_) {
      line = "[\"T\",";
      rec_body(line, r);
      line += ']';
      emit();
    }
    for (const auto& [id, dv] : nodes_) {
      line = "[\"N\",";
      jesc(line, id);
      line += ',';
      jesc(line, dv.first);
      line += dv.second ? ",true]" : ",false]";
      emit();
    }
    for (const auto& [email, doc] : accounts_) {
      line = "[\"A\",";
      jesc(line, email);
      line += ',';
      jesc(line, doc);
      line += ']';
      emit();
    }
    if (!logmap_.empty()) {
      line = "[\"M\",";
      jesc(line, logmap_);
      line += ']';
      emit();
    }
    if (cold_boundary_ > 0) {
      // the compacted snapshot re-emits only HOT records below — the
      // cold watermark line keeps aged ids resolving to their
      // segments after the rewrite
      line = "[\"G\",";
      jint(line, cold_boundary_);
      line += ']';
      emit();
    }
    for (const Rec& r : recs_) {
      wal_create(line, r);
      emit();
    }
    wok = wok && fflush(out) == 0 && fdatasync(fileno(out)) == 0;
    fclose(out);
    if (!wok) {
      remove(tmp.c_str());
      err = "snapshot write to " + tmp + " failed: " + strerror(errno);
      return false;
    }
    if (rename(tmp.c_str(), path.c_str()) != 0) {
      err = "rename failed for " + tmp;
      return false;
    }
    wal_ = &wal_storage_;
    if (!wal_->open_append(path, sync_per_commit)) {
      err = "cannot append to " + path;
      wal_ = nullptr;
      return false;
    }
    scan_segments();
    return true;
  }

  void sweep() {
    if (wal_) wal_->sync();
  }

  // Move every record whose UTC day fell out of the hot window into
  // its day's immutable segment file, then trim the deque and append a
  // durable ["G", boundary] watermark to the WAL.  Crash-safe by
  // ordering: segments are written + fsynced FIRST (union by id — a
  // redo converges on the same bytes), the trim + watermark land
  // after; a kill -9 in between leaves the rows hot and the watermark
  // behind, and reads stay exact because the cold tier is only
  // consulted at or below the watermark.  The aged set is always a
  // strict id-PREFIX of the deque (stop at the first record still in
  // the window), preserving the contiguous-id invariant get_log and
  // cursor mode index by.  Returns records aged.
  // bounded like joblog.py's AGE_PASS_RECORDS: one monolithic pass on
  // first enablement (retain_ defaults to 1M) would copy the whole
  // backlog under mu, stalling every wire op for the duration
  static constexpr size_t kAgePassRecords = 50000;

  long long age_out(double now) {
    if (hot_days_ == 0 || seg_dir_.empty() || !wal_) return 0;
    // one pass at a time: the sweeper thread and the wire op can race,
    // and two concurrent write_segment() calls truncate each other's
    // .tmp mid-write — a torn segment published by the slower rename
    // would read as empty AFTER the trim (the Python _age_mu contract)
    std::lock_guard<std::mutex> ag(age_mu_);
    double cutoff = hot_cutoff_ts(now, hot_days_);
    long long total = 0;
    while (true) {
      long long aged = age_pass(cutoff);
      total += aged;
      if (aged < (long long)kAgePassRecords) break;
    }
    if (total) op_count("aged_records", total);
    return total;
  }

 private:
  long long age_pass(double cutoff) {
    std::vector<Rec> aged;
    long long nb = 0;
    {
      std::lock_guard<std::mutex> g(mu);
      for (const Rec& r : recs_) {
        if (r.begin >= cutoff || aged.size() >= kAgePassRecords) break;
        aged.push_back(r);
        nb = r.id;
      }
    }
    if (aged.empty()) return 0;
    long long count = (long long)aged.size();
    // segment writes OUTSIDE the lock: new creates only get ids > nb,
    // so the aged set is immutable while the files build; a reader
    // racing the rename sees the old inode, whose rows are still hot
    // and filtered out of cold reads by the unadvanced watermark
    std::map<std::string, std::vector<Rec>> by_day;
    for (Rec& r : aged) by_day[day_of(r.begin)].push_back(std::move(r));
    std::vector<Seg> entries;
    for (auto& [day, rs] : by_day) {
      Seg e;
      if (!write_segment(day, rs, e)) {
        fprintf(stderr, "age_out: segment write failed for %s: %s\n",
                day.c_str(), strerror(errno));
        return 0;   // rows stay hot; the next pass retries
      }
      entries.push_back(std::move(e));
    }
    {
      std::lock_guard<std::mutex> g(mu);
      while (!recs_.empty() && recs_.front().id <= nb) recs_.pop_front();
      if (nb > cold_boundary_) cold_boundary_ = nb;
      std::string line = "[\"G\",";
      jint(line, nb);
      line += ']';
      wal_->append(line);
      for (const Seg& e : entries) upsert_seg(e);
      // drop segments wholly below the retention floor — invisible
      // either way; bounds disk like the untiered pop bounds memory
      if (retain_) {
        long long floor_id = next_id_ - 1 - (long long)retain_;
        std::vector<Seg> keep;
        for (Seg& s : segs_) {
          if (s.max_id <= floor_id) remove(s.path.c_str());
          else keep.push_back(std::move(s));
        }
        segs_.swap(keep);
      }
    }
    return count;
  }

  // ---- cold-tier segments (format shared with logsink/tiering.py) ------

  static bool read_segment(const std::string& path, std::vector<Rec>& out) {
    FILE* f = fopen(path.c_str(), "r");
    if (!f) return false;
    char* lineptr = nullptr;
    size_t cap = 0;
    ssize_t n;
    bool first = true, ok = true;
    while ((n = getline(&lineptr, &cap, f)) != -1) {
      std::string line(lineptr, (size_t)n);
      while (!line.empty() && (line.back() == '\n' || line.back() == '\r'))
        line.pop_back();
      if (line.empty()) continue;
      JParser jp(line);
      JV v;
      if (!jp.value(v) || v.t != JV::ARR || v.arr.empty() ||
          v.arr[0].t != JV::STR) {
        ok = false;
        break;
      }
      if (first) {
        first = false;
        if (v.arr[0].s != "d") { ok = false; break; }
        continue;
      }
      Rec r;
      if (v.arr[0].s != "L" || !parse_rec(v, 1, r)) {
        ok = false;
        break;
      }
      out.push_back(std::move(r));
    }
    free(lineptr);
    fclose(f);
    if (!ok) out.clear();   // torn/garbage file: treated as absent —
                            // cold reads stop at the watermark, and the
                            // age-out redo rewrites it whole
    std::sort(out.begin(), out.end(),
              [](const Rec& a, const Rec& b) { return a.id < b.id; });
    return ok;
  }

  static std::string idx_path_of(const std::string& seg_path) {
    return seg_path.substr(0, seg_path.size() - 4) + ".idx";
  }

  // ranged cold read: ids in [lo, hi] from one segment, id ASC.  With a
  // FRESH .idx sidecar (its mirrored header equals the segment's — any
  // crash ordering between the two renames fails the match and degrades
  // to a top-of-file scan, never a wrong seek) the scan fseeks to
  // within IDX_STRIDE lines of lo and stops at the first id past hi
  // (ids ascend on disk), so a single-id lookup or a floor/watermark-
  // bounded history scan parses O(stride + matches) lines, not the
  // whole day (logsink/tiering.py read_segment_range pins the same
  // contract via mmap).
  static bool read_segment_range(const std::string& path, long long lo,
                                 long long hi, std::vector<Rec>& out) {
    FILE* f = fopen(path.c_str(), "r");
    if (!f) return false;
    char* lineptr = nullptr;
    size_t cap = 0;
    ssize_t n = getline(&lineptr, &cap, f);
    if (n == -1) {
      free(lineptr);
      fclose(f);
      return false;
    }
    std::string hline(lineptr, (size_t)n);
    while (!hline.empty() &&
           (hline.back() == '\n' || hline.back() == '\r'))
      hline.pop_back();
    JParser hp(hline);
    JV hv;
    if (!hp.value(hv) || hv.t != JV::ARR || hv.arr.size() < 5 ||
        hv.arr[0].t != JV::STR || hv.arr[0].s != "d") {
      free(lineptr);
      fclose(f);
      return false;
    }
    if (hv.arr[4].as_int() < lo || hv.arr[3].as_int() > hi) {
      free(lineptr);
      fclose(f);
      return true;            // disjoint by header: nothing in range
    }
    long long seek_off = -1;
    if (FILE* fi = fopen(idx_path_of(path).c_str(), "r")) {
      char* il = nullptr;
      size_t icap = 0;
      ssize_t in_;
      bool first = true;
      while ((in_ = getline(&il, &icap, fi)) != -1) {
        std::string line(il, (size_t)in_);
        while (!line.empty() &&
               (line.back() == '\n' || line.back() == '\r'))
          line.pop_back();
        if (line.empty()) continue;
        JParser jp(line);
        JV v;
        if (!jp.value(v) || v.t != JV::ARR || v.arr.size() < 3 ||
            v.arr[0].t != JV::STR) {
          seek_off = -1;      // garbage sidecar: scan from the top
          break;
        }
        if (first) {
          first = false;
          bool fresh = v.arr[0].s == "i" && v.arr.size() >= 5 &&
                       v.arr[1].t == JV::STR &&
                       v.arr[1].s == hv.arr[1].s &&
                       v.arr[2].as_int() == hv.arr[2].as_int() &&
                       v.arr[3].as_int() == hv.arr[3].as_int() &&
                       v.arr[4].as_int() == hv.arr[4].as_int();
          if (!fresh) break;
          continue;
        }
        if (v.arr[0].s != "e") {
          seek_off = -1;
          break;
        }
        if (v.arr[1].as_int() <= lo)
          seek_off = v.arr[2].as_int();
        else
          break;              // marks ascend: first mark past lo ends it
      }
      free(il);
      fclose(fi);
    }
    if (seek_off > 0) fseek(f, (long)seek_off, SEEK_SET);
    bool ok = true;
    while ((n = getline(&lineptr, &cap, f)) != -1) {
      std::string line(lineptr, (size_t)n);
      while (!line.empty() &&
             (line.back() == '\n' || line.back() == '\r'))
        line.pop_back();
      if (line.empty()) continue;
      JParser jp(line);
      JV v;
      Rec r;
      if (!jp.value(v) || v.t != JV::ARR || v.arr.empty() ||
          v.arr[0].t != JV::STR || v.arr[0].s != "L" ||
          !parse_rec(v, 1, r)) {
        ok = false;
        break;
      }
      if (r.id > hi) break;   // id ASC on disk: nothing further matches
      if (r.id < lo) continue;
      out.push_back(std::move(r));
    }
    free(lineptr);
    fclose(f);
    if (!ok) out.clear();     // torn/garbage: absent, like read_segment
    return ok;
  }

  bool write_segment(const std::string& day, std::vector<Rec>& recs,
                     Seg& entry) {
    // union by id with the existing file — idempotent, so the crash
    // redo and a late-record pass both converge on the same bytes;
    // atomic publish via temp + fdatasync + rename
    mkdir(seg_dir_.c_str(), 0777);
    std::string path = seg_dir_ + "/" + day + ".seg";
    std::map<long long, Rec> by_id;
    {
      std::vector<Rec> old;
      read_segment(path, old);
      for (Rec& r : old) by_id[r.id] = std::move(r);
    }
    for (Rec& r : recs) by_id[r.id] = std::move(r);
    std::string tmp = path + ".tmp";
    FILE* out = fopen(tmp.c_str(), "w");
    if (!out) return false;
    std::string line = "[\"d\",";
    jesc(line, day);
    line += ',';
    jint(line, (long long)by_id.size());
    line += ',';
    jint(line, by_id.empty() ? 0 : by_id.begin()->first);
    line += ',';
    jint(line, by_id.empty() ? 0 : by_id.rbegin()->first);
    line += "]\n";
    bool wok = fwrite(line.data(), 1, line.size(), out) == line.size();
    // sparse-index sidecar body built alongside: a mirrored header
    // (freshness check for read_segment_range) + one (id, byte offset)
    // mark every kIdxStride records
    constexpr int kIdxStride = 64;
    long long off = (long long)line.size();
    std::string idx = "[\"i\",";
    jesc(idx, day);
    idx += ',';
    jint(idx, (long long)by_id.size());
    idx += ',';
    jint(idx, by_id.empty() ? 0 : by_id.begin()->first);
    idx += ',';
    jint(idx, by_id.empty() ? 0 : by_id.rbegin()->first);
    idx += "]\n";
    long long row_i = 0;
    for (const auto& [id, r] : by_id) {
      line.clear();
      wal_create(line, r);
      line += '\n';
      if (row_i++ % kIdxStride == 0) {
        idx += "[\"e\",";
        jint(idx, id);
        idx += ',';
        jint(idx, off);
        idx += "]\n";
      }
      off += (long long)line.size();
      wok = wok && fwrite(line.data(), 1, line.size(), out) == line.size();
    }
    wok = wok && fflush(out) == 0 && fdatasync(fileno(out)) == 0;
    fclose(out);
    if (!wok || rename(tmp.c_str(), path.c_str()) != 0) {
      remove(tmp.c_str());
      return false;
    }
    // fsync the DIRECTORY: the caller durably records the watermark
    // right after, and a power loss must not persist a watermark whose
    // segment's directory entry never hit disk (logsink/tiering.py
    // pins the same ordering)
    int dfd = open(seg_dir_.c_str(), O_RDONLY | O_DIRECTORY);
    if (dfd >= 0) {
      fsync(dfd);
      close(dfd);
    }
    // publish the sidecar AFTER the segment (a fresh idx never
    // describes an unpublished seg); advisory data — a failed write
    // leaves ranged reads on the full-scan path, never wrong
    std::string ipath = idx_path_of(path);
    std::string itmp = ipath + ".tmp";
    if (FILE* fi = fopen(itmp.c_str(), "w")) {
      bool iok = fwrite(idx.data(), 1, idx.size(), fi) == idx.size();
      iok = iok && fflush(fi) == 0 && fdatasync(fileno(fi)) == 0;
      fclose(fi);
      if (!iok || rename(itmp.c_str(), ipath.c_str()) != 0)
        remove(itmp.c_str());
    }
    entry.day = day;
    entry.path = path;
    entry.min_id = by_id.empty() ? 0 : by_id.begin()->first;
    entry.max_id = by_id.empty() ? 0 : by_id.rbegin()->first;
    entry.count = (long long)by_id.size();
    return true;
  }

  void scan_segments() {
    segs_.clear();
    DIR* d = opendir(seg_dir_.c_str());
    if (!d) return;
    while (struct dirent* e = readdir(d)) {
      std::string name = e->d_name;
      std::string path = seg_dir_ + "/" + name;
      if (name.size() > 4 && name.compare(name.size() - 4, 4, ".tmp") == 0) {
        remove(path.c_str());   // never published (rename is atomic)
        continue;
      }
      if (name.size() <= 4 || name.compare(name.size() - 4, 4, ".seg") != 0)
        continue;
      FILE* f = fopen(path.c_str(), "r");
      if (!f) continue;
      char* lineptr = nullptr;
      size_t cap = 0;
      ssize_t n = getline(&lineptr, &cap, f);
      fclose(f);
      std::string line = n > 0 ? std::string(lineptr, (size_t)n) : "";
      free(lineptr);
      while (!line.empty() && (line.back() == '\n' || line.back() == '\r'))
        line.pop_back();
      JParser jp(line);
      JV v;
      if (!jp.value(v) || v.t != JV::ARR || v.arr.size() < 5 ||
          v.arr[0].t != JV::STR || v.arr[0].s != "d")
        continue;
      Seg s;
      s.day = v.arr[1].s;
      s.path = path;
      s.count = v.arr[2].as_int();
      s.min_id = v.arr[3].as_int();
      s.max_id = v.arr[4].as_int();
      segs_.push_back(std::move(s));
    }
    closedir(d);
    std::sort(segs_.begin(), segs_.end(),
              [](const Seg& a, const Seg& b) { return a.day < b.day; });
  }

  void upsert_seg(const Seg& e) {
    for (Seg& s : segs_)
      if (s.day == e.day) {
        s = e;
        return;
      }
    segs_.push_back(e);
    std::sort(segs_.begin(), segs_.end(),
              [](const Seg& a, const Seg& b) { return a.day < b.day; });
  }

  // collect cold records passing `match` with ids in (min_id,
  // cold_boundary_] and above the retention floor, day-pruned by the
  // [begin, end) begin_ts filter — caller holds mu.  `keep` bounds the
  // rows RETAINED (top `keep` under the caller's merge order: id ASC,
  // or (begin DESC, id) with `hist`) while `total` stays exact, and an
  // unfiltered (`no_filter`) wholly-visible segment whose every record
  // must sort after the kept set contributes its header count without
  // being parsed — a 90-day cold tier doesn't materialize per poll
  // (logsink/tiering.py cold_query pins the same).  Returns segments
  // actually read.
  template <typename F>
  int cold_collect(const F& match, bool no_filter, bool has_begin,
                   double begin, bool has_end, double end,
                   long long min_id, size_t keep, bool hist,
                   std::vector<Rec>& out, long long& total) {
    long long floor_id = retain_ ? next_id_ - 1 - (long long)retain_ : 0;
    if (floor_id > min_id) min_id = floor_id;
    auto order = [hist](const Rec& a, const Rec& b) {
      if (hist) {
        if (a.begin != b.begin) return a.begin > b.begin;
        return a.id < b.id;
      }
      return a.id < b.id;
    };
    std::vector<Seg> segs = segs_;
    std::sort(segs.begin(), segs.end(), [hist](const Seg& a, const Seg& b) {
      return hist ? a.day > b.day : a.min_id < b.min_id;
    });
    int touched = 0;
    for (const Seg& s : segs) {
      if (s.min_id > cold_boundary_ || s.max_id <= min_id) continue;
      double d0 = day_start(s.day);
      if (d0 >= 0) {
        if (has_begin && d0 + 86400.0 <= begin) continue;
        if (has_end && d0 >= end) continue;
      }
      bool whole = no_filter && min_id < s.min_id &&
                   s.max_id <= cold_boundary_ &&
                   (!has_begin || (d0 >= 0 && begin <= d0)) &&
                   (!has_end || (d0 >= 0 && end >= d0 + 86400.0));
      if (whole && out.size() >= keep && !out.empty()) {
        // out is kept sorted below; the worst kept row decides
        if (hist ? (d0 >= 0 && out.back().begin >= d0 + 86400.0)
                 : s.min_id > out.back().id) {
          total += s.count;
          continue;
        }
      }
      touched++;
      std::vector<Rec> rows;
      // ranged read: the retention floor and durable watermark become
      // the seek bounds — a cursor poll deep into the tier seeks past
      // everything already served instead of re-parsing it
      read_segment_range(s.path, min_id + 1, cold_boundary_, rows);
      for (Rec& r : rows) {
        if (match(r)) {
          total++;
          out.push_back(std::move(r));
        }
      }
      std::sort(out.begin(), out.end(), order);
      if (out.size() > keep) out.resize(keep);
    }
    return touched;
  }

  void apply_create(const Rec& r) {
    // the retained window stays contiguous in id: get_log indexes by
    // id - front.id
    recs_.push_back(r);
    while (recs_.size() > retain_) recs_.pop_front();
    latest_[{r.job_id, r.node}] = r;
    for (const std::string& day : {std::string(), day_of(r.begin)}) {
      Stat& s = stats_[day];
      s.total++;
      (r.success ? s.ok : s.fail)++;
    }
  }

  static void rec_body(std::string& out, const Rec& r) {
    jint(out, r.id);
    out += ',';
    jesc(out, r.job_id);
    out += ',';
    jesc(out, r.group);
    out += ',';
    jesc(out, r.name);
    out += ',';
    jesc(out, r.node);
    out += ',';
    jesc(out, r.user);
    out += ',';
    jesc(out, r.command);
    out += ',';
    jesc(out, r.output);
    out += r.success ? ",true," : ",false,";
    jdbl(out, r.begin);
    out += ',';
    jdbl(out, r.end);
  }

  static void wal_create(std::string& out, const Rec& r) {
    out += "[\"L\",";
    rec_body(out, r);
    out += ']';
  }

  static void stat_wire(std::string& out, const Stat& s,
                        const std::string* day) {
    out += '{';
    if (day) {
      out += "\"day\":";
      jesc(out, *day);
      out += ',';
    }
    out += "\"total\":";
    jint(out, s.total);
    out += ",\"successed\":";
    jint(out, s.ok);
    out += ",\"failed\":";
    jint(out, s.fail);
    out += '}';
  }

  static void node_wire(std::string& out, const std::string& doc,
                        bool alived) {
    // inject "alived" into the stored JSON object text
    size_t close = doc.rfind('}');
    if (doc.empty() || close == std::string::npos) {
      out += alived ? "{\"alived\":true}" : "{\"alived\":false}";
      return;
    }
    bool empty_obj = doc.find_first_not_of(" \t{", doc.find('{') + 0) == close;
    out.append(doc, 0, close);
    if (!empty_obj) out += ',';
    out += alived ? "\"alived\":true}" : "\"alived\":false}";
  }

  static bool parse_rec(const JV& a, size_t off, Rec& r) {
    if (a.arr.size() < off + 11) return false;
    auto S = [&](size_t i) { return a.arr[off + i].s; };
    r.id = a.arr[off + 0].as_int();
    r.job_id = S(1);
    r.group = S(2);
    r.name = S(3);
    r.node = S(4);
    r.user = S(5);
    r.command = S(6);
    r.output = S(7);
    r.success = a.arr[off + 8].t == JV::BOOL && a.arr[off + 8].b;
    r.begin = a.arr[off + 9].as_dbl();
    r.end = a.arr[off + 10].as_dbl();
    return true;
  }

  bool replay_line(const std::string& line) {
    JParser jp(line);
    JV v;
    if (!jp.value(v) || v.t != JV::ARR || v.arr.empty() ||
        v.arr[0].t != JV::STR)
      return false;
    const std::string& tag = v.arr[0].s;
    if (tag == "v") {
      if (v.arr.size() < 2) return false;
      next_id_ = v.arr[1].as_int();
    } else if (tag == "L") {
      Rec r;
      if (!parse_rec(v, 1, r)) return false;
      // replayed retained records must NOT re-bump stats/latest when a
      // "C"/"T" snapshot already accounts for them — snapshot lines
      // always precede "L" lines in a compacted file, so replay is
      // additive only for post-snapshot appends ... which also re-count
      // via apply_create.  To keep one code path, compaction rewrites
      // stats BEFORE records and replay of an L line only bumps stats
      // when the record's id is >= the snapshot watermark (next_id_ at
      // snapshot time is carried by the "v" line, which precedes all).
      bool post_snapshot = r.id >= snapshot_watermark_;
      recs_.push_back(r);
      while (recs_.size() > retain_) recs_.pop_front();
      // a retained pre-snapshot record must not clobber a NEWER latest
      // entry restored from its "T" snapshot (that record may have aged
      // out of the retention window)
      auto lit = latest_.find({r.job_id, r.node});
      if (lit == latest_.end() || r.id >= lit->second.id)
        latest_[{r.job_id, r.node}] = r;
      if (post_snapshot) {
        for (const std::string& day : {std::string(), day_of(r.begin)}) {
          Stat& s = stats_[day];
          s.total++;
          (r.success ? s.ok : s.fail)++;
        }
      }
      if (r.id >= next_id_) next_id_ = r.id + 1;
    } else if (tag == "T") {
      Rec r;
      if (!parse_rec(v, 1, r)) return false;
      latest_[{r.job_id, r.node}] = r;
    } else if (tag == "C") {
      if (v.arr.size() < 5) return false;
      Stat& s = stats_[v.arr[1].s];
      s.total = v.arr[2].as_int();
      s.ok = v.arr[3].as_int();
      s.fail = v.arr[4].as_int();
      snapshot_watermark_ = next_id_;
    } else if (tag == "N") {
      if (v.arr.size() < 4) return false;
      nodes_[v.arr[1].s] = {v.arr[2].s, v.arr[3].t == JV::BOOL && v.arr[3].b};
    } else if (tag == "S") {
      if (v.arr.size() < 3) return false;
      auto it = nodes_.find(v.arr[1].s);
      if (it != nodes_.end())
        it->second.second = v.arr[2].t == JV::BOOL && v.arr[2].b;
    } else if (tag == "A") {
      if (v.arr.size() < 3) return false;
      accounts_[v.arr[1].s] = v.arr[2].s;
    } else if (tag == "M") {
      if (v.arr.size() < 2) return false;
      logmap_ = v.arr[1].s;
    } else if (tag == "G") {
      // cold watermark: every record appended before this line with
      // id <= boundary moved to its day segment — drop it from the
      // hot deque (stats/latest already account for it; L lines that
      // FOLLOW a G line are post-trim appends and stay hot)
      if (v.arr.size() < 2) return false;
      long long b = v.arr[1].as_int();
      while (!recs_.empty() && recs_.front().id <= b) recs_.pop_front();
      if (b > cold_boundary_) cold_boundary_ = b;
    } else if (tag == "D") {
      if (v.arr.size() < 2) return false;
      accounts_.erase(v.arr[1].s);
    } else {
      return false;
    }
    return true;
  }

  std::mutex mu;
  std::mutex age_mu_;           // serializes age-out passes (see age_out)
  size_t retain_;
  size_t hot_days_ = 0;         // 0 = no day aging (tiering rollback)
  long long cold_boundary_ = 0; // ids <= this live in segments
  std::string seg_dir_;         // <wal>.segs (empty = no cold tier)
  std::vector<Seg> segs_;       // index, day ASC
  long long next_id_ = 1;
  long long snapshot_watermark_ = 0;
  std::deque<Rec> recs_;
  std::vector<std::shared_ptr<Subscriber>> subs_;  // live change streams
  std::map<std::pair<std::string, std::string>, Rec> latest_;
  // serialized-reply memo for the latest view, keyed on the request's
  // canonical filter string -> (revision, marshalled reply).  Guarded
  // by mu; sound because the revision and the reply are read/written
  // under the SAME mu hold writers take to mutate (the py serve
  // layer's memo one backend over; hits count as q_latest_memo).
  std::map<std::string, std::pair<long long, std::string>> latest_memo_;
  std::map<std::string, Stat> stats_;
  std::map<std::string, std::pair<std::string, bool>> nodes_;
  std::map<std::string, std::string> accounts_;
  std::string logmap_;
  std::unordered_map<std::string, long long> idem_;
  std::deque<std::string> idem_fifo_;
  // trace plane (all under mu)
  std::unordered_map<std::string, TraceEnt> traces_;
  std::deque<std::string> trace_fifo_;
  long long trace_hist_[6][14] = {{0}};
  double trace_sum_[6] = {0};
  long long trace_cnt_[6] = {0};
  long long trace_spans_ = 0;
  Wal wal_storage_;
  Wal* wal_ = nullptr;
};

// ---------------------------------------------------------------------------
// connections: request/response, plus subscription push frames — one
// reader thread per conn, one pusher thread per live subscription, all
// writes serialized by the connection's shared write mutex
// ---------------------------------------------------------------------------

// Per-subscription pusher: waits for buffered events, serializes
// {"s":sid,"evs":[...]} frames (2048-event chunks, serve.py's bound)
// and writes them under the connection's write mutex.  On overflow it
// sends the terminal {"s":sid,"lost":true} frame and exits — the
// subscription is dead, the client re-lists and re-subscribes.
static void sub_pusher(std::shared_ptr<Subscriber> s, LogStore* store) {
  while (true) {
    std::vector<std::string> evs;
    bool lost = false;
    {
      std::unique_lock<std::mutex> lk(s->mu);
      s->cv.wait(lk, [&] { return s->closed || s->lost || !s->buf.empty(); });
      if (s->closed) break;
      lost = s->lost;
      if (!lost) {
        evs.assign(s->buf.begin(), s->buf.end());
        s->buf.clear();
      }
    }
    std::string frame;
    if (lost) {
      frame = "{\"s\":" + std::to_string(s->sid) + ",\"lost\":true}\n";
    } else {
      size_t i = 0;
      while (i < evs.size()) {
        size_t n = std::min(evs.size() - i, (size_t)2048);
        frame += "{\"s\":" + std::to_string(s->sid) + ",\"evs\":[";
        for (size_t k = 0; k < n; k++) {
          if (k) frame += ',';
          frame += evs[i + k];
        }
        frame += "]}\n";
        i += n;
      }
    }
    bool ok = true;
    {
      std::lock_guard<std::mutex> wl(*s->wmu);
      size_t off = 0;
      while (off < frame.size()) {
        ssize_t w =
            ::send(s->fd, frame.data() + off, frame.size() - off, MSG_NOSIGNAL);
        if (w <= 0) { ok = false; break; }
        off += (size_t)w;
      }
    }
    if (lost || !ok) {
      std::lock_guard<std::mutex> lk(s->mu);
      s->closed = true;
      break;
    }
  }
  store->unsubscribe_sub(s);
  ::close(s->fd);  // our dup — the reader's fd stays live
}

static std::string g_token;

static std::string arg_s(const JV& a, size_t i) {
  return i < a.arr.size() && a.arr[i].t == JV::STR ? a.arr[i].s : std::string();
}

static bool arg_b(const JV& a, size_t i) {
  return i < a.arr.size() && a.arr[i].t == JV::BOOL && a.arr[i].b;
}

static void handle(LogStore& store, const std::string& line, bool& authed,
                   std::string& out, int fd,
                   const std::shared_ptr<std::mutex>& wmu,
                   std::vector<std::shared_ptr<Subscriber>>& conn_subs,
                   std::shared_ptr<Subscriber>& pending_sub) {
  long long rid = 0;
  std::string op;
  JV args;
  if (!parse_request(line, rid, op, args)) {
    out.clear();               // protocol violation: caller drops the conn
    return;
  }
  out = "{\"i\":";
  jint(out, rid);
  if (!authed) {
    if (op == "auth" && token_eq(arg_s(args, 0), g_token)) {
      authed = true;
      out += ",\"r\":true}\n";
      return;
    }
    out += ",\"e\":\"unauthenticated\"}\n";
    out += '\0';               // sentinel: reply then close (see caller)
    return;
  }
  std::string res;
  long long t0 = mono_ns();
  if (op == "auth") {
    res = "true";
  } else if (op == "op_stats") {
    op_stats_json(res);
  } else if (op == "create_job_log") {
    Rec r;
    if (args.arr.empty() || !rec_unwire(args.arr[0], r)) {
      out += ",\"e\":\"bad record\"}\n";
      return;
    }
    jint(res, store.create(std::move(r), arg_s(args, 1)));
  } else if (op == "create_job_logs") {
    std::vector<Rec> recs;
    bool ok = !args.arr.empty() && args.arr[0].t == JV::ARR;
    if (ok) {
      recs.reserve(args.arr[0].arr.size());
      for (const JV& w : args.arr[0].arr) {
        Rec r;
        if (!rec_unwire(w, r)) { ok = false; break; }
        recs.push_back(std::move(r));
      }
    }
    if (!ok) {
      out += ",\"e\":\"bad record\"}\n";
      return;
    }
    store.create_many(recs, arg_s(args, 1), res,
                      args.arr.size() > 2 ? &args.arr[2] : nullptr);
  } else if (op == "query_logs") {
    store.query(args.arr.empty() ? JV{} : args.arr[0], res);
  } else if (op == "get_log") {
    long long id = args.arr.empty() ? 0 : args.arr[0].as_int();
    if (!store.get_log(id, res)) res = "null";
  } else if (op == "revision") {
    jint(res, store.revision());
  } else if (op == "subscribe") {
    long long after = args.arr.empty() ? 0 : args.arr[0].as_int();
    long long cap = args.arr.size() > 1 ? args.arr[1].as_int() : 4096;
    int sfd = ::dup(fd);
    if (sfd < 0) {
      out += ",\"e\":\"subscribe: dup failed\"}\n";
      return;
    }
    // registered (buffering) now; the caller sends the ack in `out`
    // FIRST and only then starts the pusher — frames never precede it
    pending_sub = store.subscribe(rid, after, cap, sfd, wmu, res);
  } else if (op == "unsubscribe") {
    long long sid = args.arr.empty() ? -1 : args.arr[0].as_int();
    bool found = false;
    for (auto& s : conn_subs) {
      if (s->sid != sid) continue;
      found = true;
      std::lock_guard<std::mutex> lk(s->mu);
      s->closed = true;  // pusher exits and closes its dup fd
      s->cv.notify_all();
    }
    conn_subs.erase(
        std::remove_if(conn_subs.begin(), conn_subs.end(),
                       [&](const std::shared_ptr<Subscriber>& s) {
                         return s->sid == sid;
                       }),
        conn_subs.end());
    res = found ? "true" : "false";
  } else if (op == "tail_snapshot") {
    store.tail_snapshot(args.arr.empty() ? 0 : args.arr[0].as_int(), res);
  } else if (op == "age_out") {
    double now = args.arr.empty() ? (double)time(nullptr)
                                  : args.arr[0].as_dbl();
    jint(res, store.age_out(now));
  } else if (op == "tier_info") {
    store.tier_info(res);
  } else if (op == "logmap") {
    long long n = -1;
    std::string hash;
    if (!args.arr.empty()) {
      n = args.arr[0].as_int();
      hash = arg_s(args, 1);
    }
    store.logmap(n, hash, res);
  } else if (op == "trace_get") {
    store.trace_get(arg_s(args, 0),
                    args.arr.size() > 1 ? args.arr[1].as_int() : 0, res);
  } else if (op == "trace_top") {
    store.trace_top(args.arr.empty() ? 256 : args.arr[0].as_int(), res);
  } else if (op == "trace_stats") {
    store.trace_stats(res);
  } else if (op == "stat_overall") {
    store.stat("", res);
  } else if (op == "stat_day") {
    store.stat(arg_s(args, 0), res);
  } else if (op == "stat_days") {
    store.stat_days(args.arr.empty() ? 0 : args.arr[0].as_int(), res);
  } else if (op == "upsert_node") {
    store.upsert_node(arg_s(args, 0), arg_s(args, 1), arg_b(args, 2));
    res = "null";
  } else if (op == "set_node_alived") {
    store.set_node_alived(arg_s(args, 0), arg_b(args, 1));
    res = "null";
  } else if (op == "get_nodes") {
    store.get_nodes(res);
  } else if (op == "get_node") {
    if (!store.get_node(arg_s(args, 0), res)) res = "null";
  } else if (op == "upsert_account") {
    store.upsert_account(arg_s(args, 0), arg_s(args, 1));
    res = "null";
  } else if (op == "get_account") {
    if (!store.get_account(arg_s(args, 0), res)) res = "null";
  } else if (op == "list_accounts") {
    store.list_accounts(res);
  } else if (op == "delete_account") {
    res = store.delete_account(arg_s(args, 0)) ? "true" : "false";
  } else {
    out += ",\"e\":";
    jesc(out, "unknown op " + op);
    out += "}\n";
    return;
  }
  op_record(op, t0);
  out += ",\"r\":";
  out += res;
  out += "}\n";
}

static void serve_conn(int fd, LogStore* store) {
  bool authed = g_token.empty();
  auto wmu = std::make_shared<std::mutex>();       // serializes ALL writes
  std::vector<std::shared_ptr<Subscriber>> subs;   // this conn's streams
  std::string buf;
  char chunk[65536];
  while (true) {
    ssize_t n = ::recv(fd, chunk, sizeof chunk, 0);
    if (n <= 0) break;
    buf.append(chunk, (size_t)n);
    size_t start = 0;
    bool closing = false;
    while (true) {
      size_t nl = buf.find('\n', start);
      if (nl == std::string::npos) break;
      std::string out;
      std::shared_ptr<Subscriber> pending;
      handle(*store, buf.substr(start, nl - start), authed, out, fd, wmu,
             subs, pending);
      start = nl + 1;
      if (out.empty()) { closing = true; break; }   // protocol violation
      if (!out.empty() && out.back() == '\0') {     // auth refusal
        out.pop_back();
        closing = true;
      }
      {
        std::lock_guard<std::mutex> wl(*wmu);
        size_t off = 0;
        while (off < out.size()) {
          ssize_t w = ::send(fd, out.data() + off, out.size() - off,
                             MSG_NOSIGNAL);
          if (w <= 0) { closing = true; break; }
          off += (size_t)w;
        }
      }
      if (pending) {
        if (closing) {  // ack never made it: tear down, nobody else will
          store->unsubscribe_sub(pending);
          ::close(pending->fd);
        } else {        // ack is on the wire — frames may now follow
          subs.push_back(pending);
          std::thread(sub_pusher, pending, store).detach();
        }
      }
      if (closing) break;
    }
    if (closing) break;
    if (start) buf.erase(0, start);
  }
  // sever this conn's streams: pushers wake on closed, unregister, and
  // close their dup'd fds; ours closes now (peer sees FIN once the
  // last dup goes)
  for (auto& s : subs) {
    std::lock_guard<std::mutex> lk(s->mu);
    s->closed = true;
    s->cv.notify_all();
  }
  ::close(fd);
}

int main(int argc, char** argv) {
  std::string host = "127.0.0.1";
  std::string wal_path;
  bool fsync_per_commit = false;
  int port = 7078;
  size_t retain = 1u << 20;
  size_t hot_days = 0;
  double sweep_s = 0.5;
  for (int i = 1; i < argc; i++) {
    std::string a = argv[i];
    auto next = [&]() -> const char* { return i + 1 < argc ? argv[++i] : ""; };
    if (a == "--host") host = next();
    else if (a == "--port") port = atoi(next());
    else if (a == "--db" || a == "--wal") wal_path = next();
    else if (a == "--retain") retain = (size_t)atoll(next());
    else if (a == "--hot-days") hot_days = (size_t)atoll(next());
    else if (a == "--sweep-interval") sweep_s = atof(next());
    else if (a == "--fsync-per-commit") fsync_per_commit = true;
    else if (a == "--token") g_token = next();
    else if (a == "--token-file") {
      FILE* tf = fopen(next(), "r");
      if (!tf) { fprintf(stderr, "cannot read token file\n"); return 1; }
      char tbuf[4096];
      size_t tn = fread(tbuf, 1, sizeof tbuf, tf);
      if (tn == sizeof tbuf) {
        fprintf(stderr, "token file exceeds %zu bytes\n", sizeof tbuf - 1);
        fclose(tf);
        return 1;
      }
      fclose(tf);
      while (tn && (tbuf[tn - 1] == '\n' || tbuf[tn - 1] == '\r')) tn--;
      g_token.assign(tbuf, tn);
    }
    else if (a == "--die-with-parent") {
      prctl(PR_SET_PDEATHSIG, SIGKILL);
      if (getppid() == 1) return 1;
    }
    else if (a == "--help") {
      printf("cronsun-logd --host H --port P [--db FILE] [--retain N] "
             "[--hot-days D] [--sweep-interval S] [--fsync-per-commit] "
             "[--token T | --token-file F] [--die-with-parent]\n");
      return 0;
    }
  }
  // the tiering rollback switch (logsink/joblog.py honors the same):
  // day aging off, everything stays in the retain-bounded deque
  const char* tier_env = getenv("CRONSUN_TIERING");
  if (tier_env && (!strcmp(tier_env, "off") || !strcmp(tier_env, "0") ||
                   !strcmp(tier_env, "false")))
    hot_days = 0;
  signal(SIGPIPE, SIG_IGN);

  int lfd = ::socket(AF_INET, SOCK_STREAM, 0);
  int one = 1;
  setsockopt(lfd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons((uint16_t)port);
  if (inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
    fprintf(stderr, "bad host %s\n", host.c_str());
    return 1;
  }
  if (bind(lfd, (sockaddr*)&addr, sizeof addr) != 0) {
    perror("bind");
    return 1;
  }
  if (listen(lfd, 512) != 0) {
    perror("listen");
    return 1;
  }
  static LogStore store(retain, hot_days);
  if (!wal_path.empty()) {
    std::string err;
    if (!store.open_wal(wal_path, err, fsync_per_commit)) {
      fprintf(stderr, "wal: %s\n", err.c_str());
      return 1;
    }
  }
  socklen_t alen = sizeof addr;
  getsockname(lfd, (sockaddr*)&addr, &alen);
  printf("READY %s:%d\n", host.c_str(), (int)ntohs(addr.sin_port));
  fflush(stdout);
  std::thread([&] {
    while (true) {
      std::this_thread::sleep_for(std::chrono::duration<double>(sweep_s));
      store.sweep();
      // day aging rides the sweeper: O(1) when nothing aged (the walk
      // stops at the first record still inside the hot window)
      store.age_out((double)time(nullptr));
    }
  }).detach();

  while (true) {
    int fd = ::accept(lfd, nullptr, nullptr);
    if (fd < 0) continue;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    std::thread(serve_conn, fd, &store).detach();
  }
}
