"""Leased metrics snapshots — the fleet-wide observability protocol.

Every component (scheduler, agent) periodically puts a JSON snapshot
under ``/metrics/<component>/<instance>`` bound to a short lease, so a
dead publisher's numbers expire instead of going stale; any web server
renders the whole keyspace as Prometheus text at ``/v1/metrics``.  This
module is THE publish protocol — one place for the
keepalive-or-regrant lease dance, the ttl sizing and the
failure-must-not-stall-the-caller rule.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import Callable, Dict, Optional

from . import log
from .core import Keyspace


class OpStats:
    """Per-op server-side timing/count aggregation behind one lock:
    op -> [count, total_ns, max_ns].  The shared primitive behind both
    stores' ``op_stats`` surfaces (memstore's claim/put/watch timings
    and the result store's create/query timings), so their snapshot
    shape — and the ``/v1/metrics`` rendering built on it — cannot
    drift between the two."""

    __slots__ = ("_ns", "_lock")

    def __init__(self):
        self._ns: Dict[str, list] = {}
        self._lock = threading.Lock()

    def record(self, op: str, t0_ns: int) -> None:
        dt = time.perf_counter_ns() - t0_ns
        with self._lock:
            ent = self._ns.get(op)
            if ent is None:
                self._ns[op] = [1, dt, dt]
            else:
                ent[0] += 1
                ent[1] += dt
                if dt > ent[2]:
                    ent[2] = dt

    def count(self, op: str, n: int = 1) -> None:
        """Count-only stat (no timing): contention ticks, frame/event
        tallies, per-record tallies under a bulk op."""
        with self._lock:
            ent = self._ns.get(op)
            if ent is None:
                self._ns[op] = [n, 0, 0]
            else:
                ent[0] += n

    def snapshot(self) -> dict:
        """{op: {count, total_ms, max_ms}} — the op_stats wire shape."""
        with self._lock:
            return {op: {"count": c, "total_ms": round(t / 1e6, 3),
                         "max_ms": round(m / 1e6, 3)}
                    for op, (c, t, m) in self._ns.items()}


def percentile(ordered: list, p: float) -> float:
    """The ``p`` quantile of an ascending list; 0.0 of an empty one."""
    if not ordered:
        return 0.0
    return ordered[min(len(ordered) - 1, int(p * len(ordered)))]


class LatencyRing:
    """Bounded ring of recent latency samples with percentile reads —
    the shared primitive behind every ``*_p50_ms``/``*_p99_ms`` gauge
    (step cycle, device plan, per-phase spans, pipeline stage times).
    Appends are GIL-atomic list ops, so a producer thread (the step
    loop or the pipeline's build worker) never contends with the
    metrics snapshot reader."""

    __slots__ = ("cap", "_v")

    def __init__(self, cap: int = 128):
        self.cap = cap
        self._v: list = []

    def add(self, v: float) -> None:
        self._v.append(float(v))
        if len(self._v) > self.cap:
            del self._v[:-self.cap]

    def clear(self) -> None:
        self._v = []

    def __len__(self) -> int:
        return len(self._v)

    def values(self) -> list:
        return list(self._v)

    def percentile(self, p: float) -> float:
        return percentile(sorted(self._v), p)

    def sum(self) -> float:
        return float(sum(self._v))


# the leaf each thread has open, ``<layer>.<name>``: the pause accounts
# put a compile or a collector pass under the span it landed in
_open = threading.local()


def open_leaf() -> str:
    """The ``<layer>.<name>`` of the :class:`Spans` block open on the
    calling thread (a ring-only holder's too), or ``none``."""
    return getattr(_open, "leaf", None) or "none"


def leaf_key(leaf: str) -> str:
    """A leaf as snapshot keys name it: its ``<name>``, as the
    ``step_span_<name>_*`` gauges name its ring."""
    return leaf.rpartition(".")[2]


class _Span:
    """One timed block of a :class:`Spans` holder; ``ms`` is set when
    the block ends."""

    __slots__ = ("_holder", "_key", "_into", "_note", "_t0", "_leaf",
                 "_prev", "ms")

    def __init__(self, holder, key, into, note, since, leaf):
        self._holder, self._key, self._into = holder, key, into
        self._note, self._t0, self.ms = note, since, 0.0
        self._leaf = leaf

    def __enter__(self):
        if self._note is not None:
            self._note.__enter__()
        self._prev = getattr(_open, "leaf", None)
        _open.leaf = self._leaf
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (time.perf_counter() - self._t0) * 1e3
        _open.leaf = self._prev
        if self._note is not None:
            self._note.__exit__(*exc)
        if self._into is not None:
            self._into[self._key] = self._into.get(self._key, 0.0) + self.ms
        else:
            self._holder.ring(self._key).add(self.ms)
        return False


class Spans:
    """The one timing primitive of a component's phases.  ``with
    spans.span(name, **ids):`` adds the block's duration (ms) to the
    :class:`LatencyRing` of that name and, when the holder was built
    with an annotation factory, shows the block on the profiler's
    timeline as ``cronsun.<layer>.<name>`` with ``ids`` as its event
    stats — the same clock the device trace is on.  The scheduler
    passes ``jax.profiler.TraceAnnotation``; this module never imports
    JAX (agents, store, logd and web import it), and a holder without
    a factory only times.

    ``with`` blocks sit at step / window / batch granularity and are
    LEAVES: at most one open per thread (a trace reduction joins every
    overlapping name into the label of an idle gap, so an enclosing
    span would sit on every label).  Per fire there is no block: the
    agent stamps ``time.perf_counter()`` where an execution changes
    hands, folds the differences into a dict carried on the task and
    hands it to ``commit`` ONCE when the execution is over, under the
    agent's own lock (``node/agent.py`` STAGES, ``_task_done``) — a
    ring's append is safe from one producer, and there the producers
    are 64 pool threads.  Nothing is timed per order or per key.
    While a block is open, :func:`open_leaf` names it on its thread.

    ``ring`` names the ring where it differs from the span's name;
    ``into`` collects the duration in a dict instead (summed per name:
    the step commits its spans at its end, a standby's are dropped);
    ``since`` is a ``time.perf_counter()`` reading to measure from
    where the work began on another thread."""

    def __init__(self, layer: str, annotate: Optional[Callable] = None,
                 rings: Optional[Dict[str, LatencyRing]] = None):
        self.layer = layer
        self.rings: Dict[str, LatencyRing] = {} if rings is None else rings
        self._annotate = annotate

    def ring(self, name: str) -> LatencyRing:
        ring = self.rings.get(name)
        if ring is None:
            ring = self.rings[name] = LatencyRing()
        return ring

    def span(self, name: str, ring: Optional[str] = None,
             into: Optional[dict] = None, since: Optional[float] = None,
             **ids) -> _Span:
        note = (self._annotate(f"cronsun.{self.layer}.{name}", **ids)
                if self._annotate is not None else None)
        return _Span(self, ring or name, into, note, since,
                     f"{self.layer}.{name}")

    def commit(self, spans: Dict[str, float]) -> None:
        for name, ms in spans.items():
            self.ring(name).add(ms)


class CollectorPauses:
    """The interpreter's cyclic collector, pass by pass: ONE account a
    process, fed by a ``gc.callbacks`` hook (:meth:`install`).  Each
    pass is timed between its ``start`` and ``stop`` callbacks on the
    thread that ran it, which holds the interpreter throughout, so
    every other thread of the process waited as long.  Kept: passes
    and ms per generation, and the ms of full (generation-2) passes by
    the leaf open on that thread (:func:`open_leaf`).

    Installed with an annotation factory (the scheduler's), a full
    pass is also ``cronsun.gc.full`` on the profiler's timeline, with
    the objects of the oldest generation as its id ``objects`` (counted
    only while the factory's ``is_enabled()`` says a trace is being
    recorded: the count walks the heap).  It is the one annotation that
    nests inside an open leaf, so an idle gap of the device under it
    reads ``cronsun.gc.full + cronsun.step.<leaf>``.  Young passes come
    thousands of times a second and get totals only."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.ms = [0.0, 0.0, 0.0]
        self.full_ms: Dict[str, float] = {}     # leaf -> ms of full passes
        self.annotate: Optional[Callable] = None
        self._t0 = 0.0
        self._note = None
        self._installed = False

    def install(self, annotate: Optional[Callable] = None) -> None:
        """Hook the collector (once a process); ``annotate`` replaces the
        factory where given."""
        if annotate is not None:
            self.annotate = annotate
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_pass)

    def _on_pass(self, phase, info, _clock=time.perf_counter):
        if phase == "start":
            if info["generation"] == 2 and self.annotate is not None:
                self._open_note()
            self._t0 = _clock()
            return
        ms = (_clock() - self._t0) * 1e3
        gen = info["generation"]
        self.passes[gen] += 1
        self.ms[gen] += ms
        if gen == 2:
            leaf = open_leaf()
            self.full_ms[leaf] = self.full_ms.get(leaf, 0.0) + ms
            note, self._note = self._note, None
            if note is not None:
                note.__exit__(None, None, None)

    def _open_note(self):
        annotate = self.annotate
        enabled = getattr(annotate, "is_enabled", None)
        ids = ({"objects": len(gc.get_objects(2))}
               if enabled is None or enabled() else {})
        self._note = annotate("cronsun.gc.full", **ids)
        self._note.__enter__()

    def pause_ms(self) -> float:
        """ms of every pass so far, all generations."""
        return sum(self.ms)

    def totals(self) -> Dict[str, float]:
        """The account so far, flat: ``pause_ms`` (every generation),
        ``full_passes``, ``full_ms`` and ``full_ms_<leaf key>``."""
        out = {"pause_ms": sum(self.ms), "full_ms": self.ms[2],
               "full_passes": self.passes[2]}
        # a copy in one C call: a pass from another thread may add a leaf
        for leaf, ms in dict(self.full_ms).items():
            key = "full_ms_" + leaf_key(leaf)
            out[key] = out.get(key, 0.0) + ms
        return out


# the process's one collector account
gc_pauses = CollectorPauses()


class Gains:
    """What a growing counter dict (``read()``) gained over the
    stretches a condition held: ``poll(held)`` adds the gain since the
    previous poll when ``held`` says the condition held over it."""

    def __init__(self, read: Callable[[], Dict[str, float]]):
        self._read = read
        self._last = read()
        self.total: Dict[str, float] = {}

    def poll(self, held: bool) -> None:
        now = self._read()
        if held:
            last, total = self._last, self.total
            for key, v in now.items():
                gain = v - last.get(key, 0)
                if gain:
                    total[key] = total.get(key, 0) + gain
        self._last = now


def process_age_s() -> Optional[float]:
    """Seconds since the OS started this process (``starttime`` of
    ``/proc/self/stat`` against ``/proc/uptime``; 10 ms ticks), or None
    where there is no ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        # field 22 of the line; the split starts at field 3 (state)
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        return max(0.0, uptime - started)
    except (OSError, ValueError, IndexError):
        return None


class PhaseClock:
    """Consecutive phases of one start-up on one clock: ``mark(name)``
    gives the time since the previous mark (or the start) to ``name``,
    so the phases tile the stretch from the start to the last mark and
    nothing in it goes unnamed.  ``from_process_start`` starts the
    clock where the OS started the process — before the interpreter,
    the imports and any launcher."""

    def __init__(self, age_s: float = 0.0):
        self._last = self.t0 = time.monotonic() - age_s
        self.seconds: Dict[str, float] = {}

    @classmethod
    def from_process_start(cls) -> "PhaseClock":
        return cls(process_age_s() or 0.0)

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return self._last - self.t0


def parse_exposition(text: str):
    """Small Prometheus text-exposition parser used by the metrics
    smoke tests (and anything that wants to machine-check /v1/metrics).
    Returns {(name, frozenset(label items)): float}; raises ValueError
    on any line that does not parse or any duplicate
    (metric, label-set) series."""
    import re
    series: Dict[tuple, float] = {}
    line_rx = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(-?[0-9.eE+-]+|'
        r'[+-]?Inf|NaN)$')
    lbl_rx = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        m = line_rx.match(ln)
        if not m:
            raise ValueError(f"unparseable exposition line: {ln!r}")
        name, labels_s, val = m.groups()
        labels = {}
        if labels_s:
            consumed = 0
            for lm in lbl_rx.finditer(labels_s):
                if lm.start() != consumed:
                    # unmatched bytes BETWEEN pairs (or before the
                    # first) must fail too, not just trailing ones
                    raise ValueError(
                        f"bad label section in: {ln!r}")
                labels[lm.group(1)] = lm.group(2)
                consumed = lm.end()
                if consumed < len(labels_s):
                    if labels_s[consumed] != ",":
                        raise ValueError(
                            f"bad label separator in: {ln!r}")
                    consumed += 1
            if consumed < len(labels_s):
                raise ValueError(f"trailing label garbage in: {ln!r}")
        key = (name, frozenset(labels.items()))
        if key in series:
            raise ValueError(
                f"duplicate series {name}{{{labels_s or ''}}}")
        series[key] = float(val)
    return series


class MetricsPublisher:
    def __init__(self, store, ks: Keyspace, component: str, instance: str,
                 snapshot_fn: Callable[[], dict], interval_s: float = 10.0,
                 clock: Callable[[], float] = time.time):
        self.store = store
        self.key = ks.metrics_key(component, instance)
        self.snapshot_fn = snapshot_fn
        self.interval_s = interval_s
        self.clock = clock
        self._lease: Optional[int] = None
        self._next_at = 0.0

    def maybe_publish(self):
        """Publish if the interval elapsed; errors are logged, never
        raised — metrics must not stall the caller's loop."""
        if self.clock() < self._next_at:
            return
        try:
            if self._lease is None or not self.store.keepalive(self._lease):
                self._lease = self.store.grant(self.interval_s * 3 + 5)
            self.store.put(self.key,
                           json.dumps(self.snapshot_fn(),
                                      separators=(",", ":")),
                           lease=self._lease)
        except Exception as e:  # noqa: BLE001
            log.warnf("metrics publish for %s failed: %s", self.key, e)
            self._lease = None
        self._next_at = self.clock() + self.interval_s

    def revoke(self):
        """Withdraw the snapshot immediately (clean shutdown) — the
        metrics surface must not keep rendering a gone component for the
        remaining lease TTL."""
        if self._lease is not None:
            try:
                self.store.revoke(self._lease)
            except Exception:  # noqa: BLE001 — best effort on the way out
                pass
            self._lease = None
