"""TickPlanner: the device-resident scheduling state + one-call tick plan.

This is the TPU replacement for the reference's entire per-node hot loop
(node/cron/cron.go:210-275): instead of N nodes each sorting entries and
walking ``Schedule.Next`` per job, one planner holds ALL jobs' compiled
schedules, the bitpacked eligibility matrix, per-node loads and capacities on
device, and answers "who fires this second, and where does each run" in a
single fused dispatch chain:

    fire_mask [J] -> compact fired rows into a fixed bucket [K] ->
    capacity-constrained waterfill assign on the bucket -> scatter back [J]

Compaction is the key asymmetry: fire rates are sparse (a second matches few
schedules), so the expensive [K, N] solve runs on the fired bucket, not all
J rows.  Bucket sizes snap to powers of two so XLA compiles a handful of
variants, never per-tick.

State updates (job churn, node churn, load decay, completed executions) are
in-place scatters at fixed shapes — no recompiles.
"""

from __future__ import annotations

import dataclasses
import threading
from datetime import timezone
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .assign import _assign_excl, _fanout_load, assign
from .schedule_table import ScheduleTable, build_table
from .tick import fire_mask

_UTC = timezone.utc

def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


_CB = 256   # compact block width


@partial(jax.jit, static_argnames=("k",))
def _compact(fire: jax.Array, k: int):
    """Indices of up to k fired jobs + validity mask + overflow count.

    NOT ``jnp.nonzero``: XLA lowers nonzero-with-size through a full sort
    of all J rows (~9 ms/tick at 1M on v5e — measured, it dominated the
    plan step).  Two-level counting instead: per-block fire counts + a
    short block-level cumsum locate each output's block by binary search;
    a [k, block] gather + row-wise running count finds the exact element.
    Sort-free, no J-length cumsum, identical output order to nonzero."""
    J = fire.shape[0]
    if J % _CB:
        # small/odd tables: plain cumsum + searchsorted (still sort-free)
        total = jnp.sum(fire.astype(jnp.int32))
        counts = jnp.cumsum(fire.astype(jnp.int32))
        t = jnp.arange(1, k + 1, dtype=jnp.int32)
        idx = jnp.searchsorted(counts, t, side="left").astype(jnp.int32)
        valid = t <= total
        return jnp.where(valid, idx, 0), valid, total
    nb = J // _CB
    f2 = fire.reshape(nb, _CB).astype(jnp.int32)
    bcum = jnp.cumsum(f2.sum(axis=1))                       # [nb]
    total = bcum[-1]
    t = jnp.arange(1, k + 1, dtype=jnp.int32)
    blk = jnp.minimum(jnp.searchsorted(bcum, t, side="left"),
                      nb - 1).astype(jnp.int32)             # [k]
    rows = f2[blk]                                          # [k, _CB]
    rcum = jnp.cumsum(rows, axis=1)
    prev = jnp.where(blk > 0, bcum[jnp.maximum(blk - 1, 0)], 0)
    tin = (t - prev)[:, None]
    off = jnp.sum((rcum < tin).astype(jnp.int32), axis=1)
    idx = blk * _CB + off
    valid = t <= total
    return jnp.where(valid, idx, 0), valid, total


@partial(jax.jit, static_argnames=("kx", "kc", "rounds", "impl",
                                   "use_deps", "use_tenants"),
         donate_argnames=("load", "rem_cap", "dep_last_fire"))
def _plan_window_step(table: ScheduleTable, fields_w, elig, exclusive, cost,
                      load, rem_cap, dep_succ, dep_fail, dep_block,
                      dep_last_fire, kx: int, kc: int, rounds: int,
                      impl: str, use_deps: bool,
                      tn_perm=None, tn_sorted=None, tn_segbase=None,
                      tb_rate=None, tb_burst=None, tb_limited=None,
                      tb_weight=None, tb_tokens=None,
                      use_tenants: bool = False):
    """W seconds in one dispatch: lax.scan over the window, exactly the
    semantics of W consecutive single ticks (load/capacity carry through),
    but one dispatch + one fetch — the host round-trip amortizes over the
    window.  This is how the production loop plans ahead of wall-clock
    (window [t+1, t+W] is solved while t executes).

    Two latency asymmetries exploited:
    - the fire mask for ALL W seconds is one fused pass before the scan —
      the schedule table (the big [J]-width read) streams from HBM once
      per window, not once per second;
    - fired jobs compact into SEPARATE buckets by kind: only exclusive
      fires (bucket kx) pay the ``rounds``x [K, N] bid sweep; Common
      fires (bucket kc) need exactly one fan-out pass for their load.

    ``use_deps`` (static) folds the workflow-DAG trigger into the same
    scan: per second, one masked gather over the padded dep matrix ORs
    dep fires into the time fires, and the carried ``dep_last_fire``
    advances so a row fires once per upstream round.  False compiles the
    dep ops OUT — a dep-free table runs the exact pre-DAG program (the
    differential test pins bit-identity).

    ``use_tenants`` (static) folds per-tenant token-bucket admission in
    after the dep OR (ops/tenancy.py): refill + rank + clamp per second,
    the ``tb_tokens`` column carried through the scan, per-tenant
    throttle/shed counts a third scan output.  False compiles ALL of it
    out — carry, outputs and every tenant operand vanish from the
    lowered module (they default to None), so a tenant-free table runs
    the exact pre-tenancy program (pinned like the dep test).

    The herd-smearing ``table.jitter`` column never appears in this
    function: plans are built at logical seconds and the deterministic
    per-fire shift is applied by the scheduler host at emission, so
    jitter needs no static arm at all — the unused leaf is pruned by
    jit and the lowered module is identical with or without it (pinned
    in tests/test_jitter.py)."""
    from .tick import _fire_mask_jit
    cols = [fields_w[:, i] for i in range(7)]
    t_rel_w = fields_w[:, 6]
    with jax.named_scope("cronsun.fire_mask"):
        fire_w = _fire_mask_jit(table, *cols)              # [J, W]

    # assigned rides int16 when node columns fit: it halves that output's
    # bytes, and the host fetches both arrays in one device_get
    n_cols = elig.shape[1] * 32
    adt = jnp.int16 if n_cols <= 32767 else jnp.int32

    def body(carry, xs):
        if use_tenants:
            load, rem_cap, last_fire, tokens = carry
        else:
            load, rem_cap, last_fire = carry
        fire_col, t_rel = xs
        time_col = fire_col
        dep_f = dep_consume = round_max = None
        if use_deps:
            with jax.named_scope("cronsun.deps"):
                from .deps import dep_ready
                dep_f, dep_consume, round_max = dep_ready(
                    table, dep_succ, dep_fail, dep_block, last_fire)
                fire_col = fire_col | dep_f
        if use_tenants:
            with jax.named_scope("cronsun.tenants"):
                from .tenancy import admit
                admitted, tokens, thr_t, shed_t = admit(
                    fire_col, time_col, exclusive, tokens, tb_rate,
                    tb_burst, tb_limited, tb_weight, rem_cap,
                    tn_perm, tn_sorted, tn_segbase, tb_rate.shape[0])
                fire_col = fire_col & admitted
        if use_deps:
            # advance to the newest consumed upstream epoch, not just
            # the tick: a round scheduled ahead of the firing tick must
            # not re-satisfy the next window.  A THROTTLED dep fire
            # (admission refused it) does NOT advance — it retries when
            # the bucket refills, late-never-lost like every other gate.
            eff_dep = (dep_f & fire_col) if use_tenants else dep_f
            last_fire = jnp.where(
                eff_dep | dep_consume,
                jnp.maximum(t_rel, round_max), last_fire)
        with jax.named_scope("cronsun.compact"):
            xidx, xvalid, xtotal = _compact(fire_col & exclusive, kx)
            cidx, cvalid, ctotal = _compact(fire_col & ~exclusive, kc)
        with jax.named_scope("cronsun.fanout"):
            load = _fanout_load(elig[cidx], cvalid, cost[cidx], load, impl)
        with jax.named_scope("cronsun.assign"):
            assigned, load, rem_cap = _assign_excl(
                xvalid, elig[xidx], load, rem_cap, cost[xidx], rounds, impl)
        out32 = jnp.concatenate([
            jnp.asarray([xtotal, ctotal], jnp.int32),
            xidx, cidx])                               # [2 + kx + kc]
        if use_tenants:
            return (load, rem_cap, last_fire, tokens), \
                (out32, assigned.astype(adt),
                 jnp.stack([thr_t, shed_t]))           # [2, T]
        return (load, rem_cap, last_fire), (out32, assigned.astype(adt))

    if use_tenants:
        (load, rem_cap, dep_last_fire, tb_tokens), \
            (outs32, outs16, outs_t) = jax.lax.scan(
                body, (load, rem_cap, dep_last_fire, tb_tokens),
                (fire_w.T, t_rel_w))
    else:
        (load, rem_cap, dep_last_fire), (outs32, outs16) = \
            jax.lax.scan(body, (load, rem_cap, dep_last_fire),
                         (fire_w.T, t_rel_w))
        outs_t = tb_tokens = None
    return outs32, outs16, outs_t, load, rem_cap, dep_last_fire, tb_tokens


class _AdaptiveBucket:
    """Adaptive fired-bucket size: ~1.3x headroom over the last observed
    fire count (overflowed ticks bounce back because ``feed`` reports the
    true total, not the truncated bucket).  Grows immediately; shrinks
    only after 300 consecutive smaller ticks (seconds of planned time,
    regardless of window size), so the bucket — and the compiled plan
    step — doesn't flap (a bucket change recompiles, ~20s on TPU)."""

    def __init__(self, max_bucket: int, cap: int):
        self.max_bucket = max_bucket
        self.cap = cap
        self.last_total = max_bucket
        self.cur_k = 0
        self._shrink_streak = 0
        self._ticks_pending = 0
        # sizes this bucket has already run at: shrinking BACK to one is
        # free (its executable is cached), so the hysteresis only gates
        # shrinks to never-seen sizes.  Without this, one cron-herd
        # minute boundary pins the bucket at its burst size for 300
        # planned seconds and every steady window pays the burst-sized
        # output fetch (~10 MB/window at a herd-sized bucket).
        self.seen: set = set()

    def feed(self, total: int, ticks: int):
        self.last_total = total
        self._ticks_pending += ticks

    def _want(self) -> int:
        """~1.3x headroom over the last observed fire count, snapped to
        a power of two within [2048, min(max_bucket->pow2, cap)] — THE
        sizing formula, shared by size() and peek() so a standby's
        warm-compile always targets the executable a fresh leader's
        first plan will actually request."""
        want = max(2048, self.last_total + (self.last_total >> 2)
                   + (self.last_total >> 4))
        return min(_next_pow2(min(want, self.max_bucket)), self.cap)

    def peek(self) -> int:
        """The size the next ``size(None)`` call would return, without
        mutating the hysteresis state (standby warm-compile)."""
        return self.cur_k or self._want()

    def size(self, sla: Optional[int]) -> int:
        if sla is not None:
            # an explicit SLA is a true override, clamped only by the
            # structural cap (J): the scheduler's overflow re-plan
            # escalates PAST max_bucket so a burst second becomes
            # latency, never loss — and multi-host workers, which
            # receive the sla via the broadcast header, clamp
            # identically without sharing max_bucket state
            return min(_next_pow2(sla), self.cap)
        ticks = max(1, self._ticks_pending)
        self._ticks_pending = 0
        want = self._want()
        if not self.cur_k or want > self.cur_k:
            self.cur_k = want
            self._shrink_streak = 0
        elif want < self.cur_k:
            self._shrink_streak += ticks
            if want in self.seen or self._shrink_streak >= 300:
                self.cur_k = want
                self._shrink_streak = 0
        else:
            self._shrink_streak = 0
        self.seen.add(self.cur_k)
        return self.cur_k


@dataclasses.dataclass
class TickPlan:
    """Result of one planning step (host-side views)."""
    epoch_s: int
    fired: np.ndarray        # [F] job rows that fired (valid entries)
    assigned: np.ndarray     # [F] node column for exclusive jobs, -1 for
                             #     Common (fan-out) or no-capacity skips
    overflow: int            # fired jobs beyond the bucket SLA (absent
                             #     from `fired`; the scheduler re-plans
                             #     the second with an escalated bucket)
    total_fired: int = 0     # TRUE fire count this second (>= len(fired);
                             #     sizes the escalation re-plan)
    n_excl: int = 0          # fired[:n_excl] are the exclusive
                             #     placements (assigned valid);
                             #     fired[n_excl:] are Common fan-outs —
                             #     dispatchers iterate each half without
                             #     a per-fire kind branch
    # multi-tenant admission: per-tenant-id refusal counts this second
    # (None on tenant-free tables — the ops are compiled out).
    # throttled = all refused fires; shed = the time-triggered subset
    # (permanently dropped; throttled dep fires retry next tick).
    tenant_throttled: Optional[np.ndarray] = None   # [T] int32
    tenant_shed: Optional[np.ndarray] = None        # [T] int32


class TickPlanner:
    """Owns device state; call :meth:`plan` once per second (or window).

    Capacity model: ``rem_cap[n]`` is the node's remaining concurrency
    budget for *exclusive* placements.  The solve reserves a slot at plan
    time (rem_cap decremented inside assign); executors release it with
    :meth:`job_finished` at completion — the batched analogue of the
    reference's in-process Parallels accounting (job.go:165-187).
    Common-kind fan-out runs never consume rem_cap; they contribute load
    only (via the fanout kernel at plan time, released with
    :meth:`common_finished`).
    """

    def __init__(self, job_capacity: int, node_capacity: int,
                 tz=_UTC, rounds: int = 2, impl: str = "auto",
                 max_fire_bucket: int = 65536,
                 tenant_capacity: int = 64):
        # rounds=2 (one waterfill-quota round + one capacity-final round)
        # is the latency/balance sweet spot on v5e: each extra round costs
        # ~5 ms/tick at 10k nodes for marginal placement-spread gains.
        # The reference has NO load balancing at all (lock races,
        # job.go:243-271), so even rounds=1 dominates it on balance.
        self.tz = tz
        self.impl = impl
        self.rounds = rounds
        self.max_fire_bucket = max_fire_bucket
        self.J = _next_pow2(job_capacity)
        self.N = ((node_capacity + 31) // 32) * 32
        self.table: ScheduleTable = build_table([], capacity=self.J)
        self.elig = jnp.zeros((self.J, self.N // 32), jnp.uint32)
        self.exclusive = jnp.zeros(self.J, bool)
        self.cost = jnp.ones(self.J, jnp.float32)
        self.load = jnp.zeros(self.N, jnp.float32)
        self.rem_cap = jnp.zeros(self.N, jnp.int32)   # dead columns stay 0
        # workflow DAG state: per-row latest-round epochs (monotone max
        # fold of dep/ completion events), the last-fire vector the scan
        # carries, and the host-computed max_in_flight gate.  The dep
        # ops stay compiled OUT (use_deps static arg) until the
        # scheduler installs the first dep row — dep-free tables run the
        # exact pre-DAG program.
        from .deps import NEVER
        self.dep_succ = jnp.full(self.J, NEVER, jnp.int32)
        self.dep_fail = jnp.full(self.J, NEVER, jnp.int32)
        self.dep_last_fire = jnp.zeros(self.J, jnp.int32)
        self.dep_block = jnp.zeros(self.J, bool)
        self._dep_enabled = False
        # multi-tenant admission state: per-tenant token-bucket columns
        # (rate/burst/limited scattered from quota records, tokens
        # carried through the window scan) and the host row->tenant
        # snapshot the admission permutation derives from.  Compiled
        # OUT (use_tenants static arg) until the scheduler arms it —
        # tenant-free tables run the exact pre-tenancy program.
        self.T = _next_pow2(max(2, tenant_capacity))
        self.tb_rate = jnp.zeros(self.T, jnp.float32)
        self.tb_burst = jnp.zeros(self.T, jnp.float32)
        self.tb_limited = jnp.zeros(self.T, bool)
        self.tb_weight = jnp.ones(self.T, jnp.float32)
        self.tb_tokens = jnp.zeros(self.T, jnp.float32)
        self._tenants_enabled = False
        self._tenant_np = np.zeros(self.J, np.int32)
        self._tn_dirty = True
        self._tn_perm = self._tn_sorted = self._tn_segbase = None
        # Adaptive fired-buckets (one per kind — exclusive fires pay the
        # bid rounds, Common fires only the fan-out): sized from the last
        # observed fire count so quiet tables don't pay the max-SLA solve.
        self._bx = _AdaptiveBucket(max_fire_bucket, self.J)
        self._bc = _AdaptiveBucket(max_fire_bucket, self.J)
        # Double-buffered handles: the scheduler DISPATCHES window N+1
        # (plan_window_async, step thread) while window N is still being
        # GATHERED on the pipeline's build worker.  Each handle freezes
        # its own (kx, kc), so a later bucket resize never corrupts an
        # in-flight gather; this lock is only for the adaptive buckets'
        # hysteresis counters, which the two threads would otherwise
        # read-modify-write concurrently.
        self._bucket_mu = threading.Lock()
        # single-second bucket sizes warmed by warm_escalation: overflow
        # replans snap UP to one of these so a herd burst hits a cached
        # executable instead of compiling mid-step
        self._warmed_single: set = set()

    # -- state maintenance (all fixed-shape scatters) ----------------------

    def set_table(self, table: ScheduleTable):
        if table.capacity != self.J:
            raise ValueError(f"table capacity {table.capacity} != {self.J}")
        self.table = table

    def block_until_ready(self) -> None:
        """Wait until the device holds every update scattered so far
        (the setters below dispatch asynchronously)."""
        jax.block_until_ready((self.table, self.elig, self.exclusive,
                               self.cost, self.load, self.rem_cap))

    def update_table_rows(self, rows: np.ndarray, vals) -> None:
        """Scatter schedule-row updates — the planner-agnostic mutator
        the scheduler (and the mesh-sync replay) drive; subclasses
        re-pin sharding in their set_table."""
        from .schedule_table import update_rows
        self.set_table(update_rows(self.table, rows, vals))

    def set_load(self, loads: np.ndarray) -> None:
        self.load = jnp.asarray(np.asarray(loads, np.float32))

    def set_eligibility_rows(self, rows: np.ndarray, values: np.ndarray):
        if len(rows):
            self.elig = self.elig.at[jnp.asarray(rows)].set(jnp.asarray(values))

    def set_job_meta(self, rows: np.ndarray, exclusive: np.ndarray,
                     cost: np.ndarray):
        if len(rows):
            r = jnp.asarray(np.asarray(rows, np.int32))
            self.exclusive = self.exclusive.at[r].set(jnp.asarray(exclusive))
            self.cost = self.cost.at[r].set(jnp.asarray(cost, ).astype(jnp.float32))

    def set_node_capacity(self, cols: Sequence[int], caps: Sequence[int]):
        if len(cols):
            c = jnp.asarray(np.asarray(cols, np.int32))
            self.rem_cap = self.rem_cap.at[c].set(
                jnp.asarray(np.asarray(caps, np.int32)))

    # -- workflow DAG state (scheduler-driven scatters) --------------------

    @property
    def dep_enabled(self) -> bool:
        return self._dep_enabled

    def set_dep_enabled(self, flag: bool = True):
        """Arm (or disarm) the dep ops in the plan program.  Flipping
        recompiles the window executable once (a static jit arg) — the
        scheduler arms it when the first dep row lands and leaves it on
        (disarming mid-flight would churn executables for no win)."""
        self._dep_enabled = bool(flag)

    def set_dep_epochs(self, rows, succ, fail):
        """Fold completion-round epochs into the per-row vectors —
        MONOTONE max, so duplicate watch deliveries, multi-node Common
        completions of one round and pad_pow2's repeated rows are all
        idempotent."""
        if len(rows):
            r = jnp.asarray(np.asarray(rows, np.int32))
            self.dep_succ = self.dep_succ.at[r].max(
                jnp.asarray(np.asarray(succ, np.int32)))
            self.dep_fail = self.dep_fail.at[r].max(
                jnp.asarray(np.asarray(fail, np.int32)))

    def reset_dep_rows(self, rows, last_fire_rel=0):
        """Row (re)initialization: epochs back to NEVER and last_fire to
        the registration anchor (a fresh dep row only reacts to upstream
        rounds NEWER than its registration — an upstream success from an
        hour ago must not fire a just-created chain)."""
        if len(rows):
            from .deps import NEVER
            r = jnp.asarray(np.asarray(rows, np.int32))
            self.dep_succ = self.dep_succ.at[r].set(NEVER)
            self.dep_fail = self.dep_fail.at[r].set(NEVER)
            self.dep_last_fire = self.dep_last_fire.at[r].set(
                jnp.asarray(np.asarray(last_fire_rel, np.int32)))
            self.dep_block = self.dep_block.at[r].set(False)

    def set_dep_block(self, rows, vals):
        """max_in_flight saturation gate (host-computed per step)."""
        if len(rows):
            r = jnp.asarray(np.asarray(rows, np.int32))
            self.dep_block = self.dep_block.at[r].set(
                jnp.asarray(np.asarray(vals, bool)))

    def dep_state(self) -> dict:
        """Host copies of the mutable dep vectors (checkpoint capture)."""
        return dict(succ=np.asarray(self.dep_succ),
                    fail=np.asarray(self.dep_fail),
                    last_fire=np.asarray(self.dep_last_fire),
                    block=np.asarray(self.dep_block))

    def set_dep_state(self, succ, fail, last_fire, block):
        """Install checkpointed dep vectors whole (restore path)."""
        self.dep_succ = jnp.asarray(np.asarray(succ, np.int32))
        self.dep_fail = jnp.asarray(np.asarray(fail, np.int32))
        self.dep_last_fire = jnp.asarray(
            np.asarray(last_fire, np.int32))
        self.dep_block = jnp.asarray(np.asarray(block, bool))

    # -- multi-tenant admission state (scheduler-driven) -------------------

    @property
    def tenants_enabled(self) -> bool:
        return self._tenants_enabled

    def set_tenants_enabled(self, flag: bool = True):
        """Arm (or disarm) the admission ops in the plan program.  Like
        the dep plane, flipping recompiles the window executable once
        (a static jit arg); the scheduler arms it when the first
        LIMITED tenant quota lands and leaves it on."""
        self._tenants_enabled = bool(flag)

    def set_row_tenants(self, rows, tids):
        """Update the host row->tenant snapshot (the device ``tenant``
        table column rides the normal row scatters; THIS copy feeds the
        admission permutation, recomputed lazily on the next dispatch).
        """
        if len(rows):
            self._tenant_np[np.asarray(rows, np.int32)] = \
                np.asarray(tids, np.int32)
            self._tn_dirty = True

    def set_tenant_quota(self, tid: int, rate: float, burst: float,
                         weight: float = 1.0):
        """Install/refresh one tenant's bucket column.  Tokens reset to
        a FULL bucket (a fresh/raised quota must not inherit a starved
        bucket; a lowered one clamps at the next refill's min)."""
        t = jnp.asarray([int(tid)], jnp.int32)
        limited = rate > 0
        self.tb_rate = self.tb_rate.at[t].set(np.float32(rate))
        self.tb_burst = self.tb_burst.at[t].set(np.float32(burst))
        self.tb_limited = self.tb_limited.at[t].set(bool(limited))
        self.tb_weight = self.tb_weight.at[t].set(
            np.float32(max(weight, 1e-6)))
        self.tb_tokens = self.tb_tokens.at[t].set(
            np.float32(burst if limited else 0.0))

    def clear_tenant_quota(self, tid: int):
        """Quota record deleted: the tenant reverts to unlimited."""
        self.set_tenant_quota(tid, 0.0, 0.0, 1.0)

    def _tenant_args(self):
        """The admission operands for a plan dispatch: a consistent
        device snapshot of (perm, sorted tenant, segment base),
        recomputed host-side only when the row->tenant map changed."""
        if self._tn_dirty:
            from .tenancy import tenant_order
            perm, ts, segbase = tenant_order(self._tenant_np)
            self._tn_perm = jnp.asarray(perm)
            self._tn_sorted = jnp.asarray(ts)
            self._tn_segbase = jnp.asarray(segbase)
            self._tn_dirty = False
        return dict(tn_perm=self._tn_perm, tn_sorted=self._tn_sorted,
                    tn_segbase=self._tn_segbase, tb_rate=self.tb_rate,
                    tb_burst=self.tb_burst, tb_limited=self.tb_limited,
                    tb_weight=self.tb_weight)

    def tenant_state(self) -> dict:
        """Host copies of the mutable tenant vectors (checkpoint
        capture).  Rate/burst/limited re-derive from the quota registry
        the scheduler checkpoints; tokens are the dynamic state."""
        return dict(tokens=np.asarray(self.tb_tokens))

    def set_tenant_state(self, tokens):
        """Install checkpointed token columns whole (restore path)."""
        self.tb_tokens = jnp.asarray(np.asarray(tokens, np.float32))

    def job_finished(self, node_col: int, cost: float):
        """Exclusive execution completed: release the capacity slot the
        solve reserved and retire its load."""
        self.rem_cap = self.rem_cap.at[node_col].add(1)
        self.load = self.load.at[node_col].add(-float(cost))

    def common_finished(self, node_col: int, cost: float):
        """Common (fan-out) execution completed: retire load only — Common
        runs never held a capacity slot."""
        self.load = self.load.at[node_col].add(-float(cost))

    def decay_load(self, factor: float = 0.99):
        self.load = self.load * factor

    def _impl(self, kx: int, kc: int) -> str:
        if self.impl != "auto":
            return self.impl
        from .assign import choose_impl
        return choose_impl(self.N, kx, kc)

    def first_window_impl(self) -> str:
        """The kernel variant the next unpinned window resolves to."""
        with self._bucket_mu:
            return self._impl(self._bx.peek(), self._bc.peek())

    # -- the tick ----------------------------------------------------------

    def plan_async(self, epoch_s: int, sla_bucket: Optional[int] = None):
        """Dispatch one tick (a one-second window).  Does not synchronize —
        callers can pipeline several ticks and materialize with
        :meth:`gather`.  ``plan`` is the sync convenience."""
        return self.plan_window_async(epoch_s, 1, sla_bucket)

    def gather(self, handle) -> TickPlan:
        """Materialize a plan_async result (the single host transfer)."""
        return self.gather_window(handle)[0]

    def plan(self, epoch_s: int, sla_bucket: Optional[int] = None) -> TickPlan:
        """Fire + place every job due at ``epoch_s`` (one-second tick)."""
        return self.gather(self.plan_async(epoch_s, sla_bucket))

    # -- windowed planning -------------------------------------------------

    def plan_window_async(self, epoch_s: int, window_s: int,
                          sla_bucket: Optional[int] = None):
        """Dispatch one window of ``window_s`` consecutive seconds.

        ``sla_bucket`` pins both buckets: an int pins each to it, a
        (kx, kc) tuple pins them separately.

        Handles may be double-buffered: a second window may be
        dispatched before the first is gathered (the returned handle
        carries its own kx/kc and output futures; carried load/capacity
        state chains in dispatch order on device).  Dispatch must stay
        on ONE thread; gather may run on another."""
        from .schedule_table import FRAMEWORK_EPOCH
        from .timecal import window_fields
        if isinstance(sla_bucket, tuple):
            sla_x, sla_c = sla_bucket
        else:
            sla_x = sla_c = sla_bucket
        with self._bucket_mu:
            kx = self._bx.size(sla_x)
            kc = self._bc.size(sla_c)
        impl = self._impl(kx, kc)
        f = window_fields(epoch_s, window_s, tz=self.tz)
        fields_w = np.stack([
            f["sec"], f["min"], f["hour"], f["dom"], f["month"], f["dow"],
            np.arange(window_s, dtype=np.int64) + (epoch_s - FRAMEWORK_EPOCH),
        ], axis=1).astype(np.int32)                     # [W, 7]
        # w: the first second the window covers — the id the window's
        # spans share from here to its HWM (sched/service.py)
        with jax.profiler.TraceAnnotation("cronsun.plan.dispatch",
                                          w=epoch_s):
            # + 0.0 / | 0: the jit donates its load/rem_cap/last_fire
            # args, and the dispatch may run on the scheduler's dispatch
            # thread while the step thread scatters capacity/load
            # updates onto the SAME buffers — donating the live buffer
            # would leave the step holding a deleted one.  Donating a
            # fresh copy costs three [N]/[J] ops; a concurrently-landing
            # scatter can at worst be lost for one window, and the
            # scheduler's reconcile rewrites load/capacity absolutely
            # every step (dep epoch folds are monotone max — a lost
            # window re-applies at the next drain's scatter).
            tkw = {}
            if self._tenants_enabled:
                tkw = dict(self._tenant_args(),
                           tb_tokens=self.tb_tokens + 0.0,
                           use_tenants=True)
            outs32, outs16, outs_t, self.load, self.rem_cap, \
                self.dep_last_fire, tokens = _plan_window_step(
                    self.table, jnp.asarray(fields_w),
                    self.elig, self.exclusive, self.cost, self.load + 0.0,
                    self.rem_cap | 0, self.dep_succ, self.dep_fail,
                    self.dep_block, self.dep_last_fire | 0,
                    kx, kc, self.rounds, impl, self._dep_enabled, **tkw)
            # overflow-escalation replans (sla_bucket set) RE-plan
            # seconds whose refill/spend already advanced the carried
            # bucket: persisting a second pass would permanently drift
            # a throttled tenant below its quota (spend exceeds the
            # burst-clamped refill on exactly the herd seconds that
            # overflow) — replans read the bucket, never write it back
            if tokens is not None and sla_bucket is None:
                self.tb_tokens = tokens
        return epoch_s, kx, kc, outs32, outs16, outs_t

    def gather_window(self, handle):
        """Materialize a window dispatch into a list of TickPlans.

        Exclusive placements come first in ``fired``/``assigned``; Common
        fires follow with assigned = -1 (fan-out is the dispatcher's job).
        """
        epoch_s, kx, kc, outs32, outs16, outs_t = handle
        with jax.profiler.TraceAnnotation("cronsun.plan.gather", w=epoch_s):
            # one fetch per window: the gather is the pipeline's only
            # host<->device synchronization point
            o, oa, ot = jax.device_get((outs32, outs16, outs_t))
        plans = []
        W = o.shape[0]
        for w in range(W):
            xt, ct = int(o[w, 0]), int(o[w, 1])
            nx, nc = min(xt, kx), min(ct, kc)
            xidx = o[w, 2:2 + nx]
            assigned_x = oa[w, :nx].astype(np.int32)
            cidx = o[w, 2 + kx:2 + kx + nc]
            fired = np.concatenate([xidx, cidx])
            assigned = np.concatenate(
                [assigned_x, np.full(nc, -1, np.int32)])
            plans.append(TickPlan(
                epoch_s=epoch_s + w, fired=fired, assigned=assigned,
                overflow=max(0, xt - kx) + max(0, ct - kc),
                total_fired=xt + ct, n_excl=nx,
                tenant_throttled=(ot[w, 0] if ot is not None else None),
                tenant_shed=(ot[w, 1] if ot is not None else None)))
        if W:
            # adaptive sizing tracks each bucket's worst second; the shrink
            # hysteresis counts *ticks*, not calls.  Gather may run on the
            # pipeline's build worker while the step thread sizes the next
            # dispatch — the bucket lock keeps the counters coherent.
            with self._bucket_mu:
                self._bx.feed(int(o[:, 0].max()), W)
                self._bc.feed(int(o[:, 1].max()), W)
        return plans

    def plan_window(self, epoch_s: int, window_s: int,
                    sla_bucket: Optional[int] = None):
        return self.gather_window(
            self.plan_window_async(epoch_s, window_s, sla_bucket))

    def warm_window(self, epoch_s: int, window_s: int) -> None:
        """Compile (and cache) the windowed plan executable WITHOUT
        mutating carried state — warm standbys call this once so their
        first LEADING step doesn't pay the XLA compile (measured: tens
        of seconds of takeover outage at 1M-job shapes).  Bucket sizes
        are derived the same way a fresh leader's first plan would
        derive them, so the warmed executable is the one the takeover
        actually runs."""
        from .schedule_table import FRAMEWORK_EPOCH
        from .timecal import window_fields
        with self._bucket_mu:
            kx, kc = self._bx.peek(), self._bc.peek()
        impl = self._impl(kx, kc)
        f = window_fields(epoch_s, window_s, tz=self.tz)
        fields_w = np.stack([
            f["sec"], f["min"], f["hour"], f["dom"], f["month"], f["dow"],
            np.arange(window_s, dtype=np.int64)
            + (epoch_s - FRAMEWORK_EPOCH),
        ], axis=1).astype(np.int32)
        # + 0.0 / | 0: fresh buffers so the jit's donation can't
        # invalidate the planner's live load/rem_cap/last_fire
        outs32 = _plan_window_step(
            self.table, jnp.asarray(fields_w), self.elig, self.exclusive,
            self.cost, self.load + 0.0, self.rem_cap | 0, self.dep_succ,
            self.dep_fail, self.dep_block, self.dep_last_fire | 0, kx, kc,
            self.rounds, impl, self._dep_enabled, **self._warm_tkw())[0]
        np.asarray(outs32[0, 0])   # wait for the compile + run

    def warm_escalation(self, epoch_s: int, factor: int = 4) -> int:
        """Compile the single-second overflow-replan executable at the
        escalated bucket a cron-herd burst will request (the scheduler's
        ``_replan_overflow`` plans W=1 at pow2(true fire count)).  The
        first minute-boundary herd otherwise pays this compile INSIDE a
        live step — measured as tens of seconds of p99 at 1M jobs.
        Returns the warmed bucket size."""
        from .schedule_table import FRAMEWORK_EPOCH
        from .timecal import window_fields
        with self._bucket_mu:
            k = min(_next_pow2(max(self._bx.peek(),
                                   self._bc.peek()) * factor),
                    self.J)
        impl = self._impl(k, k)
        f = window_fields(epoch_s, 1, tz=self.tz)
        fields_w = np.stack([
            f["sec"], f["min"], f["hour"], f["dom"], f["month"], f["dow"],
            np.asarray([epoch_s - FRAMEWORK_EPOCH], np.int64),
        ], axis=1).astype(np.int32)
        outs32 = _plan_window_step(
            self.table, jnp.asarray(fields_w), self.elig, self.exclusive,
            self.cost, self.load + 0.0, self.rem_cap | 0, self.dep_succ,
            self.dep_fail, self.dep_block, self.dep_last_fire | 0, k, k,
            self.rounds, impl, self._dep_enabled, **self._warm_tkw())[0]
        np.asarray(outs32[0, 0])
        self._warmed_single.add(k)
        return k

    def _warm_tkw(self) -> dict:
        """Tenant operands for the warm-compile paths: fresh token
        copies so the warm run can't mutate carried bucket state."""
        if not self._tenants_enabled:
            return {}
        return dict(self._tenant_args(),
                    tb_tokens=self.tb_tokens + 0.0, use_tenants=True)

    def snap_escalation(self, want: int) -> int:
        """Smallest warmed single-second bucket >= ``want``, else
        ``want`` itself — an oversized-but-compiled bucket beats a
        right-sized compile inside a live burst step."""
        cands = [s for s in self._warmed_single if s >= want]
        return min(cands) if cands else want
