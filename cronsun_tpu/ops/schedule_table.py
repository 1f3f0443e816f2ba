"""Device-resident batched schedule table.

A compiled cron spec is six uint64 bitmasks (reference: node/cron/spec.go:7-9).
On TPU the native integer width is 32 bits, so each 64-bit mask is stored as a
(lo, hi) uint32 pair and the star bits (bit 63, node/cron/spec.go:48-51) are
hoisted into separate bool columns — they only matter for the day-of-month vs
day-of-week OR/AND rule (node/cron/spec.go:149-158).

``@every`` schedules (node/cron/constantdelay.go) are held in the same table
as (period, phase) rows: a job fires when
``(t - phase) mod period == 0``.  Phase is anchored at registration time, so
the fire train matches the reference's chained ``prev + period`` behaviour as
long as no window is skipped; unlike the reference, a lagging scheduler does
not shift the phase (deliberate divergence — deterministic fire instants).

All epoch arithmetic is relative to :data:`FRAMEWORK_EPOCH` (2020-01-01 UTC)
so device-side seconds fit int32 until 2088 without enabling x64.

Tables are fixed-capacity: allocate for ``capacity`` jobs, mark live rows with
``active``; row churn from watch deltas is in-place buffer donation, never a
reshape, so XLA never recompiles on job add/remove (SURVEY.md §7 "incremental
updates without recompile").
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.models import MAX_DEPS
from ..cron.parser import CronSpec, EverySpec, parse

# 2020-01-01T00:00:00Z — device times are int32 seconds relative to this.
FRAMEWORK_EPOCH = 1577836800

_MASK32 = (1 << 32) - 1
_STAR_OFF = ~(1 << 63)  # strip star bit before splitting

# dependency-column sentinels (the [capacity, MAX_DEPS] dep_cols block):
# >= 0 is the upstream job's table row; DEP_EMPTY pads unused slots
# (always satisfied); DEP_BROKEN marks an unresolvable upstream (job
# missing / no rows) — never satisfied, so the row holds instead of
# firing dep-less.
DEP_EMPTY = -1
DEP_BROKEN = -2


def _split64(mask: int) -> "tuple[int, int]":
    m = mask & _STAR_OFF
    return m & _MASK32, (m >> 32) & _MASK32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScheduleTable:
    """Struct-of-arrays schedule batch; every field is shape [capacity]."""

    sec_lo: jax.Array   # uint32
    sec_hi: jax.Array   # uint32 (bits 32..59)
    min_lo: jax.Array   # uint32
    min_hi: jax.Array   # uint32
    hour: jax.Array     # uint32 (bits 0..23)
    dom: jax.Array      # uint32 (bits 1..31)
    month: jax.Array    # uint32 (bits 1..12)
    dow: jax.Array      # uint32 (bits 0..6)
    dom_star: jax.Array  # bool
    dow_star: jax.Array  # bool
    is_every: jax.Array  # bool
    period: jax.Array    # int32, >=1 always (1 for cron rows: no div-by-zero)
    phase_mod: jax.Array  # int32, phase mod period (framework-epoch relative)
    active: jax.Array    # bool — live row
    paused: jax.Array    # bool — Job.Pause (reference job.go:53)
    # workflow DAG plane: the padded dependency matrix beside the cron
    # masks.  has_dep marks dep-triggered rows (their cron masks are
    # empty); dep_cols is the [capacity, MAX_DEPS] upstream-row block
    # (DEP_EMPTY pads, DEP_BROKEN never satisfies); dep_policy is the
    # misfire policy (POLICY_* in ops/deps.py).  Success/fail epochs and
    # the last-fire vector are PLANNER state (they mutate on watch
    # events / inside the scan), not table rows.
    has_dep: jax.Array   # bool
    dep_policy: jax.Array  # int32 (POLICY_SKIP/FIRE/HOLD)
    dep_cols: jax.Array    # int32 [capacity, MAX_DEPS]
    # multi-tenant control plane: small tenant id per row (0 = the
    # default, never-limited tenant).  The admission pass itself runs
    # off the planner's host-snapshotted permutation (ops/tenancy.py),
    # so this column is the durable row->tenant record (it rides
    # checkpoints with the table) rather than a per-tick operand.
    tenant: jax.Array      # int32
    # herd smearing: per-row deterministic jitter width in seconds
    # (0..300, 0 = fire exactly at the matched second).  The device tick
    # never reads this column — the smear delta is evaluated on the host
    # at plan emission (sched/service.py) from the cached per-row FNV
    # state, so the lowered program is identical whether or not any row
    # sets jitter.  Riding the table means checkpoints carry it for
    # free, exactly like ``tenant``.
    jitter: jax.Array      # int32

    @property
    def capacity(self) -> int:
        return self.sec_lo.shape[0]


_NO_DEPS = (DEP_EMPTY,) * MAX_DEPS


def make_row(spec: Union[CronSpec, EverySpec, str], phase_epoch_s: int = 0,
             paused: bool = False, tenant: int = 0,
             jitter: int = 0) -> dict:
    """Host-side row dict for one spec (strings are parsed)."""
    return make_rows(spec, (phase_epoch_s,), paused, tenant, jitter)[0]


def make_rows(spec: Union[CronSpec, EverySpec, str],
              phase_epochs_s: Sequence[int], paused: bool = False,
              tenant: int = 0, jitter: int = 0) -> List[dict]:
    """Row dicts for rules that share one spec, pause, tenant and
    jitter: one per phase anchor.  A cron row does not read its phase,
    so cron rows share ONE dict — row dicts are read-only once made."""
    if isinstance(spec, str):
        spec = parse(spec)
    if isinstance(spec, EverySpec):
        period = max(1, spec.period_s)
        base = dict(
            sec_lo=0, sec_hi=0, min_lo=0, min_hi=0, hour=0, dom=0, month=0,
            dow=0, dom_star=False, dow_star=False, is_every=True,
            period=period, phase_mod=0,
            active=True, paused=paused,
            has_dep=False, dep_policy=0, dep_cols=_NO_DEPS, tenant=tenant,
            jitter=int(jitter))
        return [{**base,
                 "phase_mod": int((p - FRAMEWORK_EPOCH) % period)}
                for p in phase_epochs_s]
    sec_lo, sec_hi = _split64(spec.second)
    min_lo, min_hi = _split64(spec.minute)
    base = dict(
        sec_lo=sec_lo, sec_hi=sec_hi, min_lo=min_lo, min_hi=min_hi,
        hour=spec.hour & _MASK32, dom=spec.dom & _MASK32,
        month=spec.month & _MASK32, dow=spec.dow & _MASK32,
        dom_star=spec.dom_star, dow_star=spec.dow_star,
        is_every=False, period=1, phase_mod=0, active=True, paused=paused,
        has_dep=False, dep_policy=0, dep_cols=_NO_DEPS, tenant=tenant,
        jitter=int(jitter))
    return [base] * len(phase_epochs_s)


def make_dep_row(upstream_rows, policy: int, paused: bool = False,
                 tenant: int = 0) -> dict:
    """Row dict for a dep-triggered job: cron masks empty (the row never
    time-fires), dep columns padded to MAX_DEPS with DEP_EMPTY.
    ``upstream_rows`` entries are table rows or DEP_BROKEN for
    unresolvable upstreams."""
    ups = list(upstream_rows)[:MAX_DEPS]
    cols = tuple(ups) + (DEP_EMPTY,) * (MAX_DEPS - len(ups))
    row = dict(_INACTIVE_ROW)
    row.update(active=True, paused=paused, has_dep=True,
               dep_policy=int(policy), dep_cols=cols, tenant=int(tenant))
    return row


_DTYPES = dict(
    sec_lo=np.uint32, sec_hi=np.uint32, min_lo=np.uint32, min_hi=np.uint32,
    hour=np.uint32, dom=np.uint32, month=np.uint32, dow=np.uint32,
    dom_star=np.bool_, dow_star=np.bool_, is_every=np.bool_,
    period=np.int32, phase_mod=np.int32, active=np.bool_, paused=np.bool_,
    has_dep=np.bool_, dep_policy=np.int32, dep_cols=np.int32,
    tenant=np.int32, jitter=np.int32,
)

# per-field trailing shape beyond [capacity] (only the dep matrix is 2-D)
_SHAPES = {"dep_cols": (MAX_DEPS,)}

_INACTIVE_ROW = dict(
    sec_lo=0, sec_hi=0, min_lo=0, min_hi=0, hour=0, dom=0, month=0, dow=0,
    dom_star=False, dow_star=False, is_every=False, period=1, phase_mod=0,
    active=False, paused=False,
    has_dep=False, dep_policy=0, dep_cols=_NO_DEPS, tenant=0, jitter=0)


def build_table(specs: List[Union[CronSpec, EverySpec, str]],
                capacity: Optional[int] = None,
                phase_epoch_s: int = 0,
                paused: Optional[List[bool]] = None,
                device=None, sharding=None) -> ScheduleTable:
    """Compile a list of specs into a device ScheduleTable.

    ``capacity`` pads the table (inactive rows) to a fixed size; defaults to
    the next power of two >= len(specs) so later growth rarely re-allocates.
    """
    n = len(specs)
    if capacity is None:
        capacity = max(1, 1 << (n - 1).bit_length()) if n else 1
    if capacity < n:
        raise ValueError(f"capacity {capacity} < {n} specs")
    cols = {k: np.full((capacity, *_SHAPES.get(k, ())),
                       DEP_EMPTY if k == "dep_cols" else _INACTIVE_ROW[k],
                       dtype=dt)
            for k, dt in _DTYPES.items()}
    for i, spec in enumerate(specs):
        row = make_row(spec, phase_epoch_s=phase_epoch_s,
                       paused=bool(paused[i]) if paused else False)
        for k, v in row.items():
            cols[k][i] = v
    if sharding is not None:
        arrs = {k: jax.device_put(v, sharding) for k, v in cols.items()}
    elif device is not None:
        arrs = {k: jax.device_put(v, device) for k, v in cols.items()}
    else:
        arrs = {k: jnp.asarray(v) for k, v in cols.items()}
    return ScheduleTable(**arrs)


def update_rows(table: ScheduleTable, indices: np.ndarray,
                rows: List[dict]) -> ScheduleTable:
    """Functionally update rows at ``indices`` (watch-delta path).

    Scatter at fixed shapes — no recompile, and under jit with donated
    buffers this is an in-place update.
    """
    idx = jnp.asarray(np.asarray(indices, dtype=np.int32))
    new = {}
    for k, dt in _DTYPES.items():
        vals = jnp.asarray(np.array([r[k] for r in rows], dtype=dt))
        new[k] = getattr(table, k).at[idx].set(vals)
    return ScheduleTable(**new)


def deactivate_rows(table: ScheduleTable, indices: np.ndarray) -> ScheduleTable:
    return update_rows(table, indices, [_INACTIVE_ROW] * len(indices))
