"""Windowed fire-mask evaluation and batched next-fire.

Replaces the reference's per-entry sequential walk: the cron loop's
``e.Next = e.Schedule.Next(now)`` + O(n log n) sort per tick
(node/cron/cron.go:210-275, node/cron/spec.go:55-145) become one fused
elementwise program over the whole schedule table:

- :func:`fire_mask` — [J, W] bool: which jobs fire at which window instant.
  Pure bit tests against the mask table; XLA fuses the six field tests, the
  DOM/DOW star rule and the ``@every`` modular test into one pass over HBM.
- :func:`next_fire` — batched ``Schedule.Next`` for every job at once:
  a partial-minute second-granularity pass, then escalating minute-granularity
  window chunks (a cron row with a nonempty seconds mask fires in a minute iff
  its min/hour/day/month fields match; the first second is the mask's lowest
  set bit), host-fallback free.  Gives up past a 5-year horizon exactly like
  the reference (node/cron/spec.go:70-75).

All scans are data-independent dense windows — no data-dependent control flow
inside jit; the escalation loop lives on the host.
"""

from __future__ import annotations

import datetime as _dt
from datetime import timezone
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .schedule_table import FRAMEWORK_EPOCH, ScheduleTable
from .timecal import window_fields

_UTC = timezone.utc

# The reference gives up a Next() search after five years (spec.go:70-75).
FIVE_YEARS_S = 5 * 366 * 86400


def _bit60(lo: jax.Array, hi: jax.Array, idx: jax.Array) -> jax.Array:
    """Test bit ``idx`` (0..59) of a (lo, hi) uint32 pair.

    lo/hi are [J], idx is [W]; result [J, W] bool.  Shift amounts are clamped
    to stay in-range (XLA leaves >=width shifts undefined).
    """
    idx = idx[None, :]
    lo_sh = jnp.minimum(idx, 31).astype(jnp.uint32)
    hi_sh = jnp.minimum(jnp.maximum(idx - 32, 0), 31).astype(jnp.uint32)
    lo_bit = (lo[:, None] >> lo_sh) & 1
    hi_bit = (hi[:, None] >> hi_sh) & 1
    return jnp.where(idx < 32, lo_bit, hi_bit) != 0


def _bit32(mask: jax.Array, idx: jax.Array) -> jax.Array:
    """Test bit ``idx`` (0..31) of uint32 mask; [J] x [W] -> [J, W] bool."""
    sh = jnp.minimum(idx[None, :], 31).astype(jnp.uint32)
    return ((mask[:, None] >> sh) & 1) != 0


def _day_ok(t: ScheduleTable, dom_idx: jax.Array, dow_idx: jax.Array) -> jax.Array:
    """DOM/DOW star semantics (node/cron/spec.go:149-158)."""
    dom_ok = _bit32(t.dom, dom_idx)
    dow_ok = _bit32(t.dow, dow_idx)
    either_star = (t.dom_star | t.dow_star)[:, None]
    return jnp.where(either_star, dom_ok & dow_ok, dom_ok | dow_ok)


def _every_rem(t: ScheduleTable, t_rel: jax.Array) -> jax.Array:
    """Seconds until the next @every fire at each instant: [J, W] int32.

    0 means "fires exactly at this instant"."""
    period = t.period[:, None]
    return jnp.mod(t.phase_mod[:, None] - t_rel[None, :], period)


@jax.jit
def _fire_mask_jit(t: ScheduleTable, sec, mnt, hour, dom, month, dow, t_rel):
    cron_ok = (
        _bit60(t.sec_lo, t.sec_hi, sec)
        & _bit60(t.min_lo, t.min_hi, mnt)
        & _bit32(t.hour, hour)
        & _day_ok(t, dom, dow)
        & _bit32(t.month, month)
    )
    every_ok = _every_rem(t, t_rel) == 0
    live = (t.active & ~t.paused)[:, None]
    return live & jnp.where(t.is_every[:, None], every_ok, cron_ok)


def fire_mask(table: ScheduleTable, start_epoch_s: int, window_s: int = 1,
              tz=_UTC) -> jax.Array:
    """[J, window_s] bool: fire decisions for every job over the window of
    seconds [start, start + window_s), wall-decomposed in ``tz``.

    Fires are evaluated at the LOGICAL (cron-matched) second; the
    ``table.jitter`` column is deliberately unread here — herd smearing
    is a host-side shift applied at plan emission (sched/service.py), so
    the lowered program is byte-identical whether or not any row sets
    jitter."""
    f = window_fields(start_epoch_s, window_s, step_s=1, tz=tz)
    t_rel = np.arange(window_s, dtype=np.int64) + (start_epoch_s - FRAMEWORK_EPOCH)
    return _fire_mask_jit(table, jnp.asarray(f["sec"]), jnp.asarray(f["min"]),
                          jnp.asarray(f["hour"]), jnp.asarray(f["dom"]),
                          jnp.asarray(f["month"]), jnp.asarray(f["dow"]),
                          jnp.asarray(t_rel.astype(np.int32)))


@jax.jit
def first_fire_offset(fire_jw: jax.Array):
    """First true offset per row, and whether any exists: ([J] int32, [J] bool)."""
    any_fire = jnp.any(fire_jw, axis=1)
    return jnp.argmax(fire_jw, axis=1).astype(jnp.int32), any_fire


def _ctz64(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Count trailing zeros of a (lo, hi) uint32 pair; 64 when empty."""
    def ctz32(x):
        lowest = x & (jnp.zeros_like(x) - x)
        return jnp.where(x == 0, 32,
                         jax.lax.population_count(lowest - 1).astype(jnp.int32))
    lo_z = ctz32(lo)
    return jnp.where(lo != 0, lo_z, 32 + ctz32(hi)).astype(jnp.int32)


def _ctz32(x: jax.Array) -> jax.Array:
    lowest = x & (jnp.zeros_like(x) - x)
    return jnp.where(x == 0, 32,
                     jax.lax.population_count(lowest - 1).astype(jnp.int32))


_SEC_PAD = 64      # padded partial-minute window
_MIN_PAD = 3072    # padded minute window (through end of tomorrow, any DST)
_DAY_PAD = 1856    # padded day window (5-year horizon)


# THE single definition of the packed host->device buffer layout: pack
# (next_fire) and unpack (_next_fire_packed) both iterate this, so a
# field reorder cannot silently desynchronize the two sides (all slices
# within a size group share a shape — a drift would be invisible to
# shape checks).
_PACK_LAYOUT = (
    (_SEC_PAD, ("s_sec", "s_min", "s_hour", "s_dom", "s_month", "s_dow",
                "s_rel", "s_ok")),
    (_MIN_PAD, ("m_min", "m_hour", "m_dom", "m_month", "m_dow",
                "m_rel", "m_ok")),
    (_DAY_PAD, ("d_dom", "d_month", "d_dow", "d_rel", "d_ok")),
)


@jax.jit
def _next_fire_packed(t: ScheduleTable, packed, t_rel_start):
    """Unpack the single host->device field buffer and run the fused
    next-fire pass.  One upload instead of twenty: each small transfer
    pays its own dispatch, and the whole buffer is ~124 KB (not
    measured on a locally attached chip)."""
    f = {}
    off = 0
    for size, names in _PACK_LAYOUT:
        for name in names:
            f[name] = jax.lax.slice(packed, (off,), (off + size,))
            off += size
    return _next_fire_fused(
        t, f["s_sec"], f["s_min"], f["s_hour"], f["s_dom"], f["s_month"],
        f["s_dow"], f["s_rel"], f["s_ok"].astype(bool),
        f["m_min"], f["m_hour"], f["m_dom"], f["m_month"], f["m_dow"],
        f["m_rel"], f["m_ok"].astype(bool),
        f["d_dom"], f["d_month"], f["d_dow"], f["d_rel"],
        f["d_ok"].astype(bool), t_rel_start)


@jax.jit
def _next_fire_fused(t: ScheduleTable,
                     s_sec, s_min, s_hour, s_dom, s_month, s_dow, s_rel, s_ok,
                     m_min, m_hour, m_dom, m_month, m_dow, m_rel, m_ok,
                     d_dom, d_month, d_dow, d_rel, d_ok,
                     t_rel_start):
    """ONE dispatch resolving Schedule.Next for every row (SURVEY §7's
    sparse-schedule hard part, done without escalating windows):

    - @every rows: pure modular arithmetic — no scan at all.
    - cron rows, three granularities, coarse-to-fine coverage:
      1. the partial first minute at second granularity ([J, 64]);
      2. minute granularity through the end of tomorrow ([J, ~3k]) — a
         row matches a minute iff min/hour/day/month match; the fire
         second within it is the seconds-mask's lowest bit;
      3. day granularity over the whole 5-year horizon ([J, ~1.8k]) — a
         row matches a day iff dom/month/dow match, and its first fire
         time-of-day is STATIC (lowest hour/min/sec bits), so no finer
         scan is ever needed.
    Returns [J] int32 framework-relative fire seconds, -1 = no fire in
    horizon (the reference's zero time, spec.go:70-75).
    """
    live = t.active & ~t.paused

    # 1) seconds within the partial first minute: full six-field test
    fire_s = (
        _bit60(t.sec_lo, t.sec_hi, s_sec)
        & _bit60(t.min_lo, t.min_hi, s_min)
        & _bit32(t.hour, s_hour)
        & _day_ok(t, s_dom, s_dow)
        & _bit32(t.month, s_month)
    ) & s_ok[None, :]
    any_s = jnp.any(fire_s, axis=1)
    res_s = s_rel[jnp.argmax(fire_s, axis=1)]

    # first fire second / time-of-day per row (static per row)
    sec0 = jnp.minimum(_ctz64(t.sec_lo, t.sec_hi), 59)
    tod = (_ctz32(t.hour) * 3600
           + jnp.minimum(_ctz64(t.min_lo, t.min_hi), 59) * 60 + sec0)

    # 2) minute granularity through end of tomorrow
    match_m = (
        _bit60(t.min_lo, t.min_hi, m_min)
        & _bit32(t.hour, m_hour)
        & _day_ok(t, m_dom, m_dow)
        & _bit32(t.month, m_month)
    ) & m_ok[None, :]
    any_m = jnp.any(match_m, axis=1)
    res_m = m_rel[jnp.argmax(match_m, axis=1)] + sec0

    # 3) day granularity over the horizon
    match_d = (_day_ok(t, d_dom, d_dow) & _bit32(t.month, d_month)
               ) & d_ok[None, :]
    any_d = jnp.any(match_d, axis=1)
    res_d = d_rel[jnp.argmax(match_d, axis=1)] + tod

    res_cron = jnp.where(any_s, res_s,
                         jnp.where(any_m, res_m,
                                   jnp.where(any_d, res_d, -1)))
    # @every: closed form
    rem = jnp.mod(t.phase_mod - t_rel_start, t.period)
    res_every = t_rel_start + rem
    res = jnp.where(t.is_every, res_every, res_cron)
    return jnp.where(live, res, -1), jnp.where(live & ~t.is_every & ~any_s
                                               & ~any_m & any_d,
                                               jnp.argmax(match_d, axis=1),
                                               -1)


def _pad_fields(f: dict, n: int, pad: int):
    """Pad field arrays to a static width with never-matching values
    (month 0 has no bit in any month mask; dow 7 in no dow mask)."""
    out = {}
    for k, v in f.items():
        fill = {"month": 0, "dow": 7, "dom": 0}.get(k, 0)
        out[k] = np.concatenate(
            [v[:n], np.full(pad - min(n, len(v)), fill, np.int32)])
    ok = np.zeros(pad, bool)
    ok[:n] = True
    return out, ok


def next_fire(table: ScheduleTable, after_epoch_s: int, tz=_UTC,
              horizon_s: int = FIVE_YEARS_S,
              chunk_minutes: Optional[int] = None) -> np.ndarray:
    """Batched Schedule.Next: for every job, the first fire instant strictly
    after ``after_epoch_s``.  Returns [J] int64 epoch seconds; -1 where no
    fire occurs within ``horizon_s`` (the reference's zero time).

    One fused device dispatch regardless of schedule sparsity (see
    :func:`_next_fire_fused`); ``chunk_minutes`` is accepted for backward
    compatibility and ignored.  In DST zones, rows resolved by the
    day-granularity scan onto a transition day are re-verified host-side
    with the scalar engine (wall instants shift around the transition).
    """
    del chunk_minutes
    start = after_epoch_s + 1
    t_rel_start = start - FRAMEWORK_EPOCH
    boundary = (start // 60 + 1) * 60
    w0 = boundary - start

    # window shapes (host): partial minute, minutes to end of tomorrow,
    # days across the horizon
    from .timecal import tz_fixed_offset_seconds
    off = tz_fixed_offset_seconds(tz)
    if off is not None:
        day0 = ((boundary + off) // 86400 + 2) * 86400 - off   # day after tomorrow, local midnight
        n_min = (day0 - boundary) // 60
        n_day = min(_DAY_PAD, (horizon_s + 86399) // 86400)
        day_starts = day0 + 86400 * np.arange(n_day, dtype=np.int64)
    else:
        # local midnight of the day after tomorrow, then one local
        # midnight per day (zoneinfo resolves each across transitions)
        loc = _dt.datetime.fromtimestamp(boundary, tz)
        d0 = _dt.datetime(loc.year, loc.month, loc.day) + _dt.timedelta(days=2)
        n_day = min(_DAY_PAD, (horizon_s + 86399) // 86400)
        starts = []
        cur = d0
        for _ in range(n_day):
            starts.append(cur.replace(tzinfo=tz).timestamp())
            cur += _dt.timedelta(days=1)
        day_starts = np.asarray(starts, np.int64)
        n_min = int((day_starts[0] - boundary) // 60)

    sf = window_fields(start, min(w0, _SEC_PAD) or 1, tz=tz)
    sf, s_ok = _pad_fields(sf, w0, _SEC_PAD)
    s_rel = (start + np.arange(_SEC_PAD, dtype=np.int64)
             - FRAMEWORK_EPOCH).astype(np.int32)

    n_min = min(n_min, _MIN_PAD)
    mf = window_fields(boundary, n_min, step_s=60, tz=tz)
    mf, m_ok = _pad_fields(mf, n_min, _MIN_PAD)
    m_rel = (boundary + 60 * np.arange(_MIN_PAD, dtype=np.int64)
             - FRAMEWORK_EPOCH).astype(np.int32)

    dfields = {"dom": np.empty(0, np.int32), "month": np.empty(0, np.int32),
               "dow": np.empty(0, np.int32)}
    if n_day:
        _, _, _, d_dom, d_month, d_dow = _decompose_days(day_starts, tz)
        dfields = {"dom": d_dom, "month": d_month, "dow": d_dow}
    df, d_ok = _pad_fields(dfields, n_day, _DAY_PAD)
    d_rel = np.zeros(_DAY_PAD, np.int64)
    d_rel[:n_day] = day_starts - FRAMEWORK_EPOCH
    d_rel = d_rel.astype(np.int32)

    fields = {
        "s_sec": sf["sec"], "s_min": sf["min"], "s_hour": sf["hour"],
        "s_dom": sf["dom"], "s_month": sf["month"], "s_dow": sf["dow"],
        "s_rel": s_rel, "s_ok": s_ok,
        "m_min": mf["min"], "m_hour": mf["hour"], "m_dom": mf["dom"],
        "m_month": mf["month"], "m_dow": mf["dow"],
        "m_rel": m_rel, "m_ok": m_ok,
        "d_dom": df["dom"], "d_month": df["month"], "d_dow": df["dow"],
        "d_rel": d_rel, "d_ok": d_ok,
    }
    packed = np.concatenate([
        np.asarray(fields[name], np.int32)
        for size, names in _PACK_LAYOUT for name in names])
    res_rel, day_idx = _next_fire_packed(table, jnp.asarray(packed),
                                         np.int32(t_rel_start))
    res_rel = np.asarray(res_rel).astype(np.int64)
    result = np.where(res_rel < 0, -1, res_rel + FRAMEWORK_EPOCH)

    if off is None:
        _fix_dst_days(table, result, np.asarray(day_idx), day_starts, tz)

    # The fused pass scans _DAY_PAD days; an explicit horizon beyond that
    # continues in further day-window chunks (rare — only multi-year
    # horizons with still-unresolved sparse cron rows pay this).
    days_done = n_day
    # int32 framework-relative seconds bound the scan to ~2088; 20 years
    # is already 4x the reference's give-up horizon (spec.go:70-75)
    horizon_days = min((horizon_s + 86399) // 86400, 20 * 366)
    # the row masks live on device; fetching them costs a link round
    # trip each, so they materialize only if the continuation loop is
    # actually entered (at the default 5-year horizon it never is —
    # the fused pass already scanned _DAY_PAD >= horizon days)
    is_cron = live = None
    while days_done < horizon_days:
        if is_cron is None:
            is_cron = ~np.asarray(table.is_every)
            live = np.asarray(table.active & ~table.paused)
        unresolved = (result < 0) & is_cron & live
        if not unresolved.any():
            break
        nd = min(_DAY_PAD, horizon_days - days_done)
        if off is not None:
            chunk_starts = day_starts[0] + 86400 * np.arange(
                days_done, days_done + nd, dtype=np.int64)
        else:
            cur = _dt.datetime.fromtimestamp(int(day_starts[-1]), tz)
            base = _dt.datetime(cur.year, cur.month, cur.day) \
                + _dt.timedelta(days=days_done - n_day + 1)
            starts = []
            c = base
            for _ in range(nd):
                starts.append(c.replace(tzinfo=tz).timestamp())
                c += _dt.timedelta(days=1)
            chunk_starts = np.asarray(starts, np.int64)
        _, _, _, cd_dom, cd_month, cd_dow = _decompose_days(chunk_starts, tz)
        cdf, cd_ok = _pad_fields(
            {"dom": cd_dom, "month": cd_month, "dow": cd_dow}, nd, _DAY_PAD)
        cd_rel = np.zeros(_DAY_PAD, np.int64)
        cd_rel[:nd] = chunk_starts - FRAMEWORK_EPOCH
        found, res_rel2, idx2 = _day_scan_jit(
            table, jnp.asarray(cdf["dom"]), jnp.asarray(cdf["month"]),
            jnp.asarray(cdf["dow"]), jnp.asarray(cd_rel.astype(np.int32)),
            jnp.asarray(cd_ok))
        found = np.asarray(found); res_rel2 = np.asarray(res_rel2)
        hit = unresolved & found
        result[hit] = res_rel2[hit].astype(np.int64) + FRAMEWORK_EPOCH
        if off is None:
            di = np.where(hit, np.asarray(idx2), -1)
            _fix_dst_days(table, result, di, chunk_starts, tz)
        days_done += nd

    # horizon clip (@every with huge periods / last chunk can exceed it)
    result = np.where(result > after_epoch_s + horizon_s, -1, result)
    return result


@jax.jit
def _day_scan_jit(t: ScheduleTable, d_dom, d_month, d_dow, d_rel, d_ok):
    """Day-granularity continuation chunk: first matching day + the row's
    static first time-of-day (see :func:`_next_fire_fused` step 3)."""
    match_d = (_day_ok(t, d_dom, d_dow) & _bit32(t.month, d_month)
               ) & d_ok[None, :]
    any_d = jnp.any(match_d, axis=1)
    idx = jnp.argmax(match_d, axis=1)
    sec0 = jnp.minimum(_ctz64(t.sec_lo, t.sec_hi), 59)
    tod = (_ctz32(t.hour) * 3600
           + jnp.minimum(_ctz64(t.min_lo, t.min_hi), 59) * 60 + sec0)
    return any_d, d_rel[idx] + tod, idx.astype(jnp.int32)


def _decompose_days(day_starts: np.ndarray, tz):
    """Civil fields for local-midnight day starts (noon probe avoids DST
    edge effects on the date itself)."""
    from .timecal import tz_fixed_offset_seconds, decompose_utc
    off = tz_fixed_offset_seconds(tz)
    if off is not None:
        return decompose_utc(day_starts + 43200, off)
    dom = np.empty(len(day_starts), np.int32)
    month = np.empty(len(day_starts), np.int32)
    dow = np.empty(len(day_starts), np.int32)
    for i, s in enumerate(day_starts):
        loc = _dt.datetime.fromtimestamp(int(s) + 43200, tz)
        dom[i] = loc.day
        month[i] = loc.month
        dow[i] = (loc.weekday() + 1) % 7
    return None, None, None, dom, month, dow


def _fix_dst_days(table: ScheduleTable, result: np.ndarray,
                  day_idx: np.ndarray, day_starts: np.ndarray, tz):
    """Rows the day scan resolved onto a DST-transition day get an exact
    scalar re-walk (static time-of-day arithmetic assumes 86400-s days)."""
    if not len(day_starts):
        return
    lengths = np.diff(np.concatenate([day_starts, day_starts[-1:] + 86400]))
    affected = np.nonzero((day_idx >= 0)
                          & (lengths[np.clip(day_idx, 0, len(lengths) - 1)]
                             != 86400))[0]
    if not len(affected):
        return
    from ..cron.parser import CronSpec, STAR_BIT
    from ..cron.schedule import Schedule
    sec_lo = np.asarray(table.sec_lo); sec_hi = np.asarray(table.sec_hi)
    min_lo = np.asarray(table.min_lo); min_hi = np.asarray(table.min_hi)
    hour = np.asarray(table.hour); dom = np.asarray(table.dom)
    month = np.asarray(table.month); dow = np.asarray(table.dow)
    dom_star = np.asarray(table.dom_star); dow_star = np.asarray(table.dow_star)
    for j in affected:
        spec = CronSpec(
            second=int(sec_lo[j]) | int(sec_hi[j]) << 32,
            minute=int(min_lo[j]) | int(min_hi[j]) << 32,
            hour=int(hour[j]), month=int(month[j]),
            dom=int(dom[j]) | (STAR_BIT if dom_star[j] else 0),
            dow=int(dow[j]) | (STAR_BIT if dow_star[j] else 0))
        t0 = _dt.datetime.fromtimestamp(int(day_starts[day_idx[j]]) - 1, tz)
        nxt = Schedule(spec).next(t0)
        result[j] = -1 if nxt is None else int(nxt.timestamp())


def next_fire_one(table: ScheduleTable, job_index: int, after_epoch_s: int,
                  tz=_UTC) -> Optional[int]:
    """Convenience: next fire for one row (None if unsatisfiable)."""
    r = next_fire(table, after_epoch_s, tz=tz)
    v = int(r[job_index])
    return None if v < 0 else v
