"""SPMD tick+assign over a device mesh (shard_map + XLA collectives).

Sharding layout (the scaling-book recipe: pick a mesh, annotate shardings,
let XLA insert collectives):

- mesh: 1-D ``("jobs",)`` — jobs are the big axis (1M rows x ~1.3 KB of
  schedule+eligibility state each); each device owns J/D rows.
- replicated: node load/capacity vectors ([N] — tiny), time fields.
- per tick, each shard: local fire_mask -> local compact (K/D bucket) ->
  local pallas bid.  Then the per-round reconcile, one of two paths:

  * **bucket-sharded bidding** (default, ``shard_bids=True``): each shard
    waterfills its OWN candidates against the replicated load/rem_cap and
    shards exchange only per-node DEMAND summaries — one ``all_gather`` of
    a [2, N] (count, cost-sum) block plus one ``psum`` of the accepted
    (count, cost) block — O(nodes x D) gathered bytes per round,
    independent of the fired bucket (the replicated path is linear in
    it; crossover math in ``estimate_collective_bytes``).  The accept
    predicate is the replicated waterfill's
    exactly (see assign.waterfill_accept_presplit): global within-node
    rank = earlier-shards' demand-count prefix + local rank, global
    cumulative cost likewise, so the result is bit-identical whenever
    cost sums are exact (pinned by a randomized differential test).
  * **replicated waterfill** (``shard_bids=False``, the reference path):
    ONE ``all_gather`` of the compacted candidate bids (choice/cost/flags,
    O(K) bytes) and every shard runs the *identical* waterfill accept on
    the gathered bucket.  D-1 more bid rounds repeat the exchange.

- result: each shard scatters its slice of the accept verdicts back to its
  local bucket; outputs concatenate along the bucket axis.

Inter-chip traffic per tick is O(nodes) sharded / O(fired-bucket)
replicated, independent of J either way — the design scales to multi-host
DCN the same way.  ``estimate_collective_bytes`` puts numbers on both
paths at the planner's shapes; scripts/bench_mesh.py measures them.

The reference has no analogue (every Go node redundantly runs the full cron
loop, node/cron/cron.go:210-275); this module is the scale-out story that
replaces "replicate all state on every node".
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.assign import (_steps, compact_demand, local_bid_demand,
                          scatter_demand, waterfill_accept,
                          waterfill_accept_presplit)
from ..ops.planner import TickPlan, TickPlanner, _compact, _next_pow2
from ..ops.schedule_table import FRAMEWORK_EPOCH, ScheduleTable
from ..ops.tick import _fire_mask_jit
from ..ops.timecal import window_fields

AXIS = "jobs"
NAXIS = "nodes"

# node width at which the 2-D mesh's Common fan-out psum shards by node
# blocks (each device reduces only its [N/Dn] block; one gather
# assembles) instead of psumming the full [N] — below it the dense psum
# compiles as before
NODE_BLOCK_PSUM_MIN_N = 65536


def _shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: the plan
    bodies mix replicated and sharded operands freely."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _reconcile_sharded(cand, choice, cost, load, rem_cap, is_final, axis,
                       compact_k=None):
    """One bucket-sharded accept round: exchange per-node demand
    summaries instead of the candidate bids ([k_local] x 3 per shard).

    Two wire formats for the same reconcile, selected statically by
    ``compact_k`` (None = dense):

    - **dense** ([2, N] per shard): payload independent of the fired
      bucket — 8N x D gathered + one 8N psum per round.  Right for the
      herd regime.
    - **compacted** ([3, compact_k] per shard, compact_k =
      min(k_local, N)): only the NONZERO per-node demand entries travel,
      as (node_idx, count, cost_sum) f32 triples — 12 B x compact_k x D
      gathered per exchange, proportional to DEMAND, not fleet width.
      Each shard scatter-adds the gathered triples back into the dense
      [D, 2, N] accumulator (assign.scatter_demand), so the prefix
      reduction below consumes byte-identical inputs and the accepts
      stay bit-identical to the dense path.  The accepted exchange rides
      the same compacted node list (accepted nodes are a subset of
      demand nodes), replacing the dense psum with a second 12 B x
      compact_k x D gather + local shard-axis sum.

    1. local: rank + exclusive cumulative cost among same-node
       candidates of THIS shard, and the [2, N] (count, cost-sum)
       demand block (assign.local_bid_demand);
    2. exchange the demand blocks along ``axis`` -> [D, 2, N] (dense
       all_gather, or compacted gather + scatter-add); the
       earlier-shards prefix (shard-major, matching the gathered
       bucket's candidate order) lifts local rank/cum-cost to global;
    3. the replicated waterfill's accept predicate, evaluated locally
       (assign.waterfill_accept_presplit);
    4. exchange the accepted (count, cost) block so load/rem_cap stay
       replicated (psum dense, gather+sum compacted) — integer counts
       exact, cost sums exact for integer costs (ulp-order-different
       otherwise).
    """
    n_padded = load.shape[0]
    rank_l, cum_l, demand = local_bid_demand(cand, choice, cost, n_padded)
    d = jax.lax.axis_index(axis)
    if compact_k is None:
        demand_g = jax.lax.all_gather(demand, axis)        # [D, 2, N]
    else:
        comp, comp_idx = compact_demand(demand, compact_k)  # [3, k], [k]
        comp_g = jax.lax.all_gather(comp, axis)            # [D, 3, k]
        demand_g = scatter_demand(comp_g, n_padded)        # [D, 2, N]
    nsh = demand_g.shape[0]
    before = (jnp.arange(nsh) < d)[:, None, None]
    prefix = jnp.sum(jnp.where(before, demand_g, 0.0), axis=0)  # [2, N]
    tot_w = jnp.sum(demand_g[:, 1, :])
    safe = jnp.clip(choice, 0, n_padded - 1)
    rank_g = prefix[0][safe].astype(jnp.int32) + rank_l
    cum_g = prefix[1][safe] + cum_l
    accept = waterfill_accept_presplit(
        cand, choice, cost, load, rem_cap, is_final, rank_g, cum_g, tot_w)
    a32 = accept.astype(jnp.float32)
    acc = jnp.stack([
        jnp.zeros(n_padded, jnp.float32).at[safe].add(a32),
        jnp.zeros(n_padded, jnp.float32).at[safe].add(
            jnp.where(accept, cost, 0.0))])
    if compact_k is None:
        upd = jax.lax.psum(acc, axis)
    else:
        # accepted nodes are candidate nodes, so the demand compaction's
        # node list covers them; ship (idx, acc_cnt, acc_cost) triples
        acc_comp = jnp.stack([comp[0], acc[0][comp_idx], acc[1][comp_idx]])
        acc_g = jax.lax.all_gather(acc_comp, axis)         # [D, 3, k]
        upd = jnp.sum(scatter_demand(acc_g, n_padded), axis=0)
    load = load + upd[1]
    rem_cap = rem_cap - upd[0].astype(jnp.int32)
    return accept, load, rem_cap


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (AXIS,))


def make_mesh2d(dj: int, dn: int) -> Mesh:
    """2-D mesh (jobs x nodes): shards the [J, N] eligibility matrix both
    ways.  The jobs axis is the capacity axis (schedule state); the nodes
    axis exists for fleets whose bitpacked matrix exceeds one device's HBM
    even after jobs-sharding (1M x 100k nodes is ~12 GB)."""
    devs = jax.devices()
    if dj * dn > len(devs):
        raise ValueError(f"need {dj * dn} devices, have {len(devs)}")
    return Mesh(np.array(devs[:dj * dn]).reshape(dj, dn), (AXIS, NAXIS))


def _tick_local(fire_col, elig, exclusive, cost, load, rem_cap,
                k_local: int, rounds: int, bid, fanout,
                shard_bids: bool = False, compact_k=None):
    """One second of the jobs-mesh plan, per shard: local compact + bid,
    then the per-round reconcile — bucket-sharded (demand exchange,
    ``shard_bids=True``; dense [2, N] or compacted triples per
    ``compact_k``) or the replicated waterfill on the gathered candidate
    bucket (O(K)).  THE single definition — both the per-tick body and
    the fused windowed scan call it, so their semantics cannot drift."""
    d = jax.lax.axis_index(AXIS)
    j_local = elig.shape[0]
    idx, valid, total = _compact(fire_col, k_local)
    packed_k = elig[idx]
    excl_k = exclusive[idx]
    cost_k = cost[idx].astype(jnp.float32)

    # Common fan-out: local partial load, summed across shards.
    common_w = jnp.where(valid & ~excl_k, cost_k, 0.0)
    load = load + jax.lax.psum(fanout(packed_k, common_w), AXIS)

    need0 = valid & excl_k
    assigned = jnp.full(k_local, -1, dtype=jnp.int32)
    for r in range(rounds):
        load_eff = jnp.where(rem_cap > 0, load, jnp.inf)
        best, choice = bid(packed_k, load_eff)
        cand_l = need0 & (assigned < 0) & jnp.isfinite(best)
        if shard_bids:
            accept_l, load, rem_cap = _reconcile_sharded(
                cand_l, choice, cost_k, load, rem_cap,
                r == rounds - 1, AXIS, compact_k=compact_k)
        else:
            # Exchange compacted bids; every shard sees the same global
            # bucket.
            cand_g = jax.lax.all_gather(cand_l, AXIS, tiled=True)
            choice_g = jax.lax.all_gather(choice, AXIS, tiled=True)
            cost_g = jax.lax.all_gather(cost_k, AXIS, tiled=True)
            accept_g, load, rem_cap = waterfill_accept(
                cand_g, choice_g, cost_g, load, rem_cap, r == rounds - 1)
            accept_l = jax.lax.dynamic_slice(
                accept_g, (d * k_local,), (k_local,))
        assigned = jnp.where(accept_l, choice, assigned)

    idx_global = jnp.where(jnp.arange(k_local) < total,
                           d * j_local + idx, -1).astype(jnp.int32)
    total_row = jnp.zeros_like(idx).at[0].set(total)
    out = jnp.stack([idx_global, total_row, assigned], axis=0)  # [3, k_local]
    return out, load, rem_cap


def _sharded_plan_body(table, fields, elig, exclusive, cost, load, rem_cap,
                       k_local: int, rounds: int, impl: str,
                       shard_bids: bool, compact_k=None):
    """Runs per-shard inside shard_map.  All [J/D]-shaped inputs are the
    local shard; load/rem_cap are replicated."""
    bid, fanout = _steps(impl)
    f = [fields[i:i + 1] for i in range(7)]
    fire = _fire_mask_jit(table, *f)[:, 0]
    return _tick_local(fire, elig, exclusive, cost, load, rem_cap,
                       k_local, rounds, bid, fanout, shard_bids, compact_k)


def _sharded_window_body(table, fields_w, elig, exclusive, cost, load,
                         rem_cap, k_local: int, rounds: int, impl: str,
                         shard_bids: bool, compact_k=None):
    """Fused windowed plan per shard: W seconds under one lax.scan with
    the tick collectives inside — the production cadence (plan ahead of
    wall-clock, one dispatch per window) composed with the jobs mesh.
    Identical semantics to W sequential _sharded_plan_body calls by
    construction: both run _tick_local."""
    bid, fanout = _steps(impl)
    cols = [fields_w[:, i] for i in range(7)]
    with jax.named_scope("cronsun.fire_mask"):
        fire_w = _fire_mask_jit(table, *cols)          # [J/D, W]

    def body(carry, fire_col):
        load, rem_cap = carry
        out, load, rem_cap = _tick_local(
            fire_col, elig, exclusive, cost, load, rem_cap,
            k_local, rounds, bid, fanout, shard_bids, compact_k)
        return (load, rem_cap), out

    (load, rem_cap), outs = jax.lax.scan(body, (load, rem_cap), fire_w.T)
    return outs, load, rem_cap                  # [W, 3, k_local]


def _tick2d_local(fire, elig, exclusive, cost, load, rem_cap,
                  k_local: int, rounds: int, impl: str, bid_k, fanout,
                  shard_bids: bool = False, compact_k=None,
                  node_block_fanout: bool = False):
    """One second of the (jobs x nodes) mesh plan, per device — THE
    single definition shared by the per-tick body and the fused windowed
    scan (same no-drift contract as the 1-D _tick_local).

    Collectives per tick: one all_gather of the Common fan-out block
    along nodes (O(N)), and per bid round one (best, choice) exchange
    along nodes (O(Dn*K)) + the candidate exchange along jobs (O(K)) —
    never anything proportional to J or the matrix.

    Tie order: with impl="jnp" the block bid breaks exact-score ties by
    lowest GLOBAL node id, which composes exactly with the cross-shard
    argmin reduce — placements are invariant to how columns are split.
    With impl="pallas" (the HBM-efficient path over bitpacked words) the
    in-block order is the kernel's bit-plane scan with a block-local tie
    hash: still fully deterministic for a fixed mesh shape (what failover
    replay needs — replicas run the same mesh), but a different shape can
    break ties differently."""
    from ..ops.assign import bid_block_jnp
    dj = jax.lax.axis_index(AXIS)
    dn = jax.lax.axis_index(NAXIS)
    j_local = elig.shape[0]
    n_local = elig.shape[1] * 32
    col0 = dn * n_local

    idx, valid, total = _compact(fire, k_local)
    packed_k = elig[idx]
    excl_k = exclusive[idx]
    cost_k = cost[idx].astype(jnp.float32)

    # Common fan-out: per-block partial -> sum along jobs -> concat along
    # nodes; load stays replicated everywhere.  Order of the two
    # collectives is the node-block knob: reducing FIRST (``True``, the
    # >=64k-node default) psums only this device's [N/Dn] block — each
    # (jobs-column, node-block) group reduces its own block and one
    # gather assembles — instead of psumming the full [N]; elementwise
    # sum and concat commute, so the assembled load is the same array
    # either way (pinned by differential test).
    common_w = jnp.where(valid & ~excl_k, cost_k, 0.0)
    block = fanout(packed_k, common_w)                         # [n_local]
    if node_block_fanout:
        blk = jax.lax.psum(block, AXIS)                        # [n_local]
        load = load + jax.lax.all_gather(blk, NAXIS, tiled=True)
    else:
        full = jax.lax.all_gather(block, NAXIS, tiled=True)    # [N]
        load = load + jax.lax.psum(full, AXIS)

    def bid_block(packed, load_blk):
        if impl in ("jnp", "mixed"):
            # mixed = jnp bid (the split-invariant tie order) + pallas
            # fanout (fetched from _steps above)
            best, choice = bid_block_jnp(packed, load_blk, col0=col0,
                                         bitplane_ties=False)
        else:
            best, choice = bid_k(packed, load_blk)
            choice = choice + col0
        return best, jnp.where(jnp.isfinite(best), choice, 0)

    need0 = valid & excl_k
    assigned = jnp.full(k_local, -1, dtype=jnp.int32)
    for r in range(rounds):
        load_eff = jnp.where(rem_cap > 0, load, jnp.inf)
        load_blk = jax.lax.dynamic_slice(load_eff, (col0,), (n_local,))
        best_l, choice_l = bid_block(packed_k, load_blk)
        # argmin reduce across the nodes axis: min score, ties to the
        # lowest global node id (deterministic)
        bests = jax.lax.all_gather(best_l, NAXIS)              # [Dn, k]
        choices = jax.lax.all_gather(choice_l, NAXIS)
        best = jnp.min(bests, axis=0)
        is_min = (bests == best[None, :]) & jnp.isfinite(bests)
        choice = jnp.min(jnp.where(is_min, choices, jnp.int32(1) << 30),
                         axis=0)
        choice = jnp.where(jnp.isfinite(best), choice, 0)
        cand_l = need0 & (assigned < 0) & jnp.isfinite(best)
        if shard_bids:
            # demand-summary exchange along jobs (dense O(N) or
            # compacted O(compact_k) per compact_k); the node-axis
            # argmin reduce above already made `choice` global
            accept_l, load, rem_cap = _reconcile_sharded(
                cand_l, choice, cost_k, load, rem_cap,
                r == rounds - 1, AXIS, compact_k=compact_k)
        else:
            # candidate exchange along jobs; identical accept on every
            # shard
            cand_g = jax.lax.all_gather(cand_l, AXIS, tiled=True)
            choice_g = jax.lax.all_gather(choice, AXIS, tiled=True)
            cost_g = jax.lax.all_gather(cost_k, AXIS, tiled=True)
            accept_g, load, rem_cap = waterfill_accept(
                cand_g, choice_g, cost_g, load, rem_cap, r == rounds - 1)
            accept_l = jax.lax.dynamic_slice(accept_g, (dj * k_local,),
                                             (k_local,))
        assigned = jnp.where(accept_l, choice, assigned)

    idx_global = jnp.where(jnp.arange(k_local) < total,
                           dj * j_local + idx, -1).astype(jnp.int32)
    total_row = jnp.zeros_like(idx).at[0].set(total)
    out = jnp.stack([idx_global, total_row, assigned], axis=0)
    return out, load, rem_cap


def _sharded2d_plan_body(table, fields, elig, exclusive, cost, load,
                         rem_cap, k_local: int, rounds: int, impl: str,
                         shard_bids: bool, compact_k=None,
                         node_block_fanout: bool = False):
    """Per-tick body over the (jobs, nodes) mesh — fire mask + one
    _tick2d_local."""
    bid_k, fanout = _steps(impl)
    f = [fields[i:i + 1] for i in range(7)]
    fire = _fire_mask_jit(table, *f)[:, 0]
    return _tick2d_local(fire, elig, exclusive, cost, load, rem_cap,
                         k_local, rounds, impl, bid_k, fanout, shard_bids,
                         compact_k, node_block_fanout)


def _sharded2d_window_body(table, fields_w, elig, exclusive, cost, load,
                           rem_cap, k_local: int, rounds: int, impl: str,
                           shard_bids: bool, compact_k=None,
                           node_block_fanout: bool = False):
    """Fused windowed plan over the 2-D mesh: W seconds under one
    lax.scan with all collectives inside — one dispatch per window (the
    RTT-amortizing production cadence, same as the 1-D planner's fused
    path).  Identical semantics to W sequential plans by construction:
    both run _tick2d_local."""
    bid_k, fanout = _steps(impl)
    cols = [fields_w[:, i] for i in range(7)]
    with jax.named_scope("cronsun.fire_mask"):
        fire_w = _fire_mask_jit(table, *cols)          # [J/Dj, W]

    def body(carry, fire_col):
        load, rem_cap = carry
        out, load, rem_cap = _tick2d_local(
            fire_col, elig, exclusive, cost, load, rem_cap,
            k_local, rounds, impl, bid_k, fanout, shard_bids,
            compact_k, node_block_fanout)
        return (load, rem_cap), out

    (load, rem_cap), outs = jax.lax.scan(body, (load, rem_cap), fire_w.T)
    return outs, load, rem_cap                  # [W, 3, k_local]


class _ShardedPlannerBase:
    """State surface + plan decode shared by the mesh planners.  A
    subclass provides ``_elig_spec`` (how the matrix shards), ``Dj`` (the
    jobs-axis size the fired bucket divides over), a node ``word_align``,
    and ``_body`` (the shard_map body factory)."""

    def _init_common(self, mesh: Mesh, job_capacity: int,
                     node_capacity: int, rounds: int, impl: str,
                     max_fire_bucket: int, tz, word_align: int,
                     shard_bids: bool = True,
                     demand_format: str = "auto",
                     node_block_psum=None):
        import datetime
        self.mesh = mesh
        self.tz = tz or datetime.timezone.utc
        self.rounds = rounds
        self.impl = impl
        # bucket-sharded bidding (O(nodes) demand exchange per round) is
        # the default; False keeps the replicated waterfill over the
        # gathered candidate bucket (O(fired x k)) as the reference /
        # rollback path — the randomized differential test pins the two
        # fire-set-identical
        self.shard_bids = shard_bids
        # demand wire format for the sharded reconcile: "dense" ([2, N]
        # blocks, bucket-independent), "compacted" ((idx, count, cost)
        # triples — 12 B x min(k_local, N) x D, proportional to demand:
        # the sparse-tick/wide-fleet corner), or "auto" (per-plan pick
        # by the estimate_collective_bytes crossover at the resolved
        # bucket — _resolve_demand_format, the _resolve_impl pattern).
        # Both formats produce bit-identical accepts (differential-
        # pinned); the knob is the pin/rollback.
        if demand_format not in ("auto", "dense", "compacted"):
            raise ValueError(f"demand_format {demand_format!r} not in "
                             "auto/dense/compacted")
        self.demand_format = demand_format
        self.J = _next_pow2(max(job_capacity, self.Dj * 256))
        if self.J % self.Dj:
            raise ValueError("job capacity must shard evenly")
        self.N = ((node_capacity + word_align - 1)
                  // word_align) * word_align
        # node-block-sharded Common fan-out (2-D meshes): psum only this
        # device's [N/Dn] block along the jobs axis, then gather — the
        # full-[N] psum compiles out at >=NODE_BLOCK_PSUM_MIN_N widths
        # (None = auto by width; True/False pins).  1-D meshes have no
        # node axis to block over.
        dn_ = getattr(self, "Dn", 1)
        if node_block_psum is None:
            node_block_psum = (dn_ > 1
                               and self.N >= NODE_BLOCK_PSUM_MIN_N)
        self.node_block_psum = bool(node_block_psum) and dn_ > 1
        self.max_fire_bucket = max_fire_bucket
        self._shard = NamedSharding(mesh, P(AXIS))
        self._shard2 = NamedSharding(mesh, self._elig_spec)
        self._repl = NamedSharding(mesh, P())

        from ..ops.schedule_table import build_table
        self.table = build_table([], capacity=self.J, sharding=self._shard)
        # allocated on the devices, sharded at creation: at 1M x 100k
        # nodes a host-built zero matrix is 13.4 GB to page in and ship
        self.elig = jnp.zeros((self.J, self.N // 32), jnp.uint32,
                              device=self._shard2)
        self.exclusive = jax.device_put(np.zeros(self.J, bool), self._shard)
        self.cost = jax.device_put(np.ones(self.J, np.float32), self._shard)
        self.load = jax.device_put(np.zeros(self.N, np.float32), self._repl)
        self.rem_cap = jax.device_put(np.zeros(self.N, np.int32), self._repl)
        self._step_cache = {}
        # mesh tick observability: per-tick plan latency ring + phase /
        # collective counters, surfaced by stats_snapshot() and rendered
        # at /v1/metrics as cronsun_mesh_tick_* (the scheduler publishes
        # a second leased snapshot under component "mesh")
        from ..metrics import LatencyRing
        self.tick_ms = LatencyRing()
        self._ticks_total = 0
        self._collective_bytes_total = 0
        self._compacted_bytes_total = 0      # bytes of compacted rounds
        self._compacted_ticks_total = 0      # ticks the compacted path ran
        self._last_k_local = 0
        self._last_demand_format = ("dense" if not self.shard_bids
                                    else self.demand_format)
        self._phase_profile: dict = {}
        # multi-host meshes (jax.distributed over DCN / Gloo): per-shard
        # plan outputs span non-addressable devices, so fetching them
        # needs a cross-process allgather; single-host fetches stay a
        # plain device read
        self._multiprocess = jax.process_count() > 1

    def _fetch(self, arr) -> np.ndarray:
        if self._multiprocess:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(
                arr, tiled=True))
        return np.asarray(arr)

    def _step(self, k_local: int, impl: str, fmt: str = "dense"):
        key = (k_local, impl, self.shard_bids, fmt, self.node_block_psum)
        if key not in self._step_cache:
            sm = _shard_map(
                self._body(k_local, impl, fmt), mesh=self.mesh,
                in_specs=(P(AXIS), P(), self._elig_spec, P(AXIS), P(AXIS),
                          P(), P()),
                out_specs=(P(None, AXIS), P(), P()))
            self._step_cache[key] = jax.jit(sm)
        return self._step_cache[key]

    # -- state maintenance -------------------------------------------------

    def set_table(self, table: ScheduleTable):
        if table.capacity != self.J:
            raise ValueError(f"table capacity {table.capacity} != {self.J}")
        self.table = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._shard), table)

    # one definition, two planners: set_table is the polymorphic point
    # (it re-pins the canonical sharding here), and the hostsync op-log
    # replay depends on both classes agreeing on this contract
    update_table_rows = TickPlanner.update_table_rows

    def set_load(self, loads: np.ndarray) -> None:
        self.load = np.asarray(loads, np.float32)   # setter re-pins

    def set_eligibility(self, matrix: np.ndarray):
        self.elig = jax.device_put(matrix, self._shard2)

    def set_job_meta_full(self, exclusive: np.ndarray, cost: np.ndarray):
        self.exclusive = jax.device_put(exclusive, self._shard)
        self.cost = jax.device_put(cost.astype(np.float32), self._shard)

    def set_node_capacity_full(self, caps: np.ndarray):
        self.rem_cap = jax.device_put(caps.astype(np.int32), self._repl)

    # row-wise incremental setters (the SchedulerService's watch->delta
    # surface — same contract as ops.planner.TickPlanner); scatters on
    # sharded arrays re-pin to the canonical sharding afterwards

    def set_eligibility_rows(self, rows: np.ndarray, values: np.ndarray):
        if len(rows):
            self.elig = jax.device_put(
                self.elig.at[jnp.asarray(rows)].set(jnp.asarray(values)),
                self._shard2)

    def set_job_meta(self, rows: np.ndarray, exclusive: np.ndarray,
                     cost: np.ndarray):
        if len(rows):
            r = jnp.asarray(np.asarray(rows, np.int32))
            self.exclusive = jax.device_put(
                self.exclusive.at[r].set(jnp.asarray(exclusive)),
                self._shard)
            self.cost = jax.device_put(
                self.cost.at[r].set(
                    jnp.asarray(cost).astype(jnp.float32)), self._shard)

    def set_node_capacity(self, cols, caps):
        if len(cols):
            c = jnp.asarray(np.asarray(cols, np.int32))
            self.rem_cap = jax.device_put(
                self.rem_cap.at[c].set(
                    jnp.asarray(np.asarray(caps, np.int32))), self._repl)

    # load is assigned wholesale by the service's capacity reconciliation;
    # re-pin whatever it assigns to the replicated sharding
    @property
    def load(self):
        return self._load

    @load.setter
    def load(self, v):
        self._load = jax.device_put(jnp.asarray(v), self._repl)

    def job_finished(self, node_col: int, cost: float):
        self.rem_cap = self.rem_cap.at[node_col].add(1)
        self.load = self.load.at[node_col].add(-float(cost))

    def common_finished(self, node_col: int, cost: float):
        self.load = self.load.at[node_col].add(-float(cost))

    def decay_load(self, factor: float = 0.99):
        self.load = self.load * factor

    # -- tick --------------------------------------------------------------

    def _resolve_impl(self, k_local: int) -> str:
        if self.impl != "auto":
            return self.impl
        # the 2-D mesh divides the node width by Dn before it reaches a
        # device; choose_impl holds the shared measured heuristic
        from ..ops.assign import choose_impl
        return choose_impl(self.N // getattr(self, "Dn", 1), k_local)

    def first_window_impl(self) -> str:
        """The kernel variant an unpinned plan resolves to."""
        return self._resolve_impl(
            max(256, _next_pow2(self.max_fire_bucket) // self.Dj))

    def _resolve_demand_format(self, k_local: int) -> str:
        """Static per-plan pick of the demand wire format (the
        _resolve_impl pattern: k_local is static per compiled program,
        so the choice is host-side — no collective inside a cond).
        "auto" compares the compacted vs dense branch of the byte
        model at this bucket; an explicit pin wins; the replicated
        path has no demand exchange to format."""
        return self.estimate_collective_bytes(
            k_local=k_local)["demand_format"]

    def _compact_k(self, k_local: int, fmt: str):
        # a shard's demand touches at most min(#candidates, N) distinct
        # nodes, so this pad never truncates (see ops.assign.compact_demand)
        return min(k_local, self.N) if fmt == "compacted" else None

    def _decode(self, o, epoch_s: int, k_local: int) -> TickPlan:
        """[3, Dj*k_local] per-shard-concatenated output -> TickPlan."""
        fired, assigned, total = [], [], 0
        for s in range(self.Dj):
            t_s = int(o[1, s * k_local])
            total += t_s
            n_s = min(t_s, k_local)
            fired.append(o[0, s * k_local:s * k_local + n_s])
            assigned.append(o[2, s * k_local:s * k_local + n_s])
        fired = np.concatenate(fired)
        assigned = np.concatenate(assigned)
        return TickPlan(epoch_s=epoch_s, fired=fired, assigned=assigned,
                        overflow=max(0, total - len(fired)),
                        total_fired=total)

    def plan(self, epoch_s: int, sla_bucket: Optional[int] = None) -> TickPlan:
        import time as _time
        k = sla_bucket or self.max_fire_bucket
        k_local = max(256, _next_pow2(k) // self.Dj)
        impl = self._resolve_impl(k_local)
        fmt = self._resolve_demand_format(k_local)
        f = window_fields(epoch_s, 1, tz=self.tz)
        fields = np.array([f["sec"][0], f["min"][0], f["hour"][0],
                           f["dom"][0], f["month"][0], f["dow"][0],
                           epoch_s - FRAMEWORK_EPOCH], dtype=np.int32)
        t0 = _time.perf_counter()
        out, self.load, self.rem_cap = self._step(k_local, impl, fmt)(
            self.table, jax.device_put(fields, self._repl), self.elig,
            self.exclusive, self.cost, self.load, self.rem_cap)
        o = self._fetch(out)             # [3, Dj*k_local]
        self._account_ticks(1, (_time.perf_counter() - t0) * 1e3, k_local,
                            fmt)
        return self._decode(o, epoch_s, k_local)

    def _window_step(self, k_local: int, impl: str, fmt: str = "dense"):
        key = ("window", k_local, impl, self.shard_bids, fmt,
               self.node_block_psum)
        if key not in self._step_cache:
            sm = _shard_map(
                self._window_body(k_local, impl, fmt), mesh=self.mesh,
                in_specs=(P(AXIS), P(), self._elig_spec, P(AXIS), P(AXIS),
                          P(), P()),
                out_specs=(P(None, None, AXIS), P(), P()))
            self._step_cache[key] = jax.jit(sm)
        return self._step_cache[key]

    def plan_window(self, epoch_s: int, window_s: int, sla_bucket=None):
        """Fused windowed scan over the mesh: W seconds, ONE dispatch
        (the RTT-amortizing production cadence composed with multichip) —
        semantics identical to W sequential plans, collectives inside the
        scan."""
        from ..ops.schedule_table import FRAMEWORK_EPOCH as FE
        k = sla_bucket or self.max_fire_bucket
        k_local = max(256, _next_pow2(k) // self.Dj)
        impl = self._resolve_impl(k_local)
        fmt = self._resolve_demand_format(k_local)
        f = window_fields(epoch_s, window_s, tz=self.tz)
        fields_w = np.stack([
            f["sec"], f["min"], f["hour"], f["dom"], f["month"], f["dow"],
            np.arange(window_s, dtype=np.int64) + (epoch_s - FE),
        ], axis=1).astype(np.int32)
        import time as _time
        t0 = _time.perf_counter()
        outs, self.load, self.rem_cap = self._window_step(
            k_local, impl, fmt)(
            self.table, jax.device_put(fields_w, self._repl), self.elig,
            self.exclusive, self.cost, self.load, self.rem_cap)
        o = self._fetch(outs)            # [W, 3, Dj*k_local]
        self._account_ticks(window_s, (_time.perf_counter() - t0) * 1e3,
                            k_local, fmt)
        return [self._decode(o[w], epoch_s + w, k_local)
                for w in range(window_s)]

    # -- observability -----------------------------------------------------

    def _account_ticks(self, n_ticks: int, total_ms: float, k_local: int,
                       fmt: str = "dense"):
        # ONE ring sample per plan call (the window-averaged per-tick
        # ms): repeating it per tick would let a single long window
        # evict every real sample and flatten p99 onto p50
        self.tick_ms.add(total_ms / max(1, n_ticks))
        self._ticks_total += n_ticks
        self._last_k_local = k_local
        self._last_demand_format = fmt
        est = self.estimate_collective_bytes(k_local=k_local,
                                             demand_format=fmt)
        self._collective_bytes_total += n_ticks * est["per_tick"]
        if fmt == "compacted":
            self._compacted_ticks_total += n_ticks
            self._compacted_bytes_total += (
                n_ticks * self.rounds * est["compacted_per_round"])

    def estimate_collective_bytes(self, sla_bucket: Optional[int] = None,
                                  k_local: Optional[int] = None,
                                  demand_format: Optional[str] = None,
                                  ) -> dict:
        """Analytic per-tick inter-chip payload model at the planner's
        shapes — the number the bench ladder reports and the slow-tier
        gate compares.  ONE convention for every collective: the full
        GATHERED output size for an all_gather (each device materializes
        D x the per-shard payload; a ring moves ~that much past every
        device), the logical payload once for a psum (reduce, not
        replicate):

        - replicated round: candidate triple all_gather — (1+4+4) B x
          Dj*k_local gathered — linear in the fired bucket;
        - sharded round: [2, N] f32 demand all_gather (8N x Dj
          gathered) + [2, N] f32 accepted psum (8N) — independent of
          the bucket but NOT of Dj: 8N*(Dj+1).  The crossover is
          therefore 9*K vs 8N*(Dj+1): sharded bidding wins once the
          fired bucket K clears ~0.9 x N x (Dj+1) rows — the herd
          regime the optimization targets; at sparse ticks on wide
          fleets (K below that) the replicated exchange is smaller
          (see ROADMAP: compacted demand gather);
        - compacted round: the same demand exchange as (idx, count,
          cost) f32 triples padded to k_comp = min(k_local, N) — two
          [3, k_comp] all_gathers (demand out, accepted back), 12 B x
          k_comp x Dj gathered each: 24*k_comp*Dj per round,
          proportional to DEMAND instead of fleet width.  vs dense
          8N(Dj+1) the crossover sits near k_comp ~ N(Dj+1)/(3Dj) ~
          N/3: sparse ticks on wide fleets go compacted, the herd
          regime stays dense ("auto" picks per plan from this model);
        - 2-D meshes add the node-axis (best, choice) reduce — 8 B x
          Dn*k_local gathered per round — and the [N] Common fan-out
          gather; both paths pay those identically.  With node-block
          psum the Common fan-out reduces only this shard's [N/Dn]
          block along jobs (4N/Dn) before the [N] assembly gather.
        """
        if k_local is None:
            k = sla_bucket or self.max_fire_bucket
            k_local = max(256, _next_pow2(k) // self.Dj)
        N = self.N
        dn = getattr(self, "Dn", 1)
        k_comp = min(k_local, N)
        repl_round = 9 * self.Dj * k_local
        shard_round = 2 * N * 4 * (self.Dj + 1)
        comp_round = 2 * 3 * 4 * k_comp * self.Dj
        if dn > 1:                       # fanout psum + 2-D assembly gather
            common = (4 * N // dn if self.node_block_psum else 4 * N) + 4 * N
        else:
            common = 4 * N
        naxis_round = 8 * dn * k_local if dn > 1 else 0
        fmt = demand_format
        if fmt is None:
            fmt = self.demand_format if self.shard_bids else "dense"
        if fmt == "auto":
            fmt = "compacted" if comp_round < shard_round else "dense"
        mine = (repl_round if not self.shard_bids
                else comp_round if fmt == "compacted" else shard_round)
        return {
            "replicated_per_round": repl_round + naxis_round,
            "sharded_per_round": shard_round + naxis_round,
            "compacted_per_round": comp_round + naxis_round,
            "per_round": mine + naxis_round,
            "per_tick": self.rounds * (mine + naxis_round) + common,
            "k_local": k_local,
            "demand_format": fmt if self.shard_bids else "dense",
        }

    def measured_collective_bytes(self, sla_bucket: Optional[int] = None,
                                  demand_format: Optional[str] = None):
        """Per-tick collective bytes as actually COMPILED: lower the
        single-tick step at the planner's current shapes and sum the
        collective-op result shapes out of the HLO text, under the same
        convention as estimate_collective_bytes (gathered output size
        for an all-gather, logical payload once for a reduce).  The
        bench ladder reports this next to the analytic estimate so a
        crossover-model drift is a bench fact, not a hope.  Returns
        None when the backend's compiled text isn't inspectable."""
        import re
        k = sla_bucket or self.max_fire_bucket
        k_local = max(256, _next_pow2(k) // self.Dj)
        impl = self._resolve_impl(k_local)
        fmt = (demand_format if demand_format in ("dense", "compacted")
               else self._resolve_demand_format(k_local))
        f = window_fields(0, 1, tz=self.tz)
        fields = np.array([f["sec"][0], f["min"][0], f["hour"][0],
                           f["dom"][0], f["month"][0], f["dow"][0],
                           -FRAMEWORK_EPOCH], dtype=np.int32)
        try:
            txt = self._step(k_local, impl, fmt).lower(
                self.table, jax.device_put(fields, self._repl), self.elig,
                self.exclusive, self.cost, self.load,
                self.rem_cap).compile().as_text()
        except Exception:
            return None
        widths = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                  "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                  "s64": 8, "u64": 8, "f64": 8}
        shape_re = re.compile(r"\b([a-z]+\d*)\[([0-9,]*)\]")
        total = 0
        for line in txt.splitlines():
            m = re.search(r"=\s*(\(?[^)]*?\)?)\s*"
                          r"(all-gather|all-reduce|reduce-scatter)\(", line)
            if not m:
                continue
            for dt, dims in shape_re.findall(m.group(1)):
                if dt not in widths:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += n * widths[dt]
        return total if total else None

    def profile_phases(self, sla_bucket: Optional[int] = None,
                       iters: int = 10) -> dict:
        """Per-phase microbench at the planner's CURRENT shapes: one
        bid sweep, one round's collective exchange, one round's
        waterfill/reconcile math — each timed as its own jitted program
        (phases inside the fused shard_map step can't be timed in
        situ).  Returns {bid_ms, gather_ms, reconcile_ms} per round and
        caches the result for stats_snapshot()."""
        import time as _time
        k = sla_bucket or self.max_fire_bucket
        k_local = max(256, _next_pow2(k) // self.Dj)
        impl = self._resolve_impl(k_local)
        bid, _ = _steps(impl)
        dn = getattr(self, "Dn", 1)
        w32 = self.N // 32 // dn
        rng = np.random.default_rng(0)
        packed = jnp.asarray(
            rng.integers(0, 2**32, (k_local, w32), dtype=np.uint32))
        load = jnp.asarray(rng.random(w32 * 32).astype(np.float32))
        loadN = jnp.asarray(rng.random(self.N).astype(np.float32))
        cap = jnp.full(self.N, 4, jnp.int32)
        cand = jnp.asarray(rng.random(k_local) < 0.5)
        choice = jnp.asarray(
            rng.integers(0, self.N, k_local).astype(np.int32))
        cost = jnp.ones(k_local, jnp.float32)

        bid_f = jax.jit(lambda p, l: bid(p, l))

        if self.shard_bids:
            fmt = self._resolve_demand_format(k_local)
            if fmt == "compacted":
                # two triple gathers (demand out, accepted back) — the
                # +1.0 defeats CSE folding them into one collective
                k_comp = min(k_local, self.N)

                def gather_body(c3):
                    g1 = jax.lax.all_gather(c3, AXIS)
                    g2 = jax.lax.all_gather(c3 + 1.0, AXIS)
                    return g1.sum(0) + g2.sum(0)
                gather_arg = (jnp.zeros((3, k_comp), jnp.float32),)
            else:
                def gather_body(d2):
                    g = jax.lax.all_gather(d2, AXIS)
                    return jax.lax.psum(d2, AXIS) + g.sum(0)
                gather_arg = (jnp.zeros((2, self.N), jnp.float32),)
            gather_f = jax.jit(_shard_map(
                gather_body, mesh=self.mesh,
                in_specs=(P(),), out_specs=P()))

            def rec_f(cand, choice, cost, load, cap):
                rank, cum, demand = local_bid_demand(
                    cand, choice, cost, self.N)
                acc = waterfill_accept_presplit(
                    cand, choice, cost, load, cap, False, rank, cum,
                    jnp.sum(demand[1]))
                return acc, demand
            rec_f = jax.jit(rec_f)
            rec_args = (cand, choice, cost, loadN, cap)
        else:
            def gather_body(c, ch, co):
                return (jax.lax.all_gather(c, AXIS, tiled=True),
                        jax.lax.all_gather(ch, AXIS, tiled=True),
                        jax.lax.all_gather(co, AXIS, tiled=True))
            gather_f = jax.jit(_shard_map(
                gather_body, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), P(AXIS)),
                out_specs=(P(), P(), P())))
            gather_arg = (
                jax.device_put(np.zeros(self.Dj * k_local, bool),
                               self._shard),
                jax.device_put(np.zeros(self.Dj * k_local, np.int32),
                               self._shard),
                jax.device_put(np.zeros(self.Dj * k_local, np.float32),
                               self._shard))
            K = self.Dj * k_local
            cand_g = jnp.asarray(rng.random(K) < 0.5)
            choice_g = jnp.asarray(
                rng.integers(0, self.N, K).astype(np.int32))
            rec_f = jax.jit(partial(waterfill_accept, is_final=False))
            rec_args = (cand_g, choice_g, jnp.ones(K, jnp.float32),
                        loadN, cap)

        def timed(fn, args):
            out = fn(*args)
            jax.tree_util.tree_map(
                lambda a: getattr(a, "block_until_ready", lambda: a)(),
                out)
            best = np.inf
            for _ in range(iters):
                s = _time.perf_counter()
                out = fn(*args)
                jax.tree_util.tree_map(
                    lambda a: getattr(a, "block_until_ready",
                                      lambda: a)(), out)
                best = min(best, _time.perf_counter() - s)
            return best * 1e3

        prof = {
            "bid_ms": round(timed(bid_f, (packed, load)), 4),
            "gather_ms": round(timed(gather_f, gather_arg), 4),
            "reconcile_ms": round(timed(rec_f, rec_args), 4),
        }
        self._phase_profile = prof
        return prof

    def stats_snapshot(self) -> dict:
        """Leased-metrics snapshot (component "mesh"): per-tick latency
        distribution, tick totals, the analytic collective-bytes
        estimate, and the last per-phase microbench if one ran."""
        est = self.estimate_collective_bytes(
            k_local=self._last_k_local or None,
            demand_format=self._last_demand_format)
        return {
            "tick_p50_ms": round(self.tick_ms.percentile(0.50), 3),
            "tick_p99_ms": round(self.tick_ms.percentile(0.99), 3),
            "ticks_total": self._ticks_total,
            "collective_bytes_total": self._collective_bytes_total,
            "collective_bytes_per_tick": est["per_tick"],
            "collective_bytes_per_round": est["per_round"],
            "compacted_bytes_total": self._compacted_bytes_total,
            "compacted_ticks_total": self._compacted_ticks_total,
            # string field: /v1/metrics renders it as the demand_format
            # LABEL on every cronsun_mesh_tick_* sample, not a gauge
            "demand_format": est["demand_format"],
            "node_block_psum": 1 if self.node_block_psum else 0,
            "devices": int(self.mesh.devices.size),
            "shard_bids": 1 if self.shard_bids else 0,
            "rounds": self.rounds,
            **{f"phase_{k}": v for k, v in self._phase_profile.items()},
        }


class ShardedTickPlanner(_ShardedPlannerBase):
    """TickPlanner over a 1-D jobs-sharded mesh.  Same contract as
    ops.planner.TickPlanner; state arrays live sharded across devices."""

    def __init__(self, mesh: Mesh, job_capacity: int, node_capacity: int,
                 rounds: int = 3, impl: str = "auto",
                 max_fire_bucket: int = 65536, tz=None,
                 shard_bids: bool = True, demand_format: str = "auto"):
        self.Dj = self.D = mesh.devices.size
        self._elig_spec = P(AXIS, None)
        self._init_common(mesh, job_capacity, node_capacity, rounds, impl,
                          max_fire_bucket, tz, word_align=32,
                          shard_bids=shard_bids,
                          demand_format=demand_format)

    def _body(self, k_local: int, impl: str, fmt: str = "dense"):
        return partial(_sharded_plan_body, k_local=k_local,
                       rounds=self.rounds, impl=impl,
                       shard_bids=self.shard_bids,
                       compact_k=self._compact_k(k_local, fmt))

    def _window_body(self, k_local: int, impl: str, fmt: str = "dense"):
        return partial(_sharded_window_body, k_local=k_local,
                       rounds=self.rounds, impl=impl,
                       shard_bids=self.shard_bids,
                       compact_k=self._compact_k(k_local, fmt))


class Sharded2DTickPlanner(_ShardedPlannerBase):
    """Tick+assign over a (jobs x nodes) 2-D mesh: the eligibility matrix
    shards both ways, so neither 1M-row schedule state nor 100k-node
    bitmask width needs to fit one device.  Same contract as
    ShardedTickPlanner.

    impl="jnp"/"mixed" break exact-score ties by lowest global node id —
    placements invariant to the column split; impl="pallas" runs the
    HBM-efficient bitpacked block kernel — deterministic per mesh shape
    (see _tick2d_local).  The default "auto" resolves per plan from the
    per-device tile (ops.assign.choose_impl): at 1M x 102,400 nodes on
    a 2x2 mesh the jnp bid's [32768, 51200] f32 tile makes the window
    program need 11.9 GB of temporaries beside 3.4 GB of arguments —
    15.3 of a v5e chip's 15.75 GB — where the pallas bid needs 4.0 GB
    (tests/test_chip_compile.py compiles both)."""

    def __init__(self, mesh: Mesh, job_capacity: int, node_capacity: int,
                 rounds: int = 3, impl: str = "auto",
                 max_fire_bucket: int = 65536, tz=None,
                 shard_bids: bool = True, demand_format: str = "auto",
                 node_block_psum=None):
        if mesh.axis_names != (AXIS, NAXIS):
            raise ValueError(f"need a ({AXIS!r}, {NAXIS!r}) mesh")
        self.Dj = mesh.shape[AXIS]
        self.Dn = mesh.shape[NAXIS]
        self._elig_spec = P(AXIS, NAXIS)
        self._init_common(mesh, job_capacity, node_capacity, rounds, impl,
                          max_fire_bucket, tz, word_align=32 * self.Dn,
                          shard_bids=shard_bids,
                          demand_format=demand_format,
                          node_block_psum=node_block_psum)

    def _body(self, k_local: int, impl: str, fmt: str = "dense"):
        return partial(_sharded2d_plan_body, k_local=k_local,
                       rounds=self.rounds, impl=impl,
                       shard_bids=self.shard_bids,
                       compact_k=self._compact_k(k_local, fmt),
                       node_block_fanout=self.node_block_psum)

    def _window_body(self, k_local: int, impl: str, fmt: str = "dense"):
        return partial(_sharded2d_window_body, k_local=k_local,
                       rounds=self.rounds, impl=impl,
                       shard_bids=self.shard_bids,
                       compact_k=self._compact_k(k_local, fmt),
                       node_block_fanout=self.node_block_psum)
