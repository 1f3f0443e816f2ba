"""The no-chip compile gate: the served path's device programs, at the
sizes chip_smoke.py runs them, through the TPU's own compiler against a
DESCRIBED v5e 2x2 topology (no chip attached, nothing executes).

What this catches that interpret-mode tests cannot: a Mosaic refusal
(tiling, scoped VMEM), a program that does not fit one chip's HBM, a
shard_map step that does not partition.  What it does not say: anything
about results or times — a compile that passes is not a chip run.

Rules this file keeps (libtpu is one-process-at-a-time and the tier-1
run has several xdist workers, each importing every test file): the
topology is described inside the module-scoped fixture below — never at
import, in conftest, or in a skipif/parametrize argument — everything
built from it is built in fixtures or tests, every compile runs in this
process, and all of it lives in this ONE file.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from cronsun_tpu.ops.assign import choose_impl
from cronsun_tpu.ops.pallas_kernels import bid_argmin, fanout_add
from cronsun_tpu.ops.planner import _plan_window_step
from cronsun_tpu.ops.schedule_table import (_DTYPES, _SHAPES,
                                            ScheduleTable)
from cronsun_tpu.parallel import mesh as pmesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

J = 1 << 20                 # the 2^20-row table 1,000,000 jobs live in
N_FLEET = 10_240            # chip_smoke.py's one-chip fleet
N_WIDE = 102_400            # chip_smoke.py --mesh4's wide fleet
BUCKET = 65_536             # max_fire_bucket: first-window / mesh bucket
# one v5e chip reports 15.75 GB of HBM to the compiler.  A program may
# take half: the double-buffered window and the background-warmed
# escalation program are resident beside it in the same process.
HBM_BYTES = int(15.75 * 2**30)
PROGRAM_BUDGET = HBM_BYTES // 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from importlib.util import find_spec
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    if find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    # libtpu is here: a topology that cannot be described (another
    # process holds libtpu, a broken install) FAILS the gate — a skip
    # would take it out of the run without a word
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: the next run would warn
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def window_s():
    with open(os.path.join(REPO, "conf", "base.json.sample")) as f:
        return int(json.load(f)["window_s"])


def _table(sds):
    """ScheduleTable of shapes: ``sds(shape, dtype)`` makes each leaf."""
    return ScheduleTable(**{k: sds((J, *_SHAPES.get(k, ())), dt)
                            for k, dt in _DTYPES.items()})


def _as_on_tpu(monkeypatch, resolve, *args):
    """What an impl resolver answers on a TPU: choose_impl asks
    jax.default_backend(), which here is the CPU, so the answer is
    steered for this one call."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return resolve(*args)


def _program_bytes(compiled):
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes + m.argument_size_in_bytes


@pytest.mark.parametrize("kernel", ["bid_argmin", "fanout_add"])
@pytest.mark.parametrize("k,n", [(16384, N_FLEET), (2048, N_WIDE)])
def test_kernel_compiles_for_v5e(one_chip, kernel, k, n):
    packed = jax.ShapeDtypeStruct((k, n // 32), jnp.uint32,
                                  sharding=one_chip)
    if kernel == "bid_argmin":
        lowered = bid_argmin.lower(packed, jax.ShapeDtypeStruct(
            (n,), jnp.float32, sharding=one_chip))
    else:
        lowered = fanout_add.lower(packed, jax.ShapeDtypeStruct(
            (k,), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("bucket,impl", [(16384, "mixed"), (BUCKET, None)])
def test_window_program_fits_one_chip(one_chip, window_s, monkeypatch,
                                      bucket, impl):
    """TickPlanner's whole window program at 1M jobs x 10,240 nodes: the
    steady bucket under "mixed", and the 65,536 bucket every fresh
    planner starts at (and herds escalate to) under whatever choose_impl
    resolves on a TPU at that size."""
    if impl is None:
        impl = _as_on_tpu(monkeypatch, choose_impl, N_FLEET, bucket,
                          bucket)
        assert impl == "pallas"      # 2.7 GB score tile: past the cutover

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _plan_window_step.lower(
        _table(sds), sds((window_s, 7), jnp.int32),
        sds((J, N_FLEET // 32), jnp.uint32), sds((J,), jnp.bool_),
        sds((J,), jnp.float32), sds((N_FLEET,), jnp.float32),
        sds((N_FLEET,), jnp.int32), sds((J,), jnp.int32),
        sds((J,), jnp.int32), sds((J,), jnp.bool_), sds((J,), jnp.int32),
        kx=bucket, kc=bucket, rounds=2, impl=impl,
        use_deps=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _program_bytes(compiled) < PROGRAM_BUDGET


def _bare_planner(cls, mesh, **attrs):
    """A mesh planner with exactly the attributes ``_window_step`` and
    the ``_resolve_*`` helpers read, and none of the device state its
    constructor would place — described devices hold no arrays.  The
    step lowered below is therefore the program's own jit(shard_map)
    with the program's own specs."""
    p = object.__new__(cls)
    p.mesh, p.J, p.N = mesh, J, N_WIDE
    p.rounds, p.impl, p.max_fire_bucket = 3, "auto", BUCKET
    p.shard_bids, p.demand_format = True, "auto"
    p._step_cache = {}
    for k, v in attrs.items():
        setattr(p, k, v)
    return p


@pytest.mark.parametrize("layout,pinned", [("2x2", None), ("4", None),
                                           ("2x2", "jnp")])
def test_mesh_window_step_fits_four_chips(topo, window_s, monkeypatch,
                                          layout, pinned):
    """chip_smoke.py --mesh4's two steps at 1M jobs x 102,400 nodes:
    Sharded2DTickPlanner on a (2, 2) jobs x nodes mesh and
    ShardedTickPlanner on a 4-way jobs mesh, as plan_window builds
    them.  The third case is why the 2-D planner's default impl is
    "auto" and no longer "jnp": pinned to "jnp" the same step takes
    nearly a whole chip."""
    if layout == "2x2":
        mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                    (pmesh.AXIS, pmesh.NAXIS))
        p = _bare_planner(pmesh.Sharded2DTickPlanner, mesh, Dj=2, Dn=2,
                          _elig_spec=P(pmesh.AXIS, pmesh.NAXIS),
                          node_block_psum=True)
    else:
        mesh = Mesh(np.array(topo.devices), (pmesh.AXIS,))
        p = _bare_planner(pmesh.ShardedTickPlanner, mesh, Dj=4, D=4,
                          _elig_spec=P(pmesh.AXIS, None),
                          node_block_psum=False)
    k_local = BUCKET // p.Dj
    impl = pinned or _as_on_tpu(monkeypatch, p.first_window_impl)
    # "auto" resolves away from the 6.7 GB per-device f32 score tile
    assert impl == (pinned or "pallas")
    fmt = p._resolve_demand_format(k_local)

    def sds(shape, dt, spec=P(pmesh.AXIS)):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))
    compiled = p._window_step(k_local, impl, fmt).lower(
        _table(sds), sds((window_s, 7), jnp.int32, P()),
        sds((J, N_WIDE // 32), jnp.uint32, p._elig_spec),
        sds((J,), jnp.bool_), sds((J,), jnp.float32),
        sds((N_WIDE,), jnp.float32, P()),
        sds((N_WIDE,), jnp.int32, P())).compile()
    if pinned == "jnp":
        m = compiled.memory_analysis()
        print(f"2x2 jnp: temp {m.temp_size_in_bytes / 1e9:.1f} GB + "
              f"arguments {m.argument_size_in_bytes / 1e9:.1f} GB")
        assert PROGRAM_BUDGET < _program_bytes(compiled), \
            "the jnp bid fits half a chip now: revisit the default"
        return
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    # per device: a quarter of the 13.4 GB matrix and of the table, the
    # replicated node vectors, and the step's temporaries
    elig_bytes = J * (N_WIDE // 32) * 4
    m = compiled.memory_analysis()
    assert elig_bytes // 4 < m.argument_size_in_bytes < elig_bytes // 3
    assert _program_bytes(compiled) < PROGRAM_BUDGET
    # the demand exchange and the Common fan-out sum cross chips
    assert re.search(r"\ball-gather(-start)?\(", txt)
    assert re.search(r"\ball-reduce(-start)?\(", txt)
    groups = set(re.findall(r"replica_groups=(\{\{[0-9,{}]*\}\})", txt))
    if layout == "2x2":
        # collectives along BOTH mesh axes: jobs pairs and nodes pairs
        assert {"{{0,2},{1,3}}", "{{0,1},{2,3}}"} <= groups, groups
    else:
        assert "{{0,1,2,3}}" in groups, groups
