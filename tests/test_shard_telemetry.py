"""What the device trace and the telemetry show of the VM-sharded path.

* every sharded program is named after its public entry
  (``jit_simulate_two_level_sharded``, ...), apart from the
  single-device programs and from each other;
* ``TelemetryRecorder.shard_host_bytes`` (exported as
  ``etica_shard_host_bytes_total``) counts the bytes the mesh-only host
  route moves: 0 on one device, with a mesh the sizing reductions' and
  the maintenance's TRD bytes exactly, and results are bit-identical
  either way and with span timing on;
* the ``sizing`` and ``maintenance`` spans carry ``shards``.

The mesh spans every visible device (one on CPU CI).
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EticaCache, EticaConfig, Geometry, interleave
from repro.core import reuse
from repro.core.controller import _mrc_grid
from repro.core import simulator as sim
from repro.core.simulator import Stats, make_cache_batch, policy_flags
from repro.core.policies import Policy
from repro.kernels.maintenance import ops as maint_ops
from repro.core import popularity as pop
from repro.launch.mesh import make_vm_mesh
from repro.runtime import metrics
from repro.runtime.telemetry import TelemetryRecorder
from repro.traces import make

MESH = make_vm_mesh()
D = MESH.size
V, S, W, N = 2 * D, 4, 4, 16
GEO = Geometry(num_sets=8, max_ways=16)


def _rows(dtype=jnp.int32, *tail):
    return jnp.zeros((V,) + tail, dtype)


def _lowered(name):
    st = make_cache_batch(V, S, W)
    a, w, z = _rows(jnp.int32, N), _rows(bool, N), _rows()
    if name == "simulate_two_level_sharded":
        return sim._two_level_sharded(MESH, "full").lower(
            a, w, st, st, z, z, z)
    if name == "simulate_single_level_sharded":
        flags = sim.PolicyFlags(*[jnp.broadcast_to(jnp.asarray(f), (V,))
                                  for f in policy_flags([Policy.WB] * V)])
        return sim._single_level_sharded(MESH).lower(
            a, w, st, z, flags, jnp.float32(1.0), z)
    if name == "resize_levels_sharded":
        return sim._resize_levels_sharded(MESH).lower(st, st, z, z, z, z)
    if name == "resize_batch_sharded":
        return sim._resize_batch_sharded(MESH).lower(st, z, z)
    if name == "aggregate_stats_sharded":
        return sim._aggregate_stats_sharded(MESH).lower(
            Stats(*[z] * len(Stats._fields)))
    table = pop.table_init(V, 64)
    return maint_ops._maintenance_sharded(
        MESH, 0.05, 0.5, 0, maint_ops.DEFAULT_TS, maint_ops.DEFAULT_QC,
        True, W).lower(st, table, a, w, a, z, z, z)


NAMES = ("simulate_two_level_sharded", "simulate_single_level_sharded",
         "resize_levels_sharded", "resize_batch_sharded",
         "aggregate_stats_sharded", "maintenance_sharded")


@pytest.mark.parametrize("name", NAMES)
def test_sharded_program_is_named(name):
    head = re.search(r"^module @(\S+)", _lowered(name).as_text(), re.M)
    assert head.group(1) == f"jit_{name}"
    # the single-device programs' names, which the paper host's metrics
    # read, match none of them
    for single in ("simulate_two_level_batch", "simulate_single_level_batch",
                   "_maintenance_impl", "body"):
        assert not re.search(single, head.group(1))


def _mix(num_vms=2 * D, n=1000):
    return interleave(
        [make(["hm_1", "web_3", "usr_0"][i % 3], n, seed=i,
              addr_offset=i * 10_000_000, scale=0.25)
         for i in range(num_vms)], seed=42)


def _cfg(**kw):
    return EticaConfig(dram_capacity=40 * D, ssd_capacity=80 * D,
                       geometry_dram=GEO, geometry_ssd=GEO,
                       resize_interval=600 * D, promo_interval=200, **kw)


def _run(cfg):
    cache = EticaCache(cfg, num_vms=2 * D)
    res = cache.run(_mix())
    return cache, [dict(r.stats) for r in res]


def _host_bytes(trace, cfg, rows):
    """What the mesh route moves through the host: per resize window, each
    level's sizing reduction gathered (int32 demand, ``G`` hit counts and
    read count a row), and per promotion interval the TRD decomposition
    gathered (int32 distance, served and touch masks a request slot) and
    its distances and served mask uploaded again."""
    g = _mrc_grid(GEO, cfg.mrc_points).size
    total = 0
    for w0 in range(0, len(trace), cfg.resize_interval):
        n = np.bincount(trace.vm[w0:w0 + cfg.resize_interval],
                        minlength=rows)
        total += 2 * rows * (g + 2) * 4
        p = cfg.promo_interval
        for k in range(-(-int(n.max()) // p)):
            b = reuse._bucket(int(np.clip(n - k * p, 0, p).max()))
            total += rows * b * (6 + 5)
    return total


def test_shard_host_bytes_counted_and_results_unchanged():
    plain, want = _run(_cfg())
    assert plain.telemetry.shard_host_bytes == 0
    sharded, got = _run(_cfg(mesh=MESH))
    assert got == want
    assert sharded.telemetry.shard_host_bytes == _host_bytes(
        _mix(), sharded.cfg, 2 * D)
    for a, b in zip((plain.dram, plain.ssd, plain.pop_table),
                    (sharded.dram, sharded.ssd, sharded.pop_table)):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    # span timing on (the opt-in that adds syncs) changes nothing either
    timed, again = _run(_cfg(mesh=MESH,
                             telemetry=TelemetryRecorder(span_timing=True)))
    assert again == want
    assert (timed.telemetry.shard_host_bytes
            == sharded.telemetry.shard_host_bytes)
    fams = metrics.parse_exposition(
        metrics.render(metrics.collect_telemetry(sharded.telemetry)))
    fam = fams["etica_shard_host_bytes_total"]
    assert fam["type"] == "counter"
    assert fam["samples"][()] == float(sharded.telemetry.shard_host_bytes)


@pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")
def test_sizing_and_maintenance_spans_carry_shards():
    from jaxlib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    prof = _profiler.ProfilerSession(opts)
    EticaCache(dataclasses.replace(_cfg(), mesh=MESH), num_vms=2 * D).run(
        _mix(n=300))
    profile = prof.stop_and_get_profile_data()
    args = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("sizing", "maintenance"):
                        args.setdefault(e.name, []).append(dict(e.stats))
    assert set(args) == {"sizing", "maintenance"}
    for name, seen in args.items():
        assert all(a["shards"] == D for a in seen), (name, seen)
