"""VM-axis sharding: sharded dispatches == single-device batched, bit for bit.

The mesh spans every visible device (``make_vm_mesh()``), so under the
plain tier-1 run (one CPU device) these tests exercise the sharded code
paths on a degenerate 1-device mesh, and under the CI ``sharding-smoke``
job (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) they
exercise real 8-way splits with per-device row blocks. Covered:

  * the three hot dispatches (two-level datapath, single-level datapath,
    fused maintenance) plus the resize/sizing/POD routes are
    **bit-identical** to the single-device batched oracle;
  * per-VM work is **shard-local** — the compiled HLO of every sharded
    dispatch except the Stats aggregation contains no collectives, and
    :func:`aggregate_stats_sharded` contains exactly the one intended
    all-reduce (its psum);
  * both controllers produce identical VMResults with a mesh configured,
    including a **ragged** VM count (padded with dead VMs to a multiple
    of the mesh size) and streamed per-shard block feeding;
  * the mesh helpers and controller configs reject unusable setups with
    descriptive ``ValueError``\\ s.
"""
import dataclasses
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (EticaCache, EticaConfig, Geometry, Policy, Stats,
                        aggregate_stats_sharded, interleave, make_cache_batch,
                        make_centaur, make_eci_cache, pad_batch,
                        policy_flags, resize_batch, resize_batch_sharded,
                        resize_levels, resize_levels_sharded,
                        simulate_single_level_batch,
                        simulate_single_level_sharded,
                        simulate_two_level_batch, simulate_two_level_sharded,
                        split_by_vm, table_init)
from repro.core import reuse, simulator as sim
from repro.core.controller import PartitionedSingleLevelCache
from repro.kernels.maintenance import ops as maint_ops
from repro.kernels.reuse_distance import ops as kernel_ops
from repro.launch.mesh import (device_row_blocks, make_host_mesh,
                               make_production_mesh, make_vm_mesh,
                               require_vm_divisible, vm_spec)
from repro.traces import StreamingTraceSource, make
from repro.traces.stream import StreamWindow

MESH = make_vm_mesh()                 # every visible device
D = MESH.size
V = 2 * D                             # evenly divisible row count
S, W = 4, 4                           # small geometry, all sets exercised

_COLLECTIVE = re.compile(r"all-reduce\(|all-gather\(|collective-permute\("
                         r"|all-to-all\(|reduce-scatter\(")


def _assert_local(jitted, *args, label=""):
    """The compiled dispatch moves no per-VM arrays across devices."""
    txt = jitted.lower(*args).compile().as_text()
    hits = _COLLECTIVE.findall(txt)
    assert not hits, f"{label}: unexpected collectives {hits}"


def _assert_tree_equal(a, b, msg=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), msg


def _requests(seed=0, n=96, pad_frac=0.15, addr_space=24):
    rng = np.random.default_rng(seed)
    addr = rng.integers(0, addr_space, (V, n)).astype(np.int32)
    addr[rng.random((V, n)) < pad_frac] = -1     # no-op pads mid-stream
    return addr, rng.random((V, n)) < 0.4


def _ragged(seed=3, num=V, lo=0, hi=160, addr_space=50):
    rng = np.random.default_rng(seed)
    addrs = [rng.integers(0, addr_space,
                          size=int(rng.integers(lo, hi))).astype(np.int32)
             for _ in range(num)]
    addrs[min(1, num - 1)] = np.empty(0, np.int32)   # an idle VM
    return addrs, [rng.random(a.shape[0]) < 0.4 for a in addrs]


# ---------------------------------------------------------------------------
# datapath dispatches
# ---------------------------------------------------------------------------

def test_two_level_sharded_bit_identical_and_local():
    addr, is_write = _requests(seed=1)
    rng = np.random.default_rng(11)
    wd = rng.integers(0, W + 1, V).astype(np.int32)
    ws = rng.integers(0, W + 1, V).astype(np.int32)
    t0 = rng.integers(0, 9, V).astype(np.int32)
    for mode in ("full", "npe"):
        dram = make_cache_batch(V, S, W)
        ssd = make_cache_batch(V, S, W)
        ref = simulate_two_level_batch(addr, is_write, dram, ssd, wd, ws,
                                       mode=mode, t0=t0)
        got = simulate_two_level_sharded(addr, is_write, dram, ssd, wd, ws,
                                         MESH, mode=mode, t0=t0)
        _assert_tree_equal(ref, got, f"two-level {mode}")
    _assert_local(sim._two_level_sharded(MESH, "full"),
                  jnp.asarray(addr), jnp.asarray(is_write),
                  make_cache_batch(V, S, W), make_cache_batch(V, S, W),
                  jnp.asarray(wd), jnp.asarray(ws), jnp.asarray(t0),
                  label="two-level")


def test_single_level_sharded_bit_identical_and_local():
    addr, is_write = _requests(seed=2)
    rng = np.random.default_rng(12)
    ways = rng.integers(0, W + 1, V).astype(np.int32)
    t0 = rng.integers(0, 9, V).astype(np.int32)
    policies = [list(Policy)[v % len(Policy)] for v in range(V)]
    flags = policy_flags(policies)
    state = make_cache_batch(V, S, W)
    ref = simulate_single_level_batch(addr, is_write, state, ways, flags,
                                      t0=t0)
    got = simulate_single_level_sharded(addr, is_write, state, ways, flags,
                                        MESH, t0=t0)
    _assert_tree_equal(ref, got, "single-level heterogeneous policies")
    bflags = sim.PolicyFlags(
        *[jnp.broadcast_to(jnp.asarray(f), (V,)) for f in flags])
    _assert_local(sim._single_level_sharded(MESH),
                  jnp.asarray(addr), jnp.asarray(is_write), state,
                  jnp.asarray(ways), bflags, jnp.float32(1.0),
                  jnp.asarray(t0), label="single-level")


def test_resize_sharded_bit_identical_and_local():
    addr, is_write = _requests(seed=4)
    rng = np.random.default_rng(14)
    wd = rng.integers(0, W + 1, V).astype(np.int32)
    ws = rng.integers(0, W + 1, V).astype(np.int32)
    dram, ssd, _, _ = simulate_two_level_batch(
        addr, is_write, make_cache_batch(V, S, W), make_cache_batch(V, S, W),
        wd, ws, mode="full")
    nd = rng.integers(0, W + 1, V).astype(np.int32)
    ns = rng.integers(0, W + 1, V).astype(np.int32)
    _assert_tree_equal(resize_levels(dram, ssd, wd, nd, ws, ns),
                       resize_levels_sharded(dram, ssd, wd, nd, ws, ns, MESH),
                       "resize_levels")
    _assert_tree_equal(resize_batch(ssd, ws, ns),
                       resize_batch_sharded(ssd, ws, ns, MESH),
                       "resize_batch")
    as_i32 = lambda x: jnp.asarray(x, jnp.int32)
    _assert_local(sim._resize_levels_sharded(MESH), dram, ssd, as_i32(wd),
                  as_i32(nd), as_i32(ws), as_i32(ns), label="resize_levels")
    _assert_local(sim._resize_batch_sharded(MESH), ssd, as_i32(ws),
                  as_i32(ns), label="resize_batch")


def test_aggregate_stats_sharded_is_the_only_collective():
    addr, is_write = _requests(seed=5)
    ways = np.full(V, 2, np.int32)
    _, per_vm, _ = simulate_single_level_batch(
        addr, is_write, make_cache_batch(V, S, W), ways,
        policy_flags([Policy.WB] * V))
    total = aggregate_stats_sharded(per_vm, MESH)
    for leaf, tot in zip(per_vm, total):
        assert np.asarray(tot) == np.asarray(leaf).sum()
    txt = sim._aggregate_stats_sharded(MESH).lower(
        Stats(*[jnp.asarray(x) for x in per_vm])).compile().as_text()
    if D > 1:
        # the psum is the one intended cross-device reduction of a
        # sharded controller run
        assert "all-reduce(" in txt or "all-reduce-start(" in txt
    assert not re.search(r"all-gather\(|collective-permute\(|all-to-all\(",
                         txt)


# ---------------------------------------------------------------------------
# fused maintenance
# ---------------------------------------------------------------------------

def test_maintenance_sharded_bit_identical_and_local():
    addr, is_write = _requests(seed=6, n=64)
    ways = np.full(V, 3, np.int32)
    # populate dirty SSD states by running the datapath first
    _, ssd, _, _ = simulate_two_level_batch(
        addr, is_write, make_cache_batch(V, S, W), make_cache_batch(V, S, W),
        np.full(V, 2, np.int32), ways, mode="full")
    rng = np.random.default_rng(16)
    n = 48
    waddr = rng.integers(0, 24, (V, n)).astype(np.int32)
    dist = rng.integers(-1, 8, (V, n)).astype(np.int32)
    served = (rng.random((V, n)) < 0.5) & (dist >= 0)
    wlen = rng.integers(0, n + 1, V).astype(np.int32)
    wlen[0] = 0                      # an idle VM rides along untouched
    t = rng.integers(1, 9, V).astype(np.int32)
    table = table_init(V, 64)
    kw = dict(evict_frac=0.25, decay=0.5, clean_quota=2, interpret=True)
    ref = maint_ops.maintenance_interval(ssd, table, dist, served, waddr,
                                         wlen, ways, t, **kw)
    got = maint_ops.maintenance_interval(ssd, table, dist, served, waddr,
                                         wlen, ways, t, mesh=MESH, **kw)
    _assert_tree_equal(ref, got, "fused maintenance")
    _assert_local(
        maint_ops._maintenance_sharded(MESH, 0.25, 0.5, 2,
                                       maint_ops.DEFAULT_TS,
                                       maint_ops.DEFAULT_QC, True),
        ssd, table, jnp.asarray(dist), jnp.asarray(served, bool),
        jnp.asarray(waddr), jnp.asarray(wlen), jnp.asarray(ways),
        jnp.asarray(t), label="maintenance")


def test_maintenance_sharded_ways_bucket_bit_identical_and_local():
    """The sharded dispatch on the leading ways (bucket 2: the largest
    active way count over every row) == the single-device full width."""
    addr, is_write = _requests(seed=8, n=64)
    ways = np.tile(np.asarray([0, 2, 1, 1], np.int32), V)[:V]
    _, ssd, _, _ = simulate_two_level_batch(
        addr, is_write, make_cache_batch(V, S, W), make_cache_batch(V, S, W),
        np.full(V, 1, np.int32), ways, mode="full")
    rng = np.random.default_rng(18)
    n = 48
    waddr = rng.integers(0, 24, (V, n)).astype(np.int32)
    dist = rng.integers(-1, 8, (V, n)).astype(np.int32)
    served = (rng.random((V, n)) < 0.5) & (dist >= 0)
    wlen = rng.integers(1, n + 1, V).astype(np.int32)
    wlen[0] = 0                      # an idle VM rides along untouched
    t = rng.integers(1, 9, V).astype(np.int32)
    table = table_init(V, 64)
    kw = dict(evict_frac=0.25, decay=0.5, clean_quota=2, interpret=True)
    assert maint_ops.ways_bucket_of(ways, W) == 2
    ref = maint_ops.maintenance_interval(ssd, table, dist, served, waddr,
                                         wlen, ways, t, ways_bucket=W, **kw)
    got = maint_ops.maintenance_interval(ssd, table, dist, served, waddr,
                                         wlen, ways, t, mesh=MESH, **kw)
    _assert_tree_equal(ref, got, "fused maintenance, way bucket 2")
    _assert_local(
        maint_ops._maintenance_sharded(MESH, 0.25, 0.5, 2,
                                       maint_ops.DEFAULT_TS,
                                       maint_ops.DEFAULT_QC, True, 2),
        ssd, table, jnp.asarray(dist), jnp.asarray(served, bool),
        jnp.asarray(waddr), jnp.asarray(wlen), jnp.asarray(ways),
        jnp.asarray(t), label="maintenance, way bucket 2")


# ---------------------------------------------------------------------------
# sizing / POD reductions (manual per-device dispatch)
# ---------------------------------------------------------------------------

def test_sizing_sharded_matches_jnp_and_kernel_routes():
    addrs, writes = _ragged(seed=7)
    grid = np.array([1, 4, 16, 64], np.int32)
    for kind in reuse.SIZING_KINDS:
        ref = reuse.sizing_metrics_batch(addrs, writes, kind, grid)
        got = reuse.sizing_metrics_batch(addrs, writes, kind, grid,
                                         mesh=MESH)
        for x, y in zip(ref, got):
            assert np.array_equal(x, y), f"jnp {kind}"
    for kind in ("urd", "wss"):      # the kernel-backed route
        ref = kernel_ops.sizing_metrics_batch(addrs, writes, kind, grid)
        got = kernel_ops.sizing_metrics_batch(addrs, writes, kind, grid,
                                              mesh=MESH)
        for x, y in zip(ref, got):
            assert np.array_equal(x, y), f"kernel {kind}"


def test_pod_distances_sharded_matches():
    addrs, writes = _ragged(seed=8)
    lens = [a.shape[0] for a in addrs]
    amat, wmat = reuse._pad_rows(addrs, writes, list(range(V)), lens)
    for policy in (Policy.WB, Policy.RO, Policy.WBWO):
        ref = reuse._decompose_vmapped(amat, wmat, policy=policy,
                                       sizing_reads_only=True, chunk=256)
        got = reuse._decompose_sharded(MESH, amat, wmat, policy, True, 256)
        _assert_tree_equal(ref, got, policy)


def test_device_row_blocks_partition():
    blocks = device_row_blocks(V, MESH)
    assert len(blocks) == D
    assert [b[1] for b in blocks] == [
        slice(i * (V // D), (i + 1) * (V // D)) for i in range(D)]
    assert [b[0] for b in blocks] == list(MESH.devices.flat)


# ---------------------------------------------------------------------------
# controllers: sharded run == batched run, ragged V
# ---------------------------------------------------------------------------

GEO = Geometry(num_sets=8, max_ways=16)
RAGGED_V = max(3, D - 1)             # never a multiple of D when D > 1


def _mixed_trace(num_vms, reqs=1800):
    names = ["hm_1", "usr_0", "web_3", "proj_0", "src2_0", "mds_0",
             "stg_1", "wdev_0"]
    return interleave(
        [make(names[i % len(names)], reqs, seed=i,
              addr_offset=i * 10_000_000, scale=0.25)
         for i in range(num_vms)], seed=0)


def _assert_results_equal(ref, got, num_vms):
    for v in range(num_vms):
        assert ref[v].stats == got[v].stats, v
        assert np.array_equal(ref[v].alloc_history, got[v].alloc_history), v


def test_etica_controller_sharded_ragged_v():
    trace = _mixed_trace(RAGGED_V)
    cfg = EticaConfig(dram_capacity=60, ssd_capacity=120, geometry_dram=GEO,
                      geometry_ssd=GEO, resize_interval=1500,
                      promo_interval=500, mode="full", clean_quota=2)
    ref = EticaCache(cfg, RAGGED_V).run(trace)
    cache = EticaCache(dataclasses.replace(cfg, mesh=MESH), RAGGED_V)
    assert cache._rows % D == 0 and cache._rows >= RAGGED_V
    got = cache.run(trace)
    _assert_results_equal(ref, got, RAGGED_V)


@pytest.mark.parametrize("factory", [make_eci_cache, make_centaur])
def test_single_level_controller_sharded_ragged_v(factory):
    trace = _mixed_trace(RAGGED_V)
    ref = factory(120, RAGGED_V, geometry=GEO, resize_interval=1500).run(
        trace)
    c = factory(120, RAGGED_V, geometry=GEO, resize_interval=1500)
    sharded = PartitionedSingleLevelCache(
        dataclasses.replace(c.cfg, mesh=MESH), RAGGED_V, c.metric,
        c.policy_fn)
    _assert_results_equal(ref, sharded.run(trace), RAGGED_V)


# ---------------------------------------------------------------------------
# streamed per-shard feeding
# ---------------------------------------------------------------------------

def test_stream_blocks_sharded_placement_and_values():
    from jax.sharding import NamedSharding
    trace = _mixed_trace(3, reqs=600)
    subs = split_by_vm(trace, 3)
    pad = (-3) % D if D > 1 else 1          # pad 3 real VMs up to rows
    rows = 3 + pad
    sharding = NamedSharding(MESH, vm_spec(MESH)) if rows % D == 0 else None
    host = StreamWindow(0, subs, chunk=64, prefetch_depth=0, pad_vms=pad)
    dev = StreamWindow(0, subs, chunk=64, prefetch_depth=2, pad_vms=pad,
                      sharding=sharding)
    got = list(dev.blocks())
    ref = list(host.blocks())
    assert len(got) == len(ref) > 0
    for (a, w, kth), (ra, rw, rkth) in zip(got, ref):
        assert a.shape == (rows, 64)
        assert np.array_equal(np.asarray(a), np.asarray(ra))
        assert np.array_equal(np.asarray(w), np.asarray(rw))
        assert np.all(np.asarray(ra)[3:] == -1)     # dead-VM pad rows
        assert len(kth) == len(rkth) == 3           # maintenance sees real VMs
        if sharding is not None:
            assert a.sharding.is_equivalent_to(sharding, a.ndim)


def test_streaming_source_depths_bit_identical():
    trace = _mixed_trace(3, reqs=900)
    outs = []
    for depth in (0, 1, 2, 3):
        src = StreamingTraceSource(trace, num_vms=3, window=400, chunk=64,
                                   prefetch=True, prefetch_depth=depth)
        blocks = [(np.asarray(a), np.asarray(w))
                  for win in src.windows() for a, w, _ in win.blocks()]
        outs.append(blocks)
    for blocks in outs[1:]:
        assert len(blocks) == len(outs[0])
        for (a, w), (ra, rw) in zip(blocks, outs[0]):
            assert np.array_equal(a, ra) and np.array_equal(w, rw)


# ---------------------------------------------------------------------------
# descriptive errors
# ---------------------------------------------------------------------------

def test_mesh_helper_errors():
    with pytest.raises(ValueError, match="devices"):
        make_vm_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="1-d mesh"):
        vm_spec(make_host_mesh())               # ('data', 'model') is 2-d
    with pytest.raises(ValueError, match="divisible"):
        make_host_mesh(model=len(jax.devices()) + 1)
    if len(jax.devices()) < 256:
        with pytest.raises(ValueError, match="devices"):
            make_production_mesh()
    if D > 1:
        with pytest.raises(ValueError, match="divisible"):
            require_vm_divisible(D + 1, MESH)
        with pytest.raises(ValueError, match="divisible"):
            device_row_blocks(D + 1, MESH)


def test_controller_mesh_config_errors():
    cfg = EticaConfig(dram_capacity=60, ssd_capacity=120, geometry_dram=GEO,
                      geometry_ssd=GEO, mesh=MESH)
    with pytest.raises(ValueError, match="batched"):
        EticaCache(dataclasses.replace(cfg, batched=False), 2)
    with pytest.raises(ValueError, match="fused_maintenance"):
        EticaCache(dataclasses.replace(cfg, fused_maintenance=False), 2)
    with pytest.raises(ValueError, match="classifier"):
        EticaCache(dataclasses.replace(cfg, classifier=object()), 2)
    c = make_eci_cache(60, 2, geometry=GEO)
    with pytest.raises(ValueError, match="batched"):
        PartitionedSingleLevelCache(
            dataclasses.replace(c.cfg, mesh=MESH, batched=False), 2,
            c.metric, c.policy_fn)
