"""Paper Figs. 15 & 16: cache reallocation and hit ratio as VMs come
online (1 -> 2 -> 4 -> 8 VMs against a fixed total cache), plus the
batched-datapath head-to-heads: one vmapped dispatch for all VMs
(``batched=True``, the default) vs the sequential per-VM dispatch loop
(``batched=False``, the reference oracle) — for ETICA's two-level
controller AND for the one-level baseline chassis (ECI-Cache), whose
sizing metrics now ride the same batched reuse pipeline. Each
head-to-head asserts both paths produce *exactly* the same aggregate
Stats before reporting the wall-clock speedup. The batched ETICA run
uses the DEFAULT fused maintenance (device popularity table + Pallas
promote/evict kernels through the CPU interpreter), so the equality
assert is also the gate that fused maintenance stays bit-identical to
the sequential per-VM numpy oracle end to end.

The ``fig15/streaming_*`` rows scale consolidation to 32–128 VMs fed
from a chunked on-disk :class:`TraceStore` (per-VM demux = one stable
sort per shard, ``[V, chunk]`` blocks double-buffered host->device):
wall-clock per request plus peak host RSS, with the full trace never
resident — one resize window at a time. At the smallest streaming scale
the streamed run is asserted bit-identical to the in-memory run.

The ``fig15/sharded_*`` rows weak-scale the mesh-sharded controller
(``EticaConfig.mesh``, PR: VM-axis sharding) over 1/2/4/8 device shards
at a fixed VM count per shard — 128/shard at full scale, so the 8-shard
row is the 1000-VM-class consolidation run (1024 VMs). Per-VM state,
datapath, maintenance and sizing all stay shard-local; the largest scale
is asserted bit-identical to the single-device batched oracle before its
timing row is reported. On CPU, force placeholder devices first:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``sharding-smoke`` job runs exactly that with ``--smoke``).
"""
from __future__ import annotations

import dataclasses
import resource
import sys
import tempfile
import tracemalloc
from pathlib import Path

import numpy as np

from repro.core import EticaCache, Trace, make_eci_cache
from repro.traces import VM_ADDR_STRIDE, TraceStore, make, make_store

from .common import GEO, Timer, aggregate_stats as _aggregate
from .common import etica_config, row, vm_mix

PHASES = [1, 2, 4, 8, 16]
REQS_PER_PHASE = 4_000
WORKLOADS = ["hm_1", "proj_0", "stg_1", "usr_0", "ts_0", "wdev_0",
             "web_3", "src2_0"] * 2  # 16 consolidated VMs (ECI-Cache scale)
STREAM_PHASES = [32, 64, 128]        # ECI-Cache-paper consolidation x8
STREAM_REQS_PER_VM = 750


def _phase_trace(vm_traces, phase: int, active: int) -> Trace:
    """Interleave the active VMs' segments for one phase."""
    chunks, vm_ids = [], []
    for v in range(active):
        seg = vm_traces[v][phase * REQS_PER_PHASE:
                           (phase + 1) * REQS_PER_PHASE]
        chunks.append(np.asarray(seg.addr))
        vm_ids.append(np.full(len(seg), v, np.int32))
    rng = np.random.default_rng(phase)
    order = rng.permutation(sum(len(c) for c in chunks))
    addr = np.concatenate(chunks)[order]
    wr = np.concatenate(
        [np.asarray(vm_traces[v][phase * REQS_PER_PHASE:
                                 (phase + 1) * REQS_PER_PHASE]
                    .is_write) for v in range(active)])[order]
    vm = np.concatenate(vm_ids)[order]
    return Trace(addr=addr, is_write=wr, vm=vm)


def scaling_ramp(vm_traces) -> None:
    """The paper's figure: VMs coming online against a fixed cache."""
    num_vms = max(PHASES)
    cache = EticaCache(etica_config("full", dram=200, ssd=400), num_vms)
    with Timer() as t:
        for phase, active in enumerate(PHASES):
            res = cache.run(_phase_trace(vm_traces, phase, active))
            hits = np.mean([r.hit_ratio for r in res[:active]])
            allocs = [int(l.alloc.sum()) for l in cache.logs_ssd[-2:]]
            row(f"fig15/phase_{active}vms", 0.0,
                f"avg_hit={hits:.3f} ssd_alloc_total={allocs[-1]}")
    row("fig15/total", t.us / (REQS_PER_PHASE * sum(PHASES)), "done")


def _head_to_head(build, label: str, vm_traces, active: int) -> None:
    """Batched-vs-sequential protocol shared by every head-to-head:
    warm-up compile per path, timed runs, exact aggregate-Stats equality
    assert, then the speedup row. ``build(batched)`` returns a fresh
    controller."""
    trace = _phase_trace(vm_traces, 0, active)

    # warm-up pass per path compiles every executable (shapes repeat)
    for batched in (True, False):
        build(batched).run(trace)

    runs = {}
    for batched in (True, False):
        cache = build(batched)
        with Timer() as t:
            res = cache.run(trace)
        runs[batched] = (_aggregate(res), t.dt)
    agg_b, time_b = runs[True]
    agg_s, time_s = runs[False]
    assert agg_b == agg_s, (
        f"{label}: batched and sequential paths diverged at {active} VMs:\n"
        f"  batched:    {agg_b}\n  sequential: {agg_s}")
    speedup = time_s / time_b
    row(f"fig15/{label}_{active}vms",
        time_b * 1e6 / (active * REQS_PER_PHASE),
        f"speedup={speedup:.2f}x sequential_s={time_s:.2f} "
        f"batched_s={time_b:.2f} stats_equal=True")


def batched_vs_sequential(vm_traces, active: int) -> None:
    """Head-to-head at ``active`` VMs: identical results, fewer
    dispatches. ``batched=True`` runs the fused maintenance dispatch
    (Pallas kernels, interpret mode on CPU) — the Stats equality assert
    inside :func:`_head_to_head` is the fused-vs-sequential-oracle
    bit-identity gate."""

    def build(batched: bool) -> EticaCache:
        cfg = dataclasses.replace(etica_config("full", dram=200, ssd=400),
                                  batched=batched)
        return EticaCache(cfg, active)

    _head_to_head(build, "batched_speedup", vm_traces, active)


def baseline_batched_vs_sequential(vm_traces, active: int) -> None:
    """Same head-to-head for the one-level baseline chassis (ECI-Cache):
    with batched sizing, URD for all VMs is one vmapped reduction per
    resize interval instead of a per-VM Python metric loop."""

    def build(batched: bool):
        return make_eci_cache(600, active, geometry=GEO,
                              resize_interval=2_000, sim_chunk=500,
                              batched=batched)

    _head_to_head(build, "eci_batched_speedup", vm_traces, active)


def _rss_mb() -> float:
    # ru_maxrss is KB on Linux but bytes on macOS
    scale = 2**20 if sys.platform == "darwin" else 1024
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / scale


def _store_mb(path: Path) -> float:
    return sum(f.stat().st_size for f in Path(path).iterdir()) / 2**20


def streaming_scaling(tmp: str) -> None:
    """32–128 consolidated VMs fed from an on-disk TraceStore.

    Each scale generates its mix straight into a store, then drives the
    batched two-level controller from the store: the full trace stays on
    disk; host memory holds one resize window + the two in-flight
    ``[V, chunk]`` blocks. Reported per scale: wall-clock per request,
    the run's own peak Python-heap use (``tracemalloc``, the host-side
    trace/window/block allocations — this is the bounded quantity; the
    full trace would show up here if it were ever materialized) and
    ``ru_maxrss`` (cumulative process peak, dominated by whatever ran
    earlier in the process). The smallest scale is cross-checked
    bit-identically against the in-memory path before any timing is
    trusted."""
    for active in STREAM_PHASES:
        workloads = (WORKLOADS * ((active + len(WORKLOADS) - 1)
                                  // len(WORKLOADS)))[:active]
        path = Path(tmp) / f"mix_{active}"
        store = make_store(path, workloads, STREAM_REQS_PER_VM, scale=0.25,
                           shard_size=4 * REQS_PER_PHASE)
        cfg = etica_config("full", dram=200, ssd=400)
        if active == STREAM_PHASES[0]:
            ref = EticaCache(cfg, active).run(store.to_trace())
            agg_ref = _aggregate(ref)
        # warm-up pass compiles this scale's [V, chunk] executables so the
        # timed row measures streaming throughput, not one-time JIT
        EticaCache(cfg, active).run(TraceStore.open(path))
        cache = EticaCache(cfg, active)
        tracemalloc.start()
        with Timer() as t:
            res = cache.run(TraceStore.open(path))
        _, peak_py = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        if active == STREAM_PHASES[0]:
            assert _aggregate(res) == agg_ref, (
                f"streamed and in-memory paths diverged at {active} VMs")
        hits = np.mean([r.hit_ratio for r in res])
        row(f"fig15/streaming_{active}vms",
            t.us / (active * STREAM_REQS_PER_VM),
            f"avg_hit={hits:.3f} peak_py_mb={peak_py / 2**20:.1f} "
            f"peak_rss_mb={_rss_mb():.0f} store_mb={_store_mb(path):.2f} "
            f"stats_equal={'True' if active == STREAM_PHASES[0] else 'n/a'}")


def consolidation_mix(active: int, reqs: int, seed: int = 0) -> Trace:
    """The sharded consolidation mix: ``active`` VMs cycling through
    :data:`WORKLOADS`, ``reqs`` requests each."""
    workloads = (WORKLOADS * ((active + len(WORKLOADS) - 1)
                              // len(WORKLOADS)))[:active]
    return vm_mix(workloads, reqs=reqs, seed=seed)


def consolidation_cache(active: int, total: int, mesh) -> EticaCache:
    """The sharded consolidation controller for ``active`` VMs over a
    ``total``-request mix; ``mesh=None`` is the single-device oracle."""
    cfg = dataclasses.replace(
        etica_config("full", dram=12 * active, ssd=25 * active),
        resize_interval=max(500, total // 3),
        promo_interval=max(125, total // 12), mesh=mesh)
    return EticaCache(cfg, active)


def sharded_consolidation(smoke: bool = False) -> None:
    """Weak scaling over a VM-axis device mesh: fixed VMs per shard,
    1/2/4/8 shards (capped at the visible device count). Every per-VM
    dispatch is shard-local (asserted by ``tests/test_sharding.py``); the
    largest scale re-runs on a single device (the batched oracle) and the
    aggregate Stats must match bit for bit before the rows are trusted.
    At full scale the 8-shard row is the 1024-VM consolidation run."""
    import jax

    from repro.launch.mesh import make_vm_mesh

    ndev = len(jax.devices())
    shard_counts = [n for n in (1, 2, 4, 8) if n <= ndev]
    per_shard = 16 if smoke else 128
    reqs = 100 if smoke else 150
    if ndev < 8:
        row("fig15/sharded_devices", 0.0,
            f"only {ndev} device(s) visible — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 for the "
            "full weak-scaling sweep")

    agg_at: dict[int, dict] = {}
    for n in shard_counts:
        active = per_shard * n
        trace = consolidation_mix(active, reqs)
        mesh = make_vm_mesh(n)
        consolidation_cache(active, len(trace), mesh).run(trace)  # warm-up
        with Timer() as t:
            res = consolidation_cache(active, len(trace), mesh).run(trace)
        agg_at[n] = _aggregate(res)
        hits = np.mean([r.hit_ratio for r in res])
        row(f"fig15/sharded_{n}shards_{active}vms", t.us / len(trace),
            f"avg_hit={hits:.3f} reqs={len(trace)} wall_s={t.dt:.2f}")

    # bit-identity gate at the largest scale: same VMs on ONE device
    n = shard_counts[-1]
    active = per_shard * n
    trace = consolidation_mix(active, reqs)
    oracle = _aggregate(consolidation_cache(active, len(trace),
                                            None).run(trace))
    assert oracle == agg_at[n], (
        f"sharded ({n} shards) and single-device batched runs diverged "
        f"at {active} VMs:\n  sharded: {agg_at[n]}\n  oracle:  {oracle}")
    row(f"fig15/sharded_oracle_{active}vms", 0.0,
        f"stats_equal=True shards={n}")


def main(smoke: bool = False):
    global PHASES, REQS_PER_PHASE, STREAM_PHASES, STREAM_REQS_PER_VM
    if smoke:
        PHASES = [1, 2, 4]
        REQS_PER_PHASE = 1_000
        STREAM_PHASES = [32]
        STREAM_REQS_PER_VM = 400
    vm_traces = [make(w, REQS_PER_PHASE * len(PHASES), seed=i,
                      addr_offset=i * VM_ADDR_STRIDE, scale=0.25)
                 for i, w in enumerate(WORKLOADS)]
    scaling_ramp(vm_traces)
    batched_vs_sequential(vm_traces, max(PHASES))
    baseline_batched_vs_sequential(vm_traces, max(PHASES))
    with tempfile.TemporaryDirectory() as tmp:
        streaming_scaling(tmp)
    sharded_consolidation(smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(
        description="fig15: VM-scaling / consolidation benchmarks")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: fewer phases/requests, smallest "
                         "streaming scale only, 16 VMs per shard")
    main(ap.parse_args().smoke)
