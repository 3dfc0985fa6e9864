"""Baseline caching schemes the paper compares against (§2, Table 1).

One-level hypervisor baselines (share the PartitionedSingleLevelCache
chassis; they differ in sizing metric + policy chooser):

  * ECI-Cache [6]  — URD sizing, dynamic per-VM WB/RO policy. The paper's
    primary comparison point.
  * Centaur [11]   — TRD sizing, WB.
  * S-CAVE [10]    — WSS (working-set size) sizing, WT.
  * vCacheShare [9]— reuse-intensity sizing, RO (write-around).

Sizing metric definitions (see :mod:`repro.core.reuse` for the shared
distance engine; ETICA §2.1, Fig. 5):

  * **URD** (ECI-Cache, arXiv:1805.00976): max reuse distance over read
    re-references only (RAR + RAW); ``demand = max URD + 1`` blocks.
  * **TRD** (Centaur; classic Mattson stack distance): max reuse distance
    over *all* re-accesses, read or write; ``demand = max TRD + 1``.
  * **WSS** (S-CAVE): distinct blocks touched in the window — no distance
    filtering at all, the over-allocating estimator ETICA criticizes.
  * **reuse intensity** (vCacheShare): distinct *re-referenced read*
    blocks — a locality x burstiness proxy; its curve uses POD(RO)
    distances since vCacheShare runs a read-only (write-around) cache.
  * ETICA itself replaces all of these with **POD** (§4.3.1, Eq. 2),
    which also conditions on the cache write policy.

Each metric exists in two forms with bit-identical results: a
:class:`SizingMetric` whose ``batch`` method reduces *all* VMs' stacked
reuse-distance histograms in one vmapped jitted dispatch
(:func:`repro.core.reuse.sizing_metrics_batch`), and the original per-VM
``*_ref`` closure kept as the sequential oracle that
``SingleLevelConfig(batched=False)`` exercises.

Global (non-partitioned) two-level baselines, simplified to their content
policies (used in the motivational comparisons):

  * FAST [3]   — DRAM(WB) + SSD(WB); blocks with > 3 accesses in the last
    window are promoted to the SSD; no eviction rule.
  * L2ARC [33] — DRAM read cache; DRAM evictions pushed to a FIFO SSD;
    read-only benefit.
  * uCache [37]— all requests land in DRAM; DRAM evictions demoted to SSD.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import reuse
from .controller import (Geometry, MetricFn, PartitionedSingleLevelCache,
                         PolicyChooser, SingleLevelConfig, _mrc_grid)
from .policies import Policy
from .trace import Trace


# ---------------------------------------------------------------------------
# sizing metrics — sequential per-VM reference closures (*_ref oracles)
# ---------------------------------------------------------------------------

def _metric_from_dist(r, n: int, geom: Geometry, points: int):
    grid = _mrc_grid(geom, points)
    hits = reuse.hit_counts_at_sizes(r.dist, r.served, grid)
    curve = np.asarray(hits, np.float64) / max(n, 1)
    return reuse.demand_blocks(int(r.max)), grid, curve


def urd_metric_ref(geom: Geometry, points: int = 17) -> MetricFn:
    def metric(sub: Trace):
        r = reuse.urd_distances(sub.addr, sub.is_write)
        return _metric_from_dist(r, len(sub), geom, points)
    return metric


def trd_metric_ref(geom: Geometry, points: int = 17) -> MetricFn:
    def metric(sub: Trace):
        r = reuse.trd_distances(sub.addr, sub.is_write)
        return _metric_from_dist(r, len(sub), geom, points)
    return metric


def wss_metric_ref(geom: Geometry, points: int = 17) -> MetricFn:
    """S-CAVE: demand = working-set size (distinct blocks touched).

    The MRC is still needed for partitioning under pressure; use the
    TRD-based curve (WSS has no native notion of a curve — this is the
    'deprecated' estimation the paper criticizes, and it over-allocates
    for sequential workloads by construction)."""
    def metric(sub: Trace):
        wss = int(np.unique(np.asarray(sub.addr)).size)
        r = reuse.trd_distances(sub.addr, sub.is_write)
        _, grid, curve = _metric_from_dist(r, len(sub), geom, points)
        return wss, grid, curve
    return metric


def reuse_intensity_metric_ref(geom: Geometry, points: int = 17) -> MetricFn:
    """vCacheShare: locality x burstiness proxy — distinct re-referenced
    read blocks scaled by access intensity."""
    def metric(sub: Trace):
        addr = np.asarray(sub.addr)
        rd = addr[~np.asarray(sub.is_write)]
        uniq, cnt = np.unique(rd, return_counts=True)
        rereferenced = int((cnt > 1).sum())
        r = reuse.pod_distances(sub.addr, sub.is_write, Policy.RO)
        _, grid, curve = _metric_from_dist(r, len(sub), geom, points)
        return rereferenced, grid, curve
    return metric


# ---------------------------------------------------------------------------
# batched metric protocol: all VMs sized in one vmapped dispatch
# ---------------------------------------------------------------------------

def _use_kernel_sizing() -> bool:
    """Route batched sizing through the Pallas ``sizing_reduction`` path.

    Default: only where Pallas compiles natively (TPU). Off TPU,
    ``ETICA_SIZING_KERNEL=1`` forces the kernel path (through the
    interpreter, which is how CI parity-checks it) and ``=0`` keeps the
    jnp reduction. On a TPU backend the kernel path is always taken and
    ``=0`` raises rather than quietly swapping in the jnp reference.
    """
    from repro.kernels import env_flag, on_tpu, refuse_on_tpu
    forced = env_flag("ETICA_SIZING_KERNEL")
    if on_tpu():
        if forced is False:
            refuse_on_tpu("ETICA_SIZING_KERNEL=0", "the jnp reference")
        return True
    return bool(forced)


@dataclasses.dataclass(frozen=True)
class SizingMetric:
    """A baseline sizing metric in both batched and sequential forms.

    ``batch`` reduces every VM's stacked reuse-distance histogram in one
    vmapped jitted dispatch; ``ref`` is the original per-VM closure the
    sequential (``batched=False``) controller path uses as its
    bit-identical oracle. :class:`PartitionedSingleLevelCache` accepts
    either a plain closure or this object.
    """

    kind: str                 # one of reuse.SIZING_KINDS
    # the metric's own MRC size grid (blocks); excluded from eq/hash so
    # the frozen dataclass stays comparable/hashable despite the ndarray
    grid: np.ndarray = dataclasses.field(compare=False)
    ref: MetricFn = dataclasses.field(compare=False)  # sequential oracle

    def batch(self, addrs: list[np.ndarray], writes: list[np.ndarray],
              with_reads: bool = False, mesh=None):
        """(demands [V], grid [G], curves [V, G]) for all VMs at once.

        Rows for empty traces are zero — exactly what the sequential loop
        produces by skipping them. With ``with_reads`` the per-VM read
        counts (already reduced inside the same dispatch, for the dynamic
        write-policy choosers) are appended to the return.

        On backends that compile Pallas (TPU; forced anywhere by
        ``ETICA_SIZING_KERNEL=1``) the O(N^2) distance channel runs
        through the ``kernels/reuse_distance`` Pallas kernel; the pure
        jnp reduction stays the CPU fallback, parity-asserted in
        ``tests/test_kernels.py``. ``mesh`` shards the VM rows across a
        device mesh on either route (shard-local, bit-identical).
        """
        if _use_kernel_sizing():
            from repro.kernels.reuse_distance import ops as rd_ops
            demands, hits, reads = rd_ops.sizing_metrics_batch(
                addrs, writes, self.kind, self.grid, mesh=mesh)
        else:
            demands, hits, reads = reuse.sizing_metrics_batch(
                addrs, writes, self.kind, self.grid, mesh=mesh)
        ns = np.array([max(np.shape(a)[0], 1) for a in addrs], np.float64)
        curves = hits.astype(np.float64) / ns[:, None]
        if with_reads:
            return demands, self.grid, curves, reads
        return demands, self.grid, curves


def _sizing_metric(kind: str, geom: Geometry, points: int,
                   ref: MetricFn) -> SizingMetric:
    return SizingMetric(kind=kind, grid=_mrc_grid(geom, points), ref=ref)


def urd_metric(geom: Geometry, points: int = 17) -> SizingMetric:
    """ECI-Cache's URD sizing (batched + sequential oracle)."""
    return _sizing_metric("urd", geom, points, urd_metric_ref(geom, points))


def trd_metric(geom: Geometry, points: int = 17) -> SizingMetric:
    """Centaur's TRD sizing (batched + sequential oracle)."""
    return _sizing_metric("trd", geom, points, trd_metric_ref(geom, points))


def wss_metric(geom: Geometry, points: int = 17) -> SizingMetric:
    """S-CAVE's working-set-size sizing (batched + sequential oracle)."""
    return _sizing_metric("wss", geom, points, wss_metric_ref(geom, points))


def reuse_intensity_metric(geom: Geometry, points: int = 17) -> SizingMetric:
    """vCacheShare's reuse-intensity sizing (batched + sequential oracle)."""
    return _sizing_metric("reuse_intensity", geom, points,
                          reuse_intensity_metric_ref(geom, points))


# ---------------------------------------------------------------------------
# policy choosers
# ---------------------------------------------------------------------------

def eci_policy(read_heavy_threshold: float = 0.8) -> PolicyChooser:
    """ECI-Cache dynamically assigns RO to read-dominated VMs (endurance)
    and WB otherwise (performance).

    Returned as a :class:`~repro.core.controller.PolicyChooser`: with a
    batched :class:`SizingMetric` the per-VM read ratios come out of the
    same vmapped sizing dispatch (zero per-VM host work); the host-loop
    closure stays as the ``ref`` oracle the sequential path runs."""
    def from_ratio(read_ratio: float) -> Policy:
        return (Policy.RO if read_ratio >= read_heavy_threshold
                else Policy.WB)

    def chooser(sub: Trace) -> Policy:
        return from_ratio(sub.n_reads / max(len(sub), 1))

    return PolicyChooser(from_read_ratio=from_ratio, ref=chooser)


def fixed_policy(p: Policy):
    return lambda sub: p


# ---------------------------------------------------------------------------
# factories
# ---------------------------------------------------------------------------

def make_eci_cache(capacity: int, num_vms: int,
                   geometry: Geometry | None = None,
                   resize_interval: int = 10_000,
                   **kw) -> PartitionedSingleLevelCache:
    geometry = geometry or Geometry()
    cfg = SingleLevelConfig(capacity=capacity, geometry=geometry,
                            resize_interval=resize_interval, **kw)
    return PartitionedSingleLevelCache(cfg, num_vms,
                                       urd_metric(geometry), eci_policy())


def make_centaur(capacity: int, num_vms: int,
                 geometry: Geometry | None = None, **kw):
    geometry = geometry or Geometry()
    cfg = SingleLevelConfig(capacity=capacity, geometry=geometry, **kw)
    return PartitionedSingleLevelCache(cfg, num_vms,
                                       trd_metric(geometry),
                                       fixed_policy(Policy.WB))


def make_scave(capacity: int, num_vms: int,
               geometry: Geometry | None = None, **kw):
    geometry = geometry or Geometry()
    cfg = SingleLevelConfig(capacity=capacity, geometry=geometry, **kw)
    return PartitionedSingleLevelCache(cfg, num_vms,
                                       wss_metric(geometry),
                                       fixed_policy(Policy.WT))


def make_vcacheshare(capacity: int, num_vms: int,
                     geometry: Geometry | None = None, **kw):
    geometry = geometry or Geometry()
    cfg = SingleLevelConfig(capacity=capacity, geometry=geometry, **kw)
    return PartitionedSingleLevelCache(cfg, num_vms,
                                       reuse_intensity_metric(geometry),
                                       fixed_policy(Policy.RO))


# ---------------------------------------------------------------------------
# global (non-partitioned) two-level baselines — Table 1's uCache/FAST/L2ARC
# family, reduced to their content policies over our two-level datapath
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402

from .controller import VMResult, _acc, _pad  # noqa: E402
from .simulator import (Stats, make_cache, promote_blocks,  # noqa: E402
                        resident_blocks, simulate_single_level,
                        simulate_two_level)


class FastCache:
    """Dell EMC FAST-style global two-level cache: DRAM(WB) + SSD(WB),
    blocks with > ``hot_threshold`` accesses in the last window promoted
    to the SSD, no eviction rule beyond LRU (paper §2.2.2)."""

    def __init__(self, dram_capacity: int, ssd_capacity: int,
                 geometry: Geometry | None = None, window: int = 1_000,
                 hot_threshold: int = 3):
        self.geom = geometry or Geometry()
        self.dram = make_cache(self.geom.num_sets, self.geom.max_ways)
        self.ssd = make_cache(self.geom.num_sets, self.geom.max_ways)
        from .simulator import capacity_to_ways
        self.wd = int(capacity_to_ways(dram_capacity, self.geom.num_sets,
                                       self.geom.max_ways))
        self.ws = int(capacity_to_ways(ssd_capacity, self.geom.num_sets,
                                       self.geom.max_ways))
        self.window = window
        self.hot_threshold = hot_threshold
        self.stats: dict = {}
        self.t = 0

    def run(self, trace: Trace) -> VMResult:
        for win in trace.intervals(self.window):
            a, w = _pad(np.asarray(win.addr, np.int32),
                        np.asarray(win.is_write), self.window)
            # NPE-mode two-level datapath approximates WB+WB content flow
            self.dram, self.ssd, st, t_end = simulate_two_level(
                a, w, self.dram, self.ssd, self.wd, self.ws,
                mode="npe", t0=self.t)
            self.t = int(t_end)
            _acc(self.stats, st)
            # FAST promotion: > threshold accesses in the window
            uniq, counts = np.unique(np.asarray(win.addr),
                                     return_counts=True)
            hot = uniq[counts > self.hot_threshold]
            hot = hot[~np.isin(hot, resident_blocks(self.ssd, self.ws))]
            if hot.size:
                self.ssd, n = promote_blocks(self.ssd, hot, self.ws, self.t)
                self.stats["cache_writes_l2"] = (
                    self.stats.get("cache_writes_l2", 0.0) + int(n))
        return VMResult(dict(self.stats), np.zeros(1, np.int64))


def make_fast(dram_capacity: int, ssd_capacity: int, **kw) -> FastCache:
    return FastCache(dram_capacity, ssd_capacity, **kw)


class L2ARCCache:
    """ZFS L2ARC-style global two-level cache (paper §2.2.2): DRAM read
    cache; blocks evicted from DRAM are pushed into a FIFO SSD; reads
    only — writes bypass both levels. No popularity logic."""

    def __init__(self, dram_capacity: int, ssd_capacity: int,
                 geometry: Geometry | None = None, window: int = 1_000):
        from .simulator import capacity_to_ways
        self.geom = geometry or Geometry()
        self.dram = make_cache(self.geom.num_sets, self.geom.max_ways)
        self.ssd = make_cache(self.geom.num_sets, self.geom.max_ways)
        self.wd = int(capacity_to_ways(dram_capacity, self.geom.num_sets,
                                       self.geom.max_ways))
        self.ws = int(capacity_to_ways(ssd_capacity, self.geom.num_sets,
                                       self.geom.max_ways))
        self.window = window
        self.stats: dict = {}
        self.t = 0

    def run(self, trace: Trace) -> VMResult:
        prev_resident = resident_blocks(self.dram, self.wd)
        for win in trace.intervals(self.window):
            a, w = _pad(np.asarray(win.addr, np.int32),
                        np.asarray(win.is_write), self.window)
            # reads-only two-level flow: full mode never writes misses to
            # the SSD; writes pass through (DRAM level is RO already)
            self.dram, self.ssd, st, t_end = simulate_two_level(
                a, w, self.dram, self.ssd, self.wd, self.ws,
                mode="full", t0=self.t)
            self.t = int(t_end)
            _acc(self.stats, st)
            # L2ARC: push predicted-to-be-evicted DRAM blocks to the SSD
            # (approximated as blocks that left DRAM this window)
            now_resident = resident_blocks(self.dram, self.wd)
            evicted = prev_resident[~np.isin(prev_resident, now_resident)]
            prev_resident = now_resident
            evicted = evicted[~np.isin(evicted,
                                       resident_blocks(self.ssd, self.ws))]
            if evicted.size:
                self.ssd, n = promote_blocks(self.ssd, evicted, self.ws,
                                             self.t)
                self.stats["cache_writes_l2"] = (
                    self.stats.get("cache_writes_l2", 0.0) + int(n))
        return VMResult(dict(self.stats), np.zeros(1, np.int64))


def make_l2arc(dram_capacity: int, ssd_capacity: int, **kw) -> L2ARCCache:
    return L2ARCCache(dram_capacity, ssd_capacity, **kw)
