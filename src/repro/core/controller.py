"""Interval-driven cache controllers (the hypervisor-side brain).

:class:`EticaCache` is the paper's full system: every ``resize_interval``
requests it recomputes POD(RO)/POD(WBWO) per VM, re-partitions both cache
levels via PPC, and resizes the per-VM caches; every ``promo_interval``
requests it refreshes popularity scores and executes the
promotion/eviction queues (pull-mode SSD maintenance, §4.2).

:class:`PartitionedSingleLevelCache` is the shared chassis for the
one-level baselines (ECI-Cache, Centaur, S-CAVE, vCacheShare) — they
differ only in the sizing metric and the per-VM write-policy chooser (see
``repro.core.baselines``).

All datapath simulation happens in fixed-shape jitted ``lax.scan`` windows
(padded with addr = -1 no-ops). With ``batched=True`` (the default) the
per-VM cache states are stacked into one pytree with a leading ``[V]``
axis and each window simulates **all VMs in one vmapped dispatch**; POD
sizing and the one-level baselines' sizing metrics (URD/TRD/WSS/reuse
intensity via ``SizingMetric.batch``) batch across VMs the same way.
ETICA's promotion/eviction maintenance goes further: the whole interval
— Eq. 1 popularity refresh into a device-resident ``[V, K]`` table,
queue building, and the Pallas evict/promote scatters — is ONE fused
jitted dispatch with no host round-trips between stages
(``repro.kernels.maintenance``; ``fused_maintenance=False`` keeps the
staged tracker-based path as the intermediate oracle). Per-VM ways —
and, for the one-level chassis, per-VM write policies — are traced
operands, so heterogeneous allocations and ECI-style dynamic policies
share one compiled executable. ``batched=False`` preserves the
sequential per-VM architecture (separate per-VM states, V dispatches
per window, host-side numpy maintenance) as the bit-identical reference
oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np

from repro.runtime.telemetry import TelemetryRecorder, span, sync

from . import popularity as pop
from .partition import partition as _partition
from . import reuse, simulator
from .policies import Policy
from .simulator import (CacheState, Stats, capacity_to_ways, make_cache,
                        make_cache_batch, policy_flags, resize_batch)
from .trace import Trace


# fused-maintenance executables already compiled at every way bucket, by
# the shapes and options of the dispatch (see EticaCache._warm_ways_buckets)
_WARMED_WAYS_BUCKETS: set = set()


def _window_source(trace, num_vms: int, window: int, chunk: int,
                   prefetch: bool, prefetch_depth: int = 2,
                   pad_vms: int = 0, sharding=None):
    """Normalize ``run``'s input (Trace | TraceStore |
    StreamingTraceSource) into a resize-window iterator. Imported lazily
    so ``repro.core`` does not depend on ``repro.traces`` at import
    time."""
    from repro.traces.stream import window_source
    return window_source(trace, num_vms, window, chunk, prefetch,
                         prefetch_depth, pad_vms, sharding)


def _mesh_setup(mesh, num_vms: int, batched: bool, classifier):
    """Validate a controller's mesh config; returns ``(num_rows,
    sharding)`` — the dead-VM-padded row count the device state carries
    and the ``NamedSharding`` that places ``[V_pad, ...]`` arrays one row
    block per device. Dead rows (``ways = 0``, ``addr = -1`` blocks) are
    exact no-ops, so results stay bit-identical to the unpadded run."""
    if mesh is None:
        return num_vms, None
    if not batched:
        raise ValueError(
            "mesh sharding requires batched=True — the sequential "
            "per-VM oracle has no [V] axis to shard")
    if classifier is not None:
        raise ValueError(
            "mesh sharding does not support an IO classifier yet — the "
            "classified datapath dispatches have no sharded variants")
    from jax.sharding import NamedSharding

    from repro.launch.mesh import vm_spec
    d = mesh.size
    num_rows = -(-num_vms // d) * d
    return num_rows, NamedSharding(mesh, vm_spec(mesh))


@dataclasses.dataclass
class Geometry:
    num_sets: int = 64
    max_ways: int = 64

    @property
    def capacity(self) -> int:
        return self.num_sets * self.max_ways


@dataclasses.dataclass
class IntervalLog:
    """Per-interval record for the Fig. 10/15-style plots."""
    demands: np.ndarray          # [V] blocks requested by the metric
    alloc: np.ndarray            # [V] blocks granted
    policies: list[str] | None = None


@dataclasses.dataclass
class VMResult:
    stats: dict[str, float]
    alloc_history: np.ndarray    # [intervals]

    @property
    def hit_ratio(self) -> float:
        s = self.stats
        return (s["read_hits_l1"] + s["read_hits_l2"] + s["write_hits_l2"]) / max(
            s["reads"] + s["writes"], 1)

    @property
    def mean_latency(self) -> float:
        return self.stats["latency_sum"] / max(
            self.stats["reads"] + self.stats["writes"], 1)

    def contended_latency(self, beta: float = 8.0) -> float:
        """Mean latency under an SSD write-contention model.

        Sustained writes trigger SSD garbage collection that inflates the
        latency of *every* SSD access (well documented for NAND devices;
        the paper's own premise is that performance degrades with
        committed writes). Modeled as
        ``t_ssd_eff = T_SSD * (1 + beta * write_share)`` applied to all
        SSD accesses, with write_share = SSD writes / SSD accesses.
        This couples the endurance win to a latency win — the regime the
        paper's real-hardware numbers reflect."""
        from .policies import T_SSD
        s = self.stats
        ssd_accesses = (s["read_hits_l2"] + s["write_hits_l2"]
                        + s["cache_writes_l2"])
        if ssd_accesses <= 0:
            return self.mean_latency
        write_share = s["cache_writes_l2"] / ssd_accesses
        extra = ssd_accesses * T_SSD * beta * write_share
        return (s["latency_sum"] + extra) / max(
            s["reads"] + s["writes"], 1)

    @property
    def ssd_writes(self) -> float:
        return self.stats["cache_writes_l2"]


def _pad(addr: np.ndarray, is_write: np.ndarray, n: int):
    k = n - addr.shape[0]
    if k <= 0:
        return addr[:n], is_write[:n]
    return (np.concatenate([addr, np.full(k, -1, addr.dtype)]),
            np.concatenate([is_write, np.zeros(k, bool)]))


def _vm_slice(state: CacheState, v: int) -> CacheState:
    """View VM ``v``'s cache out of a stacked [V, S, W] state."""
    return jax.tree_util.tree_map(lambda x: x[v], state)


def _stats_to_dict(st: Stats) -> dict[str, float]:
    return {k: float(v) for k, v in zip(Stats._fields, st)}


def _acc(d: dict[str, float], st: Stats) -> None:
    for k, v in zip(Stats._fields, st):
        d[k] = d.get(k, 0.0) + float(v)


def _cls_chunk(cls_subs: list[np.ndarray], k: int, chunk: int) -> np.ndarray:
    """The ``[V, chunk]`` class-id block matching datapath chunk ``k``
    (padding positions are class 0 — masked no-ops either way)."""
    out = np.zeros((len(cls_subs), chunk), np.int32)
    for v, cs in enumerate(cls_subs):
        seg = cs[k * chunk:(k + 1) * chunk]
        out[v, :len(seg)] = seg
    return out


def _class_policy_flags(pol_vc: list[list[Policy]]) -> "simulator.PolicyFlags":
    """``[V, C]`` :class:`~repro.core.simulator.PolicyFlags` from per-
    (VM, class) policies (classifier override or the VM's own policy)."""
    f = lambda attr: np.asarray(
        [[getattr(p, attr) for p in row] for row in pol_vc], bool)
    return simulator.PolicyFlags(f("allocates_reads"), f("write_invalidates"),
                                 f("holds_dirty"), f("write_through"))


def _strip_bypass(chunks: list[Trace | None], cls_subs: list[np.ndarray],
                  k: int, chunk: int, byp: np.ndarray) -> list[Trace | None]:
    """Drop bypass-class requests from a maintenance chunk list: bypassed
    requests never touch the cache, so they must not feed popularity
    either. Chunks without bypassed requests pass through unchanged."""
    out = []
    for v, c in enumerate(chunks):
        if c is None or len(c) == 0:
            out.append(c)
            continue
        m = ~byp[cls_subs[v][k * chunk:(k + 1) * chunk]]
        out.append(c if m.all() else c[m])
    return out


def _mrc_grid(geom: Geometry, points: int = 17) -> np.ndarray:
    ways = np.unique(np.round(np.linspace(0, geom.max_ways, points)).astype(int))
    return (ways * geom.num_sets).astype(np.int64)


def _expand_to_capacity(alloc: np.ndarray, counts: np.ndarray,
                        capacity: int, geom: Geometry) -> np.ndarray:
    """Distribute surplus capacity beyond instantaneous demand.

    Paper Fig. 15/16: "ETICA increases the allocated cache to VM0, since
    other VMs' demand is low" — spare space goes to VMs in proportion to
    their request share (bounded by the per-VM geometry), so promotion
    has room to build each VM's popular set beyond the strict POD demand.
    """
    left = capacity - int(alloc.sum())
    if left <= 0 or counts.sum() == 0:
        return alloc
    share = counts / counts.sum()
    extra = np.floor(left * share).astype(np.int64)
    return np.minimum(alloc + extra, geom.capacity)


# ---------------------------------------------------------------------------
# ETICA (two-level)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EticaConfig:
    dram_capacity: int               # total DRAM-level blocks across VMs
    ssd_capacity: int                # total SSD-level blocks across VMs
    geometry_dram: Geometry = dataclasses.field(default_factory=Geometry)
    geometry_ssd: Geometry = dataclasses.field(default_factory=Geometry)
    resize_interval: int = 10_000    # paper §5.1
    promo_interval: int = 1_000      # paper §5.3
    promo_frac: float = 0.05         # paper §4.2.1: top/bottom 5%
    evict_frac: float = 0.05
    popularity_decay: float = 0.5
    mode: str = "full"               # "full" | "npe"
    mrc_points: int = 17
    batched: bool = True             # one vmapped dispatch for all VMs
    prefetch: bool = True            # pipeline host->device blocks
    prefetch_depth: int = 2          # blocks in flight beyond the consumed
    mesh: object | None = None       # launch.mesh.make_vm_mesh: shard the
    #                                  VM axis across devices (requires
    #                                  batched + fused_maintenance; VM
    #                                  count padded with dead VMs to a
    #                                  multiple of the mesh size)
    fused_maintenance: bool = True   # one fused jitted maintenance dispatch
    pop_capacity: int = 8192         # per-VM device popularity-table slots
    classifier: object | None = None  # repro.classify.Classifier | None
    clean_quota: int = 0             # background cleaner: max dirty-block
    #                                  flushes per VM per maintenance
    #                                  interval (0 disables the stage)
    telemetry: object | None = None  # repro.runtime.telemetry
    #                                  .TelemetryRecorder; None gets a
    #                                  default bounded recorder (same
    #                                  results either way — the recorder
    #                                  only reads already-fetched host
    #                                  values)


class EticaCache:
    """The proposed system: DRAM(RO) + SSD(WBWO), POD sizing, PPC
    partitioning, popularity-driven promotion/eviction.

    With ``cfg.batched`` the per-VM states live stacked in one
    ``[V, S, W]`` pytree (``self.dram`` / ``self.ssd``); without it they
    are lists of per-VM states. Use :meth:`vm_dram` / :meth:`vm_ssd` for a
    single VM's view in either layout.
    """

    def __init__(self, cfg: EticaConfig, num_vms: int):
        self.cfg = cfg
        self.num_vms = num_vms
        if cfg.mesh is not None and not cfg.fused_maintenance:
            raise ValueError(
                "EticaCache mesh sharding requires fused_maintenance=True "
                "— the staged maintenance path round-trips through host "
                "trackers and cannot stay shard-local")
        # device state carries V_pad rows when a mesh is configured; the
        # pad rows are dead VMs (ways 0, addr -1 blocks) that every
        # dispatch treats as exact no-ops. Host-side structures (stats,
        # logs, trackers) stay at the real VM count.
        self._rows, self._sharding = _mesh_setup(
            cfg.mesh, num_vms, cfg.batched, cfg.classifier)
        self._shards = 1 if cfg.mesh is None else cfg.mesh.size
        rows = self._rows
        gd, gs = cfg.geometry_dram, cfg.geometry_ssd
        if cfg.batched:
            self.dram = make_cache_batch(rows, gd.num_sets, gd.max_ways)
            self.ssd = make_cache_batch(rows, gs.num_sets, gs.max_ways)
        else:
            self.dram = [make_cache(gd.num_sets, gd.max_ways)
                         for _ in range(num_vms)]
            self.ssd = [make_cache(gs.num_sets, gs.max_ways)
                        for _ in range(num_vms)]
        self.ways_dram = np.zeros(rows, np.int32)
        self.ways_ssd = np.zeros(rows, np.int32)
        self.t = np.zeros(rows, np.int32)
        # popularity state: the fused batched path keeps ONE [V, K]
        # device-resident table; the staged/sequential paths use the
        # host trackers (the table's bit-exact oracle)
        self.pop_table = (pop.table_init(rows, cfg.pop_capacity)
                          if cfg.batched and cfg.fused_maintenance else None)
        self.trackers = [pop.PopularityTracker(cfg.popularity_decay)
                         for _ in range(num_vms)]
        self.stats = [dict() for _ in range(num_vms)]
        self.logs_dram: list[IntervalLog] = []
        self.logs_ssd: list[IntervalLog] = []
        # interval telemetry: one bounded journal row per promo-interval
        # chunk, fed exclusively from host values the interval already
        # fetched (zero extra device→host syncs). The maintenance temps
        # below carry this interval's promote/evict/clean counts from
        # the maintenance step to the sampler.
        if cfg.telemetry is not None:
            self.telemetry = cfg.telemetry
        else:
            self.telemetry = TelemetryRecorder()
        self._m_promoted = np.zeros(num_vms, np.int64)
        self._m_evicted = np.zeros(num_vms, np.int64)
        self._m_cleaned = np.zeros(num_vms, np.int64)
        self._m_dirty = np.zeros(num_vms, np.int64)
        self._m_clean_ran = False
        # IO classification (repro.classify): per-VM sequential-run carry
        # plus the per-class tables the classified simulators consume
        self.classifier = cfg.classifier
        if self.classifier is not None:
            self._cls_end, self._cls_len = self.classifier.init_carry(num_vms)
            self._byp = np.asarray(self.classifier.bypass, bool)
            c = self.classifier.num_classes
            self._lo_d = self._hi_d = np.zeros((num_vms, c), np.int32)
            self._lo_s = self._hi_s = np.zeros((num_vms, c), np.int32)
            # per-(VM, class) served hit/miss counters (telemetry export)
            self.cls_hits = np.zeros((num_vms, c), np.int64)
            self.cls_miss = np.zeros((num_vms, c), np.int64)

    def vm_dram(self, v: int) -> CacheState:
        return _vm_slice(self.dram, v) if self.cfg.batched else self.dram[v]

    def vm_ssd(self, v: int) -> CacheState:
        return _vm_slice(self.ssd, v) if self.cfg.batched else self.ssd[v]

    # -- telemetry ----------------------------------------------------------
    # Pre-PR-9 cleaner telemetry (`clean_log`/`dirty_log`) was a pair of
    # unbounded Python lists growing one [V] vector per maintenance
    # interval forever. They are now bounded-journal views: the rows
    # where the batched cleaner actually ran — same entries the lists
    # held (the sequential oracle never recorded them, and still
    # doesn't), capped at the journal window.
    @property
    def clean_log(self) -> list[np.ndarray]:
        return self.telemetry.cache_clean_log()

    @property
    def dirty_log(self) -> list[np.ndarray]:
        return self.telemetry.cache_dirty_log()

    def _sample_interval(self) -> None:
        """Append one journal row for the chunk just simulated — per-VM
        deltas from the cumulative stats plus the maintenance counts the
        interval's existing device_get already brought to host."""
        gd, gs = self.cfg.geometry_dram, self.cfg.geometry_ssd
        cls = self.classifier is not None
        self.telemetry.sample_cache(
            self.stats,
            alloc_l1=self.ways_dram[:self.num_vms].astype(np.int64)
            * gd.num_sets,
            alloc_l2=self.ways_ssd[:self.num_vms].astype(np.int64)
            * gs.num_sets,
            promoted=self._m_promoted, evict_queue=self._m_evicted,
            cleaned=self._m_cleaned, dirty=self._m_dirty,
            clean_ran=self._m_clean_ran,
            cls_hits=self.cls_hits if cls else None,
            cls_miss=self.cls_miss if cls else None)
        self._m_promoted = np.zeros(self.num_vms, np.int64)
        self._m_evicted = np.zeros(self.num_vms, np.int64)
        self._m_cleaned = np.zeros(self.num_vms, np.int64)
        self._m_clean_ran = False          # _m_dirty is a gauge: carries

    # -- sizing -----------------------------------------------------------
    def _size_level(self, subs: list[Trace], policy: Policy, geom: Geometry,
                    capacity: int, cls_subs: list[np.ndarray] | None = None,
                    *, level: str):
        grid = _mrc_grid(geom, self.cfg.mrc_points)
        with self.telemetry.span("sizing", level=level,
                                 shards=self._shards) as sp:
            demands = np.zeros(self.num_vms, np.int64)
            curves = np.zeros((self.num_vms, grid.size))
            addrs = [np.asarray(s.addr) for s in subs]
            writes = [np.asarray(s.is_write) for s in subs]
            wts = None
            if cls_subs is not None:
                # per-class sizing weights: weight-0 (bypass) requests
                # never reach the cache, so they are cut from the sizing
                # sub-traces; the rest weight the hit curves per class
                cw = self.classifier.weights
                wts = []
                for v, cs in enumerate(cls_subs):
                    w_req = cw[cs]
                    keep = w_req > 0
                    if not keep.all():
                        addrs[v] = addrs[v][keep]
                        writes[v] = writes[v][keep]
                        w_req = w_req[keep]
                    wts.append(w_req)
            if self.cfg.batched:
                # all VMs' POD decompositions in one vmapped dispatch
                # (with a mesh: dead-VM rows pad to the sharded row count
                # and each device decomposes its own block)
                if self.cfg.mesh is not None:
                    pad = self._rows - self.num_vms
                    dists = reuse.pod_distances_batch(
                        addrs + [np.empty(0, np.int32)] * pad,
                        writes + [np.empty(0, bool)] * pad,
                        policy, mesh=self.cfg.mesh,
                        telemetry=self.telemetry)[: self.num_vms]
                else:
                    dists = reuse.pod_distances_batch(addrs, writes, policy)
                sp.ready(dists)
            else:
                dists = [reuse.pod_distances(a, w, policy) if a.size
                         else None for a, w in zip(addrs, writes)]
            for v, r in enumerate(dists):
                if r is None:
                    continue
                demands[v] = min(
                    reuse.demand_blocks(int(sync("demand", r.max))),
                    geom.capacity)
                if wts is None:
                    hits = reuse.hit_counts_at_sizes(r.dist, r.served, grid)
                    curves[v] = (np.asarray(hits, np.float64)
                                 / max(len(subs[v]), 1))
                else:
                    hits = reuse.hit_counts_at_sizes_weighted(
                        r.dist, r.served, grid, wts[v])
                    curves[v] = hits / max(wts[v].sum(), 1)
        with span("partition"):
            res = _partition(demands, curves, grid, capacity)
            if wts is None:
                counts = np.array([len(s) for s in subs], np.float64)
            else:
                counts = np.array([w.sum() for w in wts], np.float64)
            alloc = _expand_to_capacity(res.alloc, counts, capacity, geom)
        return alloc, demands, dists

    # -- maintenance --------------------------------------------------------
    def _alloc_blocks(self, v: int) -> int:
        return int(self.ways_ssd[v]) * self.cfg.geometry_ssd.num_sets

    def _refresh_tracker(self, v: int, window: Trace, r) -> None:
        # Eq. 1 sums over ALL re-references (paper: "POD(i,t) is the POD of
        # B_i in the t-th access") — write re-references included, so
        # write-hot blocks (usr_0-style workloads) become popular and get
        # promoted into the WBWO SSD where subsequent writes hit.
        contrib = pop.contributions(r.dist, r.served,
                                    max(self._alloc_blocks(v), 1))
        self.trackers[v].update(np.asarray(window.addr), np.asarray(contrib))

    def _maintain_seq(self, v: int, window: Trace) -> None:
        """Per-VM popularity refresh + promotion/eviction (paper §4.2) —
        the pre-batching host-side numpy path (reference oracle)."""
        cfg = self.cfg
        if len(window) == 0:
            return
        alloc_blocks = self._alloc_blocks(v)
        r = reuse.trd_distances(window.addr, window.is_write)
        self._refresh_tracker(v, window, r)

        ssd_res = simulator.resident_blocks(self.ssd[v], int(self.ways_ssd[v]))
        # eviction queue: least popular 5% of SSD-resident blocks — only
        # once the partition is near-full (an empty cache has nothing
        # worth churning; paper evicts to make room for promotions). The
        # 90% gate is integer arithmetic so every path (host and device)
        # agrees at the boundary.
        if ssd_res.size and ssd_res.size * 10 >= alloc_blocks * 9:
            evict = self.trackers[v].least_popular(ssd_res, cfg.evict_frac)
            if evict.size:
                self._m_evicted[v] += int(evict.size)
                self.ssd[v], flushed = simulator.evict_blocks_ref(
                    self.ssd[v], evict)
                self.stats[v]["disk_writes"] = (
                    self.stats[v].get("disk_writes", 0.0) + flushed)
                self.stats[v]["evict_flushes"] = (
                    self.stats[v].get("evict_flushes", 0.0) + flushed)
        # promotion queue: the most popular blocks known to the tracker
        # that lack an SSD copy (paper: "the most popular 5% of the data
        # blocks in disk subsystem"), drained up to the free space
        residents = simulator.resident_blocks(self.ssd[v],
                                              int(self.ways_ssd[v]))
        free = max(alloc_blocks - residents.size, 0)
        if free:
            promote = self.trackers[v].top_known(residents, free)
            if promote.size:
                self.ssd[v], n = simulator.promote_blocks_ref(
                    self.ssd[v], promote, int(self.ways_ssd[v]),
                    int(self.t[v]))
                self._m_promoted[v] += int(n)
                # each promotion = 1 disk read + 1 SSD write (endurance cost)
                self.stats[v]["cache_writes_l2"] = (
                    self.stats[v].get("cache_writes_l2", 0.0) + n)
                self.stats[v]["disk_reads"] = (
                    self.stats[v].get("disk_reads", 0.0) + n)
        # background cleaner (third stage): flush the quota oldest dirty
        # blocks so evictions later in the run hit clean blocks
        if cfg.clean_quota > 0:
            self.ssd[v], n_fl, left = simulator.clean_blocks_ref(
                self.ssd[v], int(self.ways_ssd[v]), cfg.clean_quota)
            self.stats[v]["flushes"] = (
                self.stats[v].get("flushes", 0.0) + n_fl)
            self.stats[v]["disk_writes"] = (
                self.stats[v].get("disk_writes", 0.0) + n_fl)
            self.stats[v]["dirty_resident"] = float(left)
            self._m_cleaned[v] += int(n_fl)
            self._m_dirty[v] = int(left)

    def _residents(self, tags_np: np.ndarray, v: int) -> np.ndarray:
        t = tags_np[v, :, : max(int(self.ways_ssd[v]), 0)]
        return t[t >= 0]

    def _maintain_all(self, chunks: list[Trace | None]) -> None:
        """All VMs' maintenance for one window, batched.

        With ``cfg.fused_maintenance`` (default) the whole interval —
        popularity refresh into the device table, queue building, the
        eviction scatter, and the promotion scatter — runs as ONE fused
        jitted dispatch through the Pallas maintenance kernels
        (:func:`repro.kernels.maintenance.ops.maintenance_interval`);
        the state never visits the host between stages. Without it, the
        staged path keeps host trackers and separate vmapped dispatches
        (the intermediate oracle). Per-VM semantics are identical to
        :meth:`_maintain_seq` either way.
        """
        if self.cfg.fused_maintenance:
            self._maintain_fused(chunks)
        else:
            with span("maintenance"):
                self._maintain_staged(chunks)

    def _maintain_fused(self, chunks: list[Trace | None]) -> None:
        """One fused jitted dispatch for the whole interval's maintenance
        (device popularity table + Pallas promote/evict kernels)."""
        from repro.kernels.maintenance import ops as maint_ops
        cfg = self.cfg
        empty = np.empty(0, np.int32)
        addrs = [empty if c is None else np.asarray(c.addr) for c in chunks]
        writes = [empty.astype(bool) if c is None else np.asarray(c.is_write)
                  for c in chunks]
        # dead-VM pad rows (mesh only): zero-length like idle VMs
        addrs += [empty] * (self._rows - self.num_vms)
        writes += [empty.astype(bool)] * (self._rows - self.num_vms)
        lens = [int(a.shape[0]) for a in addrs]
        live = [v for v, n in enumerate(lens) if n > 0]
        if not live:
            return
        # the dispatch works on the SSD level's leading `wb` ways, enough
        # for every VM's active ways (the rest hold no block)
        wb = maint_ops.ways_bucket_of(self.ways_ssd,
                                      cfg.geometry_ssd.max_ways)
        self.telemetry.ways_buckets[wb] += 1
        with self.telemetry.span("maintenance", ways_bucket=wb,
                                 shards=self._shards) as sp:
            # batched TRD decomposition (same bucketing as
            # trd_distances_batch) — results stay on device and feed the
            # fused dispatch directly. ALL VMs ride as rows (idle ones
            # zero-length) so the fused executable is keyed only by the
            # window bucket, not by which subset of VMs is live. With a
            # mesh both the decomposition and the fused maintenance run
            # one row block per device.
            amat, wmat = reuse._pad_rows(addrs, writes,
                                         list(range(self._rows)), lens)
            if cfg.mesh is not None:
                r = reuse._decompose_sharded(cfg.mesh, amat, wmat,
                                             Policy.WB, False, 256,
                                             self.telemetry)
                # the gathered TRD distances go up again, to the shards
                self.telemetry.shard_host_bytes += (r.dist.nbytes
                                                    + r.served.nbytes)
            else:
                r = reuse._decompose_vmapped(amat, wmat, policy=Policy.WB,
                                             sizing_reads_only=False,
                                             chunk=256)
            args = (self.ssd, self.pop_table, r.dist, r.served, amat)
            kw = dict(evict_frac=cfg.evict_frac,
                      decay=cfg.popularity_decay,
                      clean_quota=cfg.clean_quota, mesh=cfg.mesh)
            self._warm_ways_buckets(args, kw)
            (self.ssd, self.pop_table, flushed, promoted, eqlen, pqlen,
             pdrops, cleaned, dirty_left) = maint_ops.maintenance_interval(
                    *args, np.asarray(lens, np.int32), self.ways_ssd, self.t,
                    ways_bucket=wb, **kw)
            sp.ready((self.ssd, self.pop_table, flushed))
            # ONE host transfer for all per-VM counters — the cleaner's
            # two vectors ride the sync the interval already paid for
            (flushed, promoted, eqlen, pqlen, pdrops, cleaned,
             dirty_left) = sync("maintenance", (
                 flushed, promoted, eqlen, pqlen, pdrops, cleaned,
                 dirty_left))
            # drop the dead-VM pad rows (all-zero: wlen == 0 skips them)
            (flushed, promoted, eqlen, pqlen, pdrops, cleaned,
             dirty_left) = (np.asarray(x)[: self.num_vms]
                            for x in (flushed, promoted, eqlen, pqlen,
                                      pdrops, cleaned, dirty_left))
            for v in live:
                if pdrops[v]:
                    # merge-overflow: popularity entries pushed past the
                    # [V, K] table's capacity this interval (device-table
                    # path only — the host trackers are effectively
                    # unbounded)
                    self.stats[v]["pop_drops"] = (
                        self.stats[v].get("pop_drops", 0.0)
                        + int(pdrops[v]))
                if eqlen[v]:
                    self.stats[v]["disk_writes"] = (
                        self.stats[v].get("disk_writes", 0.0)
                        + int(flushed[v]))
                    self.stats[v]["evict_flushes"] = (
                        self.stats[v].get("evict_flushes", 0.0)
                        + int(flushed[v]))
                if pqlen[v]:
                    # each promotion = 1 disk read + 1 SSD write
                    # (endurance)
                    self.stats[v]["cache_writes_l2"] = (
                        self.stats[v].get("cache_writes_l2", 0.0)
                        + int(promoted[v]))
                    self.stats[v]["disk_reads"] = (
                        self.stats[v].get("disk_reads", 0.0)
                        + int(promoted[v]))
                if cfg.clean_quota > 0:
                    self.stats[v]["flushes"] = (
                        self.stats[v].get("flushes", 0.0) + int(cleaned[v]))
                    self.stats[v]["disk_writes"] = (
                        self.stats[v].get("disk_writes", 0.0)
                        + int(cleaned[v]))
                    self.stats[v]["dirty_resident"] = float(dirty_left[v])
            # same masking as the stats credits above: the kernel outputs
            # are only meaningful where the corresponding queue was
            # non-empty
            self._m_promoted += np.where(np.asarray(pqlen) > 0,
                                         np.asarray(promoted, np.int64), 0)
            self._m_evicted += np.asarray(eqlen, np.int64)
            if cfg.clean_quota > 0:
                self._m_cleaned += np.asarray(cleaned, np.int64)
                self._m_dirty = np.asarray(dirty_left, np.int64)
                self._m_clean_ran = True

    def _warm_ways_buckets(self, args, kw) -> None:
        """Compile the fused dispatch at every way bucket the SSD level
        can reach, the first time any controller meets this window
        bucket, so that a later change of bucket compiles nothing.

        No VM holds more than ``ceil(ssd_capacity / num_sets)`` ways, so
        that bounds the buckets. Each is a no-op dispatch (every row idle,
        ``wlen == 0``) on the interval's own arrays, whose shapes and
        placement the real calls share; its outputs are dropped. This is
        set-up work: it runs once per window bucket and process.
        """
        from repro.kernels import resolve_interpret
        from repro.kernels.maintenance import ops as maint_ops
        gs = self.cfg.geometry_ssd
        cap = -(-self.cfg.ssd_capacity // gs.num_sets)
        buckets = maint_ops.ways_buckets_upto(cap, gs.max_ways)
        key = (tuple(x.shape for x in jax.tree.leaves(args)), buckets,
               resolve_interpret(None), tuple(sorted(kw.items())))
        if key in _WARMED_WAYS_BUCKETS:
            return
        idle = np.zeros(self._rows, np.int32)
        for wb in buckets:
            # one at a time: each holds a copy of the state and the table
            jax.block_until_ready(maint_ops.maintenance_interval(
                *args, idle, idle, self.t, ways_bucket=wb, **kw))
        _WARMED_WAYS_BUCKETS.add(key)

    def _maintain_staged(self, chunks: list[Trace | None]) -> None:
        """Staged batched maintenance (host trackers + separate vmapped
        dispatches with host syncs between stages) — kept as the
        intermediate oracle between :meth:`_maintain_fused` and
        :meth:`_maintain_seq`, and as the fused path's benchmark
        baseline."""
        cfg = self.cfg
        live = [v for v, c in enumerate(chunks) if c is not None and len(c)]
        if not live:
            return
        rs = reuse.trd_distances_batch(
            [np.asarray(chunks[v].addr) for v in live],
            [np.asarray(chunks[v].is_write) for v in live])
        # Eq. 1 contributions for every VM in one elementwise dispatch
        # (same values as the per-VM calls; padding rows contribute 0)
        lens = [len(chunks[v]) for v in live]
        width = simulator._next_pow2(max(lens))
        dmat = np.full((len(live), width), -1, np.int32)
        smat = np.zeros((len(live), width), bool)
        cs = np.empty((len(live), 1), np.float32)
        for i, v in enumerate(live):
            dmat[i, : lens[i]] = rs[i].dist
            smat[i, : lens[i]] = rs[i].served
            cs[i] = max(self._alloc_blocks(v), 1)
        cmat = sync("contrib", pop.contributions(dmat, smat, cs))
        for i, v in enumerate(live):
            self.trackers[v].update(np.asarray(chunks[v].addr),
                                    cmat[i, : lens[i]])

        nothing = np.empty(0, np.int64)
        tags_np = sync("tags", self.ssd.tags)
        evict_qs = [nothing] * self.num_vms
        for v in live:
            res = self._residents(tags_np, v)
            if res.size and res.size * 10 >= self._alloc_blocks(v) * 9:
                evict_qs[v] = self.trackers[v].least_popular(
                    res, cfg.evict_frac)
        if any(q.size for q in evict_qs):
            self._m_evicted += np.asarray([q.size for q in evict_qs],
                                          np.int64)
            self.ssd, flushed = simulator.evict_blocks_batch(
                self.ssd, evict_qs)
            flushed = sync("evict", flushed)
            for v in live:
                if evict_qs[v].size:
                    self.stats[v]["disk_writes"] = (
                        self.stats[v].get("disk_writes", 0.0)
                        + int(flushed[v]))
                    self.stats[v]["evict_flushes"] = (
                        self.stats[v].get("evict_flushes", 0.0)
                        + int(flushed[v]))
            tags_np = sync("tags", self.ssd.tags)

        promo_qs = [nothing] * self.num_vms
        for v in live:
            res = self._residents(tags_np, v)
            free = max(self._alloc_blocks(v) - res.size, 0)
            if free:
                promo_qs[v] = self.trackers[v].top_known(res, free)
        if any(q.size for q in promo_qs):
            self.ssd, n = simulator.promote_blocks_batch(
                self.ssd, promo_qs, self.ways_ssd, self.t)
            n = sync("promote", n)
            for v in live:
                if promo_qs[v].size:
                    self._m_promoted[v] += int(n[v])
                    self.stats[v]["cache_writes_l2"] = (
                        self.stats[v].get("cache_writes_l2", 0.0)
                        + int(n[v]))
                    self.stats[v]["disk_reads"] = (
                        self.stats[v].get("disk_reads", 0.0) + int(n[v]))

        # background cleaner (third stage): one vmapped dispatch flushes
        # the quota oldest dirty blocks per live VM
        if cfg.clean_quota > 0:
            quota = np.zeros(self.num_vms, np.int32)
            quota[live] = cfg.clean_quota
            self.ssd, cleaned, dirty_left = simulator.clean_batch(
                self.ssd, self.ways_ssd, quota)
            cleaned, dirty_left = sync("clean", (cleaned, dirty_left))
            for v in live:
                self.stats[v]["flushes"] = (
                    self.stats[v].get("flushes", 0.0) + int(cleaned[v]))
                self.stats[v]["disk_writes"] = (
                    self.stats[v].get("disk_writes", 0.0) + int(cleaned[v]))
                self.stats[v]["dirty_resident"] = float(dirty_left[v])
            self._m_cleaned += np.asarray(cleaned, np.int64)
            self._m_dirty = np.asarray(dirty_left, np.int64)
            self._m_clean_ran = True

    # -- datapath ----------------------------------------------------------
    def _run_chunk_batched(self, a, w, chunks: list[Trace | None],
                           cmat: np.ndarray | None = None) -> None:
        """One vmapped dispatch simulates this window for every VM.

        ``a``/``w`` are the rectangular ``[V, chunk]`` request block (host
        numpy or already-transferred device arrays from the streaming
        prefetcher); ``chunks`` the ragged per-VM views for stats
        attribution. ``cmat`` is the matching ``[V, chunk]`` class-id
        block when a classifier is configured."""
        cfg = self.cfg
        with self.telemetry.span("datapath") as sp:
            if cmat is None and cfg.mesh is not None:
                self.dram, self.ssd, st, t_end = \
                    simulator.simulate_two_level_sharded(
                        a, w, self.dram, self.ssd, self.ways_dram,
                        self.ways_ssd, cfg.mesh, mode=cfg.mode, t0=self.t)
            elif cmat is None:
                self.dram, self.ssd, st, t_end = \
                    simulator.simulate_two_level_batch(
                        a, w, self.dram, self.ssd, self.ways_dram,
                        self.ways_ssd, mode=cfg.mode, t0=self.t)
            else:
                self.dram, self.ssd, st, t_end, ch, cm = \
                    simulator.simulate_two_level_classified_batch(
                        a, w, cmat, self.dram, self.ssd, self.ways_dram,
                        self.ways_ssd, self._byp, self._lo_d, self._hi_d,
                        self._lo_s, self._hi_s, mode=cfg.mode, t0=self.t)
                ch, cm = sync("classes", (ch, cm))
                self.cls_hits += np.asarray(ch, np.int64)
                self.cls_miss += np.asarray(cm, np.int64)
            sp.ready(st)
        with span("stats"):
            self.t = sync("clock", t_end)
            st = sync("stats", st)
            for v, chunk in enumerate(chunks):
                if chunk is not None:
                    _acc(self.stats[v], Stats(*[f[v] for f in st]))

    def _run_chunk_sequential(self, chunks: list[Trace | None],
                              cls_subs: list[np.ndarray] | None = None,
                              k: int = 0) -> None:
        """Reference oracle: V sequential per-VM dispatches."""
        cfg = self.cfg
        for v, chunk in enumerate(chunks):
            if chunk is None:
                continue
            a, w = _pad(np.asarray(chunk.addr, np.int32),
                        np.asarray(chunk.is_write), cfg.promo_interval)
            if cls_subs is None:
                self.dram[v], self.ssd[v], st, t_end = \
                    simulator.simulate_two_level(
                        a, w, self.dram[v], self.ssd[v],
                        int(self.ways_dram[v]), int(self.ways_ssd[v]),
                        mode=cfg.mode, t0=int(self.t[v]))
            else:
                seg = cls_subs[v][k * cfg.promo_interval:
                                  (k + 1) * cfg.promo_interval]
                cpad = np.zeros(cfg.promo_interval, np.int32)
                cpad[:len(seg)] = seg
                self.dram[v], self.ssd[v], st, t_end, ch, cm = \
                    simulator.simulate_two_level_classified(
                        a, w, cpad, self.dram[v], self.ssd[v],
                        int(self.ways_dram[v]), int(self.ways_ssd[v]),
                        self._byp, self._lo_d[v], self._hi_d[v],
                        self._lo_s[v], self._hi_s[v],
                        mode=cfg.mode, t0=int(self.t[v]))
                self.cls_hits[v] += np.asarray(ch, np.int64)
                self.cls_miss[v] += np.asarray(cm, np.int64)
            self.t[v] = int(t_end)
            _acc(self.stats[v], st)

    # -- main loop ----------------------------------------------------------
    def _run_window(self, win, alloc_hist: list[list[int]]) -> None:
        """One resize window: size, partition and resize both levels, then
        simulate and maintain it promotion interval by interval."""
        cfg = self.cfg
        gd, gs = cfg.geometry_dram, cfg.geometry_ssd
        subs = win.subs
        # 0) IO classification: one fused dispatch per window, the
        # sequential-run carry threaded across windows per VM
        cls_subs = None
        if self.classifier is not None:
            cls_subs, self._cls_end, self._cls_len = \
                self.classifier.classify_subs(subs, self._cls_end,
                                              self._cls_len)
        # 1) POD sizing + PPC partitioning at both levels (§4.3)
        alloc_d, dem_d, _ = self._size_level(
            subs, Policy.RO, cfg.geometry_dram, cfg.dram_capacity,
            cls_subs, level="dram")
        alloc_s, dem_s, _ = self._size_level(
            subs, Policy.WBWO, cfg.geometry_ssd, cfg.ssd_capacity,
            cls_subs, level="ssd")
        self.logs_dram.append(IntervalLog(dem_d, alloc_d))
        self.logs_ssd.append(IntervalLog(dem_s, alloc_s))
        with span("partition"):
            wd = sync("ways", capacity_to_ways(alloc_d, gd.num_sets,
                                               gd.max_ways))
            ws = sync("ways", capacity_to_ways(alloc_s, gs.num_sets,
                                               gs.max_ways))
            # dead-VM pad rows keep zero ways forever
            wd = np.pad(wd, (0, self._rows - self.num_vms))
            ws = np.pad(ws, (0, self._rows - self.num_vms))
        # 2) resize both levels (shrinking flushes dirty blocks)
        if cfg.batched:
            with span("resize"):
                # both levels resized in ONE jitted dispatch (sharded:
                # every device resizes its own row block)
                if cfg.mesh is not None:
                    self.dram, self.ssd, _, flushed = \
                        simulator.resize_levels_sharded(
                            self.dram, self.ssd, self.ways_dram, wd,
                            self.ways_ssd, ws, cfg.mesh)
                else:
                    self.dram, self.ssd, _, flushed = \
                        simulator.resize_levels(
                            self.dram, self.ssd, self.ways_dram, wd,
                            self.ways_ssd, ws)
                flushed = sync("resize", flushed)
                for v in range(self.num_vms):
                    self.stats[v]["disk_writes"] = (
                        self.stats[v].get("disk_writes", 0.0)
                        + int(flushed[v]))
                    self.stats[v]["evict_flushes"] = (
                        self.stats[v].get("evict_flushes", 0.0)
                        + int(flushed[v]))
        else:
            for v in range(self.num_vms):
                self.dram[v], _ = simulator.resize_ref(
                    self.dram[v], int(self.ways_dram[v]), int(wd[v]))
                self.ssd[v], fl = simulator.resize_ref(
                    self.ssd[v], int(self.ways_ssd[v]), int(ws[v]))
                self.stats[v]["disk_writes"] = (
                    self.stats[v].get("disk_writes", 0.0) + fl)
                self.stats[v]["evict_flushes"] = (
                    self.stats[v].get("evict_flushes", 0.0) + fl)
        for v in range(self.num_vms):
            alloc_hist[v].append(int(alloc_d[v] + alloc_s[v]))
        self.ways_dram, self.ways_ssd = wd, ws
        # class -> sub-partition way ranges for the new allocations
        if self.classifier is not None:
            self._lo_d, self._hi_d = self.classifier.way_bounds(wd)
            self._lo_s, self._hi_s = self.classifier.way_bounds(ws)
        # 3) datapath simulation in promo-interval chunks + maintenance
        if cfg.batched:
            # [V, chunk] blocks from the source (device-put one block
            # ahead of the simulator when prefetch is on)
            for k, (a, w, kth) in enumerate(win.blocks()):
                cmat = (None if cls_subs is None else
                        _cls_chunk(cls_subs, k, cfg.promo_interval))
                self._run_chunk_batched(a, w, kth, cmat)
                if cfg.mode == "full":
                    mth = (kth if cls_subs is None else _strip_bypass(
                        kth, cls_subs, k, cfg.promo_interval, self._byp))
                    self._maintain_all(mth)
                with span("telemetry"):
                    self._sample_interval()
        else:
            chunk_lists = win.chunk_lists()
            for k in range(max(map(len, chunk_lists), default=0)):
                kth = [c[k] if k < len(c) else None for c in chunk_lists]
                self._run_chunk_sequential(kth, cls_subs, k)
                if cfg.mode == "full":
                    mth = (kth if cls_subs is None else _strip_bypass(
                        kth, cls_subs, k, cfg.promo_interval, self._byp))
                    for v, chunk in enumerate(mth):
                        if chunk is not None:
                            self._maintain_seq(v, chunk)
                self._sample_interval()

    def run(self, trace) -> list[VMResult]:
        """Drive the controller over a whole trace.

        ``trace`` may be an in-memory :class:`Trace`, an on-disk
        :class:`repro.traces.store.TraceStore`, or a pre-built
        :class:`repro.traces.stream.StreamingTraceSource` — all three
        produce bit-identical results; the store/stream paths never hold
        more than one resize window (plus the in-flight ``[V, chunk]``
        blocks) in host memory."""
        cfg = self.cfg
        alloc_hist = [[] for _ in range(self.num_vms)]
        source = _window_source(trace, self.num_vms, cfg.resize_interval,
                                cfg.promo_interval, cfg.prefetch,
                                cfg.prefetch_depth,
                                self._rows - self.num_vms, self._sharding)
        # each window's demux runs in next(), before its span, as `ingest`
        for win in source.windows():
            with span("window", index=win.index,
                      requests=sum(map(len, win.subs))):
                self._run_window(win, alloc_hist)
        return [VMResult(dict(self.stats[v]),
                         np.asarray(alloc_hist[v], np.int64))
                for v in range(self.num_vms)]


# ---------------------------------------------------------------------------
# shared chassis for one-level partitioned baselines
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SingleLevelConfig:
    capacity: int
    geometry: Geometry = dataclasses.field(default_factory=Geometry)
    resize_interval: int = 10_000
    sim_chunk: int = 1_000
    mrc_points: int = 17
    batched: bool = True             # one vmapped dispatch for all VMs
    prefetch: bool = True            # pipeline host->device blocks
    prefetch_depth: int = 2          # blocks in flight beyond the consumed
    mesh: object | None = None       # launch.mesh.make_vm_mesh: shard the
    #                                  VM axis across devices (requires
    #                                  batched; VM count padded with dead
    #                                  VMs to a multiple of the mesh size)
    classifier: object | None = None  # repro.classify.Classifier | None
    telemetry: object | None = None  # TelemetryRecorder | None (default
    #                                  bounded recorder when None)


MetricFn = Callable[[Trace], tuple[int, np.ndarray, np.ndarray]]
# returns (demand_blocks, grid_sizes, hit_curve)
PolicyFn = Callable[[Trace], Policy]


@dataclasses.dataclass(frozen=True)
class PolicyChooser:
    """A per-VM write-policy chooser in batched and sequential forms.

    ECI-Cache picks each VM's policy from its read ratio every resize
    interval. With a batched :class:`~repro.core.baselines.SizingMetric`
    the per-VM read counts already ride the vmapped sizing dispatch
    (``reuse.sizing_metrics_batch``), so :meth:`batch` turns those counts
    into policies with zero extra per-VM work; ``ref`` is the original
    host-loop closure kept as the sequential oracle
    (``batched=False``). Instances are themselves callable as a plain
    :data:`PolicyFn`.
    """

    from_read_ratio: Callable[[float], Policy]
    ref: PolicyFn                     # sequential per-VM oracle

    def __call__(self, sub: Trace) -> Policy:
        return self.ref(sub)

    def batch(self, read_counts, lens) -> list[Policy]:
        """Policies for all VMs from the sizing dispatch's read counts.

        Bit-identical to calling ``ref`` per VM: the ratio is the same
        integer division, and empty VMs keep the chassis' ``Policy.WB``
        default."""
        return [self.from_read_ratio(int(r) / max(int(n), 1))
                if n else Policy.WB
                for r, n in zip(read_counts, lens)]


class PartitionedSingleLevelCache:
    """One SSD cache level, partitioned across VMs per a sizing metric.

    ECI-Cache = URD metric + dynamic WB/RO policy; Centaur = TRD + WB;
    S-CAVE = WSS + WT; vCacheShare = reuse-intensity + RO. Push-mode
    datapath (allocates on every miss the policy admits) — exactly the
    behavior the paper criticizes in §2.1. With ``cfg.batched`` the
    per-VM states are stacked (``[V, S, W]``) and each window runs all
    VMs — including heterogeneous per-VM policies — in one vmapped
    dispatch; otherwise states are per-VM lists driven sequentially.

    ``metric`` may be a plain per-VM closure (``MetricFn``) or a
    :class:`repro.core.baselines.SizingMetric`. With a ``SizingMetric``
    and ``cfg.batched``, every resize interval sizes *all* VMs in one
    vmapped jitted reduction over the stacked reuse-distance histograms
    (zero per-VM Python-loop metric calls) — mirroring how the datapath
    and maintenance already batch. ``batched=False`` (or a plain closure)
    evaluates the sequential per-VM oracle, bit-identically.
    """

    def __init__(self, cfg: SingleLevelConfig, num_vms: int,
                 metric, policy_fn: PolicyFn):
        self.cfg = cfg
        self.num_vms = num_vms
        self.metric = metric
        self.policy_fn = policy_fn
        # device state carries dead-VM-padded rows with a mesh (see
        # EticaCache) — host structures stay at the real VM count
        self._rows, self._sharding = _mesh_setup(
            cfg.mesh, num_vms, cfg.batched, cfg.classifier)
        g = cfg.geometry
        if cfg.batched:
            self.caches = make_cache_batch(self._rows, g.num_sets,
                                           g.max_ways)
        else:
            self.caches = [make_cache(g.num_sets, g.max_ways)
                           for _ in range(num_vms)]
        self.ways = np.zeros(self._rows, np.int32)
        self.t = np.zeros(self._rows, np.int32)
        self.stats = [dict() for _ in range(num_vms)]
        self.logs: list[IntervalLog] = []
        if cfg.telemetry is not None:
            self.telemetry = cfg.telemetry
        else:
            self.telemetry = TelemetryRecorder()
        self.classifier = cfg.classifier
        if self.classifier is not None:
            self._cls_end, self._cls_len = self.classifier.init_carry(num_vms)
            self._byp = np.asarray(self.classifier.bypass, bool)
            c = self.classifier.num_classes
            self.cls_hits = np.zeros((num_vms, c), np.int64)
            self.cls_miss = np.zeros((num_vms, c), np.int64)

    def vm_cache(self, v: int) -> CacheState:
        return _vm_slice(self.caches, v) if self.cfg.batched else self.caches[v]

    def _sample_interval(self) -> None:
        """One journal row per sim chunk — same host-side delta sampling
        as :meth:`EticaCache._sample_interval`, minus the two-level
        maintenance channels this chassis doesn't have."""
        cls = self.classifier is not None
        self.telemetry.sample_cache(
            self.stats,
            alloc_l2=self.ways[:self.num_vms].astype(np.int64)
            * self.cfg.geometry.num_sets,
            cls_hits=self.cls_hits if cls else None,
            cls_miss=self.cls_miss if cls else None)

    def _run_window(self, win, alloc_hist: list[list[int]]) -> None:
        """One resize window: size every VM by the metric, choose its
        policy, partition and resize, then simulate chunk by chunk."""
        cfg = self.cfg
        subs = win.subs
        # IO classification: bypass-class requests never reach the
        # cache, so they are cut from the sizing/policy sub-traces
        cls_subs = None
        subs_sz = subs
        if self.classifier is not None:
            cls_subs, self._cls_end, self._cls_len = \
                self.classifier.classify_subs(subs, self._cls_end,
                                              self._cls_len)
            wts = self.classifier.weights
            keep = [wts[c] > 0 for c in cls_subs]
            subs_sz = [s if m.all() else s[m]
                       for s, m in zip(subs, keep)]
        grid = _mrc_grid(cfg.geometry, cfg.mrc_points)
        batched_metric = cfg.batched and hasattr(self.metric, "batch")
        with self.telemetry.span("sizing") as sp:
            demands = np.zeros(self.num_vms, np.int64)
            curves = np.zeros((self.num_vms, grid.size))
            if batched_metric:
                # all VMs' metrics in ONE vmapped reduction over the
                # stacked reuse-distance histograms (empty rows stay 0);
                # the dynamic policy choosers' read counts ride the same
                # dispatch
                pad = self._rows - self.num_vms
                dem, g_, cur, reads = self.metric.batch(
                    [np.asarray(s.addr) for s in subs_sz]
                    + [np.empty(0, np.int32)] * pad,
                    [np.asarray(s.is_write) for s in subs_sz]
                    + [np.empty(0, bool)] * pad,
                    with_reads=True, mesh=cfg.mesh)
                dem, cur, reads = (dem[:self.num_vms],
                                   cur[:self.num_vms],
                                   reads[:self.num_vms])
                sp.ready((dem, cur))
                same_grid = np.array_equal(g_, grid)
                for v, sub in enumerate(subs_sz):
                    if len(sub) == 0:
                        continue
                    demands[v] = min(int(dem[v]), cfg.geometry.capacity)
                    curves[v] = cur[v] if same_grid else np.interp(
                        grid, g_, cur[v])
            else:
                metric_fn = getattr(self.metric, "ref", self.metric)
                for v, sub in enumerate(subs_sz):
                    if len(sub) == 0:
                        continue
                    d, g_, c_ = metric_fn(sub)
                    demands[v] = min(d, cfg.geometry.capacity)
                    curves[v] = np.interp(grid, g_, c_)
        with span("partition"):
            if batched_metric and isinstance(self.policy_fn, PolicyChooser):
                policies = self.policy_fn.batch(reads,
                                                [len(s) for s in subs_sz])
            else:
                policies = [self.policy_fn(sub) if len(sub) else Policy.WB
                            for sub in subs_sz]
            res = _partition(demands, curves, grid, cfg.capacity)
            if cls_subs is None:
                counts = np.array([len(s) for s in subs], np.float64)
            else:
                counts = np.array([wts[c].sum() for c in cls_subs],
                                  np.float64)
            alloc = _expand_to_capacity(res.alloc, counts, cfg.capacity,
                                        cfg.geometry)
            self.logs.append(IntervalLog(demands, alloc,
                                         [p.value for p in policies]))
            w_new = sync("ways", capacity_to_ways(
                alloc, cfg.geometry.num_sets, cfg.geometry.max_ways))
            # dead-VM pad rows keep zero ways forever
            w_new = np.pad(w_new, (0, self._rows - self.num_vms))
            # pad rows get the WB default — dead VMs (0 ways, addr -1
            # blocks) never touch their cache whatever the policy says
            flags = policy_flags(
                policies + [Policy.WB] * (self._rows - self.num_vms))
            if cls_subs is not None:
                # per-(VM, class) policy flags + insertion way ranges
                flags_vc = _class_policy_flags(
                    self.classifier.vm_policies(policies))
                lo, hi = self.classifier.way_bounds(w_new)
        if cfg.batched:
            with span("resize"):
                if cfg.mesh is not None:
                    self.caches, flushed = simulator.resize_batch_sharded(
                        self.caches, self.ways, w_new, cfg.mesh)
                else:
                    self.caches, flushed = resize_batch(
                        self.caches, self.ways, w_new)
                flushed = sync("resize", flushed)
                for v in range(self.num_vms):
                    self.stats[v]["disk_writes"] = (
                        self.stats[v].get("disk_writes", 0.0)
                        + int(flushed[v]))
                    self.stats[v]["evict_flushes"] = (
                        self.stats[v].get("evict_flushes", 0.0)
                        + int(flushed[v]))
        else:
            for v in range(self.num_vms):
                self.caches[v], fl = simulator.resize_ref(
                    self.caches[v], int(self.ways[v]), int(w_new[v]))
                self.stats[v]["disk_writes"] = (
                    self.stats[v].get("disk_writes", 0.0) + fl)
                self.stats[v]["evict_flushes"] = (
                    self.stats[v].get("evict_flushes", 0.0) + fl)
        for v in range(self.num_vms):
            alloc_hist[v].append(int(alloc[v]))
        self.ways = w_new
        if cfg.batched:
            # [V, chunk] blocks from the source (device-put one block
            # ahead of the simulator when prefetch is on)
            for k, (a, wr, kth) in enumerate(win.blocks()):
                with self.telemetry.span("datapath") as sp:
                    if cls_subs is None and cfg.mesh is not None:
                        self.caches, st, t_end = \
                            simulator.simulate_single_level_sharded(
                                a, wr, self.caches, self.ways, flags,
                                cfg.mesh, t0=self.t)
                    elif cls_subs is None:
                        self.caches, st, t_end = \
                            simulator.simulate_single_level_batch(
                                a, wr, self.caches, self.ways, flags,
                                t0=self.t)
                    else:
                        cmat = _cls_chunk(cls_subs, k, cfg.sim_chunk)
                        self.caches, st, t_end, ch, cm = simulator.\
                            simulate_single_level_classified_batch(
                                a, wr, cmat, self.caches, self.ways,
                                flags_vc, lo, hi, self._byp, t0=self.t)
                        ch, cm = sync("classes", (ch, cm))
                        self.cls_hits += np.asarray(ch, np.int64)
                        self.cls_miss += np.asarray(cm, np.int64)
                    sp.ready(st)
                with span("stats"):
                    self.t = sync("clock", t_end)
                    st = sync("stats", st)
                    for v, chunk in enumerate(kth):
                        if chunk is not None:
                            _acc(self.stats[v], Stats(*[f[v] for f in st]))
                with span("telemetry"):
                    self._sample_interval()
        else:
            chunk_lists = win.chunk_lists()
            for k in range(max(map(len, chunk_lists), default=0)):
                kth = [c[k] if k < len(c) else None for c in chunk_lists]
                for v, chunk in enumerate(kth):
                    if chunk is None:
                        continue
                    a, wr = _pad(np.asarray(chunk.addr, np.int32),
                                 np.asarray(chunk.is_write),
                                 cfg.sim_chunk)
                    if cls_subs is None:
                        self.caches[v], st, t_end = \
                            simulator.simulate_single_level(
                                a, wr, self.caches[v], int(self.ways[v]),
                                policies[v], t0=int(self.t[v]))
                    else:
                        seg = cls_subs[v][k * cfg.sim_chunk:
                                          (k + 1) * cfg.sim_chunk]
                        cpad = np.zeros(cfg.sim_chunk, np.int32)
                        cpad[:len(seg)] = seg
                        fv = simulator.PolicyFlags(
                            *[np.asarray(f[v]) for f in flags_vc])
                        self.caches[v], st, t_end, ch, cm = \
                            simulator.simulate_single_level_classified(
                                a, wr, cpad, self.caches[v],
                                int(self.ways[v]), fv, lo[v], hi[v],
                                self._byp, t0=int(self.t[v]))
                        self.cls_hits[v] += np.asarray(ch, np.int64)
                        self.cls_miss[v] += np.asarray(cm, np.int64)
                    self.t[v] = int(t_end)
                    _acc(self.stats[v], st)
                self._sample_interval()

    def run(self, trace) -> list[VMResult]:
        """Drive the chassis over a :class:`Trace`, an on-disk
        :class:`repro.traces.store.TraceStore`, or a pre-built
        :class:`repro.traces.stream.StreamingTraceSource` — bit-identical
        results either way (the streamed paths hold one resize window at
        a time)."""
        cfg = self.cfg
        alloc_hist = [[] for _ in range(self.num_vms)]
        source = _window_source(trace, self.num_vms, cfg.resize_interval,
                                cfg.sim_chunk, cfg.prefetch,
                                cfg.prefetch_depth,
                                self._rows - self.num_vms, self._sharding)
        # each window's demux runs in next(), before its span, as `ingest`
        for win in source.windows():
            with span("window", index=win.index,
                      requests=sum(map(len, win.subs))):
                self._run_window(win, alloc_hist)
        return [VMResult(dict(self.stats[v]),
                         np.asarray(alloc_hist[v], np.int64))
                for v in range(self.num_vms)]
