"""Plain reference of the cache controllers' shared parts.

Written from the semantics alone and importing nothing of the program:
request-by-request set-associative caches, reuse distances by an LRU
stack over a Fenwick tree (not the program's pairwise count), the PPC
partition and the surplus spread, all on the host. Used by the
per-controller references beside this file.
"""
from __future__ import annotations

import numpy as np

T_DRAM = 0.5e-6
T_SSD = 10e-6
T_HDD = 5e-3
T_HDD_WRITE = 0.5e-3

# integer Stats fields the references count, in a fixed order
FIELDS = ("reads", "writes", "read_hits_l1", "read_hits_l2", "write_hits_l2",
          "cache_writes_l2", "disk_reads", "disk_writes", "evict_flushes",
          "pop_drops", "flushes")
IDX = {f: i for i, f in enumerate(FIELDS)}


# ---------------------------------------------------------------------------
# reuse distances
# ---------------------------------------------------------------------------

def distances(addr, is_write, policy: str, reads_only: bool = True):
    """Per-request reuse distance under a write policy.

    ``policy`` is ``"WB"`` (every request occupies a block), ``"RO"``
    (reads occupy; a read counts when the previous request to its block
    was a read) or ``"WBWO"`` (writes occupy; a read counts once its block
    was written, and then occupies too). In ``"WB"`` a read with any
    earlier request counts, and with ``reads_only=False`` so does such a
    write. A counted request's distance is the number of distinct blocks
    occupied since its block was last occupied. Returns ``(dist, served)``
    with ``dist = -1`` where a request does not count.
    """
    n = len(addr)
    tree = [0] * (n + 1)
    last_touch: dict[int, int] = {}
    last_kind: dict[int, bool] = {}     # block -> was its last request a write
    written: set[int] = set()
    dist = np.full(n, -1, np.int64)
    served = np.zeros(n, bool)
    for i, (a, w) in enumerate(zip(addr.tolist(), is_write.tolist())):
        prev = last_kind.get(a)
        if policy == "WB":
            touch = True
            srv = prev is not None and (not w or not reads_only)
        elif policy == "RO":
            touch = not w
            srv = not w and prev is False
        else:   # WBWO
            srv = not w and a in written
            touch = w or srv
        if srv:
            p = last_touch[a]
            # marks (one per block, at its last occupancy) in (p, i)
            c, j = 0, i
            while j > 0:
                c += tree[j]
                j -= j & -j
            j = p + 1
            while j > 0:
                c -= tree[j]
                j -= j & -j
            dist[i] = c
            served[i] = True
        if touch:
            p = last_touch.get(a)
            if p is not None:
                j = p + 1
                while j <= n:
                    tree[j] -= 1
                    j += j & -j
            j = i + 1
            while j <= n:
                tree[j] += 1
                j += j & -j
            last_touch[a] = i
        last_kind[a] = w
        if w:
            written.add(a)
    return dist, served


def mrc_grid(num_sets: int, max_ways: int, points: int) -> np.ndarray:
    ways = np.unique(np.round(np.linspace(0, max_ways, points)).astype(int))
    return (ways * num_sets).astype(np.int64)


def hits_at(dist, served, grid) -> np.ndarray:
    """Counted requests whose distance is below each grid size."""
    d = np.sort(dist[served])
    return np.searchsorted(d, grid, side="left").astype(np.int64)


def demand(dist, served) -> int:
    return int(dist[served].max()) + 1 if served.any() else 0


# ---------------------------------------------------------------------------
# PPC partition (ETICA Eq. 3) and the surplus spread
# ---------------------------------------------------------------------------

NEG = -1e30


def partition(demands, curves, sizes, capacity: int) -> np.ndarray:
    """Blocks per VM: demands when they fit, else the knapsack optimum of
    sum(H_i(c_i) / c_i) over the grid, c_i <= demand_i, then the leftover
    water-filled by marginal hit gain."""
    demands = np.asarray(demands, np.int64)
    sizes = np.asarray(sizes, np.int64)
    nv, ng = curves.shape
    if demands.sum() <= capacity:
        return demands.copy()
    steps = np.diff(np.unique(sizes))
    unit = int(steps.min()) if steps.size else 1
    cap_u = int(capacity // unit)
    size_u = (sizes // unit).astype(np.int64)
    with np.errstate(divide="ignore", invalid="ignore"):
        ppc = np.where(sizes[None, :] > 0,
                       curves / np.maximum(sizes, 1)[None, :], 0.0)
    ppc = np.where(sizes[None, :] <= np.maximum(demands, 0)[:, None], ppc, NEG)
    ppc[:, sizes == 0] = 0.0
    layers = [np.full(cap_u + 1, NEG)]
    layers[0][0] = 0.0
    for v in range(nv):
        nxt = np.full(cap_u + 1, NEG)
        for g in range(ng):
            s = int(size_u[g])
            if s > cap_u or ppc[v, g] <= NEG / 2:
                continue
            cand = np.full(cap_u + 1, NEG)
            cand[s:] = layers[-1][: cap_u + 1 - s] + ppc[v, g]
            nxt = np.maximum(nxt, cand)
        layers.append(nxt)
    c = int(np.argmax(layers[-1]))
    best = layers[-1][c]
    alloc = np.zeros(nv, np.int64)
    for v in range(nv - 1, -1, -1):
        for g in range(ng):
            s = int(size_u[g])
            if s > c or ppc[v, g] <= NEG / 2:
                continue
            prev = layers[v][c - s]
            if prev > NEG / 2 and abs(prev + ppc[v, g] - best) <= (
                    1e-12 + 1e-9 * abs(best)):
                alloc[v] = sizes[g]
                c -= s
                best = prev
                break
    left = capacity - int(alloc.sum())
    while left >= unit:
        gains = np.full(nv, -np.inf)
        for i in range(nv):
            if alloc[i] + unit > demands[i]:
                continue
            gains[i] = (float(np.interp(alloc[i] + unit, sizes, curves[i]))
                        - float(np.interp(alloc[i], sizes, curves[i])))
        best_i = int(np.argmax(gains))
        if not np.isfinite(gains[best_i]) or gains[best_i] <= 0:
            under = np.nonzero(alloc + unit <= demands)[0]
            if under.size == 0:
                break
            best_i = int(under[np.argmax(demands[under] - alloc[under])])
        alloc[best_i] += unit
        left -= unit
    return alloc


def spread_surplus(alloc, counts, capacity: int, per_vm_max: int):
    """Capacity left over after the partition goes to the VMs in
    proportion to their request counts, each capped at its geometry."""
    left = capacity - int(alloc.sum())
    if left <= 0 or counts.sum() == 0:
        return alloc
    extra = np.floor(left * (counts / counts.sum())).astype(np.int64)
    return np.minimum(alloc + extra, per_vm_max)


def to_ways(blocks, num_sets: int, max_ways: int) -> np.ndarray:
    return np.clip((np.asarray(blocks, np.int64) + num_sets - 1) // num_sets,
                   0, max_ways)


def size_vms(subs, policy: str, reads_only: bool, num_sets: int,
             max_ways: int, points: int):
    """Per-VM demand, hit-ratio curve on the grid and read count."""
    grid = mrc_grid(num_sets, max_ways, points)
    nv = len(subs)
    dem = np.zeros(nv, np.int64)
    curves = np.zeros((nv, grid.size))
    reads = np.zeros(nv, np.int64)
    for v, (a, w) in enumerate(subs):
        if a.size == 0:
            continue
        d, s = distances(a, w, policy, reads_only)
        if not reads_only:          # URD: only reads are sized
            s = s & ~w
        dem[v] = min(demand(d, s), num_sets * max_ways)
        curves[v] = hits_at(d, s, grid) / a.size
        reads[v] = int((~w).sum())
    return dem, curves, grid, reads


# ---------------------------------------------------------------------------
# set-associative cache level
# ---------------------------------------------------------------------------

class Level:
    """One VM's cache level: ``[S, W]`` tags (-1 empty), last-use times
    and dirty bits. Only the first ``ways`` ways are in use."""

    def __init__(self, num_sets: int, max_ways: int):
        self.S, self.W = num_sets, max_ways
        self.tags = np.full((num_sets, max_ways), -1, np.int64)
        self.lru = np.full((num_sets, max_ways), -1, np.int64)
        self.dirty = np.zeros((num_sets, max_ways), bool)
        self.ways = 0

    def find(self, a: int) -> int:
        """Way holding block ``a`` among the ways in use, else -1."""
        row = self.tags[a % self.S, :self.ways]
        hit = np.flatnonzero(row == a)
        return int(hit[0]) if hit.size else -1

    def victim(self, s: int) -> int:
        """First empty way in use, else the least recently used one."""
        tags = self.tags[s, :self.ways]
        empty = np.flatnonzero(tags < 0)
        if empty.size:
            return int(empty[0])
        return int(np.argmin(self.lru[s, :self.ways]))

    def insert(self, a: int, t: int, dirty: bool) -> tuple[bool, bool]:
        """Place ``a``; returns (placed, a dirty block was pushed out)."""
        if self.ways == 0:
            return False, False
        s = a % self.S
        w = self.victim(s)
        pushed = bool(self.tags[s, w] >= 0 and self.dirty[s, w])
        self.tags[s, w] = a
        self.lru[s, w] = t
        self.dirty[s, w] = dirty
        return True, pushed

    def drop(self, a: int, w: int) -> None:
        s = a % self.S
        self.tags[s, w] = -1
        self.lru[s, w] = -1
        self.dirty[s, w] = False

    def resize(self, ways: int) -> int:
        """Set the ways in use; shrinking empties the rest and returns the
        dirty blocks flushed to disk."""
        flushed = 0
        if ways < self.ways:
            flushed = int(self.dirty[:, ways:].sum())
            self.tags[:, ways:] = -1
            self.lru[:, ways:] = -1
            self.dirty[:, ways:] = False
        self.ways = int(ways)
        return flushed

    def residents(self) -> np.ndarray:
        """Blocks held in the ways in use, in (set, way) order."""
        t = self.tags[:, :self.ways].reshape(-1)
        return t[t >= 0]


def demux(vm: np.ndarray, num_vms: int):
    """Per-VM request positions of a window, arrival order kept."""
    order = np.argsort(vm, kind="stable")
    bounds = np.searchsorted(vm[order], np.arange(num_vms + 1))
    return [order[bounds[v]:bounds[v + 1]] for v in range(num_vms)]


def chunks(n: int, size: int):
    return [(k, min(k + size, n)) for k in range(0, n, size)]


class Accumulator:
    """A running sum rounded to ``dtype`` after every add, as a device
    accumulator in that precision would hold it (float64: exact-ish)."""

    def __init__(self, dtype):
        self.dtype = np.dtype(dtype)
        self.value = self.dtype.type(0)

    def add(self, x: float) -> None:
        self.value = self.dtype.type(self.value + self.dtype.type(x))
