"""Plain reference of ETICA-Full (arXiv:2106.07423 §4) for one host.

Per resize window: POD(RO) and POD(WBWO) sizing of every VM, the PPC
partition of each level, the resize (a shrink flushes dirty blocks),
then per promotion interval of each VM its requests through DRAM(RO) +
SSD(WBWO) one at a time, and the interval's maintenance: Eq. 1
popularity aged and refreshed, the bottom 5% of a near-full SSD
partition evicted (dirty ones flushed), and the most popular blocks
without an SSD copy promoted into free SSD ways.

``precision`` is the floating type of the popularity scores and of the
per-interval latency sums. The controller's stated precision is
float32; a lower one is the control that ``correct`` must reject.
Scores are flushed to zero below float32's smallest normal number, as
the accelerators do. ``exp_table`` gives ``exp(-d / c)`` for every
distance ``d`` and partition size ``c``, computed once by the caller.
"""
from __future__ import annotations

import numpy as np

from .common import (FIELDS, IDX, T_DRAM, T_HDD, T_HDD_WRITE, T_SSD,
                     Accumulator, Level, chunks, demux, distances, partition,
                     size_vms, spread_surplus, to_ways)

TINY = np.finfo(np.float32).tiny


def _flush(x: np.ndarray) -> np.ndarray:
    out = x.copy()
    out[np.abs(x.astype(np.float64)) < TINY] = 0
    return out


class Popularity:
    """One VM's popularity scores: addresses ascending, at most
    ``capacity`` of them (the highest addresses fall off)."""

    def __init__(self, capacity: int, dtype):
        self.capacity = capacity
        self.dtype = np.dtype(dtype)
        self.addr = np.empty(0, np.int64)
        self.val = np.empty(0, self.dtype)

    def update(self, addr: np.ndarray, contrib: np.ndarray, decay) -> int:
        uniq, inv = np.unique(addr, return_inverse=True)
        sums = np.zeros(uniq.size, self.dtype)
        np.add.at(sums, inv, contrib.astype(self.dtype))   # arrival order
        sums = _flush(sums)
        self.val = _flush(self.val * self.dtype.type(decay))
        pos = np.searchsorted(self.addr, uniq)
        found = np.zeros(uniq.size, bool)
        inr = pos < self.addr.size
        found[inr] = self.addr[pos[inr]] == uniq[inr]
        self.val[pos[found]] = _flush(self.val[pos[found]] + sums[found])
        addr = np.concatenate([self.addr, uniq[~found]])
        val = np.concatenate([self.val, sums[~found]])
        order = np.argsort(addr, kind="stable")
        drops = max(addr.size - self.capacity, 0)
        self.addr = addr[order][: self.capacity]
        self.val = val[order][: self.capacity]
        return drops

    def scores(self, blocks: np.ndarray) -> np.ndarray:
        out = np.zeros(blocks.size, self.dtype)
        if self.addr.size:
            pos = np.minimum(np.searchsorted(self.addr, blocks),
                             self.addr.size - 1)
            hit = self.addr[pos] == blocks
            out[hit] = self.val[pos[hit]]
        return out


class EticaReference:
    def __init__(self, cfg: dict, num_vms: int, exp_table: np.ndarray,
                 precision=np.float32):
        self.cfg = cfg
        self.V = num_vms
        self.S, self.W = cfg["num_sets"], cfg["max_ways"]
        f = cfg["dram_fraction"]
        self.dram_cap = round(cfg["total_blocks"] * f / (1 + f))
        self.ssd_cap = cfg["total_blocks"] - self.dram_cap
        self.dram = [Level(self.S, self.W) for _ in range(num_vms)]
        self.ssd = [Level(self.S, self.W) for _ in range(num_vms)]
        self.t = np.zeros(num_vms, np.int64)
        self.dtype = np.dtype(precision)
        self.pop = [Popularity(cfg["pop_capacity"], self.dtype)
                    for _ in range(num_vms)]
        self.exp = exp_table.astype(self.dtype)
        # the promotion queue holds at most this many blocks
        k = cfg["pop_capacity"]
        self.queue_cap = min(1 << (min(k, self.S * self.W) - 1).bit_length(),
                             k)
        self.windows = []        # per window: dict of what it produced

    # -- one resize window ------------------------------------------------
    def run_window(self, addr, is_write, vm) -> None:
        cfg, V = self.cfg, self.V
        pos = demux(vm, V)
        subs = [(addr[p].astype(np.int64), is_write[p]) for p in pos]
        counts = np.array([p.size for p in pos], np.float64)
        out = {"stats": np.zeros((V, len(FIELDS)), np.int64),
               "latency": np.zeros(V)}
        for name, policy, cap, levels in (
                ("dram", "RO", self.dram_cap, self.dram),
                ("ssd", "WBWO", self.ssd_cap, self.ssd)):
            dem, curves, grid, _ = size_vms(subs, policy, True, self.S,
                                            self.W, cfg["mrc_points"])
            alloc = spread_surplus(partition(dem, curves, grid, cap),
                                   counts, cap, self.S * self.W)
            out[name + "_demand"], out[name + "_alloc"] = dem, alloc
            for v, w in enumerate(to_ways(alloc, self.S, self.W)):
                fl = levels[v].resize(int(w))
                out["stats"][v, IDX["disk_writes"]] += fl
                out["stats"][v, IDX["evict_flushes"]] += fl
        for v, (a, w) in enumerate(subs):
            lat = 0.0
            for lo, hi in chunks(a.size, cfg["promo_interval"]):
                acc = Accumulator(self.dtype if self.dtype != np.float32
                                  else np.float64)
                self._datapath(v, a[lo:hi], w[lo:hi], out["stats"][v], acc)
                lat += float(acc.value)
                if cfg["mode"] == "full":
                    self._maintain(v, a[lo:hi], w[lo:hi], out["stats"][v])
            out["latency"][v] = lat
        self.windows.append(out)

    # -- datapath -----------------------------------------------------------
    def _datapath(self, v, addr, is_write, st, acc) -> None:
        dram, ssd = self.dram[v], self.ssd[v]
        t = int(self.t[v])
        c = dict.fromkeys(FIELDS, 0)
        for a, w in zip(addr.tolist(), is_write.tolist()):
            dw, sw = dram.find(a), ssd.find(a)
            if not w:
                c["reads"] += 1
                if dw >= 0:
                    c["read_hits_l1"] += 1
                    dram.lru[a % dram.S, dw] = t
                    acc.add(T_DRAM)
                else:
                    if sw >= 0:
                        c["read_hits_l2"] += 1
                        ssd.lru[a % ssd.S, sw] = t
                        acc.add(T_SSD)
                    else:
                        c["disk_reads"] += 1
                        acc.add(T_HDD)
                    dram.insert(a, t, False)    # read-only level: clean
            else:
                c["writes"] += 1
                if dw >= 0:                     # stale DRAM copy
                    dram.drop(a, dw)
                if sw >= 0:                     # write-back hit
                    s = a % ssd.S
                    ssd.lru[s, sw] = t
                    ssd.dirty[s, sw] = True
                    c["write_hits_l2"] += 1
                    c["cache_writes_l2"] += 1
                    acc.add(T_SSD)
                else:                           # miss: straight to disk
                    c["disk_writes"] += 1
                    acc.add(T_HDD_WRITE)
            t += 1
        self.t[v] = t
        st += np.array([c[f] for f in FIELDS], np.int64)

    # -- maintenance --------------------------------------------------------
    def _maintain(self, v, addr, is_write, st) -> None:
        cfg, ssd = self.cfg, self.ssd[v]
        alloc = ssd.ways * self.S
        d, served = distances(addr, is_write, "WB", reads_only=False)
        contrib = np.zeros(addr.size, self.dtype)
        m = served & (d >= 0)
        contrib[m] = self.exp[ssd.ways, d[m]]
        st[IDX["pop_drops"]] += self.pop[v].update(
            addr, contrib, cfg["popularity_decay"])
        # eviction: the least popular 5% of a >= 90% full partition
        res = ssd.residents()
        if res.size and res.size * 10 >= alloc * 9:
            k = max(int(np.ceil(np.float32(cfg["evict_frac"])
                                * np.float32(res.size))), 1)
            victims = res[np.argsort(self.pop[v].scores(res),
                                     kind="stable")[:k]]
            flushed = 0
            for a in victims.tolist():
                w = ssd.find(a)
                flushed += int(ssd.dirty[a % self.S, w])
                ssd.drop(a, w)
            st[IDX["disk_writes"]] += flushed
            st[IDX["evict_flushes"]] += flushed
        # promotion: the most popular known blocks without an SSD copy,
        # as many as there is free space, each into a free way of its set
        res = ssd.residents()
        free = max(alloc - res.size, 0)
        pop = self.pop[v]
        cand = (pop.val > 0) & ~np.isin(pop.addr, res)
        ca, cv = pop.addr[cand], pop.val[cand].astype(np.float64)
        order = np.lexsort((-ca, -cv))[: min(free, self.queue_cap)]
        n = 0
        t = int(self.t[v])
        for a in ca[order].tolist():
            s = a % self.S
            empty = np.flatnonzero(ssd.tags[s, :ssd.ways] < 0)
            if empty.size:
                ssd.tags[s, empty[0]] = a
                ssd.lru[s, empty[0]] = t
                ssd.dirty[s, empty[0]] = False
                n += 1
        st[IDX["cache_writes_l2"]] += n
        st[IDX["disk_reads"]] += n

    # -- what the run leaves behind -----------------------------------------
    def state(self) -> dict:
        return {
            "dram_tags": np.stack([x.tags for x in self.dram]),
            "dram_lru": np.stack([x.lru for x in self.dram]),
            "dram_dirty": np.stack([x.dirty for x in self.dram]),
            "ssd_tags": np.stack([x.tags for x in self.ssd]),
            "ssd_lru": np.stack([x.lru for x in self.ssd]),
            "ssd_dirty": np.stack([x.dirty for x in self.ssd]),
            "clock": self.t.copy(),
            "pop_addr": [p.addr for p in self.pop],
            "pop_val": [p.val.astype(np.float64) for p in self.pop],
        }


def exp_table(cfg: dict, dtype) -> np.ndarray:
    """``exp(-d / max(w * S, 1))`` for ways ``w`` in ``0..W`` and distances
    ``d`` below the promotion interval, in ``dtype``, computed by the
    default JAX backend's own exponential (the accelerator's rounding of
    Eq. 1's one transcendental; everything else here is host arithmetic).
    """
    import jax
    import jax.numpy as jnp
    d = jnp.arange(cfg["promo_interval"], dtype=jnp.float32).astype(dtype)
    cs = (jnp.arange(cfg["max_ways"] + 1) * cfg["num_sets"]).astype(dtype)
    return np.asarray(jax.jit(lambda d, c: jnp.exp(
        -d[None, :] / jnp.maximum(c, jnp.asarray(1.0, dtype))[:, None]))(
            d, cs))


def make(cfg: dict, num_vms: int, precision=np.float32) -> EticaReference:
    return EticaReference(cfg, num_vms, exp_table(cfg, precision), precision)
