"""Plain reference of ECI-Cache (arXiv:1805.00976), the one-level
baseline ETICA is measured against, for one host.

Per resize window: every VM is sized by its useful reuse distance (URD:
read re-references, every request occupying a block), the PPC partition
divides the SSD cache, a VM reading at least 80% of the time gets the
read-only policy (RO) and the others write-back (WB), the resize flushes
the dirty blocks of shrunk partitions, and then each VM's requests run
one at a time through its partition under its policy.

``precision`` is the floating type of the per-interval latency sums: the
controller states float32, and a lower one is the control.
"""
from __future__ import annotations

import numpy as np

from .common import (FIELDS, T_HDD, T_HDD_WRITE, T_SSD, Accumulator, Level,
                     chunks, demux, partition, size_vms, spread_surplus,
                     to_ways, IDX)


class EciReference:
    def __init__(self, cfg: dict, num_vms: int, precision=np.float32):
        self.cfg = cfg
        self.V = num_vms
        self.S, self.W = cfg["num_sets"], cfg["max_ways"]
        self.levels = [Level(self.S, self.W) for _ in range(num_vms)]
        self.t = np.zeros(num_vms, np.int64)
        self.dtype = np.dtype(precision)
        self.windows = []

    def run_window(self, addr, is_write, vm) -> None:
        cfg, V = self.cfg, self.V
        pos = demux(vm, V)
        subs = [(addr[p].astype(np.int64), is_write[p]) for p in pos]
        counts = np.array([p.size for p in pos], np.float64)
        dem, curves, grid, reads = size_vms(subs, "WB", False, self.S,
                                            self.W, cfg["mrc_points"])
        cap = cfg["total_blocks"]
        alloc = spread_surplus(partition(dem, curves, grid, cap), counts,
                               cap, self.S * self.W)
        ro = [n > 0 and r / n >= cfg["read_heavy_threshold"]
              for r, n in zip(reads.tolist(), counts.astype(int).tolist())]
        out = {"stats": np.zeros((V, len(FIELDS)), np.int64),
               "latency": np.zeros(V), "demand": dem, "alloc": alloc,
               "policy": ["RO" if r else "WB" for r in ro]}
        for v, w in enumerate(to_ways(alloc, self.S, self.W)):
            fl = self.levels[v].resize(int(w))
            out["stats"][v, IDX["disk_writes"]] += fl
            out["stats"][v, IDX["evict_flushes"]] += fl
        for v, (a, w) in enumerate(subs):
            lat = 0.0
            for lo, hi in chunks(a.size, cfg["sim_chunk"]):
                acc = Accumulator(self.dtype if self.dtype != np.float32
                                  else np.float64)
                self._datapath(v, a[lo:hi], w[lo:hi], ro[v],
                               out["stats"][v], acc)
                lat += float(acc.value)
            out["latency"][v] = lat
        self.windows.append(out)

    def _datapath(self, v, addr, is_write, ro, st, acc) -> None:
        lv = self.levels[v]
        t = int(self.t[v])
        c = dict.fromkeys(FIELDS, 0)
        for a, w in zip(addr.tolist(), is_write.tolist()):
            way = lv.find(a)
            s = a % lv.S
            if not w:
                c["reads"] += 1
                if way >= 0:
                    c["read_hits_l2"] += 1
                    lv.lru[s, way] = t
                    acc.add(T_SSD)
                else:                       # both policies fill on a read
                    c["disk_reads"] += 1
                    acc.add(T_HDD)
                    placed, pushed = lv.insert(a, t, False)
                    c["cache_writes_l2"] += placed
                    c["disk_writes"] += pushed
            elif ro:                        # write around, drop the copy
                c["writes"] += 1
                c["disk_writes"] += 1
                if way >= 0:
                    lv.drop(a, way)
                acc.add(T_HDD_WRITE)
            else:                           # write-back
                c["writes"] += 1
                if way >= 0:
                    lv.lru[s, way] = t
                    lv.dirty[s, way] = True
                    c["write_hits_l2"] += 1
                    c["cache_writes_l2"] += 1
                    acc.add(T_SSD)
                else:
                    placed, pushed = lv.insert(a, t, True)
                    if placed:
                        c["cache_writes_l2"] += 1
                        c["disk_writes"] += pushed
                        acc.add(T_SSD)
                    else:
                        c["disk_writes"] += 1
                        acc.add(T_HDD_WRITE)
            t += 1
        self.t[v] = t
        st += np.array([c[f] for f in FIELDS], np.int64)

    def state(self) -> dict:
        return {
            "tags": np.stack([x.tags for x in self.levels]),
            "lru": np.stack([x.lru for x in self.levels]),
            "dirty": np.stack([x.dirty for x in self.levels]),
            "clock": self.t.copy(),
        }


def make(cfg: dict, num_vms: int, precision=np.float32) -> EciReference:
    return EciReference(cfg, num_vms, precision)
