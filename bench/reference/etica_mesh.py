"""Plain reference of ETICA-Full (arXiv:2106.07423 §4) for a host of many
VMs, its VMs split into blocks, each run by a worker process.

The semantics are those of ``bench/reference/etica.py``, whose per-VM
code (``EticaReference``'s caches, popularity, datapath and maintenance)
the workers run unchanged. VMs meet only in the PPC partition, so a
resize window runs in three steps: the workers size their VMs (POD(RO)
and POD(WBWO) demands and hit curves), this process partitions both
levels over every VM and spreads the surplus, and the workers then
resize, replay and maintain their VMs. A worker keeps its VMs' state for
the whole run. The workers import no JAX: the ``exp`` table is computed
once here, by the default backend, as the one-process reference does.
They are started by ``spawn``, which imports the calling program's main
module again in each: it has to keep its work under
``if __name__ == "__main__"``, as ``bench/run.py`` does.
"""
from __future__ import annotations

import multiprocessing
import os
import traceback
import weakref

import numpy as np

from .common import (FIELDS, IDX, Accumulator, chunks, demux, mrc_grid,
                     partition, size_vms, spread_surplus, to_ways)
from .etica import EticaReference, exp_table

LEVELS = (("dram", "RO"), ("ssd", "WBWO"))


# -- a worker: one block of VMs ----------------------------------------------

def _size(ref: EticaReference, subs) -> list:
    """Each level's demands and hit curves of the block's VMs."""
    out = []
    for _, policy in LEVELS:
        dem, curves, _, _ = size_vms(subs, policy, True, ref.S, ref.W,
                                     ref.cfg["mrc_points"])
        out.append((dem, curves))
    return out


def _replay(ref: EticaReference, subs, ways) -> tuple:
    """Resize both levels to ``ways``, then every VM's requests interval
    by interval with its maintenance, as ``EticaReference.run_window``."""
    cfg = ref.cfg
    stats = np.zeros((ref.V, len(FIELDS)), np.int64)
    latency = np.zeros(ref.V)
    for levels, w_new in zip((ref.dram, ref.ssd), ways):
        for v, w in enumerate(w_new):
            fl = levels[v].resize(int(w))
            stats[v, IDX["disk_writes"]] += fl
            stats[v, IDX["evict_flushes"]] += fl
    for v, (a, w) in enumerate(subs):
        lat = 0.0
        for lo, hi in chunks(a.size, cfg["promo_interval"]):
            acc = Accumulator(ref.dtype if ref.dtype != np.float32
                              else np.float64)
            ref._datapath(v, a[lo:hi], w[lo:hi], stats[v], acc)
            lat += float(acc.value)
            if cfg["mode"] == "full":
                ref._maintain(v, a[lo:hi], w[lo:hi], stats[v])
        latency[v] = lat
    return stats, latency


def _serve(conn, cfg: dict, num_vms: int, exp: np.ndarray, precision):
    ref = EticaReference(cfg, num_vms, exp, precision)
    subs = None
    while True:
        op, *args = conn.recv()
        if op == "stop":
            return
        try:
            if op == "size":
                subs = args[0]
                conn.send(_size(ref, subs))
            elif op == "replay":
                conn.send(_replay(ref, subs, args[0]))
            else:
                conn.send(ref.state())
        except Exception:  # noqa: BLE001 - the caller raises it
            conn.send(RuntimeError(traceback.format_exc()))


def _stop(conns, procs) -> None:
    for c in conns:
        try:
            c.send(("stop",))
        except OSError:
            pass
    for p in procs:
        p.join(timeout=10)
        if p.is_alive():
            p.terminate()


# -- the host ----------------------------------------------------------------

class EticaMeshReference:
    def __init__(self, cfg: dict, num_vms: int, exp: np.ndarray,
                 precision=np.float32, workers: int | None = None):
        self.cfg = cfg
        self.V = num_vms
        self.S, self.W = cfg["num_sets"], cfg["max_ways"]
        f = cfg["dram_fraction"]
        dram = round(cfg["total_blocks"] * f / (1 + f))
        self.caps = {"dram": dram, "ssd": cfg["total_blocks"] - dram}
        if workers is None:
            workers = len(os.sched_getaffinity(0))
        edges = np.linspace(0, num_vms, min(workers, num_vms) + 1)
        edges = edges.round().astype(int)
        self.blocks = list(zip(edges[:-1].tolist(), edges[1:].tolist()))
        ctx = multiprocessing.get_context("spawn")
        self._conns, procs = [], []
        for lo, hi in self.blocks:
            here, there = ctx.Pipe()
            p = ctx.Process(target=_serve, daemon=True,
                            args=(there, cfg, hi - lo, exp, precision))
            p.start()
            there.close()
            self._conns.append(here)
            procs.append(p)
        self.close = weakref.finalize(self, _stop, self._conns, procs)
        self.windows = []        # per window: dict of what it produced

    def _ask(self, msgs) -> list:
        for c, m in zip(self._conns, msgs):
            c.send(m)
        out = [c.recv() for c in self._conns]
        for x in out:
            if isinstance(x, Exception):
                raise x
        return out

    def run_window(self, addr, is_write, vm) -> None:
        pos = demux(vm, self.V)
        subs = [(addr[p].astype(np.int64), is_write[p]) for p in pos]
        counts = np.array([p.size for p in pos], np.float64)
        sized = self._ask([("size", subs[lo:hi]) for lo, hi in self.blocks])
        grid = mrc_grid(self.S, self.W, self.cfg["mrc_points"])
        out, ways = {}, []
        for i, (name, _) in enumerate(LEVELS):
            dem = np.concatenate([s[i][0] for s in sized])
            curves = np.concatenate([s[i][1] for s in sized])
            cap = self.caps[name]
            alloc = spread_surplus(partition(dem, curves, grid, cap),
                                   counts, cap, self.S * self.W)
            out[name + "_demand"], out[name + "_alloc"] = dem, alloc
            ways.append(to_ways(alloc, self.S, self.W))
        done = self._ask([("replay", [w[lo:hi] for w in ways])
                          for lo, hi in self.blocks])
        out["stats"] = np.concatenate([s for s, _ in done])
        out["latency"] = np.concatenate([lat for _, lat in done])
        self.windows.append(out)

    def state(self) -> dict:
        parts = self._ask([("state",)] * len(self.blocks))
        return {k: (sum((p[k] for p in parts), []) if isinstance(v, list)
                    else np.concatenate([p[k] for p in parts]))
                for k, v in parts[0].items()}


def make(cfg: dict, num_vms: int, precision=np.float32,
         workers: int | None = None) -> EticaMeshReference:
    """``workers`` processes (default: one per CPU this process may use,
    at most one per VM), each holding a contiguous block of VMs."""
    return EticaMeshReference(cfg, num_vms, exp_table(cfg, precision),
                              precision, workers)
