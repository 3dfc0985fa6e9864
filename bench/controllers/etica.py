"""The system under test for an ``"controller": "etica"`` configuration:
``repro.core.EticaCache`` (ETICA-Full, batched, fused maintenance
through the Pallas kernels), driven through its normal ``run`` entry.

``observe`` and ``state`` read what the controller produced into the
layout of ``bench/reference/etica.py`` so that the two compare key by
key.
"""
from __future__ import annotations

import numpy as np

from bench.reference.common import FIELDS

EMPTY_SLOT = 2**31 - 1          # the popularity table's free-slot address


def build(cfg: dict, num_vms: int):
    from repro.core import EticaCache, EticaConfig, Geometry
    geo = Geometry(num_sets=cfg["num_sets"], max_ways=cfg["max_ways"])
    f = cfg["dram_fraction"]
    dram = round(cfg["total_blocks"] * f / (1 + f))
    return EticaCache(EticaConfig(
        dram_capacity=dram, ssd_capacity=cfg["total_blocks"] - dram,
        geometry_dram=geo, geometry_ssd=geo,
        resize_interval=cfg["resize_interval"],
        promo_interval=cfg["promo_interval"],
        evict_frac=cfg["evict_frac"],
        popularity_decay=cfg["popularity_decay"], mode=cfg["mode"],
        mrc_points=cfg["mrc_points"], pop_capacity=cfg["pop_capacity"],
        clean_quota=cfg["clean_quota"], batched=True), num_vms)


def run(ctrl, addr, is_write, vm) -> None:
    from repro.core import Trace
    ctrl.run(Trace(addr=addr, is_write=is_write, vm=vm))


def sync(ctrl) -> None:
    import jax
    jax.block_until_ready((ctrl.dram, ctrl.ssd, ctrl.pop_table))


def observe(ctrl) -> dict:
    """Cumulative per-VM counts and this window's sizing decisions."""
    return {
        "stats": np.array([[s.get(f, 0.0) for f in FIELDS]
                           for s in ctrl.stats], np.float64),
        "latency": np.array([s.get("latency_sum", 0.0) for s in ctrl.stats]),
        "dram_demand": ctrl.logs_dram[-1].demands.copy(),
        "dram_alloc": ctrl.logs_dram[-1].alloc.copy(),
        "ssd_demand": ctrl.logs_ssd[-1].demands.copy(),
        "ssd_alloc": ctrl.logs_ssd[-1].alloc.copy(),
    }


def state(ctrl) -> dict:
    import jax
    dram, ssd, table, t = jax.device_get(
        (ctrl.dram, ctrl.ssd, ctrl.pop_table, ctrl.t))
    addr, val = np.asarray(table.addr), np.asarray(table.val)
    keep = addr != EMPTY_SLOT
    return {
        "dram_tags": np.asarray(dram.tags), "dram_lru": np.asarray(dram.lru),
        "dram_dirty": np.asarray(dram.dirty),
        "ssd_tags": np.asarray(ssd.tags), "ssd_lru": np.asarray(ssd.lru),
        "ssd_dirty": np.asarray(ssd.dirty),
        "clock": np.asarray(t),
        "pop_addr": [a[k].astype(np.int64) for a, k in zip(addr, keep)],
        "pop_val": [x[k].astype(np.float64) for x, k in zip(val, keep)],
    }


def _bucket(n: int) -> int:
    return max(256, 1 << max(n - 1, 0).bit_length())


def signature(vm, cfg: dict) -> set:
    """The program shapes one resize window uses beyond the fixed ones:
    the sizing decompositions' live-row count and bucket, and each
    promotion interval's maintenance bucket (``core/reuse.py`` pads rows
    to a power of two of at least 256)."""
    n = np.bincount(vm, minlength=cfg["num_vms"])
    p = cfg["promo_interval"]
    out = {("sizing", int((n > 0).sum()), _bucket(int(n.max())))}
    for k in range(-(-int(n.max()) // p)):
        out.add(("maintenance", _bucket(int(np.clip(n - k * p, 0, p).max()))))
    return out


def warm_lengths(lengths) -> None:
    """Compile the sizing's per-VM eager reductions for every per-VM
    window length the stream holds: ``EticaCache._size_level`` takes
    ``DistResult.max`` of each VM's unpadded distances outside ``jit``,
    one program per length."""
    from repro.core.reuse import DistResult
    for n in sorted(lengths):
        z = np.zeros(n, np.int32)
        int(DistResult(dist=z, served=z.astype(bool), touch=z.astype(bool)).max)
