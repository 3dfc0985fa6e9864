"""The system under test for an ``"controller": "eci"`` configuration:
ECI-Cache as ``repro.core.make_eci_cache`` builds it (the one-level
``PartitionedSingleLevelCache`` with URD sizing, which on a TPU runs
through the Pallas ``count_between`` kernel), driven through its normal
``run`` entry with ``batched=True``.
"""
from __future__ import annotations

import numpy as np

from bench.reference.common import FIELDS


def build(cfg: dict, num_vms: int):
    from repro.core import Geometry, make_eci_cache
    if cfg["read_heavy_threshold"] != 0.8:
        raise ValueError("make_eci_cache fixes the RO threshold at 0.8")
    geo = Geometry(num_sets=cfg["num_sets"], max_ways=cfg["max_ways"])
    return make_eci_cache(cfg["total_blocks"], num_vms, geometry=geo,
                          resize_interval=cfg["resize_interval"],
                          sim_chunk=cfg["sim_chunk"],
                          mrc_points=cfg["mrc_points"], batched=True)


def run(ctrl, addr, is_write, vm) -> None:
    from repro.core import Trace
    ctrl.run(Trace(addr=addr, is_write=is_write, vm=vm))


def sync(ctrl) -> None:
    import jax
    jax.block_until_ready(ctrl.caches)


def observe(ctrl) -> dict:
    log = ctrl.logs[-1]
    return {
        "stats": np.array([[s.get(f, 0.0) for f in FIELDS]
                           for s in ctrl.stats], np.float64),
        "latency": np.array([s.get("latency_sum", 0.0) for s in ctrl.stats]),
        "demand": log.demands.copy(), "alloc": log.alloc.copy(),
        "policy": list(log.policies),
    }


def state(ctrl) -> dict:
    import jax
    st, t = jax.device_get((ctrl.caches, ctrl.t))
    return {"tags": np.asarray(st.tags), "lru": np.asarray(st.lru),
            "dirty": np.asarray(st.dirty), "clock": np.asarray(t)}


def signature(vm, cfg: dict) -> set:
    """The program shapes one resize window uses beyond the fixed ones:
    the sizing reduction's live-row count and power-of-two bucket."""
    n = np.bincount(vm, minlength=cfg["num_vms"])
    return {("sizing", int((n > 0).sum()),
             max(256, 1 << max(int(n.max()) - 1, 0).bit_length()))}
