"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds, for
the rehearsal tests: the same configuration and traffic files, with
16 x 8 caches, 2,000-request resize windows and 3 passes of 500
requests a VM at a tenth of the working sets."""
from __future__ import annotations

from bench.lib import cell as cells

SMALL = {"num_sets": 16, "max_ways": 8, "total_blocks": 512,
         "resize_interval": 2000, "pop_capacity": 1024}
CELLS = ("paper12.msr", "eci12.msr")


def tiny_cell(name: str):
    c = cells.load(name)
    cfg = dict(c.config, **SMALL)
    for k in ("promo_interval", "sim_chunk"):
        if k in cfg:
            cfg[k] = 200
    c.config = cfg
    c.traffic = dict(c.traffic, requests_per_vm=500, passes=3, scale=0.1)
    return c
