"""Shared benchmark plumbing: CSV rows + a consistent small-scale setup.

Every benchmark prints ``name,us_per_call,derived`` rows (derived carries
the figure's headline metric). Scales are CPU-sized but structurally
identical to the paper's setup (set-associative caches, 10k-request
resize intervals scaled down proportionally).
"""
from __future__ import annotations

import time

from repro.core import EticaCache, EticaConfig, Geometry
from repro.core.trace import interleave
from repro.traces import VM_ADDR_STRIDE, make

GEO = Geometry(num_sets=16, max_ways=32)
RESIZE = 2_000
PROMO = 500
DRAM_CAP = 400
SSD_CAP = 800
REQS = 8_000
SCALE = 0.25


def aggregate_stats(results) -> dict[str, float]:
    """Sum per-VM ``VMResult.stats`` dicts — the quantity the
    batched-vs-sequential and streamed-vs-in-memory gates compare."""
    agg: dict[str, float] = {}
    for r in results:
        for k, v in r.stats.items():
            agg[k] = agg.get(k, 0.0) + v
    return agg


def row(name: str, us_per_call: float, derived: str) -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.dt = time.time() - self.t0

    @property
    def us(self) -> float:
        return self.dt * 1e6


def vm_mix(names, reqs=REQS, scale=SCALE, seed=0):
    traces = [make(n, reqs, seed=seed + i, addr_offset=i * VM_ADDR_STRIDE,
                   scale=scale)
              for i, n in enumerate(names)]
    return interleave(traces, seed=42)


def vm_mix_source(names, reqs=REQS, scale=SCALE, streamed=False,
                  shard_size=4096):
    """The benchmark mix as either an in-memory Trace or — with
    ``streamed`` — the same arrival stream persisted shard-by-shard via
    :func:`repro.traces.make_store` (same per-VM seeds / address stride /
    interleave seed, so results are bit-identical). Controllers accept
    the returned :class:`TraceStore` directly."""
    if not streamed:
        return vm_mix(names, reqs, scale)
    import tempfile
    from pathlib import Path
    from repro.traces import make_store
    root = Path(tempfile.mkdtemp(prefix="bench_trace_store_"))
    return make_store(root / "store", list(names), reqs, seed=0, scale=scale,
                      shard_size=shard_size)


def etica_config(mode="full", dram=DRAM_CAP, ssd=SSD_CAP):
    return EticaConfig(dram_capacity=dram, ssd_capacity=ssd,
                       geometry_dram=GEO, geometry_ssd=GEO,
                       resize_interval=RESIZE, promo_interval=PROMO,
                       mode=mode)
