"""Paper Fig. 3: effect of the cache write policy on performance and SSD
endurance, per motivational workload (FIO-RandRW, Web Server, Video
Server, Varmail) x policy (WB, RO, WBWO)."""
from __future__ import annotations

import numpy as np

from repro.core import Policy, Stats, make_cache, simulate_single_level
from repro.traces import make

from .common import GEO, Timer, row

WORKLOADS = ["fio_randrw", "web_server", "video_server", "varmail"]
POLICIES = [Policy.WB, Policy.RO, Policy.WBWO]
N = 6_000


def _workload_chunks(workload: str, streamed: bool):
    """The workload as an iterable of request chunks; with ``streamed``
    the trace is persisted through :func:`repro.traces.make_store` (one
    single-VM mix) and consumed shard-by-shard at bounded memory."""
    if not streamed:
        yield make(workload, N, seed=0, scale=0.25)
        return
    import tempfile
    from pathlib import Path
    from repro.traces import make_store
    root = Path(tempfile.mkdtemp(prefix="fig3_store_"))
    store = make_store(root / workload, [workload], N, seed=0, scale=0.25,
                       shard_size=1024)
    yield from store.iter_shards()


def run_one(workload: str, policy: Policy, streamed: bool = False):
    state = make_cache(GEO.num_sets, GEO.max_ways)
    stats, t0 = Stats.zero(), 0
    with Timer() as t:
        for chunk in _workload_chunks(workload, streamed):
            state, st, t0 = simulate_single_level(
                np.asarray(chunk.addr), np.asarray(chunk.is_write), state,
                GEO.max_ways, policy, t0=t0)
            stats = stats.merge(st)
        iops = 1.0 / max(stats.mean_latency(), 1e-12)
    return t.us, iops, int(stats.cache_writes_l2)


def main(streamed: bool = False):
    results = {}
    for w in WORKLOADS:
        for p in POLICIES:
            us, iops, writes = run_one(w, p, streamed=streamed)
            results[(w, p)] = (iops, writes)
            row(f"fig3/{w}/{p.value}", us / N,
                f"iops={iops:.0f} ssd_writes={writes}")
    # headline checks mirroring the paper's four observations
    for w in WORKLOADS:
        wb_i, wb_w = results[(w, Policy.WB)]
        wo_i, wo_w = results[(w, Policy.WBWO)]
        ro_i, ro_w = results[(w, Policy.RO)]
        row(f"fig3/{w}/summary", 0.0,
            f"WBWO_writes/WB={wo_w/max(wb_w,1):.2f} "
            f"RO_writes/WB={ro_w/max(wb_w,1):.2f} "
            f"WBWO_iops/WB={wo_i/max(wb_i,1e-9):.2f}")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
