"""Paper Fig. 14: number of write operations committed to the SSD cache,
ETICA vs ECI-Cache, per workload (paper: 33.8% fewer on average, up to
95% for read-heavy web_3).

PR 8 extends the figure with the background cleaner's traffic: a second
ETICA run with ``clean_quota > 0`` reports the SSD write channels split
by source — datapath inserts (``cache_writes_l2``), eviction/resize
force-flushes (``evict_flushes``), and background clean flushes
(``flushes``) — plus the dirty-occupancy trajectory, all under asserted
invariants (cleaning never changes hit/miss stats; the dirty population
drains; the Prometheus exporter round-trips with the exact counts).

``--smoke`` shrinks to 3 VMs / 2k requests for CI; ``--streamed`` runs
the same mix through the sharded TraceStore and spot-checks that the
cleaning run's aggregate stats are bit-identical to in-memory.
"""
from __future__ import annotations

import numpy as np

from repro.core import EticaCache, make_eci_cache
from repro.runtime import metrics

from .common import (DRAM_CAP, GEO, REQS, RESIZE, SSD_CAP, Timer,
                     aggregate_stats, etica_config, row, vm_mix,
                     vm_mix_source)

VMS = ["web_3", "stg_1", "src2_0", "rsrch_0", "hm_1", "usr_0"]
CLEAN_QUOTA = 4


def _cleaning_section(vms, trace, reqs, etica, streamed):
    """The cleaner run + its asserted rows; returns total clean flushes."""
    ccfg = etica_config("full")
    ccfg.clean_quota = CLEAN_QUOTA
    cache = EticaCache(ccfg, len(vms))
    with Timer() as t3:
        cleaned = cache.run(trace)

    clog = np.stack(cache.clean_log)          # [intervals, V]
    dlog = np.stack(cache.dirty_log)
    for v, (vm, rb, rc) in enumerate(zip(vms, etica, cleaned)):
        s = rc.stats
        # cleaning only moves write-back traffic — served stats identical
        for k in ("reads", "writes", "read_hits_l1", "read_hits_l2",
                  "write_hits_l2"):
            assert s[k] == rb.stats[k], (vm, k, s[k], rb.stats[k])
        assert s["flushes"] == clog[:, v].sum(), vm
        row(f"fig14/clean/{vm}", t3.us / len(trace),
            f"insert={s['cache_writes_l2']:.0f} "
            f"evict_flush={s.get('evict_flushes', 0):.0f} "
            f"clean_flush={s['flushes']:.0f} "
            f"dirty_resident={s['dirty_resident']:.0f}")
    assert clog.sum() > 0, "cleaner never flushed"
    # the dirty population actually drains between intervals
    occ = dlog.sum(axis=1)
    assert occ.min() < occ.max(), "dirty occupancy never dipped"

    # telemetry self-check: exposition renders, parses, and carries the
    # exact flush counters
    text = metrics.render_cache(cache)
    fams = metrics.parse_exposition(text)
    for v in range(len(vms)):
        assert fams["etica_flushes_total"]["samples"][
            (("vm", str(v)),)] == cleaned[v].stats["flushes"]
    row("fig14/clean/summary", 0.0,
        f"clean_flushes={clog.sum():.0f} "
        f"peak_dirty={occ.max():.0f} final_dirty={occ[-1]:.0f} "
        f"exporter_families={len(fams)}")

    if streamed:
        # parity spot-check: the sharded TraceStore arrival stream is
        # bit-identical to the in-memory mix under cleaning
        mem = EticaCache(ccfg, len(vms)).run(vm_mix(vms, reqs))
        assert aggregate_stats(mem) == aggregate_stats(cleaned)
        row("fig14/clean/streamed_parity", 0.0, "stats_equal=True")
    return float(clog.sum())


def main(streamed: bool = False, smoke: bool = False):
    vms = VMS[:3] if smoke else VMS
    reqs = 2_000 if smoke else REQS
    trace = vm_mix_source(vms, reqs=reqs, streamed=streamed)
    with Timer() as t1:
        etica = EticaCache(etica_config("full"), len(vms)).run(trace)
    with Timer() as t2:
        eci = make_eci_cache(DRAM_CAP + SSD_CAP, len(vms), geometry=GEO,
                             resize_interval=RESIZE).run(trace)
    tot_e = tot_c = 0.0
    for vm, re_, rc in zip(vms, etica, eci):
        tot_e += re_.ssd_writes
        tot_c += rc.ssd_writes
        red = 1 - re_.ssd_writes / max(rc.ssd_writes, 1)
        row(f"fig14/{vm}", (t1.us + t2.us) / (2 * len(trace)),
            f"etica_writes={re_.ssd_writes:.0f} "
            f"eci_writes={rc.ssd_writes:.0f} reduction={red:.3f}")
    row("fig14/summary", 0.0,
        f"avg_ssd_write_reduction={1 - tot_e/max(tot_c,1):.3f} "
        f"(paper: 0.338)")
    _cleaning_section(vms, trace, reqs, etica, streamed)
    return 1 - tot_e / max(tot_c, 1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    main(streamed="--streamed" in sys.argv, smoke="--smoke" in sys.argv)
