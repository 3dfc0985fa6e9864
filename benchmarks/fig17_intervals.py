"""Paper Fig. 17: impact of the promotion/eviction interval on ETICA's
performance and endurance (interval swept 100 -> 10,000 requests; scaled
here proportionally to the benchmark trace size).

Journal-driven since PR 9: each swept run records one telemetry row per
interval into a bounded :class:`repro.runtime.telemetry
.TelemetryRecorder` journal, the headline metrics are derived from the
*journal* columns (latency / SSD-write sums over interval deltas), and
the derivation is cross-checked against the controller's own Stats plus
a JSONL spill round-trip — so the figure doubles as the observability
smoke path. ``--journal PATH`` keeps the last swept run's spill for
``tools/run_report.py``; ``--streamed`` feeds the identical mix through
the on-disk :class:`TraceStore` (bit-identical results); ``--smoke``
shrinks the sweep for CI.
"""
from __future__ import annotations

import argparse
import tempfile
from pathlib import Path

import numpy as np

from repro.core import EticaCache
from repro.runtime.telemetry import (TelemetryRecorder, load_journal,
                                     summarize_journal)

from .common import Timer, etica_config, row, vm_mix_source

VMS = ["hm_1", "usr_0", "ts_0"]
INTERVALS = [100, 250, 500, 1000, 2000]


def _sweep_one(iv: int, trace, spill: Path):
    """One swept run: controller with a journal-spilling recorder."""
    rec = TelemetryRecorder(spill=spill)
    cfg = etica_config("full")
    cfg.promo_interval = iv
    cfg.telemetry = rec
    with Timer() as t:
        cache = EticaCache(cfg, len(VMS))
        res = cache.run(trace)
    rec.journal.close()
    # journal <-> JSONL round-trip, asserted: the spill reloads to the
    # same per-interval series the in-memory ring retains
    cols = load_journal(spill)
    tail = cols["requests"][-rec.journal.retained:]
    assert np.array_equal(tail, rec.journal.column("requests"))
    # journal <-> Stats cross-check: interval deltas sum back to the
    # cumulative counters the controller kept independently
    stats = [r.stats for r in res]
    assert abs(cols["requests"].sum()
               - sum(s["reads"] + s["writes"] for s in stats)) < 1e-6
    assert abs(cols["ssd_writes"].sum()
               - sum(s["cache_writes_l2"] for s in stats)) < 1e-6
    return t, cols, len(trace)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep for CI")
    ap.add_argument("--streamed", action="store_true",
                    help="feed the mix through an on-disk TraceStore")
    ap.add_argument("--journal", default=None,
                    help="keep the last swept run's JSONL spill here")
    args = ap.parse_args(argv)

    reqs = 1_500 if args.smoke else 6_000
    intervals = [100, 500] if args.smoke else INTERVALS
    trace = vm_mix_source(VMS, reqs=reqs, streamed=args.streamed)
    tmp = Path(tempfile.mkdtemp(prefix="fig17_journal_"))
    base = None
    for iv in intervals:
        spill = (Path(args.journal) if args.journal and iv == intervals[-1]
                 else tmp / f"interval_{iv}.jsonl")
        t, cols, n = _sweep_one(iv, trace, spill)
        s = summarize_journal(cols)
        # latency / endurance from the journal columns (not VMResult)
        lat = cols["latency"].sum() / max(cols["requests"].sum(), 1)
        writes = cols["ssd_writes"].sum()
        if base is None:
            base = (lat, writes)
        row(f"fig17/interval_{iv}", t.us / n,
            f"latency_norm={lat/base[0]:.3f} "
            f"ssd_writes_norm={writes/max(base[1],1):.3f} "
            f"intervals={s['intervals']} "
            f"mean_hit={s['mean_hit_ratio']:.3f} "
            f"overloaded={s['overloaded_intervals']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
