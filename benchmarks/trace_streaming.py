"""Beyond-paper: the streaming trace-store ingestion layer.

Measures the pieces that let million-request traces (paper §5.1 runs
MSR Cambridge + FIO) drive the batched controllers at bounded host
memory:

  * ``stream/import_msr``   — MSR-CSV parse -> chunked store (us/req);
  * ``stream/store_scan``   — memory-mapped shard iteration (us/req);
  * ``stream/etica_*``      — EticaCache off a TraceStore vs the
    materialized in-memory trace: aggregate Stats asserted **equal**,
    then wall-clock for streamed (double-buffered), streamed with
    prefetch disabled, and in-memory; peak Python-heap use
    (``tracemalloc``) for the streamed vs in-memory run — the streamed
    path holds one resize window instead of the whole trace;
  * ``stream/eci_*``        — same protocol for the one-level ECI-Cache
    chassis (dynamic policies riding the batched sizing dispatch).
"""
from __future__ import annotations

import io
import tempfile
import tracemalloc
from pathlib import Path

from repro.core import EticaCache, make_eci_cache
from repro.traces import TraceStore, make_store, parse_msr_csv

from .common import GEO, RESIZE, Timer, aggregate_stats as _aggregate
from .common import etica_config, row

NUM_VMS = 8
REQS_PER_VM = 4_000
WORKLOADS = ["hm_1", "proj_0", "stg_1", "usr_0", "ts_0", "wdev_0",
             "web_3", "src2_0"]
SHARD = 6_000
BLOCK = 4096


def _msr_csv_of(trace) -> str:
    buf = io.StringIO()
    buf.write("Timestamp,Hostname,DiskNumber,Type,Offset,Size,ResponseTime\n")
    for i in range(len(trace)):
        typ = "Write" if bool(trace.is_write[i]) else "Read"
        buf.write(f"{i},vm{int(trace.vm[i])},0,{typ},"
                  f"{int(trace.addr[i]) * BLOCK},{BLOCK},100\n")
    return buf.getvalue()


def ingestion(tmp: Path, trace) -> None:
    csv_text = _msr_csv_of(trace)
    with Timer() as t:
        TraceStore.from_chunks(tmp / "imported",
                               parse_msr_csv(io.StringIO(csv_text)),
                               shard_size=SHARD)
    row("stream/import_msr", t.us / len(trace),
        f"reqs={len(trace)} shards={-(-len(trace) // SHARD)}")

    store = TraceStore.open(tmp / "imported")
    with Timer() as t:
        total = sum(len(s) for s in store.iter_shards())
    assert total == len(trace)
    row("stream/store_scan", t.us / total, f"mmap_shards={store.num_shards}")


def _head_to_head(label: str, build, store_path: Path, trace) -> None:
    """Warm up both paths, assert streamed == in-memory aggregate Stats,
    then report the three timed variants + Python-heap peaks."""
    build().run(TraceStore.open(store_path))      # compile warm-up
    n = len(trace)

    tracemalloc.start()
    with Timer() as t_str:
        res_str = build().run(TraceStore.open(store_path))
    _, peak_str = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    with Timer() as t_nopf:
        res_nopf = build(prefetch=False).run(TraceStore.open(store_path))

    tracemalloc.start()
    with Timer() as t_mem:
        res_mem = build().run(trace)
    _, peak_mem = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    agg_str, agg_mem = _aggregate(res_str), _aggregate(res_mem)
    assert agg_str == agg_mem, (
        f"{label}: streamed and in-memory diverged:\n"
        f"  streamed:  {agg_str}\n  in-memory: {agg_mem}")
    assert _aggregate(res_nopf) == agg_mem
    row(f"stream/{label}_streamed", t_str.us / n,
        f"stats_equal=True peak_py_mb={peak_str / 2**20:.1f} "
        f"window_resident={RESIZE}")
    row(f"stream/{label}_no_prefetch", t_nopf.us / n,
        f"prefetch_gain={t_nopf.dt / t_str.dt:.2f}x")
    row(f"stream/{label}_in_memory", t_mem.us / n,
        f"peak_py_mb={peak_mem / 2**20:.1f} trace_resident={n}")


def main():
    with tempfile.TemporaryDirectory() as tmp:
        tmp = Path(tmp)
        store = make_store(tmp / "mix", WORKLOADS, REQS_PER_VM, scale=0.25,
                           shard_size=SHARD)
        trace = store.to_trace()
        ingestion(tmp, trace)

        def etica(prefetch=True):
            import dataclasses
            cfg = dataclasses.replace(etica_config("full", dram=200, ssd=400),
                                      prefetch=prefetch)
            return EticaCache(cfg, NUM_VMS)

        _head_to_head("etica", etica, tmp / "mix", trace)

        def eci(prefetch=True):
            return make_eci_cache(600, NUM_VMS, geometry=GEO,
                                  resize_interval=2_000, sim_chunk=500,
                                  prefetch=prefetch)

        _head_to_head("eci", eci, tmp / "mix", trace)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
