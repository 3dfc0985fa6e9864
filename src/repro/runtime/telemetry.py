"""Interval-resolution telemetry runtime for the cache controllers.

ETICA's claims are *trajectories over maintenance intervals* (performance
and endurance per §6), so the observability layer records one structured
sample per interval rather than a single end-of-run aggregate. Four
pieces, on numpy, the stdlib and (for the spans) ``jax``:

* :class:`Journal` — a bounded columnar ring of per-interval samples
  (O(window) host memory regardless of run length) with an optional
  JSONL *spill*: every appended row is also written as one JSON line, so
  the full trajectory survives on disk while memory stays bounded.
  :func:`load_journal` reads a spill file back into stacked columns.
* :func:`span` / :func:`sync` — host spans on the profiler's clock. A
  span is a ``jax.profiler.TraceAnnotation`` and nothing else (no sync,
  no clock read, no state): it records only while a profiler session is
  active, beside the device's own trace. :func:`sync` is the one
  device→host read of the controllers' batched paths, each one a
  ``sync:<site>`` span, so the trace counts the host round trips.
* :class:`TelemetryRecorder` — the object the controllers thread through
  their interval loops. ``sample_cache`` / ``sample_serving`` turn the
  host-side stats the controller *already fetched* into per-interval
  deltas — the recorder performs no device→host transfers of its own, so
  ``telemetry`` on vs off is bit-identical and sync-count-identical.
  Its ``span()`` opens the same annotation as :func:`span`; opt-in
  ``span_timing`` adds wall-clock histograms around the fused dispatches
  (``span()`` then calls ``jax.block_until_ready`` at close, so it IS
  documented as adding syncs).
* :func:`overload_flags` — LBICA-style per-interval overload *detection*
  (PAPERS.md): a VM/tenant is flagged when its windowed hit ratio
  collapses below ``drop × best-recent-baseline`` or its dirty/used
  occupancy presses against its allocation. Detection only — the flags
  are exported (``etica_overloaded``) and journaled; rebalancing actions
  remain a ROADMAP item.

The exporter side lives in :mod:`repro.runtime.metrics`
(``collect_telemetry`` renders the span histograms and the last
interval's flags) and :mod:`repro.runtime.http` (live scrape endpoint).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import time

import jax
import numpy as np

__all__ = [
    "DISPATCH_BUCKETS", "Journal", "OverloadConfig", "SpanStats",
    "TelemetryRecorder", "load_journal", "overload_flags", "span",
    "summarize_journal", "sync",
]

# Golden-pinned histogram bucket bounds (seconds) for the dispatch span
# timers — `etica_dispatch_seconds` renders exactly these `le` edges.
DISPATCH_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                    0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

# Cumulative stats-dict keys sampled as per-interval deltas by
# ``sample_cache`` (the controllers maintain exactly these host-side).
CACHE_DELTA_KEYS = ("reads", "writes", "read_hits_l1", "read_hits_l2",
                    "write_hits_l2", "cache_writes_l2", "disk_reads",
                    "disk_writes", "flushes", "evict_flushes", "bypassed",
                    "pop_drops", "latency_sum")

SERVING_DELTA_KEYS = ("activations", "hits", "appends", "dma_read_bytes",
                      "dma_write_bytes", "latency_s", "sessions_ended",
                      "pop_drops", "flushes", "evict_flushes",
                      "dirty_dropped")


# ---------------------------------------------------------------------------
# bounded columnar journal with JSONL spill
# ---------------------------------------------------------------------------

class Journal:
    """Bounded columnar ring of per-interval rows.

    ``append(row)`` takes a ``{name: scalar | ndarray}`` dict; each column
    keeps the last ``window`` values in a preallocated ``[window, ...]``
    ring (shape and dtype fixed by the column's first appearance), so
    memory is O(window · columns), never O(run length). With ``spill``
    set, every row is additionally written as one JSON line
    (``{"i": <row index>, <column>: <value.tolist()>, ...}``) and flushed
    immediately, so a live scrape/tail sees rows as they land and the
    full trajectory survives the ring.
    """

    def __init__(self, window: int = 512, spill=None):
        if window <= 0:
            raise ValueError("journal window must be positive")
        self.window = int(window)
        self.total = 0                 # rows ever appended
        self._cols: dict[str, np.ndarray] = {}
        self._spill_path = spill
        self._spill_f = None

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def __len__(self) -> int:
        return self.total

    @property
    def retained(self) -> int:
        """Rows currently held in memory (≤ ``window``)."""
        return min(self.total, self.window)

    @property
    def columns(self) -> tuple[str, ...]:
        return tuple(self._cols)

    def append(self, row: dict) -> None:
        pos = self.total % self.window
        for name, value in row.items():
            a = np.asarray(value)
            buf = self._cols.get(name)
            if buf is None:
                buf = np.zeros((self.window,) + a.shape, a.dtype)
                self._cols[name] = buf
            elif buf.shape[1:] != a.shape:
                raise ValueError(
                    f"journal column {name!r}: shape {a.shape} != "
                    f"established {buf.shape[1:]}")
            buf[pos] = a
        self.total += 1
        if self._spill_path is not None:
            if self._spill_f is None:
                # truncate: one journal owns one spill file (row indices
                # restart at 0, and load_journal expects one schema)
                self._spill_f = open(self._spill_path, "w")
            line = {"i": self.total - 1}
            line.update({k: np.asarray(v).tolist() for k, v in row.items()})
            self._spill_f.write(json.dumps(line) + "\n")
            self._spill_f.flush()

    def _order(self) -> np.ndarray:
        n = self.retained
        if self.total <= self.window:
            return np.arange(n)
        pos = self.total % self.window
        return np.r_[pos:self.window, 0:pos]

    def column(self, name: str) -> np.ndarray:
        """Retained values of one column, oldest first — ``[retained, ...]``."""
        return self._cols[name][self._order()]

    def last_row(self) -> dict:
        """The most recent row as ``{name: ndarray | scalar}``."""
        if self.total == 0:
            raise IndexError("empty journal")
        pos = (self.total - 1) % self.window
        return {k: buf[pos] for k, buf in self._cols.items()}

    def rows(self) -> list[dict]:
        """Retained rows oldest-first (each a plain column dict)."""
        order = self._order()
        return [{k: buf[i] for k, buf in self._cols.items()} for i in order]

    def close(self) -> None:
        if self._spill_f is not None:
            self._spill_f.close()
            self._spill_f = None


def load_journal(path) -> dict[str, np.ndarray]:
    """Read a JSONL spill file back into ``{column: [rows, ...] ndarray}``.

    Inverse of the spill writer: columns stack in row order; the ``"i"``
    row index becomes an int column. Rows missing a column that other
    rows carry are rejected — spills are fixed-schema by construction.
    """
    rows = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            if not line.strip():
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}: line {ln}: {e}") from None
    if not rows:
        return {}
    keys = set(rows[0])
    for ln, r in enumerate(rows, 1):
        if set(r) != keys:
            raise ValueError(f"{path}: row {ln} schema {sorted(r)} != "
                             f"{sorted(keys)}")
    return {k: np.asarray([r[k] for r in rows]) for k in keys}


# ---------------------------------------------------------------------------
# host spans on the profiler's clock; dispatch-span histograms (opt-in:
# adds block_until_ready syncs)
# ---------------------------------------------------------------------------

def span(name: str, **meta):
    """A host span named ``name`` on the profiler's clock, ``meta`` as its
    arguments: a ``jax.profiler.TraceAnnotation`` and nothing else — no
    sync, no clock read, no state. It records only while a profiler
    session is active; with none it costs about a microsecond."""
    return jax.profiler.TraceAnnotation(name, **meta)


def sync(site: str, x):
    """Read ``x`` (an array or a pytree of them, read at once) back to the
    host: ``jax.device_get`` under a ``sync:<site>`` span. Every
    device→host read of the controllers' batched paths goes through here,
    so the span is the count of host round trips."""
    with jax.profiler.TraceAnnotation("sync:" + site):
        return jax.device_get(x)


class SpanStats:
    """One wall-clock histogram: fixed bucket edges, per-bucket counts
    (the last slot is the +Inf overflow bucket), running sum."""

    __slots__ = ("buckets", "counts", "total", "n")

    def __init__(self, buckets=DISPATCH_BUCKETS):
        self.buckets = tuple(buckets)
        self.counts = np.zeros(len(self.buckets) + 1, np.int64)
        self.total = 0.0
        self.n = 0

    def observe(self, seconds: float) -> None:
        self.counts[bisect.bisect_left(self.buckets, seconds)] += 1
        self.total += float(seconds)
        self.n += 1


class _Span:
    """A recorder span: the annotation of :func:`span`, and with a
    recorder (``span_timing`` on) a timer that blocks on the value handed
    to :meth:`ready` at close — the explicit sync that makes the
    measurement mean "dispatch complete", and the reason span timing is
    opt-in."""

    __slots__ = ("_rec", "_name", "_ann", "_t0", "_val")

    def __init__(self, rec, name, meta):
        self._rec = rec
        self._name = name
        self._ann = span(name, **meta)
        self._val = None

    def ready(self, value) -> None:
        """Register the dispatch output to ``block_until_ready`` on."""
        self._val = value

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        try:
            if self._rec is not None and exc[0] is None:
                if self._val is not None:
                    jax.block_until_ready(self._val)
                self._rec._observe_span(self._name,
                                        time.perf_counter() - self._t0)
        finally:
            self._ann.__exit__(*exc)
        return False


class _NullSpan:
    """Shared no-op span: zero overhead, zero added syncs."""

    __slots__ = ()

    def ready(self, value) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


# ---------------------------------------------------------------------------
# LBICA-style overload detection (detection only — no rebalancing)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OverloadConfig:
    """Windowed hit-ratio-collapse + queue-pressure detection knobs."""
    window: int = 8          # intervals of baseline history per VM/tenant
    drop: float = 0.6        # flag when ratio < drop * best recent ratio
    min_requests: int = 32   # interval request floor for a verdict
    pressure: float = 0.95   # occupancy/allocation fraction that flags


def overload_flags(prev_hits: np.ndarray, prev_reqs: np.ndarray,
                   hits: np.ndarray, reqs: np.ndarray,
                   pressure: np.ndarray, ocfg: OverloadConfig) -> np.ndarray:
    """Per-entity overload flags for one interval.

    ``prev_hits``/``prev_reqs`` are ``[n, V]`` per-interval deltas of the
    up-to-``ocfg.window`` preceding intervals; ``hits``/``reqs`` the
    current interval's ``[V]`` deltas; ``pressure`` a ``[V]`` bool of
    queue-pressure verdicts the caller computed (e.g. dirty occupancy vs
    allocation). An entity is overloaded when its current hit ratio falls
    below ``drop ×`` the best ratio any *qualified* baseline interval
    (``>= min_requests`` requests) achieved, or when pressure flags it.
    Deterministic and pure — exactness-tested on synthetic collapses.
    """
    hits = np.asarray(hits, np.float64)
    reqs = np.asarray(reqs, np.float64)
    flags = np.zeros(hits.shape, bool)
    prev_reqs = np.asarray(prev_reqs, np.float64).reshape(-1, hits.shape[0])
    prev_hits = np.asarray(prev_hits, np.float64).reshape(-1, hits.shape[0])
    if prev_reqs.shape[0]:
        valid = prev_reqs >= ocfg.min_requests
        ratio_prev = np.where(valid, prev_hits / np.maximum(prev_reqs, 1.0),
                              -1.0)
        base = ratio_prev.max(axis=0)          # -1 when no qualified interval
        ratio = hits / np.maximum(reqs, 1.0)
        flags = ((reqs >= ocfg.min_requests) & (base > 0.0)
                 & (ratio < ocfg.drop * base))
    return flags | np.asarray(pressure, bool)


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

class TelemetryRecorder:
    """Per-interval telemetry sink threaded through the controllers.

    One recorder belongs to one controller: it keeps the previous
    cumulative-stats snapshot to compute interval deltas, so sharing an
    instance between controllers would interleave their deltas.

    Guarantees: ``sample_*`` only reads host-side values the controller
    already fetched (zero added device→host syncs) and never touches
    cache state (telemetry on vs off is bit-identical — asserted in
    ``tests/test_telemetry.py``). ``span_timing`` is the opt-in
    exception that DOES add synchronization, and says so.
    """

    def __init__(self, window: int = 512, spill=None,
                 span_timing: bool = False,
                 overload: OverloadConfig | None = None):
        self.journal = Journal(window=window, spill=spill)
        self.span_timing = bool(span_timing)
        self.spans: dict[str, SpanStats] = {}
        # fused maintenance dispatches per way bucket (the leading SSD
        # ways they work on), counted by the controller per interval
        self.ways_buckets: collections.Counter = collections.Counter()
        self.overload = overload if overload is not None else OverloadConfig()
        self._prev: dict[str, np.ndarray] = {}
        self._ov_hits = collections.deque(maxlen=self.overload.window)
        self._ov_reqs = collections.deque(maxlen=self.overload.window)

    # -- spans ------------------------------------------------------------
    def span(self, name: str, **meta):
        """The host span :func:`span` opens, as a context manager; hand
        the dispatch output to ``.ready(out)``. With ``span_timing`` on it
        also times the block into ``spans[name]``, and close waits on that
        output with ``block_until_ready``; off, it adds no sync, and with
        no profiler session to record the annotation it is the shared
        no-op."""
        if self.span_timing:
            return _Span(self, name, meta)
        if jax.profiler.TraceAnnotation.is_enabled():
            return _Span(None, name, meta)
        return _NULL_SPAN

    def _observe_span(self, name: str, seconds: float) -> None:
        s = self.spans.get(name)
        if s is None:
            s = self.spans[name] = SpanStats()
        s.observe(seconds)

    # -- interval samples -------------------------------------------------
    def _deltas(self, cur: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        out = {k: v - self._prev.get(k, np.zeros_like(v))
               for k, v in cur.items()}
        self._prev = cur
        return out

    def _flag(self, hits, reqs, pressure) -> np.ndarray:
        n = len(self._ov_hits)
        prev_h = (np.stack(self._ov_hits) if n
                  else np.zeros((0, len(hits))))
        prev_r = (np.stack(self._ov_reqs) if n
                  else np.zeros((0, len(reqs))))
        flags = overload_flags(prev_h, prev_r, hits, reqs, pressure,
                               self.overload)
        self._ov_hits.append(np.asarray(hits, np.float64))
        self._ov_reqs.append(np.asarray(reqs, np.float64))
        return flags

    def sample_cache(self, stats: list[dict], *, alloc_l1=None, alloc_l2=None,
                     promoted=None, evict_queue=None, cleaned=None,
                     dirty=None, clean_ran: bool = False,
                     cls_hits=None, cls_miss=None) -> dict:
        """One interval sample from a block-cache controller's per-VM
        stats dicts (cumulative, host-side) plus the maintenance counts
        the interval's existing device_get already fetched."""
        num_vms = len(stats)
        cur = {k: np.asarray([float(d.get(k, 0.0)) for d in stats])
               for k in CACHE_DELTA_KEYS}
        d = self._deltas(cur)
        zeros = np.zeros(num_vms, np.int64)
        alloc_l1 = np.asarray(alloc_l1 if alloc_l1 is not None else zeros,
                              np.int64)
        alloc_l2 = np.asarray(alloc_l2 if alloc_l2 is not None else zeros,
                              np.int64)
        dirty = np.asarray(dirty if dirty is not None else zeros, np.int64)
        reqs = d["reads"] + d["writes"]
        hits = d["read_hits_l1"] + d["read_hits_l2"] + d["write_hits_l2"]
        pressure = (alloc_l2 > 0) & (dirty >= self.overload.pressure
                                     * alloc_l2)
        row = {
            "requests": reqs,
            "hits": hits,
            "ssd_writes": d["cache_writes_l2"],
            "disk_reads": d["disk_reads"],
            "disk_writes": d["disk_writes"],
            "flushes": d["flushes"],
            "evict_flushes": d["evict_flushes"],
            "bypassed": d["bypassed"],
            "pop_drops": d["pop_drops"],
            "latency": d["latency_sum"],
            "dirty_resident": dirty,
            "alloc_l1": alloc_l1,
            "alloc_l2": alloc_l2,
            "promoted": np.asarray(promoted if promoted is not None
                                   else zeros, np.int64),
            "evict_queue": np.asarray(evict_queue if evict_queue is not None
                                      else zeros, np.int64),
            "cleaned": np.asarray(cleaned if cleaned is not None else zeros,
                                  np.int64),
            "clean_ran": bool(clean_ran),
            "overloaded": self._flag(hits, reqs, pressure),
        }
        if cls_hits is not None:
            ch = np.asarray(cls_hits, np.int64)
            cm = np.asarray(cls_miss, np.int64)
            prev_ch = self._prev.get("_cls_hits", np.zeros_like(ch))
            prev_cm = self._prev.get("_cls_miss", np.zeros_like(cm))
            row["cls_hits"] = ch - prev_ch
            row["cls_miss"] = cm - prev_cm
            self._prev["_cls_hits"] = ch.copy()
            self._prev["_cls_miss"] = cm.copy()
        self.journal.append(row)
        return row

    def sample_serving(self, stats, *, quota, used) -> dict:
        """One maintenance-tick sample from a serving manager's
        :class:`~repro.kvcache.manager.Stats` plus the per-tenant quota
        state (all host-side already)."""
        cur = {k: np.asarray([float(getattr(stats, k))])
               for k in SERVING_DELTA_KEYS}
        dirty = int(stats.dirty_resident)
        d = self._deltas(cur)
        quota = np.asarray(quota, np.int64)
        used = np.asarray(used, np.int64)
        # queue pressure per tenant: resident pages pressing the quota
        pressure = (quota > 0) & (used >= np.ceil(
            self.overload.pressure * quota).astype(np.int64))
        global_flag = self._flag(d["hits"], d["activations"],
                                 np.zeros(1, bool))
        row = {
            "requests": d["activations"][0],
            "hits": d["hits"][0],
            "appends": d["appends"][0],
            "dma_read_bytes": d["dma_read_bytes"][0],
            "dma_write_bytes": d["dma_write_bytes"][0],
            "latency": d["latency_s"][0],
            "flushes": d["flushes"][0],
            "evict_flushes": d["evict_flushes"][0],
            "dirty_dropped": d["dirty_dropped"][0],
            "sessions_ended": d["sessions_ended"][0],
            "pop_drops": d["pop_drops"][0],
            "dirty_resident": dirty,
            "quota": quota,
            "used": used,
            "overloaded": pressure | bool(global_flag[0]),
        }
        self.journal.append(row)
        return row

    # -- legacy cleaner-log views -----------------------------------------
    # PR 8's EticaCache.clean_log / dirty_log were unbounded Python lists
    # (one [V] array per maintenance interval, forever). They are now
    # views over the bounded journal: the rows where the cleaner actually
    # ran, exactly the intervals the old lists recorded.
    def cache_clean_log(self) -> list[np.ndarray]:
        if "clean_ran" not in self.journal:
            return []
        ran = self.journal.column("clean_ran")
        cl = self.journal.column("cleaned")
        return [cl[i] for i in np.flatnonzero(ran)]

    def cache_dirty_log(self) -> list[np.ndarray]:
        if "clean_ran" not in self.journal:
            return []
        ran = self.journal.column("clean_ran")
        dl = self.journal.column("dirty_resident")
        return [dl[i] for i in np.flatnonzero(ran)]


# ---------------------------------------------------------------------------
# journal summaries (tools/run_report.py + fig17 render from these)
# ---------------------------------------------------------------------------

def summarize_journal(cols: dict[str, np.ndarray]) -> dict:
    """Aggregate a loaded (or in-memory) journal's columns.

    ``cols`` maps column name -> ``[rows, ...]`` arrays (the shape
    :func:`load_journal` returns). Returns per-interval 1-D series
    (requests, hit_ratio, dirty, overloaded count) plus scalar totals.
    """
    if not cols:
        return {"intervals": 0}
    reqs = np.asarray(cols["requests"], np.float64)
    hits = np.asarray(cols["hits"], np.float64)
    if reqs.ndim > 1:                      # per-VM rows -> per-interval sums
        reqs_i, hits_i = reqs.sum(axis=1), hits.sum(axis=1)
    else:
        reqs_i, hits_i = reqs, hits
    dirty = np.asarray(cols.get("dirty_resident", np.zeros_like(reqs)),
                       np.float64)
    dirty_i = dirty.sum(axis=1) if dirty.ndim > 1 else dirty
    over = np.asarray(cols.get("overloaded", np.zeros_like(reqs)), bool)
    over_i = over.sum(axis=1) if over.ndim > 1 else over.astype(np.int64)
    ratio = hits_i / np.maximum(reqs_i, 1.0)
    return {
        "intervals": int(reqs_i.shape[0]),
        "requests": reqs_i,
        "hit_ratio": ratio,
        "dirty": dirty_i,
        "overloaded": over_i,
        "total_requests": float(reqs_i.sum()),
        "mean_hit_ratio": float(hits_i.sum() / max(reqs_i.sum(), 1.0)),
        "peak_dirty": float(dirty_i.max(initial=0.0)),
        "overloaded_intervals": int((over_i > 0).sum()),
    }


def format_report(cols: dict[str, np.ndarray], last: int | None = None,
                  vm: int | None = None) -> list[str]:
    """Human-readable per-interval report lines for a journal."""
    s = summarize_journal(cols)
    if not s["intervals"]:
        return ["empty journal"]
    idx = np.asarray(cols.get("i", np.arange(s["intervals"])), np.int64)
    reqs, ratio = s["requests"], s["hit_ratio"]
    dirty, over = s["dirty"], s["overloaded"]
    if vm is not None:
        r = np.asarray(cols["requests"], np.float64)
        if r.ndim < 2:
            raise ValueError("journal has no per-VM columns (serving run?)")
        h = np.asarray(cols["hits"], np.float64)
        reqs, ratio = r[:, vm], h[:, vm] / np.maximum(r[:, vm], 1.0)
        d = np.asarray(cols["dirty_resident"], np.float64)
        o = np.asarray(cols["overloaded"], bool)
        dirty, over = d[:, vm], o[:, vm].astype(np.int64)
    lines = [f"{'interval':>8} {'requests':>9} {'hit_ratio':>9} "
             f"{'dirty':>7} {'overloaded':>10}"]
    sel = range(s["intervals"]) if last is None else \
        range(max(s["intervals"] - last, 0), s["intervals"])
    for i in sel:
        lines.append(f"{int(idx[i]):>8} {reqs[i]:>9.0f} {ratio[i]:>9.3f} "
                     f"{dirty[i]:>7.0f} {int(over[i]):>10}")
    lines.append(
        f"summary: intervals={s['intervals']} "
        f"requests={s['total_requests']:.0f} "
        f"mean_hit_ratio={s['mean_hit_ratio']:.3f} "
        f"peak_dirty={s['peak_dirty']:.0f} "
        f"overloaded_intervals={s['overloaded_intervals']}")
    return lines
