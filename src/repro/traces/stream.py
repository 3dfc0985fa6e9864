"""Streaming ``[V, chunk]`` request-block ingestion for the controllers.

The batched controllers consume multi-VM traces as a sequence of resize
windows, each demuxed per VM and simulated in rectangular ``[V, chunk]``
blocks (``addr = -1`` padded). With an in-memory
:class:`~repro.core.trace.Trace` that demux used to cost V boolean-mask
scans per window; with the million-request traces the paper evaluates on
(§5.1) the trace would not even fit in host memory. This module supplies
both halves of the fix:

* :class:`StreamingTraceSource` — iterates resize windows from an
  on-disk :class:`~repro.traces.store.TraceStore` (or an in-memory
  ``Trace``) and performs the per-VM demux with **one stable sort per
  shard** (``np.argsort(vm, kind="stable")`` groups requests by VM while
  preserving per-VM arrival order), serving each window's per-VM
  sub-traces by binary-searching the sorted global-index segments. Only
  the shards overlapping the current window are resident, so peak host
  memory is O(shard + window + V·chunk) — independent of trace length.

* **Depth-``d`` host→device prefetch** — :meth:`StreamWindow.blocks`
  keeps ``prefetch_depth`` ``[V, chunk]`` blocks in flight beyond the one
  being consumed: while the simulator consumes block *k*, blocks
  *k+1 … k+d* are already being ``jax.device_put`` — the generalized
  pipeline (``d = 1`` is the classic double buffer)::

      host   : | build k | build k+1 | build k+2 |
      xfer   :      | put k | put k+1  | put k+2 |
      device :          | sim k  | sim k+1 | sim k+2 |

  JAX transfers and dispatches are asynchronous, so the copies overlap
  the simulation instead of serializing after it. ``prefetch_depth = 0``
  (or ``prefetch=False`` on the source) disables the pipeline and yields
  host arrays; results are bit-identical at every depth (asserted in
  ``tests/test_trace_store.py``).

* **Sharded feeding** — with ``sharding`` (a ``NamedSharding`` over a VM
  mesh) each prefetched block is placed directly into its per-device
  ``[V/d, chunk]`` layout, and ``pad_vms`` appends that many dead VM rows
  (all ``addr = -1``, the exact-no-op padding contract) so the padded VM
  count divides the mesh size. The demux itself is unchanged — the pad
  rows never exist on the host side beyond the block builder.

Both controllers accept a ``Trace``, a ``TraceStore``, or a pre-built
``StreamingTraceSource`` in :meth:`run` and produce **bit-identical**
results for all three (asserted in ``tests/test_trace_store.py``): the
demux equals the mask-based reference and padding/chunking are shared
with the in-memory path (:func:`repro.core.trace.pad_batch`).
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Iterator

import jax
import numpy as np

from repro.core.trace import Trace, pad_batch, split_by_vm
from repro.runtime.telemetry import span

from .store import TraceStore


@dataclasses.dataclass
class StreamWindow:
    """One resize window: per-VM sub-traces + padded datapath blocks."""

    index: int                  # window ordinal
    subs: list[Trace]           # per-VM demux (sizing / maintenance / oracle)
    chunk: int                  # datapath block width (promo/sim chunk)
    prefetch_depth: int = 2     # blocks in flight beyond the consumed one
    pad_vms: int = 0            # dead VM rows appended to each block
    sharding: object = None     # NamedSharding placing [V, chunk] per shard

    def chunk_lists(self) -> list[list[Trace]]:
        return [list(sub.intervals(self.chunk)) for sub in self.subs]

    def blocks(self) -> Iterator[tuple]:
        """Yield ``(addr [V, chunk], is_write [V, chunk], kth)`` per
        datapath chunk; ``kth`` is the ragged per-VM chunk list the
        maintenance path consumes (real VMs only — never padded). With
        ``prefetch_depth > 0`` the arrays arrive as device buffers, put up
        to that many blocks ahead of consumption; with ``sharding`` each
        transfer lands directly in the per-device row-block layout.
        Building and putting each block is an ``ingest`` span."""
        with span("ingest"):
            lists = self.chunk_lists()
        n_chunks = max(map(len, lists), default=0)
        pad = [None] * self.pad_vms

        def host_block(k: int):
            with span("ingest"):
                kth = [c[k] if k < len(c) else None for c in lists]
                a, w = pad_batch(kth + pad, self.chunk)
            return a, w, kth

        if self.prefetch_depth <= 0:
            yield from (host_block(k) for k in range(n_chunks))
            return

        def put(a, w):
            with span("ingest"):
                if self.sharding is None:
                    return jax.device_put((a, w))
                return jax.device_put((a, w), self.sharding)

        pending: deque = deque()
        for k in range(min(self.prefetch_depth, n_chunks)):
            a, w, kth = host_block(k)
            pending.append((put(a, w), kth))
        k = len(pending)
        while pending:
            dev, kth = pending.popleft()
            if k < n_chunks:        # start the next transfer before the
                a, w, nk = host_block(k)  # consumer dispatches this block
                pending.append((put(a, w), nk))
                k += 1
            yield dev[0], dev[1], kth


@dataclasses.dataclass
class _DemuxedShard:
    """One shard after its single stable sort: requests grouped by VM
    (arrival order preserved within each VM), with global indices."""

    base: int                   # global index of the shard's first request
    length: int
    addr: np.ndarray            # [n] sorted by (vm, arrival)
    is_write: np.ndarray        # [n]
    gidx: np.ndarray            # [n] ascending global index per VM segment
    bounds: np.ndarray          # [num_vms + 1] VM segment boundaries
    size: np.ndarray | None = None  # [n] request sizes (sized stores only)

    @classmethod
    def demux(cls, shard: Trace, base: int, num_vms: int) -> "_DemuxedShard":
        vm = np.asarray(shard.vm)
        order = np.argsort(vm, kind="stable")
        bounds = np.searchsorted(vm[order], np.arange(num_vms + 1))
        return cls(base=base, length=len(shard),
                   addr=np.asarray(shard.addr, np.int32)[order],
                   is_write=np.asarray(shard.is_write, bool)[order],
                   gidx=(base + order).astype(np.int64), bounds=bounds,
                   size=(None if shard.size is None
                         else np.asarray(shard.size, np.int32)[order]))

    def vm_part(self, v: int, start: int, stop: int):
        """This shard's (addr, is_write, size) for VM ``v`` restricted to
        global request range ``[start, stop)`` — a binary search, no
        scan. ``size`` is ``None`` for size-less stores."""
        lo, hi = int(self.bounds[v]), int(self.bounds[v + 1])
        g = self.gidx[lo:hi]
        a = int(np.searchsorted(g, start))
        b = int(np.searchsorted(g, stop))
        return (self.addr[lo + a: lo + b], self.is_write[lo + a: lo + b],
                None if self.size is None else self.size[lo + a: lo + b])


@dataclasses.dataclass
class StreamingTraceSource:
    """Resize-window iterator over a ``TraceStore`` or in-memory ``Trace``.

    Yields :class:`StreamWindow`\\ s whose per-VM sub-traces are
    bit-identical to ``split_by_vm(trace[s:e], num_vms)`` on the
    materialized trace. ``window`` is the controller's resize interval,
    ``chunk`` its datapath block width.
    """

    source: "TraceStore | Trace"
    num_vms: int
    window: int
    chunk: int
    prefetch: bool = True       # master switch (False -> host blocks)
    prefetch_depth: int = 2     # pipeline depth when prefetch is on
    pad_vms: int = 0            # dead VM rows appended to datapath blocks
    sharding: object = None     # NamedSharding for per-shard placement

    @property
    def depth(self) -> int:
        return self.prefetch_depth if self.prefetch else 0

    def _window(self, i: int, subs: list[Trace]) -> StreamWindow:
        return StreamWindow(i, subs, self.chunk, self.depth,
                            self.pad_vms, self.sharding)

    def windows(self) -> Iterator[StreamWindow]:
        if isinstance(self.source, Trace):
            yield from self._windows_from_trace(self.source)
        elif self.source.has_vm:
            yield from self._windows_from_store(self.source)
        else:
            yield from self._windows_from_vmless_store(self.source)

    # -- in-memory ---------------------------------------------------------
    def _windows_from_trace(self, trace: Trace) -> Iterator[StreamWindow]:
        for i, window in enumerate(trace.intervals(self.window)):
            with span("ingest"):
                subs = split_by_vm(window, self.num_vms)
            yield self._window(i, subs)

    # -- on-disk, vm channel ----------------------------------------------
    def _windows_from_store(self, store: TraceStore) -> Iterator[StreamWindow]:
        total = len(store)
        active: deque[_DemuxedShard] = deque()
        shard_idx, loaded = 0, 0
        sized = store.has_size
        empty = (np.empty(0, np.int32), np.empty(0, bool),
                 np.empty(0, np.int32) if sized else None)
        for i, ws in enumerate(range(0, total, self.window)):
            with span("ingest"):
                we = min(ws + self.window, total)
                while loaded < we:        # one stable sort per shard, once
                    sh = store.shard(shard_idx)
                    active.append(
                        _DemuxedShard.demux(sh, loaded, self.num_vms))
                    loaded += len(sh)
                    shard_idx += 1
                while active and active[0].base + active[0].length <= ws:
                    active.popleft()      # shard fully behind this window
                subs = []
                for v in range(self.num_vms):
                    parts = [d.vm_part(v, ws, we) for d in active]
                    parts = [p for p in parts if p[0].size]
                    if not parts:
                        subs.append(Trace(empty[0], empty[1], size=empty[2]))
                    elif len(parts) == 1:
                        subs.append(Trace(parts[0][0], parts[0][1],
                                          size=parts[0][2]))
                    else:
                        subs.append(Trace(
                            np.concatenate([p[0] for p in parts]),
                            np.concatenate([p[1] for p in parts]),
                            size=(np.concatenate([p[2] for p in parts])
                                  if sized else None)))
            yield self._window(i, subs)

    # -- on-disk, no vm channel (single-stream convention) -----------------
    def _windows_from_vmless_store(self, store) -> Iterator[StreamWindow]:
        # mirrors the controllers' Trace(vm=None) convention: every VM
        # sees the whole window
        for i, window in enumerate(store.iter_windows(self.window)):
            yield self._window(i, [window] * self.num_vms)


def window_source(trace, num_vms: int, window: int, chunk: int,
                  prefetch: bool = True, prefetch_depth: int = 2,
                  pad_vms: int = 0, sharding=None) -> StreamingTraceSource:
    """Normalize any accepted trace input into a StreamingTraceSource.

    ``trace`` may be an in-memory :class:`Trace`, an on-disk
    :class:`TraceStore`, or an existing :class:`StreamingTraceSource`
    (re-parameterized to the controller's intervals)."""
    if isinstance(trace, StreamingTraceSource):
        return dataclasses.replace(trace, num_vms=num_vms, window=window,
                                   chunk=chunk, prefetch=prefetch,
                                   prefetch_depth=prefetch_depth,
                                   pad_vms=pad_vms, sharding=sharding)
    if not isinstance(trace, (Trace, TraceStore)):
        raise TypeError(f"expected Trace, TraceStore or "
                        f"StreamingTraceSource, got {type(trace).__name__}")
    return StreamingTraceSource(trace, num_vms, window, chunk, prefetch,
                                prefetch_depth, pad_vms, sharding)
