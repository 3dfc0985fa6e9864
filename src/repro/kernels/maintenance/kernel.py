"""Pallas TPU kernels: maintenance scatters over stacked ``[V, S, W]``.

All three kernels run on a ``(V, num_set_strips[, num_queue_chunks])``
grid: one VM x one strip of ``TS`` sets (x one ``QC``-entry chunk of the
VM's queue) per step. The strip's output blocks stay resident across
the innermost queue-chunk axis, so each chunk updates the state left by
the previous one; the per-VM count output accumulates across strips and
chunks (the standard Pallas reduction pattern).

Layout, chosen so the TPU compiler accepts every block:

  * state strips are ``(1, TS, W)`` blocks — ``TS`` a multiple of 8 (or
    the whole padded set axis) and ``W`` the full way axis;
  * queues travel as ``[V, 1, Q]`` and are read one ``(1, 1, QC)`` chunk
    at a time from SMEM, one scalar address per loop step;
  * per-VM scalars (active ways, promote timestamp, cleaner cutoffs) sit
    whole in SMEM (``[V]`` int32) and are indexed by the VM grid axis;
  * per-VM counts come back lane-padded as ``[V, 1, 128]`` (every lane
    holds the count; the ops wrapper reads lane 0).

Kernel semantics (the contracts of ``ref.py``):

  * **evict**: every way whose tag equals a queued address (any set; the
    ``-1`` queue padding never matches) is cleared, counting dirty
    flushes. Each queue entry is compared against the whole strip.
  * **clean**: the background dirty-block cleaner. The expensive part —
    ranking dirty blocks by age — is a per-VM (lru, flat-index) cutoff
    pair precomputed in the fused dispatch (``ops._clean_cutoffs``); the
    kernel applies the cutoff per strip: a candidate flushes iff its
    lexicographic (lru, flat-index) key is <= the cutoff, clearing only
    the dirty bit (flushed blocks stay resident and clean) and
    accumulating per-VM flush counts.
  * **promote**: the queue is drained in order, exactly like
    ``repro.core.simulator.promote_blocks_ref``: an address already
    resident in an active way of its set is skipped, otherwise it lands
    in the set's lowest free active way, and a full set starves it. A
    later duplicate finds its first occurrence resident (or its set
    still full), so first-occurrence-wins needs no separate pass. Each
    entry touches one ``[1, W]`` set row, loaded and stored at a dynamic
    sublane offset.

VMEM per step: six ``(TS, W)`` int32 strip buffers (inputs and outputs,
double-buffered) — 256 sets x 64 ways is 1.5MB with lane padding.
``dirty`` travels as int32; the ops wrapper converts from/to bool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_TS = 256   # sets per grid step
DEFAULT_QC = 1024  # queue entries per grid step (one SMEM chunk)
LANES = 128        # lane width of the per-VM count outputs

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _strip(ts: int, w: int):
    return pl.BlockSpec((1, ts, w), lambda i, j, *_: (i, j, 0))


def _counts(v: int):
    """Per-VM count output: lane-padded ``[V, 1, 128]`` int32."""
    return (pl.BlockSpec((1, 1, LANES), lambda i, *_: (i, 0, 0)),
            jax.ShapeDtypeStruct((v, 1, LANES), jnp.int32))


def _queue_chunk(qc: int):
    return pl.BlockSpec((1, 1, qc), lambda i, j, k: (i, 0, k),
                        memory_space=pltpu.SMEM)


def _copy_in(pairs):
    """On a strip's first queue chunk, seed the resident output blocks
    with the input strip; later chunks update them in place."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        for src, dst in pairs:
            dst[...] = src[...]


def _zero_counts(ref, first):
    @pl.when(first)
    def _():
        ref[...] = jnp.zeros_like(ref)


# ---------------------------------------------------------------------------
# evict
# ---------------------------------------------------------------------------

def _evict_kernel(q_ref, tags_ref, lru_ref, dirty_ref,
                  otags_ref, olru_ref, odirty_ref, flush_ref, *, qc: int):
    _copy_in([(tags_ref, otags_ref), (lru_ref, olru_ref),
              (dirty_ref, odirty_ref)])
    _zero_counts(flush_ref, (pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    tags = otags_ref[0]                 # [TS, W]

    def body(e, hit):
        a = q_ref[0, 0, e]              # scalar address, -1 = padding
        return jnp.where((tags == a) & (a >= 0), 1, hit)

    hit = jax.lax.fori_loop(0, qc, body, jnp.zeros(tags.shape, jnp.int32)) > 0
    dirty = odirty_ref[0]
    flush_ref[0] += jnp.sum((hit & (dirty > 0)).astype(jnp.int32),
                            keepdims=True)[0]
    otags_ref[0] = jnp.where(hit, -1, tags)
    olru_ref[0] = jnp.where(hit, -1, olru_ref[0])
    odirty_ref[0] = jnp.where(hit, 0, dirty)


@functools.partial(jax.jit, static_argnames=("ts", "qc", "interpret"))
def _evict_call(tags, lru, dirty, queue, *, ts, qc, interpret):
    v, s, w = tags.shape
    nq = queue.shape[-1]
    strip = _strip(ts, w)
    cnt_spec, cnt_shape = _counts(v)
    state = jax.ShapeDtypeStruct(tags.shape, jnp.int32)
    return pl.pallas_call(
        functools.partial(_evict_kernel, qc=qc),
        grid=(v, s // ts, nq // qc),
        in_specs=[_queue_chunk(qc), strip, strip, strip],
        out_specs=[strip, strip, strip, cnt_spec],
        out_shape=[state, state, state, cnt_shape],
        interpret=interpret,
    )(queue.reshape(v, 1, nq), tags, lru, dirty)


def evict_scatter(tags, lru, dirty, queue, *, ts: int = DEFAULT_TS,
                  qc: int = DEFAULT_QC, interpret: bool | None = None):
    """Evict queued blocks from stacked states.

    ``tags``/``lru``/``dirty`` are ``[V, S, W]`` int32 (``S`` a multiple
    of ``ts``); ``queue`` is ``[V, Q]`` int32 with ``Q`` a multiple of
    ``qc`` and ``-1`` padding. Returns ``(tags, lru, dirty, flushed[V])``.
    """
    tags, lru, dirty, flushed = _evict_call(
        tags, lru, dirty, queue, ts=ts, qc=qc,
        interpret=resolve_interpret(interpret))
    return tags, lru, dirty, flushed[:, 0, 0]


# ---------------------------------------------------------------------------
# clean (background dirty-block flush)
# ---------------------------------------------------------------------------

def _clean_kernel(ways_ref, lcut_ref, icut_ref, dirty_ref, lru_ref,
                  odirty_ref, flush_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    _zero_counts(flush_ref, j == 0)
    dirty = dirty_ref[0]                # [TS, W] int32 (0/1)
    lru = lru_ref[0]                    # [TS, W]
    ts, w = dirty.shape
    ways = ways_ref[i]                  # active ways for this VM
    lcut = lcut_ref[i]                  # lru of the last block to flush
    icut = icut_ref[i]                  # its flat set*W+way index

    widx = jax.lax.broadcasted_iota(jnp.int32, (ts, w), 1)
    sidx = j * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, w), 0)
    flat = sidx * w + widx              # global (set, way) id
    cand = (dirty > 0) & (widx < ways)
    # the (lru, flat) keys are unique, so the lexicographic cutoff selects
    # exactly the `take` oldest candidates ranked by ops._clean_cutoffs
    flush = cand & ((lru < lcut) | ((lru == lcut) & (flat <= icut)))
    odirty_ref[0] = jnp.where(flush, 0, dirty)
    flush_ref[0] += jnp.sum(flush.astype(jnp.int32), keepdims=True)[0]


@functools.partial(jax.jit, static_argnames=("ts", "interpret"))
def _clean_call(dirty, lru, ways, lru_cut, idx_cut, *, ts, interpret):
    v, s, w = dirty.shape
    strip = _strip(ts, w)
    cnt_spec, cnt_shape = _counts(v)
    return pl.pallas_call(
        _clean_kernel,
        grid=(v, s // ts),
        in_specs=[_SMEM, _SMEM, _SMEM, strip, strip],
        out_specs=[strip, cnt_spec],
        out_shape=[jax.ShapeDtypeStruct(dirty.shape, jnp.int32), cnt_shape],
        interpret=interpret,
    )(ways, lru_cut, idx_cut, dirty, lru)


def clean_scatter(dirty, lru, ways, lru_cut, idx_cut, *,
                  ts: int = DEFAULT_TS, interpret: bool | None = None):
    """Flush (clear dirty) every dirty active block at or below the
    per-VM age cutoff.

    ``dirty``/``lru`` are ``[V, S, W]`` int32 (``S`` a multiple of
    ``ts``); ``ways``/``lru_cut``/``idx_cut`` are ``[V]`` int32 — the
    cutoff pair is the (lru, flat set*W+way index) key of the last block
    to flush (``(INT32_MIN, -1)`` = flush nothing). Returns ``(dirty,
    flushed[V])``.
    """
    dirty, flushed = _clean_call(dirty, lru, ways, lru_cut, idx_cut, ts=ts,
                                 interpret=resolve_interpret(interpret))
    return dirty, flushed[:, 0, 0]


# ---------------------------------------------------------------------------
# promote
# ---------------------------------------------------------------------------

def _promote_kernel(ways_ref, t_ref, q_ref, tags_ref, lru_ref, dirty_ref,
                    otags_ref, olru_ref, odirty_ref, n_ref, *,
                    num_sets: int, qc: int):
    i, j = pl.program_id(0), pl.program_id(1)
    _copy_in([(tags_ref, otags_ref), (lru_ref, olru_ref),
              (dirty_ref, odirty_ref)])
    _zero_counts(n_ref, (j == 0) & (pl.program_id(2) == 0))
    _, ts, w = tags_ref.shape
    ways = ways_ref[i]                  # active ways for this VM
    tstamp = t_ref[i]                   # promote timestamp
    widx = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    active = widx < ways

    def body(e, carry):
        a = q_ref[0, 0, e]              # scalar address, -1 = padding
        local = jnp.where(a >= 0, a % num_sets, -1) - j * ts
        go = (a >= 0) & (ways > 0) & (local >= 0) & (local < ts)

        @pl.when(go)
        def _():
            row = pl.ds(local, 1)       # the address's set, [1, W]
            tags = otags_ref[0, row, :]
            present = jnp.max(jnp.where((tags == a) & active, 1, 0),
                              axis=1, keepdims=True)
            first = jnp.min(jnp.where((tags < 0) & active, widx, w),
                            axis=1, keepdims=True)
            put = (widx == first) & (present == 0)   # lowest free way
            otags_ref[0, row, :] = jnp.where(put, a, tags)
            olru_ref[0, row, :] = jnp.where(put, tstamp,
                                            olru_ref[0, row, :])
            odirty_ref[0, row, :] = jnp.where(put, 0, odirty_ref[0, row, :])
            n_ref[0] += jnp.sum(put.astype(jnp.int32), axis=1, keepdims=True)

        return carry

    jax.lax.fori_loop(0, qc, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("num_sets", "ts", "qc", "interpret"))
def _promote_call(tags, lru, dirty, queue, ways, t, *, num_sets, ts, qc,
                  interpret):
    v, s, w = tags.shape
    nq = queue.shape[-1]
    strip = _strip(ts, w)
    cnt_spec, cnt_shape = _counts(v)
    state = jax.ShapeDtypeStruct(tags.shape, jnp.int32)
    return pl.pallas_call(
        functools.partial(_promote_kernel, num_sets=num_sets, qc=qc),
        grid=(v, s // ts, nq // qc),
        in_specs=[_SMEM, _SMEM, _queue_chunk(qc), strip, strip, strip],
        out_specs=[strip, strip, strip, cnt_spec],
        out_shape=[state, state, state, cnt_shape],
        interpret=interpret,
    )(ways, t, queue.reshape(v, 1, nq), tags, lru, dirty)


def promote_scatter(tags, lru, dirty, queue, ways, t, *, num_sets: int,
                    ts: int = DEFAULT_TS, qc: int = DEFAULT_QC,
                    interpret: bool | None = None):
    """Promote queued blocks into free active ways of stacked states.

    Shapes as :func:`evict_scatter` plus per-VM ``ways``/``t`` ``[V]``
    int32. ``num_sets`` is the REAL set count (tiles may pad ``S``
    beyond it; padded sets are never addressed since ``addr %% num_sets
    < num_sets``). Returns ``(tags, lru, dirty, promoted[V])``.
    """
    tags, lru, dirty, n = _promote_call(
        tags, lru, dirty, queue, ways, t, num_sets=num_sets, ts=ts, qc=qc,
        interpret=resolve_interpret(interpret))
    return tags, lru, dirty, n[:, 0, 0]
