"""Jitted wrappers for the maintenance kernels + the fused interval op.

``evict`` / ``promote`` mirror the contracts of
``repro.core.simulator.evict_blocks_batch`` / ``promote_blocks_batch``
but run the scatters through the Pallas kernels (interpret mode on CPU,
compiled on TPU — ``interpret=None`` picks by backend through
``repro.kernels.use_interpret``).

``maintenance_interval`` is the whole between-interval maintenance of
the batched :class:`~repro.core.controller.EticaCache` as ONE jitted
dispatch: Eq. 1 contributions -> device popularity-table update ->
eviction-queue build -> evict kernel -> free-space recount ->
promotion-queue build -> promote kernel -> background cleaner (age
cutoff + clean kernel, when ``clean_quota > 0``). The post-eviction
state feeds the promotion stage on device and the post-promotion state
feeds the cleaner — there is no ``np.asarray(state)`` sync anywhere
between stages; only the final per-VM counts ever reach the host. Every
stage works on the leading ``ways_bucket`` ways of the SSD state (a power
of two at least the largest active way count), which keeps the queue
widths and the kernels' loops at the size of the live ways.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import popularity as pop
from repro.core.simulator import CacheState, _next_pow2, _pad_addrs_batch
from repro.kernels import resolve_interpret

from .kernel import (DEFAULT_QC, DEFAULT_TS, clean_scatter, evict_scatter,
                     promote_scatter)


def _tiles(s: int, ts: int) -> tuple[int, int]:
    """(effective set-tile, padded S) — S padded up to a tile multiple."""
    ts = min(ts, _next_pow2(s))
    return ts, -(-s // ts) * ts


def _pad_sets(x, s_pad: int, fill):
    v, s, w = x.shape
    if s == s_pad:
        return x
    return jnp.concatenate(
        [x, jnp.full((v, s_pad - s, w), fill, x.dtype)], axis=1)


@functools.partial(jax.jit, static_argnames=("ts", "qc", "interpret"))
def _evict_state(state: CacheState, queue, *, ts, qc, interpret):
    v, s, w = state.tags.shape
    ts, s_pad = _tiles(s, ts)
    tags, lru, dirty, flushed = evict_scatter(
        _pad_sets(state.tags, s_pad, -1),
        _pad_sets(state.lru, s_pad, -1),
        _pad_sets(state.dirty.astype(jnp.int32), s_pad, 0),
        queue, ts=ts, qc=qc, interpret=interpret)
    return CacheState(tags[:, :s], lru[:, :s],
                      dirty[:, :s].astype(bool)), flushed


@functools.partial(jax.jit, static_argnames=("ts", "qc", "interpret"))
def _promote_state(state: CacheState, queue, ways, t, *, ts, qc,
                   interpret):
    v, s, w = state.tags.shape
    ts, s_pad = _tiles(s, ts)
    tags, lru, dirty, n = promote_scatter(
        _pad_sets(state.tags, s_pad, -1),
        _pad_sets(state.lru, s_pad, -1),
        _pad_sets(state.dirty.astype(jnp.int32), s_pad, 0),
        queue, jnp.asarray(ways, jnp.int32), jnp.asarray(t, jnp.int32),
        num_sets=s, ts=ts, qc=qc, interpret=interpret)
    return CacheState(tags[:, :s], lru[:, :s],
                      dirty[:, :s].astype(bool)), n


def _clean_cutoffs(dirty, lru, ways, quota):
    """Per-VM age cutoffs for the background cleaner.

    Candidates are the dirty blocks in active ways, aged by the unique
    lexicographic key (lru ascending, flat ``set * W + way`` index
    ascending). Returns ``(lru_cut[V], idx_cut[V], take[V], n_cand[V])``
    where the cutoff pair is the key of the ``take``-th oldest candidate
    (``take = min(quota, n_cand)``); sentinel ``(INT32_MIN, -1)`` when
    nothing flushes. The kernel then flushes exactly the candidates with
    key <= cutoff.

    The two-pass stable argsort is an int32-safe lexsort: sorting by lru
    first and then (stably) by not-candidate yields candidates first, in
    (lru, index) order — no composite 64-bit keys, no sentinel values
    that could collide with real lru timestamps.
    """
    v, s, w = dirty.shape
    active = jnp.arange(w, dtype=jnp.int32)[None, None, :] < ways[:, None, None]
    cflat = (dirty & active).reshape(v, s * w)
    lflat = lru.reshape(v, s * w)
    ord1 = jnp.argsort(lflat, axis=1, stable=True)
    c1 = jnp.take_along_axis(cflat, ord1, axis=1)
    order = jnp.take_along_axis(ord1, jnp.argsort(~c1, axis=1, stable=True),
                                axis=1)
    n_cand = jnp.sum(cflat, axis=1).astype(jnp.int32)
    take = jnp.minimum(jnp.asarray(quota, jnp.int32), n_cand)
    kth = jnp.maximum(take - 1, 0)
    idx_k = jnp.take_along_axis(order, kth[:, None], axis=1)[:, 0]
    lru_k = jnp.take_along_axis(lflat, idx_k[:, None], axis=1)[:, 0]
    has = take > 0
    return (jnp.where(has, lru_k, jnp.int32(-2**31)).astype(jnp.int32),
            jnp.where(has, idx_k, -1).astype(jnp.int32), take, n_cand)


@functools.partial(jax.jit, static_argnames=("ts", "interpret"))
def _clean_state(state: CacheState, ways, quota, *, ts, interpret):
    v, s, w = state.tags.shape
    lcut, icut, take, n_cand = _clean_cutoffs(state.dirty, state.lru, ways,
                                              quota)
    ts, s_pad = _tiles(s, ts)
    dirty, cleaned = clean_scatter(
        _pad_sets(state.dirty.astype(jnp.int32), s_pad, 0),
        _pad_sets(state.lru, s_pad, -1),
        ways, lcut, icut, ts=ts, interpret=interpret)
    return (CacheState(state.tags, state.lru, dirty[:, :s].astype(bool)),
            cleaned, n_cand - take)


def clean(state: CacheState, ways, quota, *, ts: int = DEFAULT_TS,
          interpret: bool | None = None):
    """Kernel-backed background cleaner over stacked states.

    Flushes (clears the dirty bit of) up to ``quota[v]`` of VM ``v``'s
    oldest dirty active blocks — age order (lru, flat index) ascending;
    flushed blocks stay resident and clean. ``ways``/``quota`` are
    ``[V]`` (scalars broadcast). Returns ``(state, flushed[V],
    dirty_left[V])``, oracle-identical to ``ref.clean_ref``.
    """
    v = state.tags.shape[0]
    ways = jnp.broadcast_to(jnp.asarray(ways, jnp.int32), (v,))
    quota = jnp.broadcast_to(jnp.asarray(quota, jnp.int32), (v,))
    return _clean_state(state, ways, quota, ts=ts,
                        interpret=resolve_interpret(interpret))


def _queue_matrix(queues) -> np.ndarray:
    """Ragged per-VM queues -> one [V, Q] -1-padded rectangle, Q a
    power-of-two multiple of the chunk width."""
    q = _pad_addrs_batch(queues)
    width = _next_pow2(q.shape[1])
    out = np.full((q.shape[0], width), -1, np.int32)
    out[:, : q.shape[1]] = q
    return out


def _pow2_queue(queue) -> jax.Array:
    """Pad a [V, Q] queue to a power-of-two width (with -1 no-ops) so
    the kernels' chunked loops cover every column — a non-multiple tail
    would otherwise be silently skipped."""
    queue = jnp.asarray(queue, jnp.int32)
    width = _next_pow2(max(queue.shape[1], 1))
    if width == queue.shape[1]:
        return queue
    return jnp.concatenate(
        [queue, jnp.full((queue.shape[0], width - queue.shape[1]), -1,
                         jnp.int32)], axis=1)


def evict(state: CacheState, queues, *, ts: int = DEFAULT_TS,
          qc: int = DEFAULT_QC, interpret: bool | None = None):
    """Kernel-backed :func:`repro.core.simulator.evict_blocks_batch`.

    ``queues`` is one (possibly empty) address array per VM, or an
    already-rectangular ``[V, Q]`` array with ``-1`` padding. Returns
    ``(state, flushed[V])`` with identical states/counts to the numpy
    oracle (``ref.evict_ref``).
    """
    if not isinstance(queues, (np.ndarray, jax.Array)):
        queues = _queue_matrix(queues)
    queues = _pow2_queue(queues)
    qc = min(qc, queues.shape[1])
    return _evict_state(state, queues, ts=ts, qc=qc,
                        interpret=resolve_interpret(interpret))


def promote(state: CacheState, queues, ways, t, *, ts: int = DEFAULT_TS,
            qc: int = DEFAULT_QC, interpret: bool | None = None):
    """Kernel-backed :func:`repro.core.simulator.promote_blocks_batch`.

    ``ways``/``t`` are ``[V]``. Queues may hold duplicates and ``-1``
    padding anywhere (first occurrence wins). Returns ``(state,
    promoted[V])``, oracle-identical (``ref.promote_ref``).
    """
    if not isinstance(queues, (np.ndarray, jax.Array)):
        queues = _queue_matrix(queues)
    queues = _pow2_queue(queues)
    qc = min(qc, queues.shape[1])
    return _promote_state(state, queues, jnp.asarray(ways, jnp.int32),
                          jnp.asarray(t, jnp.int32), ts=ts, qc=qc,
                          interpret=resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# the fused per-interval dispatch
# ---------------------------------------------------------------------------

def ways_bucket_of(ways, max_ways: int) -> int:
    """The way bucket of the fused dispatch for host ``ways``: the next
    power of two of the largest active way count (at least 1), clipped to
    the geometry's ``max_ways``."""
    top = int(np.max(np.asarray(ways), initial=0))
    return min(_next_pow2(max(top, 1)), max_ways)


def ways_buckets_upto(max_active: int, max_ways: int) -> tuple[int, ...]:
    """Every bucket :func:`ways_bucket_of` gives while no VM has more
    than ``max_active`` ways: the powers of two below the top bucket,
    then the top bucket."""
    top = ways_bucket_of([max_active], max_ways)
    return tuple(b for b in (1 << i for i in range(top.bit_length()))
                 if b < top) + (top,)


@functools.partial(
    jax.jit, static_argnames=("evict_frac", "decay", "clean_quota", "ts",
                              "qc", "interpret", "ways_bucket"))
def _maintenance_impl(ssd: CacheState, table: pop.PopularityTable,
                      dist, served, waddr, wlen, ways, t, *,
                      evict_frac: float, decay: float, clean_quota: int,
                      ts: int, qc: int, interpret: bool,
                      ways_bucket: int | None = None):
    full = ssd
    v, s, w = full.tags.shape
    # Every stage below runs on the leading `ways_bucket` ways only, and
    # the slice is written back at the end. That is exact for any bucket
    # >= max(ways): resize clears every way at or above a VM's `ways` and
    # nothing places a block there, so the tail holds no block and no
    # stage changes it. The slice's flattened (set, way) order is the
    # full state's, so the eviction candidates' stable ties break as
    # before, and the cleaner's (lru, set * w + way) key keeps its order,
    # since the kernel and _clean_cutoffs index in the same frame.
    if ways_bucket is not None and ways_bucket < w:
        w = ways_bucket
        ssd = CacheState(*(x[:, :, :w] for x in full))
    nval = jnp.asarray(wlen, jnp.int32)
    live = nval > 0
    ways = jnp.asarray(ways, jnp.int32)
    alloc = ways * s

    # 1) Eq. 1 popularity refresh, straight into the [V, K] device table
    contrib = pop.contributions(dist, served,
                                jnp.maximum(alloc, 1)[:, None])
    table, drops = pop.table_update(table, waddr, contrib, nval, live, decay)

    # 2) eviction queue (bottom-frac of residents when >= 90% full) ->
    #    evict kernel. Its valid entries are a prefix of at most
    #    ceil(frac * S * w) (the table's own f32 rounding), so the
    #    truncation below drops only -1 padding.
    equeue, eqlen = pop.table_least_popular(table, ssd.tags, ways, alloc,
                                            live, evict_frac)
    ebound = max(int(np.ceil(np.float32(evict_frac) * np.float32(s * w))), 1)
    equeue = pop.truncate_queue(equeue, _next_pow2(ebound))
    ssd, flushed = _evict_state(ssd, equeue, ts=ts,
                                qc=min(qc, equeue.shape[1]),
                                interpret=interpret)

    # 3) free space from the POST-eviction state (no host sync) ->
    #    promotion queue -> promote kernel
    active = jnp.arange(w, dtype=jnp.int32)[None, None, :] < ways[:, None, None]
    n_res = jnp.sum((ssd.tags >= 0) & active, axis=(1, 2)).astype(jnp.int32)
    free = jnp.maximum(alloc - n_res, 0)
    pqueue, pqlen = pop.table_top_known(
        table, ssd.tags, ways, free, live,
        width=_next_pow2(min(table.capacity, s * w)))
    ssd, promoted = _promote_state(ssd, pqueue, ways,
                                   jnp.asarray(t, jnp.int32), ts=ts,
                                   qc=min(qc, pqueue.shape[1]),
                                   interpret=interpret)

    # 4) background cleaner (third stage): age-ranked scan over the
    #    post-promotion dirty blocks, flushing up to `clean_quota` per
    #    live VM. Rides the same dispatch — the per-VM counts join the
    #    others in the single end-of-interval host sync.
    if clean_quota > 0:
        quota_v = jnp.where(live, jnp.int32(clean_quota), 0)
        ssd, cleaned, dirty_left = _clean_state(ssd, ways, quota_v, ts=ts,
                                                interpret=interpret)
    else:
        cleaned = jnp.zeros(v, jnp.int32)
        dirty_left = jnp.sum(ssd.dirty & active, axis=(1, 2)).astype(jnp.int32)
    if w < full.tags.shape[2]:
        ssd = CacheState(*(jax.lax.dynamic_update_slice(f, x, (0, 0, 0))
                           for f, x in zip(full, ssd)))
    return (ssd, table, flushed, promoted, eqlen, pqlen, drops, cleaned,
            dirty_left)


@functools.lru_cache(maxsize=None)
def _maintenance_sharded(mesh, evict_frac, decay, clean_quota, ts, qc,
                         interpret, ways_bucket=None):
    """``shard_map`` of :func:`_maintenance_impl` over a VM mesh: each
    device runs the full three-stage maintenance on its own ``[V/d, ...]``
    block of states/queues. Queue widths depend only on geometry, window
    bucket and way bucket (never on V; the way bucket is the maximum over
    all rows), so per-shard shapes line up and the compiled HLO is
    collective-free (asserted by the sharding tests)."""
    from repro.launch.mesh import vm_spec
    spec = vm_spec(mesh)

    def body(ssd, table, dist, served, waddr, wlen, ways, t):
        return _maintenance_impl(
            ssd, table, dist, served, waddr, wlen, ways, t,
            evict_frac=evict_frac, decay=decay, clean_quota=clean_quota,
            ts=ts, qc=qc, interpret=interpret, ways_bucket=ways_bucket)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 8, out_specs=(spec,) * 9,
        check_vma=False))


def maintenance_interval(ssd: CacheState, table: pop.PopularityTable,
                         dist, served, waddr, wlen, ways, t, *,
                         evict_frac: float, decay: float,
                         clean_quota: int = 0,
                         ts: int = DEFAULT_TS, qc: int = DEFAULT_QC,
                         interpret: bool | None = None, mesh=None,
                         ways_bucket: int | None = None):
    """One interval of ETICA maintenance for all VMs, fused.

    Args:
      ssd: stacked ``[V, S, W]`` SSD-level :class:`CacheState`.
      table: the ``[V, K]`` :class:`~repro.core.popularity.PopularityTable`.
      dist/served/waddr: ``[V, N]`` TRD distance channels + addresses of
        the VMs' windows (pad tails masked by ``wlen``). Rows are kept
        rectangular across ALL VMs — idle VMs ride along as zero-length
        rows (``wlen == 0`` -> untouched) — so the executable is keyed
        only by the window's power-of-two bucket, never by which subset
        of VMs happens to be live.
      wlen: ``[V]`` valid window lengths (0 = idle VM, no maintenance).
      ways/t: ``[V]`` active SSD ways and per-VM clocks.
      evict_frac/decay: §4.2.1 bottom-fraction and aging factor.
      clean_quota: background-cleaner flush budget per live VM per
        interval (0 disables the third stage entirely).

    Returns ``(ssd, table, flushed[V], promoted[V], evict_qlen[V],
    promo_qlen[V], pop_drops[V], cleaned[V], dirty_left[V])`` — states
    and table stay on device; the count vectors are the only thing a
    caller needs to sync for Stats. ``pop_drops`` is the number of
    popularity entries pushed past the table's ``K`` slots by this merge
    (``Stats.pop_drops``); ``cleaned`` is the cleaner's flush count and
    ``dirty_left`` the dirty blocks still resident in active ways after
    the interval (``Stats.flushes`` / ``Stats.dirty_resident``).

    ``mesh`` splits the VM axis over a 1-d device mesh (V divisible by
    the mesh size; pad with dead ``wlen == 0`` VMs first): the whole
    dispatch runs shard-local with bit-identical per-VM results.

    ``ways_bucket`` (static) is how many leading ways the dispatch works
    on; every way at or above a VM's ``ways`` must hold no block, as
    resize leaves it. Any bucket from ``max(ways)`` to ``W`` gives the
    same result. ``None`` takes :func:`ways_bucket_of` of host ``ways``
    (a numpy array or a sequence) and ``W`` for device ``ways``, which it
    does not read back.
    """
    interpret = resolve_interpret(interpret)
    w = int(ssd.tags.shape[2])
    host_ways = not isinstance(ways, jax.Array)
    if ways_bucket is None:
        ways_bucket = ways_bucket_of(ways, w) if host_ways else w
    if not 1 <= ways_bucket <= w:
        raise ValueError(f"ways_bucket {ways_bucket} outside [1, {w}]")
    if host_ways and int(np.max(np.asarray(ways), initial=0)) > ways_bucket:
        raise ValueError(f"ways_bucket {ways_bucket} is below the largest "
                         f"active way count {int(np.max(ways))}")
    args = (ssd, table, jnp.asarray(dist, jnp.int32),
            jnp.asarray(served, bool), jnp.asarray(waddr, jnp.int32),
            jnp.asarray(wlen, jnp.int32), jnp.asarray(ways, jnp.int32),
            jnp.asarray(t, jnp.int32))
    if mesh is not None:
        from repro.launch.mesh import require_vm_divisible
        require_vm_divisible(int(ssd.tags.shape[0]), mesh)
        return _maintenance_sharded(
            mesh, float(evict_frac), float(decay), int(clean_quota), ts, qc,
            interpret, ways_bucket)(*args)
    return _maintenance_impl(
        *args, evict_frac=float(evict_frac), decay=float(decay),
        clean_quota=int(clean_quota), ts=ts, qc=qc, interpret=interpret,
        ways_bucket=ways_bucket)


# ---------------------------------------------------------------------------
# the fused per-interval dispatch for the two-tier KV serving workload
# ---------------------------------------------------------------------------
#
# Serving sessions play the role of blocks (popularity is per session id)
# and tenants play the role of VMs; the "cache state" is the HBM page
# tables — per-tenant lists of resident sessions with their page counts —
# rather than a [V, S, W] tag array. One maintenance interval is one
# fused dispatch: Eq. 1 contributions over the mixed activation window,
# per-tenant demux, the [T, K] popularity-table merge, candidate scoring
# against the post-update table, and the cold-first eviction ranking that
# turns per-tenant over-quota page counts into per-session release
# counts. Only the final (order, take) queues and the updated table ever
# reach the host, which applies the releases to its page-table dicts.

@functools.partial(jax.jit,
                   static_argnames=("num_tenants", "decay", "clean_quota"))
def _serving_impl(table: pop.PopularityTable, dist, served, waddr, wtenant,
                  cand_sid, cand_pages, over, cache_size, dirty_age, *,
                  num_tenants: int, decay: float, clean_quota: int):
    t_axis, n = num_tenants, waddr.shape[0]

    # 1) Eq. 1 contributions over the MIXED window (distances were
    #    computed on the interleaved activation stream, exactly like the
    #    sequential oracle's single pod_distances call)
    contrib = pop.contributions(dist, served,
                                jnp.maximum(cache_size, 1))

    # 2) demux to [T, N] per-tenant rows, arrival order preserved: a
    #    stable sort by tenant groups each tenant's entries, and each
    #    entry's column is its rank within the group. Pad entries
    #    (tenant = -1) route to row T and are dropped.
    tn = jnp.where(wtenant >= 0, wtenant, t_axis).astype(jnp.int32)
    order = jnp.argsort(tn, stable=True)
    tn_sorted = tn[order]
    starts = jnp.searchsorted(tn_sorted,
                              jnp.arange(t_axis + 1, dtype=jnp.int32))
    col = jnp.arange(n, dtype=jnp.int32) - starts[tn_sorted]
    rows_addr = jnp.zeros((t_axis, n), jnp.int32).at[
        tn_sorted, col].set(waddr[order], mode="drop")
    rows_contrib = jnp.zeros((t_axis, n), jnp.float32).at[
        tn_sorted, col].set(contrib[order], mode="drop")
    n_valid = starts[1:] - starts[:-1]
    live = n_valid > 0

    # 3) [T, K] popularity merge (bit-identical to per-tenant
    #    PopularityTracker.update, incl. the live-row-only decay)
    table, drops = pop.table_update(table, rows_addr, rows_contrib,
                                    n_valid, live, decay)

    # 4) eviction ranking against the POST-update table: candidates are
    #    the resident sessions per tenant in page-table (slot-insertion)
    #    order; stable ascending argsort on their scores reproduces the
    #    oracle's `sorted(resident, key=score)` cold-first order, and the
    #    running page total turns the tenant's over-quota count into
    #    per-session release counts (partial last session allowed).
    valid = cand_sid >= 0
    scores = pop.table_scores(table, jnp.where(valid, cand_sid, 0))
    key = jnp.where(valid, scores, jnp.inf)
    eorder = jnp.argsort(key, axis=1, stable=True)
    pages_sorted = jnp.take_along_axis(
        jnp.where(valid, cand_pages, 0), eorder, axis=1)
    cum_before = jnp.cumsum(pages_sorted, axis=1) - pages_sorted
    take = jnp.clip(over[:, None] - cum_before, 0, pages_sorted)

    # 5) background cleaner: age-rank each tenant's dirty pages (ages are
    #    unique global append sequence numbers, so the order is total)
    #    and pick the oldest `clean_quota` to flush this interval
    if clean_quota > 0:
        dvalid = dirty_age >= 0
        dkey = jnp.where(dvalid, dirty_age, jnp.int32(2**31 - 1))
        ranks = jnp.argsort(jnp.argsort(dkey, axis=1, stable=True), axis=1)
        n_dirty = jnp.sum(dvalid, axis=1).astype(jnp.int32)
        dtake = jnp.minimum(jnp.int32(clean_quota), n_dirty)
        fpick = (dvalid & (ranks < dtake[:, None])).astype(jnp.int32)
    else:
        fpick = jnp.zeros(dirty_age.shape, jnp.int32)
    return (table, drops, eorder.astype(jnp.int32), take.astype(jnp.int32),
            fpick)


def serving_maintenance(table: pop.PopularityTable, dist, served, waddr,
                        wtenant, cand_sid, cand_pages, over, cache_size,
                        *, decay: float, dirty_age=None,
                        clean_quota: int = 0):
    """One fused serving-maintenance interval for all tenants.

    Args:
      table: the ``[T, K]`` session-popularity
        :class:`~repro.core.popularity.PopularityTable`.
      dist/served: the mixed activation window's POD(RO) channels
        (``[N]``, from ``reuse.pod_distances`` — the controller computes
        them once for the interleaved stream, as the oracle does).
      waddr: ``[N]`` session ids of the window, arrival order.
      wtenant: ``[N]`` tenant of each entry (recorded at request time;
        ``-1`` = padding).
      cand_sid/cand_pages: ``[T, Smax]`` eviction candidates — resident
        sessions per tenant in page-table insertion order with their
        resident-page counts (``-1``/0 padding). The active session must
        already be excluded by the caller.
      over: ``[T]`` pages over quota per tenant (<= 0 -> no eviction).
      cache_size: Eq. 1 normalizer (the controller passes the summed
        tenant quotas).
      decay: popularity aging factor.
      dirty_age: optional ``[T, Dmax]`` ages (unique append sequence
        numbers, ``-1`` = padding) of each tenant's dirty pages for the
        background cleaner; required when ``clean_quota > 0``.
      clean_quota: dirty pages flushed per tenant per interval (0
        disables the cleaner stage).

    Returns ``(table, pop_drops[T], order[T, Smax], take[T, Smax],
    fpick[T, Dmax])``: the updated device table, per-tenant
    merge-overflow drops, the eviction queue — ``order[t, i]`` indexes
    into ``cand_sid[t]`` coldest-first, ``take[t, i]`` is how many of
    that session's resident pages to release (0 past the quota point) —
    and the cleaner's 0/1 flush picks over ``dirty_age``'s columns
    (all-zero when the cleaner is off). Inputs are padded to
    power-of-two buckets so executables key on bucket sizes only.
    """
    n = int(np.shape(waddr)[0])
    nb = _next_pow2(max(n, 64))
    t_axis, smax = np.shape(cand_sid)
    sb = _next_pow2(max(smax, 8))
    if dirty_age is None:
        dirty_age = np.full((t_axis, 1), -1, np.int32)
    dmax = int(np.shape(dirty_age)[1])
    db = _next_pow2(max(dmax, 8))

    def padn(x, fill, dtype):
        x = jnp.asarray(x, dtype)
        return jnp.pad(x, (0, nb - n), constant_values=fill)

    cand_sid = jnp.pad(jnp.asarray(cand_sid, jnp.int32),
                       ((0, 0), (0, sb - smax)), constant_values=-1)
    cand_pages = jnp.pad(jnp.asarray(cand_pages, jnp.int32),
                         ((0, 0), (0, sb - smax)), constant_values=0)
    dirty_age = jnp.pad(jnp.asarray(dirty_age, jnp.int32),
                        ((0, 0), (0, db - dmax)), constant_values=-1)
    table, drops, eorder, take, fpick = _serving_impl(
        table, padn(dist, -1, jnp.int32), padn(served, False, bool),
        padn(waddr, 0, jnp.int32), padn(wtenant, -1, jnp.int32),
        cand_sid, cand_pages, jnp.asarray(over, jnp.int32),
        jnp.asarray(cache_size, jnp.float32), dirty_age,
        num_tenants=t_axis, decay=float(decay),
        clean_quota=int(clean_quota))
    return table, drops, eorder, take, fpick[:, :dmax]
