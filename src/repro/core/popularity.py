"""Popularity detection (paper §4.2.1, Eq. 1).

    popularity(B_i) = sum_t exp(-POD(i, t) / cacheSize)

Per-access contributions are computed in JAX (``contributions`` is what
``repro.kernels.popularity`` fuses on TPU). The running per-block scores
exist in two bit-identical forms, per the repo's batched-vs-sequential
convention:

  * :class:`PopularityTable` — ONE device-resident ``[V, K]`` jnp table
    for all VMs, whose :func:`table_update` / :func:`table_least_popular`
    / :func:`table_top_known` are batched jitted ops. This is what the
    batched controller's fused maintenance dispatch
    (``repro.kernels.maintenance.ops.maintenance_interval``) consumes —
    popularity refresh and queue building never leave the accelerator.
  * :class:`PopularityTracker` — the original host-side sorted-numpy
    table, kept as the sequential reference oracle (``batched=False``).

Both accumulate in **float32 with identical operation order** (decay
multiply, per-window per-block left-to-right contribution sums, then one
table+score add), so on CPU the device table reproduces the tracker bit
for bit — ties in the promotion/eviction orderings break identically.
Cold accesses (no finite POD) contribute 0 — a block becomes popular
only through re-references, which encodes both temporal locality (small
POD) and frequency (the sum over accesses).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# sentinel for an empty table slot; sorts after every real block address
# (block addresses are int32 and < 2**30 by the trace-store contract)
TABLE_EMPTY = np.int32(2**31 - 1)


@jax.jit
def contributions(dist: jax.Array, served: jax.Array, cache_size) -> jax.Array:
    """Eq. 1 per-access popularity contribution.

    ``cache_size`` may be a scalar or any shape broadcastable against
    ``dist`` (e.g. ``[V, 1]`` per-VM sizes against ``[V, N]`` windows).
    """
    cs = jnp.maximum(jnp.asarray(cache_size, jnp.float32), 1.0)
    d = dist.astype(jnp.float32)
    return jnp.where(served & (dist >= 0), jnp.exp(-d / cs), 0.0)


def block_scores(addr: np.ndarray, contrib: np.ndarray):
    """Aggregate per-access contributions into per-block scores.

    float32 accumulation in access order — the same partial-sum order the
    device table's segment reduction uses, so both stay bit-identical.
    """
    addr = np.asarray(addr)
    uniq, inv = np.unique(addr, return_inverse=True)
    scores = np.zeros(uniq.shape[0], np.float32)
    np.add.at(scores, inv, np.asarray(contrib, np.float32))
    return uniq, scores


class PopularityTracker:
    """Running per-block popularity with exponential aging across windows.

    8 bytes/page in the paper; here a sorted (address, score) numpy table
    — the same asymptotic overhead, kept off the datapath, with every
    operation (aging, merge, lookup, top/bottom-k) vectorized instead of
    per-key dict loops. Scores are float32, accumulated in the same
    order as :class:`PopularityTable`, so the host tracker is the
    bit-exact sequential oracle of the device table.
    """

    def __init__(self, decay: float = 0.5):
        self.decay = np.float32(decay)
        self._addr = np.empty(0, np.int64)   # sorted block addresses
        self._val = np.empty(0, np.float32)  # scores, aligned with _addr

    def __len__(self) -> int:
        return int(self._addr.size)

    def update(self, addr: np.ndarray, contrib: np.ndarray) -> None:
        self._val *= self.decay
        uniq, scores = block_scores(addr, contrib)
        uniq = uniq.astype(np.int64)
        found = np.zeros(uniq.size, bool)
        if self._addr.size and uniq.size:
            pos = np.searchsorted(self._addr, uniq)
            in_range = pos < self._addr.size
            found[in_range] = self._addr[pos[in_range]] == uniq[in_range]
            self._val[pos[found]] += scores[found]
        if (~found).any():
            merged_a = np.concatenate([self._addr, uniq[~found]])
            merged_v = np.concatenate([self._val, scores[~found]])
            order = np.argsort(merged_a, kind="stable")
            self._addr, self._val = merged_a[order], merged_v[order]
        # drop negligible entries to bound memory (paper: 0.15% overhead)
        if self._addr.size > 1_000_000:
            thr = np.percentile(self._val, 10)
            keep = self._val > thr
            self._addr, self._val = self._addr[keep], self._val[keep]

    def score(self, addr: int) -> float:
        return float(self.scores_for(np.asarray([addr]))[0])

    def scores_for(self, addrs: np.ndarray) -> np.ndarray:
        addrs = np.asarray(addrs, np.int64)
        out = np.zeros(addrs.shape, np.float32)
        if self._addr.size and addrs.size:
            pos = np.searchsorted(self._addr, addrs)
            in_range = pos < self._addr.size
            hit = in_range.copy()
            hit[in_range] = self._addr[pos[in_range]] == addrs[in_range]
            out[hit] = self._val[pos[hit]]
        return out

    def most_popular(self, candidates: np.ndarray, frac: float,
                     limit: int | None = None) -> np.ndarray:
        """Top-``frac`` of ``candidates`` by popularity (promotion queue).
        ``limit`` widens the queue up to the free space available — the
        paper drains the promotion queue "only when there is free space
        in SSD", so a mostly-empty cache admits more than the 5% floor."""
        candidates = np.asarray(candidates)
        if candidates.size == 0:
            return candidates
        s = self.scores_for(candidates)
        k = max(int(np.ceil(np.float32(frac) * np.float32(candidates.size))),
                1)
        if limit is not None:
            k = min(max(k, limit), candidates.size)
        order = np.argsort(-s, kind="stable")
        top = order[:k]
        return candidates[top[s[top] > 0]]

    def top_known(self, exclude: np.ndarray, limit: int) -> np.ndarray:
        """Highest-scored blocks the tracker knows about that are not in
        ``exclude`` — the paper's promotion queue draws from the full
        popularity table of disk-resident blocks, not only the current
        window's accesses."""
        if limit <= 0 or not self._addr.size:
            return np.empty(0, np.int64)
        cand = self._val > 0
        exclude = np.asarray(exclude)
        if exclude.size:
            cand &= ~np.isin(self._addr, exclude)
        addrs, vals = self._addr[cand], self._val[cand]
        # score desc, address desc on ties (the historical ordering)
        order = np.lexsort((-addrs, -vals))
        return addrs[order[:limit]]

    def least_popular(self, candidates: np.ndarray, frac: float) -> np.ndarray:
        """Bottom-``frac`` of ``candidates`` (eviction queue)."""
        candidates = np.asarray(candidates)
        if candidates.size == 0:
            return candidates
        s = self.scores_for(candidates)
        k = max(int(np.ceil(np.float32(frac) * np.float32(candidates.size))),
                1)
        order = np.argsort(s, kind="stable")
        return candidates[order[:k]]


# ---------------------------------------------------------------------------
# device-resident popularity: one [V, K] table, batched jitted ops
# ---------------------------------------------------------------------------

class PopularityTable(NamedTuple):
    """All VMs' popularity tables as one device-resident pytree.

    ``addr`` is int32 ``[V, K]``, sorted ascending per row with
    :data:`TABLE_EMPTY` marking free slots; ``val`` is float32 ``[V, K]``
    aligned with it. ``K`` (the per-VM capacity) is static; entries that
    a merge would push past slot ``K`` fall off the end (the analogue of
    the tracker's 1M-entry trim, kept branch-free so updates stay O(K)).
    Size ``K`` so each VM's distinct-block working set fits
    (:func:`table_len` reports per-row occupancy) and the table is a
    bit-exact device twin of :class:`PopularityTracker`.
    """

    addr: jax.Array  # int32  [V, K]
    val: jax.Array   # float32 [V, K]

    @property
    def capacity(self) -> int:
        return self.addr.shape[-1]


def table_init(num_vms: int, capacity: int) -> PopularityTable:
    return PopularityTable(
        addr=jnp.full((num_vms, capacity), TABLE_EMPTY, jnp.int32),
        val=jnp.zeros((num_vms, capacity), jnp.float32),
    )


@jax.jit
def table_len(table: PopularityTable) -> jax.Array:
    """Occupied entries per row (``[V]`` int32) — overflow telemetry."""
    return jnp.sum(table.addr != TABLE_EMPTY, axis=-1).astype(jnp.int32)


def _compact_runs(a: jax.Array, v: jax.Array):
    """Sum runs of equal sorted keys into their first slot.

    ``a`` must be sorted. Returns (addr, val) where each distinct key
    occupies one slot (its run head position in segment order) and the
    tail is ``TABLE_EMPTY``. Each run's values are summed strictly left
    to right, ``((v0 + v1) + v2) + ...``, which is what keeps the float32
    sums identical to the tracker's in-order ``np.add.at`` accumulation.
    A scatter-add cannot promise that: XLA leaves the order in which
    colliding updates apply to the backend. Pass ``r`` instead adds the
    ``r``-th element of every run to its head's sum, so the loop runs
    as many passes as the longest real run.
    """
    n = a.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    head = jnp.concatenate([jnp.ones(1, bool), a[1:] != a[:-1]])
    seg = jnp.cumsum(head) - 1
    caddr = jnp.full(n, TABLE_EMPTY, jnp.int32).at[seg].set(a)
    start = jax.lax.cummax(jnp.where(head, idx, 0))    # run head of each
    longest = jnp.max(jnp.where(a != TABLE_EMPTY, idx - start + 1, 0))
    vpad = jnp.concatenate([v, jnp.zeros(n, jnp.float32)])
    spad = jnp.concatenate([start, jnp.full(n, -1, jnp.int32)])

    def add_offset(r, acc):
        # element r places after each head, where it is still in the run
        same_run = head & (jax.lax.dynamic_slice(spad, (r,), (n,)) == idx)
        return acc + jnp.where(same_run,
                               jax.lax.dynamic_slice(vpad, (r,), (n,)), 0.0)

    acc = jax.lax.fori_loop(0, longest, add_offset,
                            jnp.zeros(n, jnp.float32))
    cval = jnp.zeros(n, jnp.float32).at[jnp.where(head, seg, n)].set(
        acc, mode="drop")
    cval = jnp.where(caddr == TABLE_EMPTY, 0.0, cval)
    return caddr, cval


def _row_update(addr, val, waddr, contrib, n_valid, live, decay):
    """One row of :func:`table_update` (vmapped over VMs).

    Sort-free in ``K``: only the ``[N]`` window is sorted; the merge
    into the (already sorted) table is a rank computation — two
    ``searchsorted`` passes and unique-destination scatters — so one
    update costs O(N log N + K) instead of O((K+N) log (K+N)).
    """
    k = addr.shape[0]
    n = waddr.shape[0]
    addr0, val0 = addr, val          # untouched row for non-live VMs
    valid = jnp.arange(n, dtype=jnp.int32) < n_valid
    wa = jnp.where(valid, waddr.astype(jnp.int32), TABLE_EMPTY)
    wc = jnp.where(valid, contrib.astype(jnp.float32), 0.0)

    # per-window per-block sums, partials in access order (= tracker's
    # block_scores): stable sort groups a block's accesses in time order
    order = jnp.argsort(wa, stable=True)
    uaddr, uval = _compact_runs(wa[order], wc[order])

    val = val * jnp.float32(decay)

    # existing blocks: one combining add per block, table + score — the
    # tracker's `_val[pos] += scores` (ordering and rounding identical)
    pos = jnp.searchsorted(addr, uaddr)
    pos_c = jnp.minimum(pos, k - 1)
    found = (pos < k) & (addr[pos_c] == uaddr)
    val = val.at[jnp.where(found, pos_c, k)].add(
        jnp.where(found, uval, 0.0), mode="drop")

    # new blocks: merge by rank. new_sorted = the not-found window
    # uniques, compacted (still ascending); each table slot shifts right
    # by the number of new addresses before it, each new address lands
    # at its insertion point plus its own rank.
    newm = ~found & (uaddr != TABLE_EMPTY)
    newm_i = newm.astype(jnp.int32)
    rank_new = jnp.cumsum(newm_i) - newm_i
    new_sorted = jnp.full(n, TABLE_EMPTY, jnp.int32).at[
        jnp.where(newm, rank_new, n)].set(uaddr, mode="drop")
    new_val = jnp.zeros(n, jnp.float32).at[
        jnp.where(newm, rank_new, n)].set(uval, mode="drop")
    shift = jnp.searchsorted(new_sorted, addr)          # [K]
    dest_table = jnp.arange(k, dtype=jnp.int32) + shift
    dest_new = jnp.searchsorted(addr, new_sorted) + jnp.arange(
        n, dtype=jnp.int32)
    # destinations are disjoint and strictly increasing per stream; any
    # entry pushed past K falls off the end — counted below as the row's
    # merge-overflow drops (surfaced via Stats.pop_drops)
    out_addr = jnp.full(k, TABLE_EMPTY, jnp.int32)
    out_val = jnp.zeros(k, jnp.float32)
    out_addr = out_addr.at[dest_table].set(addr, mode="drop")
    out_val = out_val.at[dest_table].set(val, mode="drop")
    keep_new = new_sorted != TABLE_EMPTY
    out_addr = out_addr.at[jnp.where(keep_new, dest_new, k)].set(
        new_sorted, mode="drop")
    out_val = out_val.at[jnp.where(keep_new, dest_new, k)].set(
        new_val, mode="drop")
    drops = (jnp.sum((addr != TABLE_EMPTY) & (dest_table >= k))
             + jnp.sum(keep_new & (dest_new >= k))).astype(jnp.int32)
    return (jnp.where(live, out_addr, addr0),
            jnp.where(live, out_val, val0),
            jnp.where(live, drops, 0))


@jax.jit
def table_update(table: PopularityTable, waddr, contrib, n_valid,
                 live, decay):
    """Merge one window of Eq. 1 contributions into every VM's table.

    ``waddr``/``contrib`` are ``[V, N]`` (entries at positions >=
    ``n_valid[v]`` are padding and ignored); ``live`` is a ``[V]`` bool —
    rows with ``live=False`` are untouched (no decay), exactly like the
    sequential path skipping a VM with an empty window. Bit-identical to
    calling :meth:`PopularityTracker.update` per live VM.

    Returns ``(table, drops)`` where ``drops`` is the ``[V]`` int32 count
    of entries pushed past the row's ``K`` slots by this merge (the
    previously-silent overflow, surfaced as ``Stats.pop_drops``).
    """
    addr, val, drops = jax.vmap(
        _row_update, in_axes=(0, 0, 0, 0, 0, 0, None)
    )(table.addr, table.val, waddr, contrib,
      jnp.asarray(n_valid, jnp.int32), jnp.asarray(live, bool),
      jnp.float32(decay))
    return PopularityTable(addr, val), drops


def _row_scores(addr_row, val_row, queries):
    """Table lookup for one row: score of each query address (0 if absent)."""
    k = addr_row.shape[0]
    pos = jnp.searchsorted(addr_row, queries)
    pos_c = jnp.minimum(pos, k - 1)
    hit = (pos < k) & (addr_row[pos_c] == queries)
    return jnp.where(hit, val_row[pos_c], 0.0)


@jax.jit
def table_scores(table: PopularityTable, addrs) -> jax.Array:
    """``[V, M]`` scores for ``[V, M]`` query addresses (0 when unknown)."""
    return jax.vmap(_row_scores)(table.addr, table.val,
                                 jnp.asarray(addrs, jnp.int32))


def _row_least_popular(addr_row, val_row, tags, ways, alloc, live, frac):
    """Eviction queue for one VM (vmapped): the bottom-``frac`` of the
    resident blocks, only when the partition is >= 90% full."""
    s, w = tags.shape
    flat = tags.reshape(s * w)
    validc = (jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32),
                               (s, w)).reshape(s * w) < ways) & (flat >= 0)
    n_res = jnp.sum(validc, dtype=jnp.int32)
    # near-full gate, exact in integers (both controller paths use this)
    do = live & (n_res > 0) & (n_res * 10 >= alloc * 9)
    scores = _row_scores(addr_row, val_row, flat)
    order = jnp.argsort(jnp.where(validc, scores, jnp.inf), stable=True)
    k = jnp.maximum(
        jnp.ceil(jnp.float32(frac) * n_res.astype(jnp.float32)), 1.0
    ).astype(jnp.int32)
    take = do & (jnp.arange(s * w, dtype=jnp.int32) < k)
    return jnp.where(take, flat[order], -1), jnp.where(do, k, 0)


@jax.jit
def table_least_popular(table: PopularityTable, tags, ways, alloc,
                        live, frac):
    """Batched eviction queues: ``( [V, S*W] queue, [V] queue length )``.

    ``tags`` is the stacked ``[V, S, W]`` SSD tag array; candidates are
    the resident blocks of the first ``ways[v]`` ways, in ``(set, way)``
    scan order — the order :func:`repro.core.simulator.resident_blocks`
    yields, so stable ties break exactly like the tracker path. Queue
    entries beyond the per-VM length are ``-1`` no-ops.
    """
    return jax.vmap(
        _row_least_popular, in_axes=(0, 0, 0, 0, 0, 0, None)
    )(table.addr, table.val, tags, jnp.asarray(ways, jnp.int32),
      jnp.asarray(alloc, jnp.int32), jnp.asarray(live, bool),
      jnp.float32(frac))


def _row_top_known(addr_row, val_row, tags, ways, limit, live, width):
    """Promotion queue for one VM (vmapped): the highest-scored known
    blocks without an SSD copy, best first, up to ``limit`` entries."""
    k = addr_row.shape[0]
    s, w = tags.shape
    # residency = membership in the sorted resident set (binary search;
    # exactly the tracker's `isin(residents)` exclusion)
    flat = tags.reshape(s * w)
    activef = (jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32),
                                (s, w)).reshape(s * w) < ways) & (flat >= 0)
    res_sorted = jnp.sort(jnp.where(activef, flat, TABLE_EMPTY))
    rpos = jnp.minimum(jnp.searchsorted(res_sorted, addr_row), s * w - 1)
    resident = res_sorted[rpos] == addr_row
    cand = (val_row > 0) & (addr_row != TABLE_EMPTY) & ~resident
    # lexsort((-addr, -val)) via top_k on the REVERSED row: top_k breaks
    # value ties toward the lower index, which after the reversal is the
    # higher address — the tracker's exact tie order. Only the top
    # `width` can ever be drained (limit <= S*W), so no full-K sort.
    key = jnp.where(cand, val_row, -jnp.inf)[::-1]
    topv, topi = jax.lax.top_k(key, min(width, k))
    qa = addr_row[::-1][topi]
    take = ((topv > -jnp.inf) & live
            & (jnp.arange(topv.shape[0], dtype=jnp.int32) < limit))
    queue = jnp.where(take, qa, -1)
    if width > k:
        queue = jnp.concatenate(
            [queue, jnp.full(width - k, -1, jnp.int32)])
    return queue, jnp.sum(take, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("width",))
def table_top_known(table: PopularityTable, tags, ways, limit, live,
                    width: int | None = None):
    """Batched promotion queues: ``( [V, width] queue, [V] length )``.

    Per VM: table entries with positive score and no copy in the first
    ``ways[v]`` ways of ``tags``, ordered by (score desc, address desc)
    — :meth:`PopularityTracker.top_known`'s exact ordering (residency
    via binary search over the sorted resident set, the tracker's
    ``isin(residents)`` exclusion) — truncated to ``limit[v]`` entries,
    ``-1``-padded. ``width`` (static, default the table capacity) bounds
    the queue; callers must keep ``limit <= width``.
    """
    width = table.capacity if width is None else width
    return jax.vmap(
        functools.partial(_row_top_known, width=width)
    )(table.addr, table.val, tags, jnp.asarray(ways, jnp.int32),
      jnp.asarray(limit, jnp.int32), jnp.asarray(live, bool))


@functools.partial(jax.jit, static_argnames=("width",))
def truncate_queue(queue: jax.Array, width: int) -> jax.Array:
    """Static truncation/padding of a ``[V, Q]`` queue to ``width``."""
    v, q = queue.shape
    if q >= width:
        return queue[:, :width]
    return jnp.concatenate(
        [queue, jnp.full((v, width - q), -1, queue.dtype)], axis=1)
