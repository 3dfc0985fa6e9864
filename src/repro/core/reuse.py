"""Reuse-distance engine: TRD, URD, and the paper's POD metric (§4.3.1).

Metric definitions (the sizing metrics of ETICA and its baselines):

  * **TRD** — Traditional (Mattson) Reuse Distance: the number of distinct
    blocks accessed between two consecutive accesses to the same block,
    counting *every* re-access, read or write (Centaur's sizing metric;
    ETICA §2.1 / Fig. 5a).
  * **URD** — Useful Reuse Distance (ECI-Cache, arXiv:1805.00976): TRD
    restricted to *read* re-references (RAR + RAW) — writes refresh blocks
    but their own distances do not count toward sizing (ETICA §2.1 /
    Fig. 5b).
  * **POD** — Policy Optimized reuse Distance (ETICA §4.3.1, Eq. 2): URD
    further filtered by the cache *write policy*, so only requests the
    policy would actually serve occupy blocks or earn a distance (key
    ideas 1–4, Figs. 8–9). ``demand = max POD + 1`` blocks (Eq. 2's
    allocation rule; 0 when nothing is served).
  * **WSS** — Working-Set Size (S-CAVE): the count of distinct blocks
    touched in the window, regardless of type or policy — the
    over-allocating estimator ETICA §2.1 criticizes.

All of them are instances of one computation over a *policy-filtered
sub-trace*:

  * ``touch[j]``  — access j inserts-or-hits the cache under the policy and
    therefore both occupies a block and refreshes its LRU position.
  * ``served[i]`` — access i would hit an infinite cache under the policy
    (these are the accesses whose distances matter for sizing; per the
    paper, only *read* accesses count toward sizing in every policy).
  * ``dist[i]``   — for served i: the number of DISTINCT addresses touched
    strictly between ``p(i)`` (the previous touch of ``addr[i]``) and i.
    Blocks invalidated in the window still count until their next touch
    (a conservative upper bound; the paper's worked examples are exact).

Then  ``metric = max(dist[served])``  and the allocation is ``metric + 1``
blocks (0 if nothing is served).

Policy filters (paper §4.3.1 key ideas 1-4):

  * TRD        : touch = all,           served = any re-access (R or W)
  * URD        : touch = all,           served = RAR + RAW reads
  * POD(WB/WT) : identical to URD.
  * POD(RO)    : touch = reads,         served = reads whose previous access
                 to the same address is a read (writes invalidate).
  * POD(WBWO)  : touch = writes + served reads,
                 served = reads with an earlier write to the same address
                 (RAW and, transitively, RARAW).

The pairwise distinct-count is O(N·N) with tiny constants — it is exactly
the windowed-counting computation that ``repro.kernels.reuse_distance``
tiles for TPU (this module is the oracle the kernel is tested against; the
kernel is used by ``ops.reuse_distances`` when running on TPU).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.telemetry import sync

from .policies import Policy

# Sentinel distance for cold / not-served accesses. A numpy scalar, not a
# device-committed jnp constant: closing over a committed array inside a
# ``shard_map`` body makes GSPMD treat it as sharded operand state and
# insert spurious all-reduces (observed on CPU host devices), corrupting
# every shard but the first. np scalars weave into traces as literals.
COLD = np.int32(-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistResult:
    """Per-access reuse-distance decomposition."""

    dist: jax.Array     # int32 [N]; -1 where not served
    served: jax.Array   # bool  [N]; access would hit an infinite cache
    touch: jax.Array    # bool  [N]; access occupies/refreshes a block

    def tree_flatten(self):
        return (self.dist, self.served, self.touch), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def max(self) -> jax.Array:
        return jnp.max(jnp.where(self.served, self.dist, COLD))


# ---------------------------------------------------------------------------
# prev/next same-address helpers (sort-based, O(N log N))
# ---------------------------------------------------------------------------

def argsort_stable(x: jax.Array) -> jax.Array:
    """Stable ascending argsort over the last axis via raw ``lax.sort``.

    Equivalent to ``jnp.argsort(x, stable=True)`` (a stable argsort is
    uniquely determined), but shard-safe: ``jnp.argsort`` is internally
    jitted, and under ``vmap`` inside a manual ``shard_map`` region GSPMD
    wraps the nested sort in spurious cross-shard all-reduces (observed on
    CPU host devices — results corrupt on every device but the first).
    Sorting ``(keys, iota)`` with ``num_keys=1`` stays a plain sort HLO.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    _, out = jax.lax.sort((x, iota), dimension=x.ndim - 1,
                          is_stable=True, num_keys=1)
    return out


def _prev_same(addr: jax.Array, mask: jax.Array) -> jax.Array:
    """prev[i] = largest j < i with addr[j] == addr[i] and mask[j]; else -1.

    Defined for every i (masked or not): the previous *masked* occurrence.
    """
    n = addr.shape[0]
    # Stable sort by address keeps original index order within each address
    # run; a scan down the sorted sequence then yields, for every position
    # (masked or not), the nearest *masked* predecessor in its run.
    order = argsort_stable(addr)
    s_addr = addr[order]
    s_mask = mask[order]
    s_idx = order.astype(jnp.int32)

    def body(carry, x):
        last_addr, last_masked = carry
        a, m, i = x
        same_run = a == last_addr
        prev_m = jnp.where(same_run, last_masked, -1)
        new_last = jnp.where(m, i, prev_m)
        return (a, new_last), prev_m

    (_, _), prev_sorted = jax.lax.scan(
        body, (jnp.int32(-(2**31) + 1), jnp.int32(-1)), (s_addr, s_mask, s_idx)
    )
    return jnp.zeros(n, dtype=jnp.int32).at[order].set(prev_sorted)


def _next_same(addr: jax.Array, mask: jax.Array) -> jax.Array:
    """next[i] = smallest j > i with addr[j]==addr[i] and mask[j]; else N."""
    n = addr.shape[0]
    rev_prev = _prev_same(addr[::-1], mask[::-1])
    # index transform: position i in reversed array is n-1-i originally
    nxt = jnp.where(rev_prev[::-1] >= 0, n - 1 - rev_prev[::-1], n)
    return nxt.astype(jnp.int32)


# ---------------------------------------------------------------------------
# distinct-count between previous touch and current access
# ---------------------------------------------------------------------------

def _count_between(prev_touch: jax.Array, touch: jax.Array,
                   next_touch: jax.Array, chunk: int = 256) -> jax.Array:
    """count[i] = #{ j : prev_touch[i] < j < i, touch[j], next_touch[j] >= i }.

    Each qualifying j is the LAST touch of its address inside the window,
    so the count equals the number of distinct addresses touched in the
    window. O(N^2) pairwise, evaluated in row chunks.
    """
    n = touch.shape[0]
    j = jnp.arange(n, dtype=jnp.int32)
    tj = touch
    ntj = next_touch

    def rows(i_block):
        i = i_block  # [chunk]
        p = prev_touch[i]  # [chunk]
        m = (
            (j[None, :] > p[:, None])
            & (j[None, :] < i[:, None])
            & tj[None, :]
            & (ntj[None, :] >= i[:, None])
        )
        return jnp.sum(m, axis=1, dtype=jnp.int32)

    pad = (-n) % chunk
    i_all = jnp.arange(n + pad, dtype=jnp.int32).reshape(-1, chunk)
    i_all = jnp.minimum(i_all, n - 1)
    counts = jax.lax.map(rows, i_all).reshape(-1)[:n]
    return counts


# ---------------------------------------------------------------------------
# per-policy decomposition
# ---------------------------------------------------------------------------

def _decompose(addr: jax.Array, is_write: jax.Array, policy: Policy,
               *, sizing_reads_only: bool = True,
               chunk: int = 256) -> DistResult:
    addr = addr.astype(jnp.int32)
    is_read = ~is_write
    all_mask = jnp.ones_like(is_write)

    prev_any = _prev_same(addr, all_mask)
    has_prev = prev_any >= 0

    if policy in (Policy.WB, Policy.WT):
        touch = all_mask
        served = is_read & has_prev
    elif policy is Policy.RO:
        touch = is_read
        prev_is_read = jnp.where(has_prev, ~is_write[jnp.maximum(prev_any, 0)], False)
        served = is_read & prev_is_read
    elif policy in (Policy.WBWO, Policy.WO):
        prev_write = _prev_same(addr, is_write)
        served = is_read & (prev_write >= 0)
        touch = is_write | served
    else:  # pragma: no cover
        raise ValueError(policy)

    prev_touch = _prev_same(addr, touch)
    next_touch = _next_same(addr, touch)
    dist = _count_between(prev_touch, touch, next_touch, chunk=chunk)
    if not sizing_reads_only:
        served = served | (is_write & has_prev)
    dist = jnp.where(served, dist, COLD)
    return DistResult(dist=dist, served=served, touch=touch)


# Public API ----------------------------------------------------------------
#
# Inputs are padded up to power-of-two buckets with trailing writes to
# fresh, never-reused addresses. Appended accesses sit after every real
# access, so no real (p, i) window contains them; they themselves are
# cold writes (never "served"); and as WBWO touches they only ever occupy
# positions after all real windows. Hence bucketing is exact while keeping
# the number of distinct jit shapes logarithmic.

_PAD_BASE = np.int32(2**30)


def _bucket(n: int, min_size: int = 256) -> int:
    return max(min_size, 1 << (n - 1).bit_length())


def _pad_trace(addr, is_write):
    addr = np.asarray(addr, np.int32)
    is_write = np.asarray(is_write, bool)
    n = addr.shape[0]
    b = _bucket(n)
    if b == n:
        return addr, is_write, n
    k = b - n
    pad_addr = _PAD_BASE + np.arange(k, dtype=np.int32)
    return (np.concatenate([addr, pad_addr]),
            np.concatenate([is_write, np.ones(k, bool)]), n)


_decompose_jit = jax.jit(
    _decompose, static_argnames=("policy", "sizing_reads_only", "chunk"))


def _slice(r: DistResult, n: int) -> DistResult:
    return DistResult(dist=r.dist[:n], served=r.served[:n], touch=r.touch[:n])


def pod_distances(addr, is_write, policy: Policy, chunk: int = 256) -> DistResult:
    """POD decomposition for a policy (paper §4.3.1)."""
    a, w, n = _pad_trace(addr, is_write)
    return _slice(_decompose_jit(a, w, policy, chunk=chunk), n)


def _pad_rows(addrs, writes, live: list[int], lens: list[int]):
    """Stack the live rows of ragged per-VM request lists into rectangular
    ``[L, b]`` arrays, padded to a common power-of-two bucket with the same
    never-reused trailing writes as :func:`_pad_trace` (exact, see above)."""
    b = _bucket(max(lens[v] for v in live))
    amat = np.empty((len(live), b), np.int32)
    wmat = np.empty((len(live), b), bool)
    for i, v in enumerate(live):
        pad_addr = _PAD_BASE + np.arange(b - lens[v], dtype=np.int32)
        amat[i] = np.concatenate([np.asarray(addrs[v], np.int32), pad_addr])
        wmat[i] = np.concatenate(
            [np.asarray(writes[v], bool), np.ones(b - lens[v], bool)])
    return amat, wmat


@functools.partial(jax.jit,
                   static_argnames=("policy", "sizing_reads_only", "chunk"))
def _decompose_vmapped(amat, wmat, policy, sizing_reads_only, chunk):
    return jax.vmap(
        lambda a, w: _decompose(a, w, policy,
                                sizing_reads_only=sizing_reads_only,
                                chunk=chunk))(amat, wmat)


# --- sharded variants (VM axis split across a 1-d device mesh) -------------
#
# These routes deliberately do NOT use ``shard_map``: on CPU host devices
# the GSPMD partitioner wraps the decompose body (``_count_between`` fed by
# a data-dependent touch mask, as the RO/WBWO policies and the
# reuse_intensity metric produce) in spurious cross-shard all-reduces that
# corrupt every device but the first — and which outputs trigger it shifts
# unpredictably with the returned pytree. Instead each device runs the
# *same* single-device jitted executable as the oracle path on its own
# ``[V/d, b]`` row block (dispatched asynchronously, gathered on the
# host), so results are bit-identical and zero collectives exist by
# construction. The clean shard_map routes (datapath, maintenance,
# resize, stats aggregation) live in ``core.simulator`` /
# ``kernels.maintenance``.

def _decompose_sharded(mesh, amat, wmat, policy, sizing_reads_only, chunk,
                       telemetry=None):
    """Each device decomposes its row block; the blocks are gathered to
    host numpy, and ``telemetry.shard_host_bytes`` (where a recorder is
    given) counts the bytes gathered."""
    from ..launch.mesh import device_row_blocks
    parts = []
    for dev, rows in device_row_blocks(amat.shape[0], mesh):
        a = jax.device_put(jnp.asarray(amat[rows]), dev)
        w = jax.device_put(jnp.asarray(wmat[rows]), dev)
        parts.append(_decompose_vmapped(a, w, policy=policy,
                                        sizing_reads_only=sizing_reads_only,
                                        chunk=chunk))
    parts = sync("decompose", parts)
    out = DistResult(*[
        np.concatenate([getattr(p, f) for p in parts], axis=0)
        for f in ("dist", "served", "touch")])
    if telemetry is not None:
        telemetry.shard_host_bytes += sum(x.nbytes for x in
                                          (out.dist, out.served, out.touch))
    return out


def _sizing_sharded(mesh, amat, wmat, nvec, grid, kind, chunk,
                    telemetry=None):
    """Each device reduces its row block; the ``(demand, hits, reads)``
    blocks are gathered to host numpy, and ``telemetry.shard_host_bytes``
    (where a recorder is given) counts the bytes gathered."""
    from ..launch.mesh import device_row_blocks
    parts = []
    for dev, rows in device_row_blocks(amat.shape[0], mesh):
        a = jax.device_put(jnp.asarray(amat[rows]), dev)
        w = jax.device_put(jnp.asarray(wmat[rows]), dev)
        n = jax.device_put(jnp.asarray(nvec[rows]), dev)
        g = jax.device_put(jnp.asarray(grid), dev)
        parts.append(_sizing_reduce_vmapped(a, w, n, g,
                                            kind=kind, chunk=chunk))
    parts = sync("sizing", parts)
    out = tuple(np.concatenate([p[i] for p in parts], axis=0)
                for i in range(3))
    if telemetry is not None:
        telemetry.shard_host_bytes += sum(x.nbytes for x in out)
    return out


def _require_divisible(num_rows: int, mesh) -> None:
    from ..launch.mesh import require_vm_divisible
    require_vm_divisible(num_rows, mesh)


def _distances_batch(addrs, writes, policy: Policy, sizing_reads_only: bool,
                     chunk: int) -> list[DistResult | None]:
    """Decompose many traces in ONE vmapped dispatch.

    ``addrs``/``writes`` are ragged per-VM request lists; rows are padded
    to a common power-of-two bucket with the same never-reused trailing
    writes as :func:`_pad_trace` (exact, see above), so per-VM results are
    bit-identical to calling the unbatched functions per VM. Empty rows
    come back as ``None``.
    """
    lens = [int(np.shape(a)[0]) for a in addrs]
    live = [v for v, n in enumerate(lens) if n > 0]
    out: list[DistResult | None] = [None] * len(lens)
    if not live:
        return out
    amat, wmat = _pad_rows(addrs, writes, live, lens)
    r = sync("decompose", _decompose_vmapped(
        amat, wmat, policy=policy, sizing_reads_only=sizing_reads_only,
        chunk=chunk))
    for i, v in enumerate(live):
        out[v] = DistResult(dist=r.dist[i, : lens[v]],
                            served=r.served[i, : lens[v]],
                            touch=r.touch[i, : lens[v]])
    return out


def pod_distances_batch(addrs, writes, policy: Policy,
                        chunk: int = 256) -> list[DistResult | None]:
    """Per-VM :func:`pod_distances` in one vmapped dispatch (ragged input,
    bit-identical per-VM results; empty traces -> ``None``)."""
    return _distances_batch(addrs, writes, policy, True, chunk)


def trd_distances_batch(addrs, writes,
                        chunk: int = 256) -> list[DistResult | None]:
    """Per-VM :func:`trd_distances` in one vmapped dispatch."""
    return _distances_batch(addrs, writes, Policy.WB, False, chunk)


def urd_distances(addr, is_write, chunk: int = 256) -> DistResult:
    """URD (ECI-Cache): read re-references over WB content semantics."""
    a, w, n = _pad_trace(addr, is_write)
    return _slice(_decompose_jit(a, w, Policy.WB, chunk=chunk), n)


def trd_distances(addr, is_write, chunk: int = 256) -> DistResult:
    """Traditional reuse distance: every re-access counts (Centaur)."""
    a, w, n = _pad_trace(addr, is_write)
    return _slice(
        _decompose_jit(a, w, Policy.WB, sizing_reads_only=False, chunk=chunk), n)


def pod(trace, policy: Policy) -> int:
    """max POD of a trace under ``policy`` (−1 if nothing is served)."""
    r = pod_distances(jnp.asarray(trace.addr), jnp.asarray(trace.is_write), policy)
    return int(r.max)


def urd(trace) -> int:
    r = urd_distances(jnp.asarray(trace.addr), jnp.asarray(trace.is_write))
    return int(r.max)


def trd(trace) -> int:
    r = trd_distances(jnp.asarray(trace.addr), jnp.asarray(trace.is_write))
    return int(r.max)


def demand_blocks(metric_value: int) -> int:
    """Cache size (blocks) implied by a max reuse distance (paper: POD+1)."""
    return int(metric_value) + 1 if metric_value >= 0 else 0


# ---------------------------------------------------------------------------
# Miss-ratio curves (analytic path)
# ---------------------------------------------------------------------------

def hit_counts_at_sizes(dist, served, sizes) -> np.ndarray:
    """hits[s] = #served accesses with dist < sizes[s] (LRU inclusion).

    Host-side analytics (variable shapes); the heavy part — computing the
    distances — is the jitted/kernelized piece upstream.
    """
    d = np.where(np.asarray(served), np.asarray(dist), np.int32(2**30))
    return np.sum(d[None, :] < np.asarray(sizes)[:, None], axis=1, dtype=np.int64)


def hit_counts_at_sizes_weighted(dist, served, sizes, weights) -> np.ndarray:
    """:func:`hit_counts_at_sizes` with per-request sizing weights.

    Used by the classified controllers: each request contributes its IO
    class's ``weight`` to the hit curve instead of 1. With all-one
    weights the float64 sums are exact integer counts, equal to the
    unweighted path bit for bit.
    """
    d = np.where(np.asarray(served), np.asarray(dist), np.int32(2**30))
    w = np.asarray(weights, np.float64)
    return ((d[None, :] < np.asarray(sizes)[:, None]) * w[None, :]).sum(axis=1)


def mrc(trace, policy: Policy, sizes: np.ndarray) -> np.ndarray:
    """Hit-ratio curve H(c) for the trace under ``policy`` at ``sizes``.

    By LRU stack inclusion, a served access hits iff its policy-filtered
    stack distance is < allocated blocks. Ratio is over *all* requests, so
    curves are comparable across policies.
    """
    r = pod_distances(jnp.asarray(trace.addr), jnp.asarray(trace.is_write), policy)
    hits = hit_counts_at_sizes(r.dist, r.served, jnp.asarray(sizes, jnp.int32))
    return np.asarray(hits, dtype=np.float64) / max(len(trace), 1)


# ---------------------------------------------------------------------------
# Batched sizing reductions (ETICA's POD and the baselines' metrics, §2.1)
# ---------------------------------------------------------------------------
#
# ETICA sizes its two levels by POD (§4.3.1); the one-level baselines
# (ECI-Cache, Centaur, S-CAVE, vCacheShare) size their per-VM partitions
# from four more metrics. All are reductions over the same policy-filtered
# distance decompositions computed above:
#
#   kind               demand (blocks)              hit-curve channel
#   ----               ---------------              -----------------
#   "urd"              max URD + 1                  URD (WB dist, read re-refs)
#   "trd"              max TRD + 1                  TRD (WB dist, all re-refs)
#   "wss"              distinct blocks touched      TRD
#   "reuse_intensity"  re-referenced read blocks    POD(RO)
#   "pod_ro"           max POD(RO) + 1              POD(RO)
#   "pod_wbwo"         max POD(WBWO) + 1            POD(WBWO)
#
# URD and TRD share one decomposition (same all-touch distances, different
# served masks), so each kind costs exactly one O(N^2) distance pass.
# ``sizing_metrics_batch`` evaluates one metric for many VM sub-traces in
# ONE vmapped jitted dispatch, so controllers never loop over VMs.

SIZING_KINDS = ("urd", "trd", "wss", "reuse_intensity", "pod_ro", "pod_wbwo")

# the sizing kind of ETICA's POD under each level's write policy
POD_KINDS = {Policy.RO: "pod_ro", Policy.WBWO: "pod_wbwo"}

_SERVED_BIG = np.int32(2**30)  # not-served sentinel (np: shard-safe, see COLD)


def read_count(is_write, n_valid=None) -> jax.Array:
    """#reads among the first ``n_valid`` requests (int32).

    The per-VM read-ratio the ECI-style dynamic write-policy choosers
    consume, computed inside the same batched sizing dispatch instead of
    a host loop. ``n_valid=None`` counts the whole row — exact for
    bucket-padded rows too, whose pads are all writes."""
    is_read = ~is_write
    if n_valid is not None:
        is_read = is_read & (jnp.arange(is_write.shape[0],
                                        dtype=jnp.int32) < n_valid)
    return jnp.sum(is_read, dtype=jnp.int32)


def sizing_policy(kind: str) -> tuple[Policy, bool]:
    """The (policy, sizing_reads_only) decomposition a sizing kind rides."""
    if kind in ("reuse_intensity", "pod_ro"):
        return Policy.RO, True
    if kind == "pod_wbwo":
        return Policy.WBWO, True
    return Policy.WB, False


def sizing_from_dists(addr, is_write, r: DistResult, n_valid, grid,
                      kind: str):
    """``(demand, hit_counts[G])`` from a decomposed distance channel.

    The shared post-distance reduction behind both the pure-jnp batched
    path (:func:`sizing_metrics_batch`) and the Pallas-kernel path
    (``repro.kernels.reuse_distance.ops.sizing_reduction``): served-mask
    selection, hit histogram, and the demand scalar. ``r`` must be the
    :func:`sizing_policy` decomposition for ``kind``. ``n_valid`` masks
    any pad tail out of the WSS distinct-count (the other reductions are
    pad-invariant by construction: pads are cold writes to fresh
    addresses).
    """
    is_read = ~is_write
    served = (r.served & is_read) if kind == "urd" else r.served
    d = jnp.where(served, r.dist, _SERVED_BIG)
    hits = jnp.sum(d[None, :] < grid[:, None], axis=1, dtype=jnp.int32)
    if kind == "wss":
        valid = jnp.arange(addr.shape[0], dtype=jnp.int32) < n_valid
        first = _prev_same(addr, jnp.ones_like(is_write)) < 0
        demand = jnp.sum(first & valid, dtype=jnp.int32)
    elif kind == "reuse_intensity":
        prev_read = _prev_same(addr, is_read)
        next_read = _next_same(addr, is_read)
        demand = jnp.sum(is_read & (prev_read < 0)
                         & (next_read < addr.shape[0]), dtype=jnp.int32)
    else:
        demand = jnp.maximum(jnp.max(jnp.where(served, r.dist, COLD)) + 1, 0)
    return demand, hits


def _sizing_one(addr, is_write, n_valid, grid, kind: str, chunk: int):
    """``(demand, hit_counts[G], n_reads)`` for one (possibly padded)
    trace: one O(N^2) :func:`_decompose` pass + the shared reduction, with
    the policy choosers' read count riding the same dispatch."""
    policy, reads_only = sizing_policy(kind)
    r = _decompose(addr, is_write, policy,
                   sizing_reads_only=reads_only, chunk=chunk)
    demand, hits = sizing_from_dists(addr, is_write, r, n_valid, grid, kind)
    return demand, hits, read_count(is_write, n_valid)


@functools.partial(jax.jit, static_argnames=("kind", "chunk"))
def _sizing_reduce_vmapped(amat, wmat, nvec, grid, kind, chunk):
    return jax.vmap(
        lambda a, w, n: _sizing_one(a, w, n, grid, kind, chunk)
    )(amat, wmat, nvec)


def sizing_metrics_batch(addrs, writes, kind: str, grid,
                         chunk: int = 256, mesh=None, telemetry=None
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Evaluate one sizing metric for many VM sub-traces in ONE dispatch.

    Args:
      addrs/writes: ragged per-VM request arrays (empty rows allowed).
      kind: one of :data:`SIZING_KINDS`.
      grid: ascending candidate cache sizes (blocks) for the hit curve.

    Returns:
      ``(demands, hit_counts, read_counts)``: int64 ``[V]`` demanded
      blocks, int64 ``[V, G]`` served-access hit counts at each grid size,
      and int64 ``[V]`` per-VM read counts (for the dynamic write-policy
      choosers) — zero rows for empty traces. Per-VM values are
      bit-identical to evaluating the sequential per-VM closures in
      :mod:`repro.core.baselines` (for the POD kinds: :func:`pod_distances`,
      :func:`demand_blocks` and :func:`hit_counts_at_sizes` per VM) — the
      padding is the same never-reused trailing writes as
      :func:`_pad_trace`, which no real distance window can see, and the
      WSS distinct-count and read count mask the pad tail explicitly.

    With ``mesh`` the VM rows (all of them, empty ones packed as pure-pad
    rows that reduce to zeros) are split over the mesh's VM axis; each
    device runs its own shard-local reduction and no collectives exist.
    ``telemetry`` (a :class:`~repro.runtime.telemetry.TelemetryRecorder`)
    then counts the bytes gathered from the shards.
    """
    if kind not in SIZING_KINDS:
        raise ValueError(f"kind must be one of {SIZING_KINDS}, got {kind!r}")
    lens = [int(np.shape(a)[0]) for a in addrs]
    grid = np.asarray(grid, np.int32)
    demands = np.zeros(len(lens), np.int64)
    hits = np.zeros((len(lens), grid.size), np.int64)
    reads = np.zeros(len(lens), np.int64)
    live = [v for v, n in enumerate(lens) if n > 0]
    if not live:
        return demands, hits, reads
    if mesh is not None:
        _require_divisible(len(lens), mesh)
        rows = list(range(len(lens)))
        amat, wmat = _pad_rows(addrs, writes, rows, lens)
        nvec = np.array(lens, np.int32)
        d, h, r = _sizing_sharded(mesh, amat, wmat, nvec, grid, kind, chunk,
                                  telemetry)
        demands[:] = np.asarray(d, np.int64)
        hits[:] = np.asarray(h, np.int64)
        reads[:] = np.asarray(r, np.int64)
        # pure-pad rows reduce to zeros row-wise; re-zero anyway so empty
        # traces match the unsharded contract exactly by construction
        empty = [v for v, n in enumerate(lens) if n == 0]
        demands[empty] = 0
        hits[empty] = 0
        reads[empty] = 0
        return demands, hits, reads
    amat, wmat = _pad_rows(addrs, writes, live, lens)
    nvec = np.array([lens[v] for v in live], np.int32)
    d, h, r = sync("sizing", _sizing_reduce_vmapped(
        amat, wmat, nvec, jnp.asarray(grid), kind=kind, chunk=chunk))
    demands[live] = np.asarray(d, np.int64)
    hits[live] = np.asarray(h, np.int64)
    reads[live] = np.asarray(r, np.int64)
    return demands, hits, reads
