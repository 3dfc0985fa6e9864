"""Jitted wrappers: policy-filtered reuse distances via the Pallas kernel.

``reuse_distances`` mirrors ``repro.core.reuse.pod_distances`` but runs
the O(N^2) distinct-count through the TPU kernel (compiled on TPU; off
TPU ``interpret=None`` executes the same kernel body through the Pallas
interpreter for validation). The prev/next-touch
bookkeeping stays in regular jnp (sort-based, O(N log N)) — it is not the
hot spot. ``sizing_reduction`` additionally reduces the kernel-computed
distance channels into the one-level baselines' sizing metrics.

Metric definitions (ETICA §2.1 / §4.3.1; see ``repro.core.reuse`` for the
oracle engine these wrappers are tested against):

  * **TRD** — classic Mattson stack distance: distinct blocks between
    consecutive accesses to the same block, any re-access counting
    (Centaur's sizing metric).
  * **URD** — Useful Reuse Distance (ECI-Cache, arXiv:1805.00976): TRD
    restricted to read re-references (RAR + RAW).
  * **POD** — Policy Optimized reuse Distance (ETICA Eq. 2): URD further
    filtered by the cache write policy, so only requests the policy would
    serve occupy blocks or earn distances; ``demand = max POD + 1``.
  * **WSS** — working-set size (S-CAVE): distinct blocks touched, no
    distance filtering.

All of them reduce over the same decomposed distance channels: one
all-touch (read+write) distance pass serves URD/TRD/WSS, one read-only
touch pass serves POD(RO), and the served masks select the read, write,
or total re-reference populations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policies import Policy
from repro.core import reuse as core_reuse
from repro.kernels import resolve_interpret
from repro.runtime.telemetry import sync
from .kernel import count_between


def reuse_distances(addr, is_write, policy: Policy, *,
                    sizing_reads_only: bool = True,
                    interpret: bool | None = None,
                    ti: int = 256, tj: int = 512):
    """DistResult with the pairwise count computed by the Pallas kernel.

    ``sizing_reads_only=False`` widens the served set to write
    re-references too (the TRD convention), matching
    ``core.reuse._decompose``.
    """
    addr = jnp.asarray(addr, jnp.int32)
    is_write = jnp.asarray(is_write)
    is_read = ~is_write
    all_mask = jnp.ones_like(is_write)

    prev_any = core_reuse._prev_same(addr, all_mask)
    has_prev = prev_any >= 0
    if policy in (Policy.WB, Policy.WT):
        touch = all_mask
        served = is_read & has_prev
    elif policy is Policy.RO:
        touch = is_read
        prev_is_read = jnp.where(has_prev,
                                 ~is_write[jnp.maximum(prev_any, 0)], False)
        served = is_read & prev_is_read
    elif policy in (Policy.WBWO, Policy.WO):
        prev_write = core_reuse._prev_same(addr, is_write)
        served = is_read & (prev_write >= 0)
        touch = is_write | served
    else:  # pragma: no cover
        raise ValueError(policy)

    prev_touch = core_reuse._prev_same(addr, touch)
    next_touch = core_reuse._next_same(addr, touch)
    dist = count_between(prev_touch, touch.astype(jnp.int32), next_touch,
                         ti=ti, tj=tj, interpret=interpret)
    if not sizing_reads_only:
        served = served | (is_write & has_prev)
    dist = jnp.where(served, dist, core_reuse.COLD)
    return core_reuse.DistResult(dist=dist, served=served, touch=touch)


def sizing_reduction(addr, is_write, kind: str, grid, *, n_valid=None,
                     with_reads: bool = False,
                     interpret: bool | None = None, ti: int = 256,
                     tj: int = 512):
    """``(demand, hit_counts[G])`` for one trace, kernel-backed.

    The kernel analogue of the batched jnp sizing path: the O(N^2)
    distance channel comes from the Pallas ``count_between`` kernel and
    the metric reduction is the SAME shared ``core.reuse``
    ``sizing_from_dists`` code; used when the sizing path runs next to
    the datapath on TPU. ``kind`` is one of ``core.reuse.SIZING_KINDS``;
    ``n_valid`` (default: full length) masks a pad tail out of the WSS
    distinct-count when the caller hands in bucket-padded rows. With
    ``with_reads`` the per-VM read count (the dynamic write-policy
    choosers' input, ``core.reuse.read_count``) is appended, mirroring
    ``sizing_metrics_batch``.
    """
    if kind not in core_reuse.SIZING_KINDS:
        raise ValueError(
            f"kind must be one of {core_reuse.SIZING_KINDS}, got {kind!r}")
    addr = jnp.asarray(addr, jnp.int32)
    is_write = jnp.asarray(is_write)
    grid = jnp.asarray(grid, jnp.int32)
    if n_valid is None:
        n_valid = addr.shape[0]
    policy, reads_only = core_reuse.sizing_policy(kind)
    r = reuse_distances(addr, is_write, policy, sizing_reads_only=reads_only,
                        interpret=interpret, ti=ti, tj=tj)
    demand, hits = core_reuse.sizing_from_dists(addr, is_write, r, n_valid,
                                                grid, kind)
    if with_reads:
        return demand, hits, core_reuse.read_count(is_write, n_valid)
    return demand, hits


# ---------------------------------------------------------------------------
# batched kernel-backed sizing (the TPU route of SizingMetric.batch)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("kind", "interpret", "ti", "tj"))
def _sizing_reduce_vmapped(amat, wmat, nvec, grid, kind, interpret, ti, tj):
    policy, reads_only = core_reuse.sizing_policy(kind)

    def one(addr, is_write, n_valid):
        r = reuse_distances(addr, is_write, policy,
                            sizing_reads_only=reads_only,
                            interpret=interpret, ti=ti, tj=tj)
        demand, hits = core_reuse.sizing_from_dists(addr, is_write, r,
                                                    n_valid, grid, kind)
        return demand, hits, core_reuse.read_count(is_write, n_valid)

    return jax.vmap(one)(amat, wmat, nvec)


def _sizing_sharded(mesh, amat, wmat, nvec, grid, kind, interpret, ti, tj):
    # Manual per-device dispatch, not shard_map: see core.reuse — the CPU
    # GSPMD partitioner corrupts the decompose body with spurious
    # all-reduces. Each device runs the same single-device jitted
    # executable as the oracle path on its own row block (async dispatch,
    # host-side gather), so this stays bit-identical and collective-free.
    from repro.launch.mesh import device_row_blocks
    parts = []
    for dev, rows in device_row_blocks(amat.shape[0], mesh):
        a = jax.device_put(jnp.asarray(amat[rows]), dev)
        w = jax.device_put(jnp.asarray(wmat[rows]), dev)
        n = jax.device_put(jnp.asarray(nvec[rows]), dev)
        g = jax.device_put(jnp.asarray(grid), dev)
        parts.append(_sizing_reduce_vmapped(a, w, n, g, kind=kind,
                                            interpret=interpret,
                                            ti=ti, tj=tj))
    parts = sync("sizing", parts)
    return tuple(np.concatenate([p[i] for p in parts], axis=0)
                 for i in range(3))


def sizing_metrics_batch(addrs, writes, kind: str, grid, *,
                         interpret: bool | None = None, ti: int = 256,
                         tj: int = 512, mesh=None):
    """Kernel-backed ``core.reuse.sizing_metrics_batch``: same ragged
    contract and ``(demands, hit_counts, read_counts)`` returns, but the
    O(N^2) distance channel of every VM runs through the Pallas
    ``count_between`` kernel, vmapped across the stacked rows (the
    batching rule adds the VM axis to the kernel grid). This is what
    ``SizingMetric.batch`` dispatches to when the backend compiles
    Pallas (TPU) — bit-identical to the jnp path, which stays the CPU
    fallback and parity oracle (``tests/test_kernels.py``). ``mesh``
    splits the VM rows over a device mesh, shard-local like the jnp
    route (empty rows packed as pure-pad rows that reduce to zeros).
    """
    if kind not in core_reuse.SIZING_KINDS:
        raise ValueError(
            f"kind must be one of {core_reuse.SIZING_KINDS}, got {kind!r}")
    interpret = resolve_interpret(interpret)
    lens = [int(np.shape(a)[0]) for a in addrs]
    grid = np.asarray(grid, np.int32)
    demands = np.zeros(len(lens), np.int64)
    hits = np.zeros((len(lens), grid.size), np.int64)
    reads = np.zeros(len(lens), np.int64)
    live = [v for v, n in enumerate(lens) if n > 0]
    if not live:
        return demands, hits, reads
    if mesh is not None:
        from repro.launch.mesh import require_vm_divisible
        require_vm_divisible(len(lens), mesh)
        rows = list(range(len(lens)))
        amat, wmat = core_reuse._pad_rows(addrs, writes, rows, lens)
        d, h, r = _sizing_sharded(mesh, amat, wmat,
                                  np.array(lens, np.int32),
                                  np.asarray(grid, np.int32),
                                  kind, interpret, ti, tj)
        demands[:] = np.asarray(d, np.int64)
        hits[:] = np.asarray(h, np.int64)
        reads[:] = np.asarray(r, np.int64)
        empty = [v for v, n in enumerate(lens) if n == 0]
        demands[empty] = 0
        hits[empty] = 0
        reads[empty] = 0
        return demands, hits, reads
    amat, wmat = core_reuse._pad_rows(addrs, writes, live, lens)
    nvec = np.array([lens[v] for v in live], np.int32)
    d, h, r = sync("sizing", _sizing_reduce_vmapped(
        amat, wmat, nvec, jnp.asarray(grid), kind=kind,
        interpret=interpret, ti=ti, tj=tj))
    demands[live] = np.asarray(d, np.int64)
    hits[live] = np.asarray(h, np.int64)
    reads[live] = np.asarray(r, np.int64)
    return demands, hits, reads
