"""Trace-driven cache simulators (exact datapath, `jax.lax.scan`).

Two entry points:

  * :func:`simulate_single_level` — one cache device in front of the disk
    under any :class:`~repro.core.policies.Policy` (used for the paper's
    motivational Fig. 3 study and the one-level baselines ECI-Cache,
    Centaur, S-CAVE, vCacheShare).
  * :func:`simulate_two_level` — ETICA's DRAM(RO) + SSD(WBWO) hierarchy
    (paper §4.1/§4.2), in ``"full"`` (pull-mode SSD: misses never update
    the SSD on the datapath) or ``"npe"`` (no promotion/eviction: write
    misses allocate in the SSD datapath) modes.

Caches are set-associative (paper: 512-block sets; geometry configurable).
The *allocated* capacity of a VM's cache is expressed as active ways —
resizing between intervals activates/deactivates ways (deactivation
flushes dirty blocks, counted as disk writes). All datapath state is a
pytree scanned over the request stream, so a full interval simulates as
one fused XLA loop.

Batched multi-VM contract
-------------------------

ETICA partitions one physical cache across V VMs; the batched entry
points run one interval for *all* VMs as a single jitted dispatch instead
of V sequential ones:

  * :func:`simulate_single_level_batch` — ``addr``/``is_write`` are
    ``[V, N]``, the :class:`CacheState` pytree carries a leading VM axis
    (``tags``/``lru``/``dirty`` are ``[V, S, W]``), ``ways_active`` and
    ``t0`` are ``[V]``, and the write policy is a :class:`PolicyFlags` of
    ``[V]`` booleans (build with :func:`policy_flags`) — so heterogeneous
    per-VM policies (ECI-Cache's dynamic RO/WB) and per-VM allocations
    batch in one executable.
  * :func:`simulate_two_level_batch` — same layout for both levels;
    ``mode`` stays static (it is global to the hierarchy).

Both return the same (state(s), :class:`Stats`, ``t_end``) tuple with a
leading ``[V]`` axis on every leaf, **bit-identical** per VM to running
the unbatched functions per VM (the batched path vmaps the very same
step function; integer counters and float32 latency accumulate in the
same order). Padding requests with ``addr == -1`` makes them exact
no-ops, which is how ragged per-VM windows batch to a rectangle. Use
:func:`make_cache_batch` / :func:`stack_states` / :func:`unstack_states`
to build and take apart the stacked pytrees.

The between-interval maintenance helpers (:func:`resize`,
:func:`evict_blocks`, :func:`promote_blocks`) are vectorized ``jnp`` ops
with ``(state, count)`` contracts, jit-able and vmappable
(:func:`resize_batch` maps :func:`resize` over the VM axis); the original
numpy implementations are kept as ``*_ref`` reference oracles for the
tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .policies import Policy, T_DRAM, T_HDD, T_HDD_WRITE, T_SSD


class CacheState(NamedTuple):
    tags: jax.Array   # int32 [S, W], -1 = invalid
    lru: jax.Array    # int32 [S, W], last-touch time (-1 = never)
    dirty: jax.Array  # bool  [S, W]


class Stats(NamedTuple):
    reads: jax.Array
    writes: jax.Array
    read_hits_l1: jax.Array    # DRAM hits (two-level only)
    read_hits_l2: jax.Array    # SSD / single-level cache read hits
    write_hits_l2: jax.Array
    cache_writes_l2: jax.Array  # endurance metric: writes committed to SSD
    disk_reads: jax.Array
    disk_writes: jax.Array
    latency_sum: jax.Array     # seconds (float32)
    # numpy scalar defaults: they carry a .dtype for the padding mask
    # multiply without forcing JAX backend init at import time
    bypassed: jax.Array = np.int32(0)    # classifier bypass channel
    pop_drops: jax.Array = np.int32(0)   # popularity-table merge overflow
    flushes: jax.Array = np.int32(0)     # background-cleaner dirty flushes
    dirty_resident: jax.Array = np.int32(0)  # gauge: dirty blocks resident
                                             # after the last maintenance

    @staticmethod
    def zero() -> "Stats":
        z = jnp.int32(0)
        return Stats(z, z, z, z, z, z, z, z, jnp.float32(0.0), z, z, z, z)

    def merge(self, o: "Stats") -> "Stats":
        return Stats(*[a + b for a, b in zip(self, o)])

    # -- derived metrics -------------------------------------------------
    @property
    def total(self):
        return self.reads + self.writes

    @property
    def hits(self):
        return self.read_hits_l1 + self.read_hits_l2 + self.write_hits_l2

    def hit_ratio(self) -> float:
        return float(self.hits) / max(int(self.total), 1)

    def mean_latency(self) -> float:
        return float(self.latency_sum) / max(int(self.total), 1)


class PolicyFlags(NamedTuple):
    """Traced write-policy predicates (see :mod:`repro.core.policies`).

    As scalars these jit-fold to the static-policy code; as ``[V]`` arrays
    they let one batched dispatch serve VMs with different policies.
    """
    allocates_reads: jax.Array   # bool
    write_invalidates: jax.Array
    holds_dirty: jax.Array
    write_through: jax.Array


def policy_flags(policy: Policy | Sequence[Policy]) -> PolicyFlags:
    """Build :class:`PolicyFlags` from one Policy (scalars) or a per-VM
    sequence (``[V]`` bool arrays)."""
    if isinstance(policy, Policy):
        return PolicyFlags(
            jnp.asarray(policy.allocates_reads),
            jnp.asarray(policy.write_invalidates),
            jnp.asarray(policy.holds_dirty),
            jnp.asarray(policy.write_through),
        )
    ps = list(policy)
    return PolicyFlags(
        jnp.asarray([p.allocates_reads for p in ps]),
        jnp.asarray([p.write_invalidates for p in ps]),
        jnp.asarray([p.holds_dirty for p in ps]),
        jnp.asarray([p.write_through for p in ps]),
    )


def make_cache(num_sets: int, ways: int) -> CacheState:
    return CacheState(
        tags=jnp.full((num_sets, ways), -1, jnp.int32),
        lru=jnp.full((num_sets, ways), -1, jnp.int32),
        dirty=jnp.zeros((num_sets, ways), bool),
    )


def make_cache_batch(num_vms: int, num_sets: int, ways: int) -> CacheState:
    """Stacked per-VM caches: every leaf carries a leading ``[V]`` axis."""
    return CacheState(
        tags=jnp.full((num_vms, num_sets, ways), -1, jnp.int32),
        lru=jnp.full((num_vms, num_sets, ways), -1, jnp.int32),
        dirty=jnp.zeros((num_vms, num_sets, ways), bool),
    )


def stack_states(states: Sequence[CacheState]) -> CacheState:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_states(state: CacheState) -> list[CacheState]:
    v = state.tags.shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], state) for i in range(v)]


def capacity_to_ways(capacity_blocks: int | jax.Array, num_sets: int,
                     max_ways: int) -> jax.Array:
    """Blocks -> active ways (ceil), clipped to the geometry."""
    w = (jnp.asarray(capacity_blocks) + num_sets - 1) // num_sets
    return jnp.clip(w, 0, max_ways).astype(jnp.int32)


# ---------------------------------------------------------------------------
# datapath primitives (single request, single set)
# ---------------------------------------------------------------------------

def _lookup(state: CacheState, s, addr, ways_active):
    active = jnp.arange(state.tags.shape[1]) < ways_active
    eq = (state.tags[s] == addr) & active
    hit = jnp.any(eq)
    way = jnp.argmax(eq)
    return hit, way, active


def _touch(state: CacheState, s, way, t, set_dirty):
    return state._replace(
        lru=state.lru.at[s, way].set(t),
        dirty=state.dirty.at[s, way].set(state.dirty[s, way] | set_dirty),
    )


def _victim(state: CacheState, s, active):
    """Pick insert way: first invalid active way, else LRU-min active way."""
    lru_s = state.lru[s]
    tags_s = state.tags[s]
    score = jnp.where(active, jnp.where(tags_s < 0, -1, lru_s), jnp.int32(2**31 - 1))
    return jnp.argmin(score)


def _insert(state: CacheState, s, addr, t, dirty, ways_active):
    """Insert a block; returns (state, evicted_valid, evicted_dirty)."""
    active = jnp.arange(state.tags.shape[1]) < ways_active
    can = ways_active > 0
    way = _victim(state, s, active)
    ev_valid = can & (state.tags[s, way] >= 0)
    ev_dirty = ev_valid & state.dirty[s, way]
    new = CacheState(
        tags=state.tags.at[s, way].set(jnp.where(can, addr, state.tags[s, way])),
        lru=state.lru.at[s, way].set(jnp.where(can, t, state.lru[s, way])),
        dirty=state.dirty.at[s, way].set(jnp.where(can, dirty, state.dirty[s, way])),
    )
    return new, can, ev_valid, ev_dirty


def _insert_range(state: CacheState, s, addr, t, dirty, way_lo, way_hi):
    """:func:`_insert` restricted to the way range ``[way_lo, way_hi)`` —
    the sub-partition slice an IO class may allocate into. With
    ``way_lo == 0`` and ``way_hi == ways_active`` this is exactly
    :func:`_insert`. An empty range means the class cannot allocate."""
    idx = jnp.arange(state.tags.shape[1])
    active = (idx >= way_lo) & (idx < way_hi)
    can = way_hi > way_lo
    way = _victim(state, s, active)
    ev_valid = can & (state.tags[s, way] >= 0)
    ev_dirty = ev_valid & state.dirty[s, way]
    new = CacheState(
        tags=state.tags.at[s, way].set(jnp.where(can, addr, state.tags[s, way])),
        lru=state.lru.at[s, way].set(jnp.where(can, t, state.lru[s, way])),
        dirty=state.dirty.at[s, way].set(jnp.where(can, dirty, state.dirty[s, way])),
    )
    return new, can, ev_valid, ev_dirty


def _invalidate(state: CacheState, s, way, pred):
    return CacheState(
        tags=state.tags.at[s, way].set(jnp.where(pred, -1, state.tags[s, way])),
        lru=state.lru.at[s, way].set(jnp.where(pred, -1, state.lru[s, way])),
        dirty=state.dirty.at[s, way].set(jnp.where(pred, False, state.dirty[s, way])),
    )


# ---------------------------------------------------------------------------
# single level
# ---------------------------------------------------------------------------

def _simulate_single_level(addr, is_write, state: CacheState, ways_active,
                           flags: PolicyFlags, t_cache, t0):
    """Unjitted single-level core over traced :class:`PolicyFlags`.

    With scalar (Python-bool) flags XLA folds the selects back to the
    static-policy code; with traced flags the same step serves any policy,
    which is what lets :func:`simulate_single_level_batch` vmap VMs with
    heterogeneous policies in one dispatch.
    """
    num_sets = state.tags.shape[0]
    ways_active = jnp.asarray(ways_active, jnp.int32)
    t_cache = jnp.float32(t_cache)

    def step(carry, req):
        st0, stats, t = carry
        a, w = req
        valid = a >= 0  # padded no-op requests carry addr == -1
        a = jnp.maximum(a, 0)
        st = st0
        s = a % num_sets
        hit, way, active = _lookup(st, s, a, ways_active)

        def on_read(st):
            lat = jnp.where(hit, t_cache, jnp.float32(T_HDD))
            st = jax.lax.cond(hit, lambda c: _touch(c, s, way, t, False),
                              lambda c: c, st)
            do_alloc = (~hit) & flags.allocates_reads
            st2, ins, _, ev_dirty = _insert(st, s, a, t, False, ways_active)
            st = jax.tree_util.tree_map(
                lambda x, y: jnp.where(do_alloc, y, x), st, st2)
            cw = jnp.where(do_alloc & ins, 1, 0)
            dw = jnp.where(do_alloc & ins & ev_dirty, 1, 0)
            return st, Stats(1, 0, 0, hit.astype(jnp.int32), 0, cw,
                             (~hit).astype(jnp.int32), dw, lat)

        def on_write(st):
            inval = flags.write_invalidates
            # RO branch: bypass + invalidate the stale cached copy
            st_ro = _invalidate(st, s, way, hit & inval)
            # allocating branch (WB/WT/WO/WBWO): write-allocate. WT commits
            # synchronously, so its cached copy stays clean.
            mark_dirty = flags.holds_dirty
            st_hit = _touch(st, s, way, t, mark_dirty)
            st_ins, ins, _, ev_dirty = _insert(st, s, a, t, mark_dirty,
                                               ways_active)
            st_alloc = jax.tree_util.tree_map(
                lambda h, i: jnp.where(hit, h, i), st_hit, st_ins)
            st = jax.tree_util.tree_map(
                lambda r, al: jnp.where(inval, r, al), st_ro, st_alloc)
            committed = hit | ins
            cw = jnp.where(inval, 0, committed.astype(jnp.int32))
            wh = jnp.where(inval, 0, hit.astype(jnp.int32))
            # write-through also commits to disk synchronously
            sync = flags.write_through.astype(jnp.int32)
            dw_alloc = sync + jnp.where((~hit) & ins & ev_dirty, 1, 0) \
                + jnp.where(~committed, 1, 0)
            dw = jnp.where(inval, 1, dw_alloc)
            lat_alloc = jnp.where(
                committed,
                jnp.where(flags.write_through, jnp.float32(T_HDD_WRITE),
                          t_cache),
                jnp.float32(T_HDD_WRITE))
            lat = jnp.where(inval, jnp.float32(T_HDD_WRITE), lat_alloc)
            return st, Stats(0, 1, 0, 0, wh, cw, 0, dw, lat)

        st, ds = jax.lax.cond(w, lambda c: on_write(c), lambda c: on_read(c), st)
        # mask out padded requests entirely
        st = jax.tree_util.tree_map(
            lambda new, old: jnp.where(valid, new, old), st, st0)
        ds = Stats(*[d * valid.astype(d.dtype) for d in ds])
        return (st, stats.merge(ds), t + valid.astype(jnp.int32)), None

    (state, stats, t_end), _ = jax.lax.scan(
        step, (state, Stats.zero(), jnp.asarray(t0, jnp.int32)),
        (jnp.asarray(addr, jnp.int32), jnp.asarray(is_write)))
    return state, stats, t_end


@functools.partial(jax.jit, static_argnames=("policy",))
def simulate_single_level(addr, is_write, state: CacheState, ways_active,
                          policy: Policy, t_cache=T_SSD, t0=0):
    """Run one request window through a single-level cache.

    Returns (state, Stats, t_end). ``t0`` is the running logical clock so
    LRU order survives across windows.
    """
    return _simulate_single_level(addr, is_write, state, ways_active,
                                  policy_flags(policy), t_cache, t0)


@jax.jit
def simulate_single_level_batch(addr, is_write, state: CacheState,
                                ways_active, flags: PolicyFlags,
                                t_cache=T_SSD, t0=0):
    """Batched :func:`simulate_single_level`: one dispatch for V VMs.

    ``addr``/``is_write`` are ``[V, N]``; ``state`` leaves are
    ``[V, S, W]``; ``ways_active``, ``t0`` and each :class:`PolicyFlags`
    field are ``[V]`` (build with :func:`policy_flags`); ``t_cache`` is a
    shared scalar. Returns (state, Stats, t_end) with a ``[V]`` axis on
    every leaf, bit-identical per VM to the unbatched function.
    """
    v = jnp.shape(addr)[0]
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (v,))
    return jax.vmap(
        _simulate_single_level, in_axes=(0, 0, 0, 0, 0, None, 0)
    )(jnp.asarray(addr, jnp.int32), jnp.asarray(is_write), state,
      jnp.asarray(ways_active, jnp.int32), flags, jnp.float32(t_cache), t0)


# ---------------------------------------------------------------------------
# two level (ETICA §4.1/§4.2)
# ---------------------------------------------------------------------------

def _simulate_two_level(addr, is_write, dram: CacheState, ssd: CacheState,
                        ways_dram, ways_ssd, mode: str, t0):
    """Unjitted two-level core (``mode`` is a Python static)."""
    assert mode in ("full", "npe")
    ns_d = dram.tags.shape[0]
    ns_s = ssd.tags.shape[0]
    ways_dram = jnp.asarray(ways_dram, jnp.int32)
    ways_ssd = jnp.asarray(ways_ssd, jnp.int32)

    def step(carry, req):
        dr0, ss0, stats, t = carry
        a, w = req
        valid = a >= 0
        a = jnp.maximum(a, 0)
        dr, ss = dr0, ss0
        sd = a % ns_d
        s2 = a % ns_s
        d_hit, d_way, _ = _lookup(dr, sd, a, ways_dram)
        s_hit, s_way, _ = _lookup(ss, s2, a, ways_ssd)

        def on_read(dr, ss):
            # paper Fig. 6a: DRAM hit -> serve; SSD hit -> promote to DRAM,
            # serve; miss -> disk, promote to DRAM only (never to SSD).
            lat = jnp.where(d_hit, jnp.float32(T_DRAM),
                            jnp.where(s_hit, jnp.float32(T_SSD),
                                      jnp.float32(T_HDD)))
            dr = jax.lax.cond(d_hit, lambda c: _touch(c, sd, d_way, t, False),
                              lambda c: c, dr)
            ss = jax.lax.cond(s_hit & ~d_hit,
                              lambda c: _touch(c, s2, s_way, t, False),
                              lambda c: c, ss)
            dr_ins, _, _, _ = _insert(dr, sd, a, t, False, ways_dram)
            promote = ~d_hit
            dr = jax.tree_util.tree_map(
                lambda x, y: jnp.where(promote, y, x), dr, dr_ins)
            return dr, ss, Stats(
                1, 0, d_hit.astype(jnp.int32),
                (s_hit & ~d_hit).astype(jnp.int32), 0, 0,
                (~(d_hit | s_hit)).astype(jnp.int32), 0, lat)

        def on_write(dr, ss):
            # bypass DRAM; invalidate stale DRAM copy (§4.2 "Write")
            dr = _invalidate(dr, sd, d_way, d_hit)
            ss_hit_st = _touch(ss, s2, s_way, t, True)
            if mode == "npe":
                ss_ins, ins, _, ev_dirty = _insert(ss, s2, a, t, True, ways_ssd)
                ss = jax.tree_util.tree_map(
                    lambda h, i: jnp.where(s_hit, h, i), ss_hit_st, ss_ins)
                committed = s_hit | ins
                cw = committed.astype(jnp.int32)
                dw = jnp.where((~s_hit) & ins & ev_dirty, 1, 0) \
                    + jnp.where(~committed, 1, 0)
                lat = jnp.where(committed, jnp.float32(T_SSD),
                                jnp.float32(T_HDD_WRITE))
            else:  # full: SSD miss -> straight to disk
                ss = jax.tree_util.tree_map(
                    lambda h, i: jnp.where(s_hit, h, i), ss_hit_st, ss)
                cw = s_hit.astype(jnp.int32)
                dw = (~s_hit).astype(jnp.int32)
                lat = jnp.where(s_hit, jnp.float32(T_SSD),
                                jnp.float32(T_HDD_WRITE))
            return dr, ss, Stats(0, 1, 0, 0, s_hit.astype(jnp.int32), cw,
                                 0, dw, lat)

        dr, ss, ds = jax.lax.cond(w, on_write, on_read, dr, ss)
        dr = jax.tree_util.tree_map(
            lambda new, old: jnp.where(valid, new, old), dr, dr0)
        ss = jax.tree_util.tree_map(
            lambda new, old: jnp.where(valid, new, old), ss, ss0)
        ds = Stats(*[d * valid.astype(d.dtype) for d in ds])
        return (dr, ss, stats.merge(ds), t + valid.astype(jnp.int32)), None

    (dram, ssd, stats, t_end), _ = jax.lax.scan(
        step, (dram, ssd, Stats.zero(), jnp.asarray(t0, jnp.int32)),
        (jnp.asarray(addr, jnp.int32), jnp.asarray(is_write)))
    return dram, ssd, stats, t_end


@functools.partial(jax.jit, static_argnames=("mode",))
def simulate_two_level(addr, is_write, dram: CacheState, ssd: CacheState,
                       ways_dram, ways_ssd, mode: str = "full", t0=0):
    """ETICA datapath: DRAM is RO (reads allocate, writes bypass+invalidate);
    SSD is WBWO. ``mode="full"`` = pull-mode SSD (no datapath updates on
    miss — contents only change via write hits and the periodic
    promotion/eviction maintenance). ``mode="npe"`` = write misses allocate
    in the SSD on the datapath (ETICA-NPE in §5.3).
    """
    return _simulate_two_level(addr, is_write, dram, ssd, ways_dram,
                               ways_ssd, mode, t0)


@functools.partial(jax.jit, static_argnames=("mode",))
def simulate_two_level_batch(addr, is_write, dram: CacheState,
                             ssd: CacheState, ways_dram, ways_ssd,
                             mode: str = "full", t0=0):
    """Batched :func:`simulate_two_level`: one dispatch for V VMs.

    ``addr``/``is_write`` are ``[V, N]``; both cache pytrees carry a
    leading ``[V]`` axis; ``ways_dram``/``ways_ssd``/``t0`` are ``[V]``.
    ``mode`` stays static (global to the hierarchy). Bit-identical per VM
    to the unbatched function.
    """
    v = jnp.shape(addr)[0]
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (v,))
    return jax.vmap(
        lambda a, w, dr, ss, wd, ws, tt: _simulate_two_level(
            a, w, dr, ss, wd, ws, mode, tt),
        in_axes=(0, 0, 0, 0, 0, 0, 0),
    )(jnp.asarray(addr, jnp.int32), jnp.asarray(is_write), dram, ssd,
      jnp.asarray(ways_dram, jnp.int32), jnp.asarray(ways_ssd, jnp.int32),
      t0)


# ---------------------------------------------------------------------------
# classified datapath (IO-class sub-partitions — repro.classify)
# ---------------------------------------------------------------------------
#
# The classified cores take a per-request class id ``cls`` alongside
# ``addr``/``is_write`` and three per-class tables: way-range bounds
# (``[C]`` per level — the sub-partition slice a class may allocate into),
# per-class :class:`PolicyFlags` (single level only; the two-level
# hierarchy keeps its fixed DRAM-RO / SSD-WBWO policies), and a ``[C]``
# bypass mask. A bypass-class read goes straight to disk without touching
# the cache; a bypass-class write goes straight to disk and drops (without
# flushing) any cached copy, which the disk write supersedes. Both count
# in the ``Stats.bypassed`` channel. Lookups stay global over the VM's
# active ways — classes share residency, they only partition *insertion*.
# With one match-all class (``lo = 0``, ``hi = ways_active``, no bypass)
# every operation below folds to the unclassified step, so results are
# bit-identical to the plain simulators.

def _simulate_single_level_classified(addr, is_write, cls, state: CacheState,
                                      ways_active, flags: PolicyFlags,
                                      way_lo, way_hi, bypass, t_cache, t0):
    """Unjitted classified single-level core: per-class policy flags
    (``[C]`` fields), per-class way ranges, bypass mask."""
    num_sets = state.tags.shape[0]
    ways_active = jnp.asarray(ways_active, jnp.int32)
    t_cache = jnp.float32(t_cache)
    nc = way_lo.shape[0]
    zero = jnp.int32(0)
    one = jnp.int32(1)

    def step(carry, req):
        st0, stats, t = carry
        a, w, c = req
        valid = a >= 0
        a = jnp.maximum(a, 0)
        c = jnp.clip(c, 0, nc - 1)
        fc = PolicyFlags(flags.allocates_reads[c], flags.write_invalidates[c],
                         flags.holds_dirty[c], flags.write_through[c])
        hi = jnp.minimum(way_hi[c], ways_active)
        lo = jnp.minimum(way_lo[c], hi)
        byp = bypass[c]
        st = st0
        s = a % num_sets
        hit, way, active = _lookup(st, s, a, ways_active)

        def on_read(st):
            lat = jnp.where(hit, t_cache, jnp.float32(T_HDD))
            st = jax.lax.cond(hit, lambda cc: _touch(cc, s, way, t, False),
                              lambda cc: cc, st)
            do_alloc = (~hit) & fc.allocates_reads
            st2, ins, _, ev_dirty = _insert_range(st, s, a, t, False, lo, hi)
            st = jax.tree_util.tree_map(
                lambda x, y: jnp.where(do_alloc, y, x), st, st2)
            cw = jnp.where(do_alloc & ins, one, zero)
            dw = jnp.where(do_alloc & ins & ev_dirty, one, zero)
            return st, Stats(one, zero, zero, hit.astype(jnp.int32), zero, cw,
                             (~hit).astype(jnp.int32), dw, lat, zero, zero)

        def on_write(st):
            inval = fc.write_invalidates
            st_ro = _invalidate(st, s, way, hit & inval)
            mark_dirty = fc.holds_dirty
            st_hit = _touch(st, s, way, t, mark_dirty)
            st_ins, ins, _, ev_dirty = _insert_range(st, s, a, t, mark_dirty,
                                                     lo, hi)
            st_alloc = jax.tree_util.tree_map(
                lambda h, i: jnp.where(hit, h, i), st_hit, st_ins)
            st = jax.tree_util.tree_map(
                lambda r, al: jnp.where(inval, r, al), st_ro, st_alloc)
            committed = hit | ins
            cw = jnp.where(inval, zero, committed.astype(jnp.int32))
            wh = jnp.where(inval, zero, hit.astype(jnp.int32))
            sync = fc.write_through.astype(jnp.int32)
            dw_alloc = sync + jnp.where((~hit) & ins & ev_dirty, one, zero) \
                + jnp.where(~committed, one, zero)
            dw = jnp.where(inval, one, dw_alloc)
            lat_alloc = jnp.where(
                committed,
                jnp.where(fc.write_through, jnp.float32(T_HDD_WRITE),
                          t_cache),
                jnp.float32(T_HDD_WRITE))
            lat = jnp.where(inval, jnp.float32(T_HDD_WRITE), lat_alloc)
            return st, Stats(zero, one, zero, zero, wh, cw, zero, dw, lat,
                             zero, zero)

        def on_bypass(st):
            st = _invalidate(st, s, way, hit & w)
            rd = jnp.where(w, zero, one)
            wr = jnp.where(w, one, zero)
            lat = jnp.where(w, jnp.float32(T_HDD_WRITE), jnp.float32(T_HDD))
            return st, Stats(rd, wr, zero, zero, zero, zero, rd, wr, lat,
                             one, zero)

        st, ds = jax.lax.cond(
            byp, on_bypass,
            lambda cc: jax.lax.cond(w, on_write, on_read, cc), st)
        st = jax.tree_util.tree_map(
            lambda new, old: jnp.where(valid, new, old), st, st0)
        ds = Stats(*[d * valid.astype(d.dtype) for d in ds])
        serve_hit = jnp.where(byp, False,
                              jnp.where(w & fc.write_invalidates, False, hit))
        elig = valid & ~byp
        return ((st, stats.merge(ds), t + valid.astype(jnp.int32)),
                (serve_hit, elig, c))

    (state, stats, t_end), (sh, el, cs) = jax.lax.scan(
        step, (state, Stats.zero(), jnp.asarray(t0, jnp.int32)),
        (jnp.asarray(addr, jnp.int32), jnp.asarray(is_write),
         jnp.asarray(cls, jnp.int32)))
    cls_hits = jnp.zeros(nc, jnp.int32).at[cs].add(
        (el & sh).astype(jnp.int32))
    cls_miss = jnp.zeros(nc, jnp.int32).at[cs].add(
        (el & ~sh).astype(jnp.int32))
    return state, stats, t_end, cls_hits, cls_miss


@jax.jit
def simulate_single_level_classified(addr, is_write, cls, state: CacheState,
                                     ways_active, flags: PolicyFlags,
                                     way_lo, way_hi, bypass,
                                     t_cache=T_SSD, t0=0):
    """Classified :func:`simulate_single_level`: ``cls`` is a per-request
    ``[N]`` class id, ``flags`` fields / ``way_lo`` / ``way_hi`` /
    ``bypass`` are ``[C]`` per-class tables. Returns ``(state, stats,
    t_end, cls_hits, cls_miss)`` — the last two are per-class ``[C]``
    served hit/miss counts over non-bypassed valid requests
    (``cls_hits + cls_miss`` sums to ``stats.reads + stats.writes -
    stats.bypassed`` and ``cls_hits`` sums to the served hits)."""
    return _simulate_single_level_classified(
        addr, is_write, cls, state, ways_active, flags,
        jnp.asarray(way_lo, jnp.int32), jnp.asarray(way_hi, jnp.int32),
        jnp.asarray(bypass, bool), t_cache, t0)


@jax.jit
def simulate_single_level_classified_batch(addr, is_write, cls,
                                           state: CacheState, ways_active,
                                           flags: PolicyFlags,
                                           way_lo, way_hi, bypass,
                                           t_cache=T_SSD, t0=0):
    """Batched classified single level: ``addr``/``is_write``/``cls`` are
    ``[V, N]``, ``flags`` fields and way bounds are ``[V, C]``, ``bypass``
    is a shared ``[C]`` mask. Per-class hit/miss counts come back as
    ``[V, C]``."""
    v = jnp.shape(addr)[0]
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (v,))
    return jax.vmap(
        _simulate_single_level_classified,
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, None, 0)
    )(jnp.asarray(addr, jnp.int32), jnp.asarray(is_write),
      jnp.asarray(cls, jnp.int32), state, jnp.asarray(ways_active, jnp.int32),
      flags, jnp.asarray(way_lo, jnp.int32), jnp.asarray(way_hi, jnp.int32),
      jnp.asarray(bypass, bool), jnp.float32(t_cache), t0)


def _simulate_two_level_classified(addr, is_write, cls, dram: CacheState,
                                   ssd: CacheState, ways_dram, ways_ssd,
                                   bypass, lo_d, hi_d, lo_s, hi_s,
                                   mode: str, t0):
    """Unjitted classified two-level core: per-class way ranges for both
    levels plus the bypass mask; policies stay DRAM-RO / SSD-WBWO."""
    assert mode in ("full", "npe")
    ns_d = dram.tags.shape[0]
    ns_s = ssd.tags.shape[0]
    ways_dram = jnp.asarray(ways_dram, jnp.int32)
    ways_ssd = jnp.asarray(ways_ssd, jnp.int32)
    nc = bypass.shape[0]
    zero = jnp.int32(0)
    one = jnp.int32(1)

    def step(carry, req):
        dr0, ss0, stats, t = carry
        a, w, c = req
        valid = a >= 0
        a = jnp.maximum(a, 0)
        c = jnp.clip(c, 0, nc - 1)
        d_hi = jnp.minimum(hi_d[c], ways_dram)
        d_lo = jnp.minimum(lo_d[c], d_hi)
        s_hi = jnp.minimum(hi_s[c], ways_ssd)
        s_lo = jnp.minimum(lo_s[c], s_hi)
        byp = bypass[c]
        dr, ss = dr0, ss0
        sd = a % ns_d
        s2 = a % ns_s
        d_hit, d_way, _ = _lookup(dr, sd, a, ways_dram)
        s_hit, s_way, _ = _lookup(ss, s2, a, ways_ssd)

        def on_read(dr, ss):
            lat = jnp.where(d_hit, jnp.float32(T_DRAM),
                            jnp.where(s_hit, jnp.float32(T_SSD),
                                      jnp.float32(T_HDD)))
            dr = jax.lax.cond(d_hit, lambda c_: _touch(c_, sd, d_way, t, False),
                              lambda c_: c_, dr)
            ss = jax.lax.cond(s_hit & ~d_hit,
                              lambda c_: _touch(c_, s2, s_way, t, False),
                              lambda c_: c_, ss)
            dr_ins, _, _, _ = _insert_range(dr, sd, a, t, False, d_lo, d_hi)
            promote = ~d_hit
            dr = jax.tree_util.tree_map(
                lambda x, y: jnp.where(promote, y, x), dr, dr_ins)
            return dr, ss, Stats(
                one, zero, d_hit.astype(jnp.int32),
                (s_hit & ~d_hit).astype(jnp.int32), zero, zero,
                (~(d_hit | s_hit)).astype(jnp.int32), zero, lat, zero, zero)

        def on_write(dr, ss):
            dr = _invalidate(dr, sd, d_way, d_hit)
            ss_hit_st = _touch(ss, s2, s_way, t, True)
            if mode == "npe":
                ss_ins, ins, _, ev_dirty = _insert_range(ss, s2, a, t, True,
                                                         s_lo, s_hi)
                ss = jax.tree_util.tree_map(
                    lambda h, i: jnp.where(s_hit, h, i), ss_hit_st, ss_ins)
                committed = s_hit | ins
                cw = committed.astype(jnp.int32)
                dw = jnp.where((~s_hit) & ins & ev_dirty, one, zero) \
                    + jnp.where(~committed, one, zero)
                lat = jnp.where(committed, jnp.float32(T_SSD),
                                jnp.float32(T_HDD_WRITE))
            else:  # full: SSD miss -> straight to disk
                ss = jax.tree_util.tree_map(
                    lambda h, i: jnp.where(s_hit, h, i), ss_hit_st, ss)
                cw = s_hit.astype(jnp.int32)
                dw = (~s_hit).astype(jnp.int32)
                lat = jnp.where(s_hit, jnp.float32(T_SSD),
                                jnp.float32(T_HDD_WRITE))
            return dr, ss, Stats(zero, one, zero, zero,
                                 s_hit.astype(jnp.int32), cw, zero, dw, lat,
                                 zero, zero)

        def on_bypass(dr, ss):
            dr = _invalidate(dr, sd, d_way, d_hit & w)
            ss = _invalidate(ss, s2, s_way, s_hit & w)
            rd = jnp.where(w, zero, one)
            wr = jnp.where(w, one, zero)
            lat = jnp.where(w, jnp.float32(T_HDD_WRITE), jnp.float32(T_HDD))
            return dr, ss, Stats(rd, wr, zero, zero, zero, zero, rd, wr, lat,
                                 one, zero)

        dr, ss, ds = jax.lax.cond(
            byp, on_bypass,
            lambda d_, s_: jax.lax.cond(w, on_write, on_read, d_, s_),
            dr, ss)
        dr = jax.tree_util.tree_map(
            lambda new, old: jnp.where(valid, new, old), dr, dr0)
        ss = jax.tree_util.tree_map(
            lambda new, old: jnp.where(valid, new, old), ss, ss0)
        ds = Stats(*[d * valid.astype(d.dtype) for d in ds])
        serve_hit = jnp.where(byp, False,
                              jnp.where(w, s_hit, d_hit | s_hit))
        elig = valid & ~byp
        return ((dr, ss, stats.merge(ds), t + valid.astype(jnp.int32)),
                (serve_hit, elig, c))

    (dram, ssd, stats, t_end), (sh, el, cs) = jax.lax.scan(
        step, (dram, ssd, Stats.zero(), jnp.asarray(t0, jnp.int32)),
        (jnp.asarray(addr, jnp.int32), jnp.asarray(is_write),
         jnp.asarray(cls, jnp.int32)))
    cls_hits = jnp.zeros(nc, jnp.int32).at[cs].add(
        (el & sh).astype(jnp.int32))
    cls_miss = jnp.zeros(nc, jnp.int32).at[cs].add(
        (el & ~sh).astype(jnp.int32))
    return dram, ssd, stats, t_end, cls_hits, cls_miss


@functools.partial(jax.jit, static_argnames=("mode",))
def simulate_two_level_classified(addr, is_write, cls, dram: CacheState,
                                  ssd: CacheState, ways_dram, ways_ssd,
                                  bypass, lo_d, hi_d, lo_s, hi_s,
                                  mode: str = "full", t0=0):
    """Classified :func:`simulate_two_level`: per-request ``[N]`` class
    ids, per-class ``[C]`` way bounds per level, ``[C]`` bypass mask.
    Returns ``(dram, ssd, stats, t_end, cls_hits, cls_miss)`` with
    per-class ``[C]`` served hit/miss counts (any-level hit on reads,
    SSD hit on writes; bypassed requests excluded)."""
    return _simulate_two_level_classified(
        addr, is_write, cls, dram, ssd, ways_dram, ways_ssd,
        jnp.asarray(bypass, bool),
        jnp.asarray(lo_d, jnp.int32), jnp.asarray(hi_d, jnp.int32),
        jnp.asarray(lo_s, jnp.int32), jnp.asarray(hi_s, jnp.int32), mode, t0)


@functools.partial(jax.jit, static_argnames=("mode",))
def simulate_two_level_classified_batch(addr, is_write, cls,
                                        dram: CacheState, ssd: CacheState,
                                        ways_dram, ways_ssd, bypass,
                                        lo_d, hi_d, lo_s, hi_s,
                                        mode: str = "full", t0=0):
    """Batched classified two level: ``addr``/``is_write``/``cls`` are
    ``[V, N]``, way bounds are ``[V, C]``, ``bypass`` is shared ``[C]``.
    Per-class hit/miss counts come back as ``[V, C]``."""
    v = jnp.shape(addr)[0]
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (v,))
    return jax.vmap(
        lambda a, w, c, dr, ss, wd, ws, ld, hd, ls, hs, tt:
            _simulate_two_level_classified(
                a, w, c, dr, ss, wd, ws, jnp.asarray(bypass, bool),
                ld, hd, ls, hs, mode, tt),
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    )(jnp.asarray(addr, jnp.int32), jnp.asarray(is_write),
      jnp.asarray(cls, jnp.int32), dram, ssd,
      jnp.asarray(ways_dram, jnp.int32), jnp.asarray(ways_ssd, jnp.int32),
      jnp.asarray(lo_d, jnp.int32), jnp.asarray(hi_d, jnp.int32),
      jnp.asarray(lo_s, jnp.int32), jnp.asarray(hi_s, jnp.int32), t0)


# ---------------------------------------------------------------------------
# maintenance ops (between-interval — paper: asynchronous). Vectorized
# jnp implementations with (state, count) contracts; jit-able/vmappable.
# ---------------------------------------------------------------------------

def resize(state: CacheState, old_ways, new_ways):
    """Deactivate ways >= new_ways; returns (state, flushed_dirty_blocks).

    Pure ``jnp`` (jit-able; counts are 0-d arrays). A grow (``new_ways >=
    old_ways``) is a no-op with 0 flushes, matching :func:`resize_ref`.
    """
    old_ways = jnp.asarray(old_ways, jnp.int32)
    new_ways = jnp.asarray(new_ways, jnp.int32)
    w = state.tags.shape[1]
    shrink = new_ways < old_ways
    clear = shrink & (jnp.arange(w) >= new_ways)          # [W]
    flushed = jnp.sum(state.dirty & clear[None, :]).astype(jnp.int32)
    return CacheState(
        tags=jnp.where(clear[None, :], -1, state.tags),
        lru=jnp.where(clear[None, :], -1, state.lru),
        dirty=jnp.where(clear[None, :], False, state.dirty),
    ), flushed


resize_batch = jax.jit(jax.vmap(resize))
"""Map :func:`resize` over stacked ``[V, S, W]`` states and ``[V]`` way
counts in one dispatch; returns (stacked state, ``[V]`` flush counts)."""


@jax.jit
def resize_levels(dram: CacheState, ssd: CacheState, old_dram, new_dram,
                  old_ssd, new_ssd):
    """Resize BOTH cache levels of all VMs in one jitted dispatch.

    The two-level controller's per-interval resize: equivalent to two
    :data:`resize_batch` calls but fused into a single executable.
    Returns (dram, ssd, dram_flushed ``[V]``, ssd_flushed ``[V]``).
    """
    dram, fl_d = jax.vmap(resize)(dram, jnp.asarray(old_dram, jnp.int32),
                                  jnp.asarray(new_dram, jnp.int32))
    ssd, fl_s = jax.vmap(resize)(ssd, jnp.asarray(old_ssd, jnp.int32),
                                 jnp.asarray(new_ssd, jnp.int32))
    return dram, ssd, fl_d, fl_s


# ---------------------------------------------------------------------------
# sharded dispatches (VM axis split across a 1-d device mesh)
# ---------------------------------------------------------------------------
#
# Same vmapped step functions as the batched entry points, wrapped in
# ``shard_map`` over a VM mesh (``launch.mesh.make_vm_mesh``): each device
# scans its own ``[V/d, N]`` block against its own ``[V/d, S, W]`` state
# shard. Everything is shard-local — the compiled HLO contains no
# collectives (asserted by the sharding tests) — so per-VM results are
# bit-identical to the single-device batched dispatch. The ONLY
# cross-device traffic in a sharded controller run is
# :func:`aggregate_stats_sharded`'s psum. Builders are lru-cached on
# (mesh, statics) so controller intervals reuse compiled executables.

def _vm_io(mesh):
    from ..launch.mesh import require_vm_divisible, vm_spec
    return vm_spec(mesh), require_vm_divisible


@functools.lru_cache(maxsize=None)
def _two_level_sharded(mesh, mode):
    spec, _ = _vm_io(mesh)

    def body(addr, is_write, dram, ssd, ways_dram, ways_ssd, t0):
        return jax.vmap(
            lambda a, w, dr, ss, wd, ws, tt: _simulate_two_level(
                a, w, dr, ss, wd, ws, mode, tt)
        )(addr, is_write, dram, ssd, ways_dram, ways_ssd, t0)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 7, out_specs=spec,
        check_vma=False))


def simulate_two_level_sharded(addr, is_write, dram: CacheState,
                               ssd: CacheState, ways_dram, ways_ssd,
                               mesh, mode: str = "full", t0=0):
    """:func:`simulate_two_level_batch` with VM rows split over ``mesh``."""
    spec, require = _vm_io(mesh)
    v = np.shape(addr)[0]
    require(v, mesh)
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (v,))
    return _two_level_sharded(mesh, mode)(
        jnp.asarray(addr, jnp.int32), jnp.asarray(is_write), dram, ssd,
        jnp.asarray(ways_dram, jnp.int32), jnp.asarray(ways_ssd, jnp.int32),
        t0)


@functools.lru_cache(maxsize=None)
def _single_level_sharded(mesh):
    from jax.sharding import PartitionSpec
    spec, _ = _vm_io(mesh)

    def body(addr, is_write, state, ways_active, flags, t_cache, t0):
        return jax.vmap(
            _simulate_single_level, in_axes=(0, 0, 0, 0, 0, None, 0)
        )(addr, is_write, state, ways_active, flags, t_cache, t0)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, PartitionSpec(), spec),
        out_specs=spec, check_vma=False))


def simulate_single_level_sharded(addr, is_write, state: CacheState,
                                  ways_active, flags: PolicyFlags, mesh,
                                  t_cache=T_SSD, t0=0):
    """:func:`simulate_single_level_batch` with VM rows split over ``mesh``.

    ``flags`` fields are broadcast to ``[V]`` (scalar flags replicate)."""
    spec, require = _vm_io(mesh)
    v = np.shape(addr)[0]
    require(v, mesh)
    t0 = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (v,))
    flags = PolicyFlags(
        *[jnp.broadcast_to(jnp.asarray(f), (v,)) for f in flags])
    return _single_level_sharded(mesh)(
        jnp.asarray(addr, jnp.int32), jnp.asarray(is_write), state,
        jnp.asarray(ways_active, jnp.int32), flags, jnp.float32(t_cache), t0)


@functools.lru_cache(maxsize=None)
def _resize_levels_sharded(mesh):
    spec, _ = _vm_io(mesh)

    def body(dram, ssd, old_dram, new_dram, old_ssd, new_ssd):
        dram, fl_d = jax.vmap(resize)(dram, old_dram, new_dram)
        ssd, fl_s = jax.vmap(resize)(ssd, old_ssd, new_ssd)
        return dram, ssd, fl_d, fl_s

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6, out_specs=spec,
        check_vma=False))


def resize_levels_sharded(dram: CacheState, ssd: CacheState, old_dram,
                          new_dram, old_ssd, new_ssd, mesh):
    """:func:`resize_levels` with VM rows split over ``mesh``."""
    _, require = _vm_io(mesh)
    require(int(dram.tags.shape[0]), mesh)
    as_i32 = lambda x: jnp.asarray(x, jnp.int32)
    return _resize_levels_sharded(mesh)(
        dram, ssd, as_i32(old_dram), as_i32(new_dram), as_i32(old_ssd),
        as_i32(new_ssd))


@functools.lru_cache(maxsize=None)
def _resize_batch_sharded(mesh):
    spec, _ = _vm_io(mesh)
    return jax.jit(jax.shard_map(
        lambda st, old, new: jax.vmap(resize)(st, old, new),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False))


def resize_batch_sharded(state: CacheState, old_ways, new_ways, mesh):
    """:data:`resize_batch` with VM rows split over ``mesh``."""
    _, require = _vm_io(mesh)
    require(int(state.tags.shape[0]), mesh)
    return _resize_batch_sharded(mesh)(
        state, jnp.asarray(old_ways, jnp.int32),
        jnp.asarray(new_ways, jnp.int32))


@functools.lru_cache(maxsize=None)
def _aggregate_stats_sharded(mesh):
    from jax.sharding import PartitionSpec
    spec, _ = _vm_io(mesh)
    ax = mesh.axis_names[0]

    def body(st):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(jnp.sum(x, axis=0), ax), st)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,), out_specs=PartitionSpec(),
        check_vma=False))


def aggregate_stats_sharded(stats: Stats, mesh) -> Stats:
    """Total :class:`Stats` over sharded ``[V]`` per-VM stats: one
    shard-local sum + ONE psum per leaf — the only cross-device collective
    a sharded controller run performs."""
    stats = Stats(*[jnp.asarray(x) for x in stats])
    return _aggregate_stats_sharded(mesh)(stats)


def resident_blocks(state: CacheState, ways_active: int) -> np.ndarray:
    tags = np.asarray(state.tags)[:, : max(ways_active, 0)]
    return tags[tags >= 0]


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _pad_addrs(addrs) -> np.ndarray:
    """Round a maintenance queue up to the next power-of-two length with
    -1 no-op entries, so jitted maintenance compiles O(log max_len) times
    instead of once per distinct queue length."""
    a = np.asarray(addrs).reshape(-1).astype(np.int32)
    return np.pad(a, (0, _next_pow2(a.size) - a.size), constant_values=-1)


@jax.jit
def _evict_blocks_impl(state: CacheState, addrs):
    mask = jnp.isin(state.tags, addrs) & (state.tags >= 0)
    flushed = jnp.sum(state.dirty & mask).astype(jnp.int32)
    return CacheState(
        tags=jnp.where(mask, -1, state.tags),
        lru=jnp.where(mask, -1, state.lru),
        dirty=jnp.where(mask, False, state.dirty),
    ), flushed


def evict_blocks(state: CacheState, addrs):
    """Evict given blocks (maintenance). Returns (state, flushed_dirty).

    Vectorized jitted ``jnp``; ``addrs`` entries of -1 are ignored
    (padding), and inputs are bucketed to power-of-two lengths so ragged
    per-VM eviction queues reuse a handful of compiled executables.
    """
    if np.size(addrs) == 0:
        return state, jnp.int32(0)
    return _evict_blocks_impl(state, _pad_addrs(addrs))


@jax.jit
def _promote_blocks_impl(state: CacheState, addrs, ways_active, t):
    tags, lru, dirty = state
    s_count, w_count = tags.shape
    n = addrs.shape[0]
    valid = addrs >= 0
    sets = jnp.where(valid, addrs % s_count, 0)
    active = jnp.arange(w_count) < ways_active               # [W]

    # first-occurrence dedupe: stable sort groups duplicates with original
    # order preserved, so the group head is the first occurrence
    order = jnp.argsort(addrs, stable=True)
    sorted_a = addrs[order]
    head = jnp.concatenate(
        [jnp.ones(1, bool), sorted_a[1:] != sorted_a[:-1]])
    first = jnp.zeros(n, bool).at[order].set(head)

    present = jnp.any((tags[sets] == addrs[:, None]) & active[None, :],
                      axis=1)
    elig = valid & first & ~present & (ways_active > 0)

    # rank of each eligible address among eligible addresses of its set,
    # in original order: stable-sort by set (ineligible -> sentinel group),
    # then position within group = index - running group start
    key = jnp.where(elig, sets, jnp.int32(s_count))
    perm = jnp.argsort(key, stable=True)
    ksort = key[perm]
    newgrp = jnp.concatenate([jnp.ones(1, bool), ksort[1:] != ksort[:-1]])
    idx = jnp.arange(n)
    grp_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(newgrp, idx, 0))
    rank = jnp.zeros(n, jnp.int32).at[perm].set(
        (idx - grp_start).astype(jnp.int32))

    # k-th eligible address of a set lands in the set's k-th free way
    free = active[None, :] & (tags < 0)                      # [S, W]
    freerank = jnp.cumsum(free, axis=1) - 1                  # [S, W]
    nfree = free.sum(axis=1)                                 # [S]
    promoted = elig & (rank < nfree[sets])
    way = jnp.argmax((freerank[sets] == rank[:, None]) & free[sets], axis=1)

    rows = jnp.where(promoted, sets, jnp.int32(s_count))     # OOB -> dropped
    return CacheState(
        tags=tags.at[rows, way].set(addrs, mode="drop"),
        lru=lru.at[rows, way].set(t, mode="drop"),
        dirty=dirty.at[rows, way].set(False, mode="drop"),
    ), jnp.sum(promoted).astype(jnp.int32)


def promote_blocks(state: CacheState, addrs, ways_active, t):
    """Insert blocks into FREE active ways only (paper: promote "only when
    there is free space in SSD"). Returns (state, n_promoted).

    Vectorized jitted ``jnp`` with the exact semantics of the sequential
    reference (:func:`promote_blocks_ref`): first occurrence of each
    address wins, addresses already resident are skipped, and free ways
    fill in ascending way order in ``addrs`` order. ``addrs`` entries of
    -1 are ignored (padding), and inputs are bucketed to power-of-two
    lengths to bound recompiles across queue sizes.
    """
    if np.size(addrs) == 0:
        return state, jnp.int32(0)
    return _promote_blocks_impl(state, _pad_addrs(addrs),
                                jnp.asarray(ways_active, jnp.int32),
                                jnp.asarray(t, jnp.int32))


_evict_blocks_vmapped = jax.jit(jax.vmap(_evict_blocks_impl))
_promote_blocks_vmapped = jax.jit(jax.vmap(_promote_blocks_impl))


def _pad_addrs_batch(queues: Sequence[np.ndarray]) -> np.ndarray:
    """Stack ragged per-VM maintenance queues into a [V, Q] rectangle of a
    power-of-two width, padding with -1 no-ops."""
    q = _next_pow2(max((np.size(a) for a in queues), default=0))
    out = np.full((len(queues), max(q, 1)), -1, np.int32)
    for v, a in enumerate(queues):
        a = np.asarray(a).reshape(-1)
        out[v, : a.size] = a
    return out


def evict_blocks_batch(state: CacheState, queues: Sequence[np.ndarray]):
    """Per-VM :func:`evict_blocks` over a stacked ``[V, S, W]`` state in
    one vmapped dispatch. ``queues`` is one (possibly empty) address array
    per VM; returns (stacked state, ``[V]`` flush counts)."""
    return _evict_blocks_vmapped(state, _pad_addrs_batch(queues))


def promote_blocks_batch(state: CacheState, queues: Sequence[np.ndarray],
                         ways_active, t):
    """Per-VM :func:`promote_blocks` over a stacked ``[V, S, W]`` state in
    one vmapped dispatch. ``ways_active``/``t`` are ``[V]``; returns
    (stacked state, ``[V]`` promotion counts)."""
    return _promote_blocks_vmapped(state, _pad_addrs_batch(queues),
                                   jnp.asarray(ways_active, jnp.int32),
                                   jnp.asarray(t, jnp.int32))


def _clean_blocks_impl(state: CacheState, ways_active, quota):
    s, w = state.tags.shape
    active = jnp.arange(w, dtype=jnp.int32)[None, :] < ways_active
    cflat = (state.dirty & active).reshape(-1)
    lflat = state.lru.reshape(-1)
    # int32-safe lexsort by (lru, flat index): stable argsort by lru, then
    # stably float the candidates to the front — candidate order is the
    # (lru, index) age order with no composite keys or lru sentinels
    ord1 = jnp.argsort(lflat, stable=True)
    order = ord1[jnp.argsort(~cflat[ord1], stable=True)]
    n_cand = jnp.sum(cflat).astype(jnp.int32)
    take = jnp.minimum(jnp.asarray(quota, jnp.int32), n_cand)
    flush = jnp.zeros(s * w, bool).at[order].set(
        jnp.arange(s * w) < take)
    return CacheState(state.tags, state.lru,
                      state.dirty & ~flush.reshape(s, w)), take, n_cand - take


@jax.jit
def clean_blocks(state: CacheState, ways_active, quota):
    """Background cleaner (maintenance): flush the ``quota`` oldest dirty
    blocks in active ways — age order (lru, flat ``set * W + way`` index)
    ascending. Flushing clears only the dirty bit; the block stays
    resident and clean. Returns (state, flushed, dirty_left), matching
    :func:`clean_blocks_ref` exactly.
    """
    return _clean_blocks_impl(state, jnp.asarray(ways_active, jnp.int32),
                              jnp.asarray(quota, jnp.int32))


_clean_blocks_vmapped = jax.jit(jax.vmap(_clean_blocks_impl))


def clean_batch(state: CacheState, ways_active, quota):
    """Per-VM :func:`clean_blocks` over a stacked ``[V, S, W]`` state in
    one vmapped dispatch. ``ways_active``/``quota`` are ``[V]``; returns
    (stacked state, ``[V]`` flush counts, ``[V]`` dirty-left counts)."""
    return _clean_blocks_vmapped(state, jnp.asarray(ways_active, jnp.int32),
                                 jnp.asarray(quota, jnp.int32))


# ---------------------------------------------------------------------------
# numpy reference oracles for the maintenance ops (sequential semantics the
# vectorized versions above must reproduce exactly — kept for the tests)
# ---------------------------------------------------------------------------

def resize_ref(state: CacheState, old_ways: int, new_ways: int):
    """Sequential numpy reference for :func:`resize`."""
    if new_ways >= old_ways:
        return state, 0
    tags = np.asarray(state.tags).copy()
    lru = np.asarray(state.lru).copy()
    dirty = np.asarray(state.dirty).copy()
    flushed = int(dirty[:, new_ways:].sum())
    tags[:, new_ways:] = -1
    lru[:, new_ways:] = -1
    dirty[:, new_ways:] = False
    return CacheState(jnp.asarray(tags), jnp.asarray(lru), jnp.asarray(dirty)), flushed


def evict_blocks_ref(state: CacheState, addrs: np.ndarray):
    """Sequential numpy reference for :func:`evict_blocks`."""
    tags = np.asarray(state.tags).copy()
    lru = np.asarray(state.lru).copy()
    dirty = np.asarray(state.dirty).copy()
    mask = np.isin(tags, addrs) & (tags >= 0)
    flushed = int((dirty & mask).sum())
    tags[mask] = -1
    lru[mask] = -1
    dirty[mask] = False
    return CacheState(jnp.asarray(tags), jnp.asarray(lru), jnp.asarray(dirty)), flushed


def promote_blocks_ref(state: CacheState, addrs: np.ndarray,
                       ways_active: int, t: int):
    """Sequential numpy reference for :func:`promote_blocks`."""
    tags = np.asarray(state.tags).copy()
    lru = np.asarray(state.lru).copy()
    dirty = np.asarray(state.dirty).copy()
    num_sets, _ = tags.shape
    n = 0
    for a in np.asarray(addrs):
        if a < 0:
            continue
        s = int(a) % num_sets
        if (tags[s, :ways_active] == a).any():
            continue
        free = np.nonzero(tags[s, :ways_active] < 0)[0]
        if free.size == 0:
            continue
        w = free[0]
        tags[s, w] = a
        lru[s, w] = t
        dirty[s, w] = False
        n += 1
    return CacheState(jnp.asarray(tags), jnp.asarray(lru), jnp.asarray(dirty)), n


def clean_blocks_ref(state: CacheState, ways_active: int, quota: int):
    """Sequential numpy reference for :func:`clean_blocks`."""
    tags = np.asarray(state.tags).copy()
    lru = np.asarray(state.lru).copy()
    dirty = np.asarray(state.dirty).copy()
    num_sets, num_ways = tags.shape
    wa = min(max(int(ways_active), 0), num_ways)
    cand = [(int(lru[s, w]), s * num_ways + w, s, w)
            for s in range(num_sets) for w in range(wa) if dirty[s, w]]
    cand.sort()
    take = min(max(int(quota), 0), len(cand))
    for _, _, s, w in cand[:take]:
        dirty[s, w] = False
    return (CacheState(jnp.asarray(tags), jnp.asarray(lru), jnp.asarray(dirty)),
            take, len(cand) - take)
