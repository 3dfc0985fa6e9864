"""Property tests: interpret-mode Pallas maintenance kernels == numpy.

Three layers, matching the package convention:

  1. the promote/evict Pallas kernels (run through the interpreter on
     CPU) against ``repro.kernels.maintenance.ref``'s sequential numpy
     oracles, on randomized stacked ``[V, S, W]`` states with ragged /
     empty / duplicate-laden queues, including full-set promote
     starvation;
  2. the batched device popularity ops against the host
     :class:`PopularityTracker` — bit-identical float32 tables and
     identically-ordered promotion/eviction queues;
  3. the fused ``maintenance_interval`` dispatch against a staged host
     reference (trackers + ``*_ref`` scatters), states and counts exact.
"""
import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import popularity as pop
from repro.core.simulator import CacheState, resident_blocks
from repro.kernels.maintenance import ops, ref

SETTINGS = dict(max_examples=20, deadline=None)

geometries = st.tuples(st.integers(1, 4),    # V
                       st.integers(2, 10),   # S (non-pow2 exercised)
                       st.integers(1, 7))    # W


def _random_state(rng, num_vms, num_sets, ways, addr_space=48,
                  set_consistent=False):
    """Stacked random state; ``set_consistent`` places every tag in its
    own set (``tag % S == s``), the invariant real simulator states obey
    (and that the set-local residency checks rely on)."""
    tags = np.full((num_vms, num_sets, ways), -1, np.int32)
    for v in range(num_vms):
        for s in range(num_sets):
            if set_consistent:
                cand = rng.permutation(np.arange(s, addr_space, num_sets))
            else:
                cand = rng.permutation(np.arange(addr_space))
            nfill = int(rng.integers(0, ways + 1))
            tags[v, s, :nfill] = cand[: min(nfill, cand.size)]
    lru = rng.integers(-1, 100, tags.shape).astype(np.int32)
    dirty = (rng.random(tags.shape) < 0.5) & (tags >= 0)
    return CacheState(jnp.asarray(tags), jnp.asarray(lru),
                      jnp.asarray(dirty))


def _assert_state(got: CacheState, tags, lru, dirty, msg=""):
    assert np.array_equal(np.asarray(got.tags), tags), msg
    assert np.array_equal(np.asarray(got.lru), lru), msg
    assert np.array_equal(np.asarray(got.dirty), dirty.astype(bool)), msg


@given(geometries, st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_evict_kernel_matches_ref(geom, seed):
    v, s, w = geom
    rng = np.random.default_rng(seed)
    st_ = _random_state(rng, v, s, w)
    # ragged queues: empty, -1-padded, duplicate and absent addresses
    queues = [rng.integers(-1, 60, int(rng.integers(0, 20)))
              for _ in range(v)]
    got, flushed = ops.evict(st_, queues, interpret=True)
    tags, lru, dirty, want_fl = ref.evict_ref(
        np.asarray(st_.tags), np.asarray(st_.lru),
        np.asarray(st_.dirty, np.int32), queues)
    _assert_state(got, tags, lru, dirty, "evict state")
    assert np.array_equal(np.asarray(flushed), want_fl)


@given(geometries, st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_promote_kernel_matches_ref(geom, seed):
    v, s, w = geom
    rng = np.random.default_rng(seed)
    st_ = _random_state(rng, v, s, w)
    queues = [rng.integers(-1, 80, int(rng.integers(0, 30)))
              for _ in range(v)]
    ways = rng.integers(0, w + 1, v).astype(np.int32)
    t = rng.integers(0, 100, v).astype(np.int32)
    got, n = ops.promote(st_, queues, ways, t, interpret=True)
    tags, lru, dirty, want_n = ref.promote_ref(
        np.asarray(st_.tags), np.asarray(st_.lru),
        np.asarray(st_.dirty, np.int32), queues, ways, t)
    _assert_state(got, tags, lru, dirty, "promote state")
    assert np.array_equal(np.asarray(n), want_n)


def test_promote_duplicates_first_occurrence_wins():
    """The in-kernel dedupe: later duplicates never displace the first."""
    rng = np.random.default_rng(5)
    st_ = _random_state(rng, 2, 4, 3)
    queues = [np.array([9, 9, 13, 9, 13, 17, 17], np.int32),
              np.array([4, 4, 4, 4], np.int32)]
    ways = np.array([3, 3], np.int32)
    t = np.array([7, 7], np.int32)
    got, n = ops.promote(st_, queues, ways, t, interpret=True)
    tags, lru, dirty, want_n = ref.promote_ref(
        np.asarray(st_.tags), np.asarray(st_.lru),
        np.asarray(st_.dirty, np.int32), queues, ways, t)
    _assert_state(got, tags, lru, dirty, "dup promote")
    assert np.array_equal(np.asarray(n), want_n)


def test_promote_assume_unique_matches_dedupe_on_unique_queues():
    """Repeating every entry of a unique queue changes nothing: the
    in-order drain lets each first occurrence win without a dedupe
    pass."""
    rng = np.random.default_rng(6)
    st_ = _random_state(rng, 3, 5, 4)
    queues = [rng.permutation(60)[: int(rng.integers(0, 25))].astype(np.int32)
              for _ in range(3)]
    ways = rng.integers(0, 5, 3).astype(np.int32)
    t = np.array([1, 2, 3], np.int32)
    a, na = ops.promote(st_, queues, ways, t, interpret=True)
    doubled = [np.repeat(q, 2) for q in queues]
    b, nb = ops.promote(st_, doubled, ways, t, interpret=True)
    _assert_state(a, np.asarray(b.tags), np.asarray(b.lru),
                  np.asarray(b.dirty, np.int32), "assume_unique")
    assert np.array_equal(np.asarray(na), np.asarray(nb))


def test_promote_starvation_on_full_sets():
    """Full active sets admit nothing; promotion count stays 0."""
    v, s, w = 2, 3, 4
    # every active way occupied (set-consistent tags)
    tags = np.stack([np.arange(s)[:, None] + s * np.arange(w)[None, :]
                     for _ in range(v)]).astype(np.int32)
    st_ = CacheState(jnp.asarray(tags),
                     jnp.zeros_like(jnp.asarray(tags)),
                     jnp.zeros(tags.shape, bool))
    fresh = np.arange(100, 130, dtype=np.int32)
    got, n = ops.promote(st_, [fresh, fresh], np.full(v, w, np.int32),
                         np.zeros(v, np.int32), interpret=True)
    assert np.array_equal(np.asarray(n), np.zeros(v, np.int32))
    assert np.array_equal(np.asarray(got.tags), tags)


def test_rectangular_queue_width_not_chunk_multiple():
    """A pre-rectangular [V, Q] queue whose Q is not a power-of-two /
    chunk multiple must still process its tail columns (regression: the
    tail used to be silently skipped by the chunked kernel loop)."""
    rng = np.random.default_rng(9)
    st_ = _random_state(rng, 2, 4, 4)
    q = np.full((2, 192), -1, np.int32)
    q[:, 150:] = rng.integers(0, 48, (2, 42))
    got, flushed = ops.evict(st_, q, interpret=True)
    tags, lru, dirty, want_fl = ref.evict_ref(
        np.asarray(st_.tags), np.asarray(st_.lru),
        np.asarray(st_.dirty, np.int32), list(q))
    _assert_state(got, tags, lru, dirty, "tail-column evict")
    assert np.array_equal(np.asarray(flushed), want_fl)
    ways = np.array([4, 4], np.int32)
    t = np.array([5, 5], np.int32)
    got, n = ops.promote(st_, q, ways, t, interpret=True)
    tags, lru, dirty, want_n = ref.promote_ref(
        np.asarray(st_.tags), np.asarray(st_.lru),
        np.asarray(st_.dirty, np.int32), list(q), ways, t)
    _assert_state(got, tags, lru, dirty, "tail-column promote")
    assert np.array_equal(np.asarray(n), want_n)
    # zero-width queues are no-ops, not a trace-time division error
    got, flushed = ops.evict(st_, np.empty((2, 0), np.int32),
                             interpret=True)
    assert np.array_equal(np.asarray(flushed), np.zeros(2, np.int32))


def test_evict_empty_queues_are_noops():
    rng = np.random.default_rng(7)
    st_ = _random_state(rng, 3, 4, 4)
    got, flushed = ops.evict(st_, [np.empty(0, np.int64)] * 3,
                             interpret=True)
    assert np.array_equal(np.asarray(flushed), np.zeros(3, np.int32))
    _assert_state(got, np.asarray(st_.tags), np.asarray(st_.lru),
                  np.asarray(st_.dirty, np.int32), "noop evict")


# ---------------------------------------------------------------------------
# batched popularity ops vs the host tracker
# ---------------------------------------------------------------------------

windows = st.lists(
    st.lists(st.tuples(st.integers(0, 29), st.integers(0, 100)),
             min_size=0, max_size=40),
    min_size=1, max_size=6)


@given(st.integers(1, 4), windows, st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_popularity_table_matches_tracker(num_vms, steps, seed):
    """table_update == PopularityTracker.update, float32 bit for bit,
    including non-live rows (no decay) and zero-contribution entries."""
    rng = np.random.default_rng(seed)
    table = pop.table_init(num_vms, 64)
    trackers = [pop.PopularityTracker(decay=0.5) for _ in range(num_vms)]
    width = 48
    for step_ops in steps:
        waddr = np.full((num_vms, width), -1, np.int32)
        contrib = np.zeros((num_vms, width), np.float32)
        nval = np.zeros(num_vms, np.int32)
        live = np.zeros(num_vms, bool)
        for v in range(num_vms):
            if rng.random() < 0.25 or not step_ops:
                continue  # this VM skips the window (stays un-decayed)
            n = min(len(step_ops), width)
            live[v] = True
            nval[v] = n
            waddr[v, :n] = [a for a, _ in step_ops[:n]]
            contrib[v, :n] = np.float32(
                [c / 100.0 for _, c in step_ops[:n]])
            trackers[v].update(waddr[v, :n], contrib[v, :n])
        table, _ = pop.table_update(table, waddr, contrib, nval, live, 0.5)
    ta, tv = np.asarray(table.addr), np.asarray(table.val)
    for v in range(num_vms):
        occupied = ta[v] != pop.TABLE_EMPTY
        assert np.array_equal(ta[v][occupied],
                              trackers[v]._addr.astype(np.int32))
        assert np.array_equal(tv[v][occupied], trackers[v]._val)


@given(st.integers(1, 8), st.integers(1, 32), st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_popularity_merge_overflow_drops(k, d, seed):
    """Overflowing a K-entry table with D distinct addresses reports
    exactly ``max(D - K, 0)`` merge drops and keeps ``min(D, K)``
    entries; a second full-table update drops every new address."""
    rng = np.random.default_rng(seed)
    table = pop.table_init(1, k)
    addrs = rng.choice(1000, size=d, replace=False).astype(np.int32)
    contrib = (rng.random(d) + 0.01).astype(np.float32)
    nval = np.asarray([d], np.int32)
    live = np.asarray([True])
    table, drops = pop.table_update(table, addrs[None], contrib[None],
                                    nval, live, 0.5)
    assert int(np.asarray(drops)[0]) == max(d - k, 0)
    assert int(np.asarray(pop.table_len(table))[0]) == min(d, k)
    if d >= k:
        # table is full: a disjoint batch must drop all its survivors
        fresh = (addrs + 1000)[:d]
        _, drops2 = pop.table_update(table, fresh[None], contrib[None],
                                     nval, live, 0.5)
        assert int(np.asarray(drops2)[0]) == d


def test_maintenance_interval_surfaces_pop_drops():
    """The fused interval's 9-tuple carries the merge-drop counter:
    a 4-entry popularity table fed 16 distinct addresses drops 12."""
    from repro.core import reuse
    from repro.core.policies import Policy

    rng = np.random.default_rng(7)
    num_vms, s, w = 2, 4, 4
    st_ = _random_state(rng, num_vms, s, w, addr_space=32,
                        set_consistent=True)
    table = pop.table_init(num_vms, 4)
    addrs = [np.arange(16, dtype=np.int32), np.arange(2, dtype=np.int32)]
    writes = [np.zeros(16, bool), np.zeros(2, bool)]
    lens = [16, 2]
    amat, wmat = reuse._pad_rows(addrs, writes, list(range(num_vms)), lens)
    r = reuse._decompose_vmapped(amat, wmat, policy=Policy.WB,
                                 sizing_reads_only=False, chunk=256)
    *_, drops, _cleaned, _left = ops.maintenance_interval(
        st_, table, r.dist, r.served, amat, np.asarray(lens, np.int32),
        np.full(num_vms, w, np.int32), np.zeros(num_vms, np.int32),
        evict_frac=0.25, decay=0.5, interpret=True)
    drops = np.asarray(drops)
    assert drops[0] == 12   # 16 distinct into capacity 4
    assert drops[1] == 0    # 2 distinct fit


@given(st.integers(1, 3), st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_popularity_queues_match_tracker(num_vms, seed):
    """Eviction/promotion queues from the device table == the tracker's
    least_popular / top_known (exact entries; exact order for promote)."""
    rng = np.random.default_rng(seed)
    s, w = 5, 4
    table = pop.table_init(num_vms, 64)
    trackers = [pop.PopularityTracker(decay=0.5) for _ in range(num_vms)]
    for _ in range(4):
        waddr = rng.integers(0, 30, (num_vms, 16)).astype(np.int32)
        contrib = rng.random((num_vms, 16)).astype(np.float32)
        for v in range(num_vms):
            trackers[v].update(waddr[v], contrib[v])
        table, _ = pop.table_update(table, waddr, contrib,
                                    np.full(num_vms, 16, np.int32),
                                    np.ones(num_vms, bool), 0.5)
    st_ = _random_state(rng, num_vms, s, w, addr_space=30,
                        set_consistent=True)
    ways = rng.integers(0, w + 1, num_vms).astype(np.int32)
    alloc = ways * s
    live = np.ones(num_vms, bool)

    eq, eqlen = pop.table_least_popular(table, st_.tags, ways, alloc,
                                        live, 0.3)
    eq, eqlen = np.asarray(eq), np.asarray(eqlen)
    limit = rng.integers(0, 15, num_vms).astype(np.int32)
    pq, pqlen = pop.table_top_known(table, st_.tags, ways, limit, live)
    pq, pqlen = np.asarray(pq), np.asarray(pqlen)

    for v in range(num_vms):
        vm_state = CacheState(*[jnp.asarray(np.asarray(x)[v])
                                for x in st_])
        res = resident_blocks(vm_state, int(ways[v]))
        if res.size and res.size * 10 >= int(alloc[v]) * 9:
            want = trackers[v].least_popular(res, 0.3)
        else:
            want = np.empty(0, np.int64)
        got = eq[v][eq[v] >= 0]
        assert eqlen[v] == want.size
        # eviction is membership-based; compare as sets
        assert np.array_equal(np.sort(got.astype(np.int64)), np.sort(want))

        want = trackers[v].top_known(res, int(limit[v]))
        got = pq[v][pq[v] >= 0]
        assert pqlen[v] == want.size
        # promotion order is the contract: exact sequence match
        assert np.array_equal(got.astype(np.int64), want)


# ---------------------------------------------------------------------------
# the fused dispatch vs a staged host reference
# ---------------------------------------------------------------------------

@given(st.integers(1, 3), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_fused_interval_matches_staged_host_reference(num_vms, seed):
    """maintenance_interval == tracker update + *_ref evict/promote,
    chained by hand on the host: states, table, and counts exact."""
    from repro.core import reuse
    from repro.core.policies import Policy

    rng = np.random.default_rng(seed)
    s, w = 4, 4
    st_ = _random_state(rng, num_vms, s, w, addr_space=32,
                        set_consistent=True)
    table = pop.table_init(num_vms, 128)
    trackers = [pop.PopularityTracker(decay=0.5) for _ in range(num_vms)]
    ways = rng.integers(0, w + 1, num_vms).astype(np.int32)
    t = rng.integers(0, 50, num_vms).astype(np.int32)
    lens = [int(rng.integers(0, 40)) for _ in range(num_vms)]
    addrs = [rng.integers(0, 32, n).astype(np.int32) for n in lens]
    writes = [rng.random(n) < 0.4 for n in lens]
    live = [v for v, n in enumerate(lens) if n > 0]
    if not live:
        return

    amat, wmat = reuse._pad_rows(addrs, writes, list(range(num_vms)), lens)
    r = reuse._decompose_vmapped(amat, wmat, policy=Policy.WB,
                                 sizing_reads_only=False, chunk=256)
    (got_ssd, got_table, flushed, promoted, eqlen, pqlen, drops,
     _cleaned, _left) = ops.maintenance_interval(
            st_, table, r.dist, r.served, amat,
            np.asarray(lens, np.int32), ways, t,
            evict_frac=0.25, decay=0.5, interpret=True)
    # 128-entry table over a 32-address space: merge never overflows
    assert np.asarray(drops).sum() == 0

    # staged host reference
    tags = np.asarray(st_.tags).copy()
    lru = np.asarray(st_.lru).copy()
    dirty = np.asarray(st_.dirty, np.int32).copy()
    want_fl = np.zeros(num_vms, np.int32)
    want_n = np.zeros(num_vms, np.int32)
    for v in live:
        d = reuse.trd_distances(addrs[v], writes[v])
        alloc = int(ways[v]) * s
        contrib = pop.contributions(d.dist, d.served, max(alloc, 1))
        trackers[v].update(addrs[v], np.asarray(contrib))
        vm = CacheState(jnp.asarray(tags[v]), jnp.asarray(lru[v]),
                        jnp.asarray(dirty[v].astype(bool)))
        res = resident_blocks(vm, int(ways[v]))
        if res.size and res.size * 10 >= alloc * 9:
            evq = trackers[v].least_popular(res, 0.25)
            assert eqlen[v] == evq.size
            tg, lr, dr, fl = ref.evict_ref(tags[v][None], lru[v][None],
                                           dirty[v][None], [evq])
            tags[v], lru[v], dirty[v] = tg[0], lr[0], dr[0]
            want_fl[v] = fl[0]
        else:
            assert eqlen[v] == 0
        vm = CacheState(jnp.asarray(tags[v]), jnp.asarray(lru[v]),
                        jnp.asarray(dirty[v].astype(bool)))
        res = resident_blocks(vm, int(ways[v]))
        free = max(alloc - res.size, 0)
        prq = trackers[v].top_known(res, free) if free else \
            np.empty(0, np.int64)
        assert pqlen[v] == prq.size
        if prq.size:
            tg, lr, dr, n = ref.promote_ref(
                tags[v][None], lru[v][None], dirty[v][None], [prq],
                ways[v:v + 1], t[v:v + 1])
            tags[v], lru[v], dirty[v] = tg[0], lr[0], dr[0]
            want_n[v] = n[0]

    _assert_state(got_ssd, tags, lru, dirty, "fused vs staged state")
    assert np.array_equal(np.asarray(flushed)[live], want_fl[live])
    assert np.array_equal(np.asarray(promoted)[live], want_n[live])
    ta, tv = np.asarray(got_table.addr), np.asarray(got_table.val)
    for v in live:
        occupied = ta[v] != pop.TABLE_EMPTY
        assert np.array_equal(ta[v][occupied],
                              trackers[v]._addr.astype(np.int32))
        assert np.array_equal(tv[v][occupied], trackers[v]._val)
