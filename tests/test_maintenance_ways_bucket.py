"""The fused maintenance dispatch on the SSD level's leading ways only.

``maintenance_interval`` works on the leading ``ways_bucket`` ways of the
``[V, S, W]`` state and writes the slice back. Where every way at or above
a VM's ``ways`` holds no block, as resize leaves it, the result must be
the full-width dispatch's bit for bit: state, table and all nine outputs.
The controller compiles every bucket the SSD level can reach when it
first meets a window bucket, so a later change of bucket compiles
nothing, and counts the bucket of each interval.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import EticaCache, EticaConfig, Geometry, Trace
from repro.core import popularity as pop
from repro.core.simulator import CacheState
from repro.kernels.maintenance import ops

V, S, W = 6, 8, 16
ADDR_SPACE = 8 * S * W


def _state(rng, ways, fill):
    """Set-consistent tags in each VM's active ways, ``fill`` of them
    resident; every way at or above ``ways[v]`` cleared as resize does."""
    tags = np.full((V, S, W), -1, np.int32)
    for v in range(V):
        for s in range(S):
            cand = rng.permutation(np.arange(s, ADDR_SPACE, S))
            live = rng.random(int(ways[v])) < fill
            tags[v, s, : int(ways[v])] = np.where(live, cand[: int(ways[v])],
                                                  -1)
    lru = np.where(tags >= 0, rng.integers(0, 50, tags.shape), -1)
    dirty = (rng.random(tags.shape) < 0.5) & (tags >= 0)
    return CacheState(jnp.asarray(tags), jnp.asarray(lru, jnp.int32),
                      jnp.asarray(dirty))


def _table(rng):
    """A popularity table that knows residents and non-residents alike."""
    table = pop.table_init(V, 256)
    for _ in range(3):
        waddr = rng.integers(0, ADDR_SPACE, (V, 64)).astype(np.int32)
        contrib = rng.random((V, 64)).astype(np.float32)
        table, _ = pop.table_update(table, waddr, contrib,
                                    np.full(V, 64, np.int32),
                                    np.ones(V, bool), 0.5)
    return table


PATTERNS = {
    "idle_and_one": [0, 1, 1, 0, 1, 1],
    "one_to_three": [2, 3, 0, 1, 2, 3],
    "five": [5, 0, 1, 2, 3, 5],
    "full_width": [W, 0, 1, 2, 3, 5],
}


@pytest.mark.parametrize("gate", ["open", "closed"])
@pytest.mark.parametrize("clean_quota", [0, 2])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_ways_bucket_matches_full_width(pattern, clean_quota, gate):
    rng = np.random.default_rng(
        [sorted(PATTERNS).index(pattern), clean_quota, gate == "open"])
    ways = np.asarray(PATTERNS[pattern], np.int32)
    ssd = _state(rng, ways, 1.0 if gate == "open" else 0.4)
    table = _table(rng)
    n = 32
    waddr = rng.integers(0, ADDR_SPACE, (V, n)).astype(np.int32)
    dist = rng.integers(-1, 8, (V, n)).astype(np.int32)
    served = (rng.random((V, n)) < 0.6) & (dist >= 0)
    wlen = np.full(V, n, np.int32)
    wlen[[0, 3]] = 0                     # idle VMs ride along untouched
    t = rng.integers(50, 60, V).astype(np.int32)
    kw = dict(evict_frac=0.25, decay=0.5, clean_quota=clean_quota,
              interpret=True)
    wb = ops.ways_bucket_of(ways, W)
    assert wb == min(1 << int(max(ways.max(), 1) - 1).bit_length(), W)
    got = ops.maintenance_interval(ssd, table, dist, served, waddr, wlen,
                                   ways, t, **kw)
    want = ops.maintenance_interval(ssd, table, dist, served, waddr, wlen,
                                    ways, t, ways_bucket=W, **kw)
    for i, (a, b) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (i, wb)
    eqlen, pqlen = np.asarray(got[4]), np.asarray(got[5])
    busy = (wlen > 0) & (ways > 0)
    # the near-full eviction gate: open for every live VM with ways, or
    # shut for all; closed, the free space is promoted into
    if gate == "open":
        assert (eqlen[busy] > 0).all()
    else:
        assert not eqlen.any() and pqlen[busy].any()
    assert not eqlen[wlen == 0].any() and not pqlen[wlen == 0].any()


def test_ways_bucket_below_active_ways_is_refused():
    rng = np.random.default_rng(0)
    ways = np.asarray(PATTERNS["five"], np.int32)
    n = 8
    with pytest.raises(ValueError, match="below the largest"):
        ops.maintenance_interval(
            _state(rng, ways, 0.5), pop.table_init(V, 16),
            np.zeros((V, n), np.int32), np.zeros((V, n), bool),
            np.zeros((V, n), np.int32), np.zeros(V, np.int32), ways,
            np.zeros(V, np.int32), evict_frac=0.25, decay=0.5,
            ways_bucket=4, interpret=True)


@pytest.mark.parametrize("max_active,want", [
    (0, (1,)), (1, (1,)), (2, (1, 2)), (3, (1, 2, 4)), (12, (1, 2, 4, 8, 16)),
    (64, (1, 2, 4, 8, 16)),
])
def test_ways_buckets_upto(max_active, want):
    assert ops.ways_buckets_upto(max_active, W) == want


def _windows(rng, shares, n=400, span=64):
    """One resize window per entry of ``shares``: ``n`` requests split
    between two VMs in those proportions, over ``span`` blocks each."""
    out = []
    for share in shares:
        vm = (rng.random(n) >= share).astype(np.int32)
        addr = (rng.integers(0, span, n) + vm * 10_000).astype(np.int32)
        out.append(Trace(addr=addr, is_write=rng.random(n) < 0.3, vm=vm))
    return out


def test_bucket_changes_compile_no_maintenance():
    """An ``EticaCache`` whose largest SSD way count moves between buckets
    from window to window: after the first window has set up, no
    maintenance program compiles, and ``ways_buckets`` counts every
    bucket the windows met."""
    geo = Geometry(num_sets=8, max_ways=16)
    ctrl = EticaCache(EticaConfig(
        dram_capacity=16, ssd_capacity=96, geometry_dram=geo,
        geometry_ssd=geo, resize_interval=400, promo_interval=200,
        pop_capacity=256), num_vms=2)
    compiles = []

    def listen(event, duration, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and "maintenance" in str(kw.get("fun_name", ""))):
            compiles.append(kw["fun_name"])

    met = []
    wins = _windows(np.random.default_rng(5), [0.5, 0.98, 0.5, 0.02])
    ctrl.run(wins[0])
    met.append(ops.ways_bucket_of(ctrl.ways_ssd, geo.max_ways))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for w in wins[1:]:
            ctrl.run(w)
            met.append(ops.ways_bucket_of(ctrl.ways_ssd, geo.max_ways))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert len(set(met)) >= 2, met
    assert set(ctrl.telemetry.ways_buckets) == set(met)
    # one count per promotion interval
    intervals = sum(-(-int(np.bincount(w.vm).max()) // 200) for w in wins)
    assert sum(ctrl.telemetry.ways_buckets.values()) == intervals
