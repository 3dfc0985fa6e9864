"""The main path's Pallas kernels compile for a TPU v5e at real shapes.

Compiled against a described ``v5e:2x2`` topology (no chip needed): the
TPU compiler installed with jaxlib refuses block specs and kernel bodies
that the Pallas interpreter happily runs, so these guard every change to
the kernels. Shapes are the paper host's (12 VMs, 1024 sets x 64 ways,
1024-request maintenance windows), a 16,384-request sizing window, and
qwen3-4b's KV geometry (8 KV heads x 128) for decode attention. Each test
asserts the kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

V, S, W = 12, 1024, 64          # paper host: VMs x sets x ways
WINDOW = 1024                   # maintenance window bucket
POP_K = 8192                    # popularity-table slots per VM


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # these compiles cannot be read back without a chip: keep them out of
    # any persistent cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiles_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_evict_kernel(spec):
    from repro.kernels.maintenance.kernel import evict_scatter
    st = spec((V, S, W))
    _compiles_kernel(
        lambda t, l, d, q: evict_scatter(t, l, d, q, interpret=False),
        st, st, st, spec((V, 4096)))


def test_promote_kernel(spec):
    from repro.kernels.maintenance.kernel import promote_scatter
    st = spec((V, S, W))
    _compiles_kernel(
        lambda t, l, d, q, w, ts: promote_scatter(
            t, l, d, q, w, ts, num_sets=S, interpret=False),
        st, st, st, spec((V, POP_K)), spec((V,)), spec((V,)))


def test_clean_kernel(spec):
    from repro.kernels.maintenance.kernel import clean_scatter
    st = spec((V, S, W))
    _compiles_kernel(
        lambda d, l, w, lc, ic: clean_scatter(d, l, w, lc, ic,
                                              interpret=False),
        st, st, spec((V,)), spec((V,)), spec((V,)))


def test_fused_maintenance(spec):
    from repro.core import popularity as pop
    from repro.core.simulator import CacheState
    from repro.kernels.maintenance import ops

    def step(tags, lru, dirty, taddr, tval, dist, served, waddr, wlen, ways,
             t):
        return ops._maintenance_impl(
            CacheState(tags, lru, dirty), pop.PopularityTable(taddr, tval),
            dist, served, waddr, wlen, ways, t, evict_frac=0.05, decay=0.5,
            clean_quota=4, ts=ops.DEFAULT_TS, qc=ops.DEFAULT_QC,
            interpret=False)

    st = spec((V, S, W))
    _compiles_kernel(step, st, st, spec((V, S, W), jnp.bool_),
                     spec((V, POP_K)), spec((V, POP_K), jnp.float32),
                     spec((V, WINDOW)), spec((V, WINDOW), jnp.bool_),
                     spec((V, WINDOW)), spec((V,)), spec((V,)), spec((V,)))


@pytest.mark.parametrize("ways_bucket", [1, 2])
def test_fused_maintenance_ways_bucket(spec, ways_bucket):
    """The fused dispatch on the leading ways: one- and two-way strips
    (a lane axis far under 128) for the three kernels."""
    from repro.core import popularity as pop
    from repro.core.simulator import CacheState
    from repro.kernels.maintenance import ops

    def step(tags, lru, dirty, taddr, tval, dist, served, waddr, wlen, ways,
             t):
        return ops._maintenance_impl(
            CacheState(tags, lru, dirty), pop.PopularityTable(taddr, tval),
            dist, served, waddr, wlen, ways, t, evict_frac=0.05, decay=0.5,
            clean_quota=4, ts=ops.DEFAULT_TS, qc=ops.DEFAULT_QC,
            interpret=False, ways_bucket=ways_bucket)

    st = spec((V, S, W))
    _compiles_kernel(step, st, st, spec((V, S, W), jnp.bool_),
                     spec((V, POP_K)), spec((V, POP_K), jnp.float32),
                     spec((V, WINDOW)), spec((V, WINDOW), jnp.bool_),
                     spec((V, WINDOW)), spec((V,)), spec((V,)), spec((V,)))


def test_sizing_reduction(spec):
    from repro.kernels.reuse_distance import ops
    n = 16_384
    grid = spec((17,))
    _compiles_kernel(
        lambda a, w, nv, g: ops._sizing_reduce_vmapped(
            a, w, nv, g, kind="urd", interpret=False, ti=256, tj=512),
        spec((V, n)), spec((V, n), jnp.bool_), spec((V,)), grid)


def test_paged_decode_attention(spec):
    from repro.kernels.decode_attention.kernel import paged_decode_attention
    pool = spec((64, 16, 8, 128), jnp.float32)
    _compiles_kernel(
        lambda q, k, v, pt, n: paged_decode_attention(q, k, v, pt, n,
                                                      interpret=False),
        spec((4, 32, 128), jnp.float32), pool, pool, spec((4, 6)),
        spec((4,)))


def test_count_between_and_popularity(spec):
    from repro.kernels.popularity.kernel import popularity
    from repro.kernels.reuse_distance.kernel import count_between
    n = 10_240
    _compiles_kernel(
        lambda p, t, nt: count_between(p, t, nt, interpret=False),
        spec((n,)), spec((n,)), spec((n,)))
    _compiles_kernel(
        lambda d, s, g: popularity(d, s, g, 3000, np.float32(4096.0),
                                   interpret=False),
        spec((n,)), spec((n,), jnp.bool_), spec((n,)))
