"""Kernel micro-benchmarks: Pallas (interpret on CPU) parity + jnp-ref
timing. On-TPU wall time is not measurable here; the derived column
reports the kernel's arithmetic/byte characteristics used in §Roofline.

The ``maintenance/fused_*`` rows are the fused-vs-staged head-to-head
for the between-interval maintenance pipeline: one fused jitted
dispatch (device popularity table + Pallas promote/evict kernels, zero
host round-trips between stages) against the staged path (host
trackers, separate vmapped dispatches, two state syncs per interval) at
8/32/128 VMs — states asserted bit-identical before timing. On CPU the
fused column pays the Pallas *interpreter* tax (the kernels execute
through the interpreter so the real kernel bodies are what is
validated); the quantity that transfers to a real accelerator is the
dispatch structure — 1 fused jitted call and 0 host syncs per interval
vs the staged path's 2 kernel dispatches + 2 device->host state syncs +
per-VM host queue loops.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EticaCache, EticaConfig, Geometry, Trace
from repro.core.simulator import (make_cache, make_cache_batch,
                                  simulate_two_level,
                                  simulate_two_level_batch)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.popularity.kernel import popularity
from repro.kernels.popularity.ref import popularity_ref
from repro.kernels.reuse_distance.kernel import count_between
from repro.kernels.reuse_distance.ref import count_between_ref

from .common import row


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main():
    rng = np.random.default_rng(0)

    # reuse distance: N=4096 window (paper's 10k interval scaled)
    n = 4096
    prev = jnp.asarray(rng.integers(-1, n, n), jnp.int32)
    touch = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    nt = jnp.asarray(rng.integers(0, n + 1, n), jnp.int32)
    us_ref = _time(jax.jit(count_between_ref), prev, touch, nt)
    got = count_between(prev, touch, nt)
    want = count_between_ref(prev, touch, nt)
    ok = bool((np.asarray(got) == np.asarray(want)).all())
    row("kernels/reuse_distance_ref_n4096", us_ref,
        f"pairwise_ops={n*n} kernel_matches_ref={ok}")

    # popularity: N=8192 accesses, 1024 blocks
    n, nb = 8192, 1024
    dist = jnp.asarray(rng.integers(-1, 500, n), jnp.int32)
    served = jnp.asarray(rng.integers(0, 2, n).astype(bool))
    seg = jnp.asarray(rng.integers(0, nb, n), jnp.int32)
    us_ref = _time(jax.jit(lambda d, s, g: popularity_ref(d, s, g, nb, 64.0)),
                   dist, served, seg)
    got = popularity(dist, served, seg, nb, 64.0)
    want = popularity_ref(dist, served, seg, nb, 64.0)
    ok = bool(np.allclose(np.asarray(got), np.asarray(want), atol=1e-5))
    row("kernels/popularity_ref_n8192", us_ref,
        f"exp_evals={n} kernel_matches_ref={ok}")

    # flash attention: B1 H4 S512 D64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    us_ref = _time(jax.jit(
        lambda a, b, c: attention_ref(a, b, c, causal=True)), q, k, v)
    got = flash_attention(q, k, v, causal=True, tq=128, tk=128)
    want = attention_ref(q, k, v, causal=True)
    ok = bool(np.allclose(np.asarray(got), np.asarray(want), atol=2e-5))
    flops = 4 * 1 * 4 * 512 * 512 * 64
    row("kernels/flash_attention_ref_s512", us_ref,
        f"flops={flops} kernel_matches_ref={ok}")

    # batched multi-VM datapath: one vmapped 500-step scan for V VMs vs V
    # sequential dispatches of the same scan (the tentpole's raw win)
    num_vms, steps, sets, ways = 8, 500, 16, 32
    addr = jnp.asarray(rng.integers(0, 4000, (num_vms, steps)), jnp.int32)
    wr = jnp.asarray(rng.random((num_vms, steps)) < 0.4)
    ways_arr = jnp.full(num_vms, 16, jnp.int32)
    dram = make_cache_batch(num_vms, sets, ways)
    ssd = make_cache_batch(num_vms, sets, ways)
    t0 = jnp.zeros(num_vms, jnp.int32)

    def batched():
        return simulate_two_level_batch(addr, wr, dram, ssd, ways_arr,
                                        ways_arr, mode="full", t0=t0)[2]

    def sequential():
        d1, s1 = make_cache(sets, ways), make_cache(sets, ways)
        out = [simulate_two_level(addr[v], wr[v], d1, s1, 16, 16,
                                  mode="full")[2] for v in range(num_vms)]
        return out[-1]

    us_b = _time(batched)
    us_s = _time(sequential)
    row("datapath/two_level_batched_v8", us_b,
        f"steps={num_vms * steps} seq_us={us_s:.1f} "
        f"speedup={us_s / us_b:.2f}x")

    maintenance_bench()


def _maintenance_chunks(num_vms: int, reqs: int, seed: int) -> list[Trace]:
    """One promo-interval window per VM: enough re-references that the
    popularity table fills the partition and the evict path engages."""
    rng = np.random.default_rng(seed)
    return [Trace(addr=(rng.integers(0, 400, reqs) + v * 100_000)
                  .astype(np.int32),
                  is_write=rng.random(reqs) < 0.4)
            for v in range(num_vms)]


def maintenance_bench(vm_counts=(8, 32, 128), reqs=256, rounds=3) -> None:
    """Fused vs staged maintenance at 8/32/128 VMs, states asserted equal."""
    geo = Geometry(num_sets=16, max_ways=32)

    def build(fused: bool) -> EticaCache:
        cfg = EticaConfig(dram_capacity=16 * num_vms,
                          ssd_capacity=64 * num_vms,
                          geometry_dram=geo, geometry_ssd=geo,
                          fused_maintenance=fused)
        cache = EticaCache(cfg, num_vms)
        cache.ways_ssd = np.full(num_vms, 8, np.int32)  # 128-block parts
        return cache

    for num_vms in vm_counts:
        windows = [_maintenance_chunks(num_vms, reqs, r)
                   for r in range(rounds)]
        caches, times = {}, {}
        for fused in (True, False):
            build(fused)._maintain_all(windows[0])      # compile/warm-up
            cache = build(fused)
            t0 = time.time()
            for chunks in windows:
                cache._maintain_all(chunks)
            jax.block_until_ready(cache.ssd)
            times[fused] = time.time() - t0
            caches[fused] = cache
        ok = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(caches[True].ssd, caches[False].ssd)
        ) and caches[True].stats == caches[False].stats
        assert ok, f"fused and staged maintenance diverged at {num_vms} VMs"
        us_f = times[True] / rounds * 1e6
        us_s = times[False] / rounds * 1e6
        row(f"maintenance/fused_{num_vms}vms", us_f,
            f"staged_us={us_s:.1f} speedup={us_s / us_f:.2f}x "
            f"reqs_per_vm={reqs} rounds={rounds} states_equal=True "
            f"pallas=interpret host_syncs_fused=0 host_syncs_staged=2")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
