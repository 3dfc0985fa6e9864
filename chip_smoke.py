#!/usr/bin/env python3
"""Chip smoke test: ETICA's main path, once, on a TPU, checked against
its sequential oracles.

    python chip_smoke.py [--seed 0]             # one chip, all phases
    python chip_smoke.py --chips 4 [--seed 0]   # the sharded path only

Phases, all in this one process, every input generated from ``--seed``:

  * ``paper_etica``: the 12-VM host of ``configs/etica_paper.py`` at its
    published request counts and intervals (20,000 requests per VM,
    resize every 10,000 requests, promotion every 1,000), generator
    working sets at full scale, 1024 sets x 64 ways per VM on both levels
    and 16,384 cache blocks split DRAM:SSD = 1:3. ETICA-Full with
    ``batched=True`` (fused maintenance through the compiled Pallas
    evict/promote kernels) against its ``batched=False`` oracle.
  * ``paper_cleaner``: the same trace again with the background cleaner
    on (``clean_quota=4``, the Pallas clean kernel); it must flush.
  * ``paper_eci``: ECI-Cache over the same 16,384 blocks (URD sizing
    through the Pallas ``count_between`` kernel) against its oracle.
  * ``serving``: the two-tier KV manager on a session churn trace with a
    materialised pool at qwen3-4b's KV geometry (8 KV heads x 128, 16-token
    pages), batched against its oracle; every 8th activation runs the
    paged decode-attention kernel and checks it against
    ``kernels/decode_attention/ref.py``.

Per-VM Stats and allocation histories must equal the oracle's exactly.
``--chips 4`` runs only fig15's 1024-VM consolidation sharded over four
chips (256 VMs per chip) against the same trace on one chip.

Each phase prints its wall time, compile time and a few Stats. The last
line of standard output is one JSON object naming the device. The script
exits non-zero, without that line, when the repo's sources are missing,
when JAX finds no TPU, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


@dataclasses.dataclass(frozen=True)
class HostSize:
    """The paper host phases' deployment; the defaults are the paper's."""
    reqs_per_vm: int = 20_000
    resize_interval: int = 10_000
    promo_interval: int = 1_000
    scale: float = 1.0            # generator working-set scale
    num_sets: int = 1024
    max_ways: int = 64
    total_blocks: int = 16_384    # ~60% of the 26,112-block hot set
    pop_capacity: int = 32_768    # > distinct blocks per VM: no drops


@dataclasses.dataclass(frozen=True)
class ServingSize:
    """The serving phase's deployment: qwen3-4b's KV geometry."""
    events: int = 4_000
    live: int = 256
    tenants: int = 4
    hbm_pages: int = 256
    page_size: int = 16
    num_kv_heads: int = 8
    head_dim: int = 128
    max_pages: int = 6
    pop_capacity: int = 2_048
    decode_every: int = 8


CONSOLIDATION_VMS = 1024          # fig15's full-scale sharded row
CONSOLIDATION_REQS = 150          # requests per VM there
CONSOLIDATION_CHIPS = 4


class CompileMeter:
    """XLA backend-compile time and persistent-cache hits and writes,
    summed from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits,
                self.cache_writes)


def _same_results(label: str, got, want) -> None:
    """Per-VM Stats and allocation histories, exactly equal."""
    if len(got) != len(want):
        raise AssertionError(f"{label}: {len(got)} VMs vs {len(want)}")
    for v, (g, w) in enumerate(zip(got, want)):
        if g.stats != w.stats or not np.array_equal(g.alloc_history,
                                                    w.alloc_history):
            raise AssertionError(
                f"{label}: VM {v} differs from its oracle\n"
                f"  batched: {g.stats} {g.alloc_history}\n"
                f"  oracle:  {w.stats} {w.alloc_history}")


def _summary(results) -> dict:
    agg: dict[str, float] = {}
    for r in results:
        for k, x in r.stats.items():
            agg[k] = agg.get(k, 0.0) + x
    reqs = agg["reads"] + agg["writes"]
    hits = agg["read_hits_l1"] + agg["read_hits_l2"] + agg["write_hits_l2"]
    return {"vms": len(results), "requests": int(reqs),
            "hit_ratio": hits / max(reqs, 1),
            "ssd_writes": int(agg["cache_writes_l2"]),
            "disk_writes": int(agg["disk_writes"]),
            "flushes": int(agg.get("flushes", 0)),
            "pop_drops": int(agg.get("pop_drops", 0)),
            "equal": True}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def paper_mix(size: HostSize, seed: int):
    """The 12-VM mix of ``configs/etica_paper.py``, one address range of
    ``VM_ADDR_STRIDE`` blocks per VM."""
    from repro.configs.etica_paper import CONFIG as PAPER
    from repro.core import interleave
    from repro.traces import VM_ADDR_STRIDE, make
    return interleave(
        [make(name, size.reqs_per_vm, seed=seed + i,
              addr_offset=i * VM_ADDR_STRIDE, scale=size.scale)
         for i, name in enumerate(PAPER.vms)], seed=seed)


def _etica(size: HostSize, batched: bool, clean_quota: int = 0):
    from repro.configs.etica_paper import CONFIG as PAPER
    from repro.core import EticaCache, EticaConfig, Geometry
    geo = Geometry(num_sets=size.num_sets, max_ways=size.max_ways)
    f = PAPER.dram_fraction                        # DRAM:SSD = 1:3
    dram = round(size.total_blocks * f / (1 + f))
    cfg = EticaConfig(dram_capacity=dram,
                      ssd_capacity=size.total_blocks - dram,
                      geometry_dram=geo, geometry_ssd=geo,
                      resize_interval=size.resize_interval,
                      promo_interval=size.promo_interval,
                      pop_capacity=size.pop_capacity,
                      clean_quota=clean_quota, batched=batched)
    return EticaCache(cfg, len(PAPER.vms))


def paper_etica(size: HostSize, trace) -> dict:
    got = _etica(size, batched=True).run(trace)
    want = _etica(size, batched=False).run(trace)
    _same_results("paper_etica", got, want)
    return _summary(got)


def paper_cleaner(size: HostSize, trace) -> dict:
    # the whole trace: SSD blocks turn dirty only on write hits to
    # promoted blocks, and at full working-set scale the first windows
    # leave the SSD level clean
    got = _etica(size, batched=True, clean_quota=4).run(trace)
    want = _etica(size, batched=False, clean_quota=4).run(trace)
    _same_results("paper_cleaner", got, want)
    out = _summary(got)
    if out["flushes"] == 0:
        raise AssertionError("paper_cleaner: the cleaner flushed nothing")
    return out


def paper_eci(size: HostSize, trace) -> dict:
    from repro.configs.etica_paper import CONFIG as PAPER
    from repro.core import Geometry, make_eci_cache
    geo = Geometry(num_sets=size.num_sets, max_ways=size.max_ways)

    def eci(batched: bool):
        return make_eci_cache(size.total_blocks, len(PAPER.vms),
                              geometry=geo,
                              resize_interval=size.resize_interval,
                              sim_chunk=size.promo_interval, batched=batched)

    got = eci(True).run(trace)
    want = eci(False).run(trace)
    _same_results("paper_eci", got, want)
    return _summary(got)


def serving(size: ServingSize, seed: int) -> dict:
    import jax
    from repro.kvcache import TwoTierConfig, TwoTierKVManager
    from repro.launch.serve import run_events
    from repro.traces import SessionSpec, generate_sessions

    cfg = TwoTierConfig(page_size=size.page_size, hbm_pages=size.hbm_pages,
                        num_kv_heads=size.num_kv_heads,
                        head_dim=size.head_dim, num_layers=1,
                        dtype="float32", pop_capacity=size.pop_capacity)
    spec = SessionSpec(num_tenants=size.tenants, target_live=size.live,
                       max_pages=size.max_pages)
    trace = generate_sessions(spec, size.events, seed=seed)
    bank = np.random.default_rng(seed).normal(
        size=(8, 1, size.page_size, size.num_kv_heads, size.head_dim)
    ).astype(np.float32)

    got = TwoTierKVManager(cfg, size.tenants, batched=True)
    run_events(got, trace, bank, bank, decode_every=size.decode_every,
               seed=seed, check_ref=True)
    want = TwoTierKVManager(cfg, size.tenants, batched=False)
    run_events(want, trace, bank, bank, seed=seed)

    a, b = got.stats.as_dict(), want.stats.as_dict()
    if a != b:
        raise AssertionError(f"serving: Stats differ\n  batched: {a}\n"
                             f"  oracle:  {b}")
    for name in ("slot_owner", "free", "tenant_quota", "tenant_used"):
        x, y = getattr(got, name), getattr(want, name)
        if not (x == y if isinstance(x, (dict, list)) else
                np.array_equal(x, y)):
            raise AssertionError(f"serving: {name} differs from the oracle")
    pools = jax.device_get((got.k_pool, got.v_pool, want.k_pool,
                            want.v_pool))
    if not (np.array_equal(pools[0], pools[2])
            and np.array_equal(pools[1], pools[3])):
        raise AssertionError("serving: HBM page pools differ")
    return {"events": size.events, "sessions": trace.num_sessions,
            "activations": a["activations"], "hit_ratio": a["hit_ratio"],
            "decode_checks": a["activations"] // size.decode_every,
            "dma_write_bytes": a["dma_write_bytes"], "equal": True}


def consolidation(num_vms: int, reqs: int, chips: int, seed: int) -> dict:
    """fig15's sharded consolidation on ``chips`` devices against the same
    trace on one device."""
    from benchmarks.fig15_vm_scaling import (consolidation_cache,
                                             consolidation_mix)
    from repro.launch.mesh import make_vm_mesh
    trace = consolidation_mix(num_vms, reqs, seed=seed)
    sharded = consolidation_cache(num_vms, len(trace), make_vm_mesh(chips))
    got = sharded.run(trace)
    devices = {s.device for s in sharded.ssd.tags.addressable_shards}
    if len(devices) != chips:
        raise AssertionError(
            f"consolidation: state on {len(devices)} devices, not {chips}")
    want = consolidation_cache(num_vms, len(trace), None).run(trace)
    _same_results("consolidation", got, want)
    return _summary(got) | {"chips": chips}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _run_phase(name: str, fn, meter: CompileMeter) -> None:
    before = meter.snapshot()
    t0 = time.perf_counter()
    info = fn()
    wall = time.perf_counter() - t0
    sec, n, hits, writes = (a - b for a, b in zip(meter.snapshot(), before))
    fields = {"wall_s": wall, "compile_s": sec, "compiles": n,
              "cache_hits": hits, "cache_writes": writes} | info
    print(f"phase {name}: "
          + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, CONSOLIDATION_CHIPS),
                    default=1,
                    help="4: run only the sharded 1024-VM consolidation")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (it found "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.kernels import use_interpret
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()      # before the first compile
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"pallas_interpret={use_interpret()} compile_cache={cache_dir}",
          flush=True)

    meter = CompileMeter()
    if args.chips == 1:
        host = HostSize()
        trace = paper_mix(host, args.seed)
        phases = [
            ("paper_etica", lambda: paper_etica(host, trace)),
            ("paper_cleaner", lambda: paper_cleaner(host, trace)),
            ("paper_eci", lambda: paper_eci(host, trace)),
            ("serving", lambda: serving(ServingSize(), args.seed)),
        ]
    else:
        phases = [("consolidation", lambda: consolidation(
            CONSOLIDATION_VMS, CONSOLIDATION_REQS, args.chips, args.seed))]
    try:
        for name, fn in phases:
            _run_phase(name, fn, meter)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
