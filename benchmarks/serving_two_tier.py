"""Beyond-paper benchmark: the ETICA two-tier KV manager vs a global-LRU
write-back manager on a churn-driven multi-tenant serving trace (hit
ratio, host-DMA traffic — the serving analogs of Fig. 13/14), at a
serving-scale population (thousands of sessions, ~1k concurrently live).

Three managers run the SAME arrival/churn stream:

  * ``etica``      — batched controller (fused device maintenance);
  * ``etica-seq``  — the host-dict sequential oracle;
  * ``lru``        — global LRU with datapath write-back.

Strict gates (AssertionError = regression):
  * batched == sequential oracle, bit for bit — Stats, final quotas,
    final slot placements, free-list order;
  * WBWO write bound — ETICA's host-DMA writes are EXACTLY one page per
    appended page (the endurance claim);
  * popularity-table capacity held (``pop_drops == 0``);
  * head-to-head — ETICA strictly beats global-LRU write-back on DMA
    writes (the endurance headline; LRU may hold a few hit-ratio points
    since it never proactively trims to quota — recorded, and sanity-
    bounded rather than asserted away).

``--smoke`` runs a seconds-scale population for CI.
"""
from __future__ import annotations

import numpy as np

from repro.kvcache import GlobalLRUManager, TwoTierConfig, TwoTierKVManager
from repro.launch.serve import run_events
from repro.traces import SessionSpec, generate_sessions

from .common import Timer, row

FULL = dict(events=20_000, live=1024, hbm_pages=512, tenants=4,
            maintenance_interval=64, resize_interval=512, pop_capacity=2048)
SMOKE = dict(events=1_200, live=64, hbm_pages=48, tenants=3,
             maintenance_interval=32, resize_interval=128, pop_capacity=256)


def _mk_cfg(p) -> TwoTierConfig:
    return TwoTierConfig(
        page_size=16, hbm_pages=p["hbm_pages"], num_kv_heads=2, head_dim=8,
        num_layers=1, dtype="float32",
        maintenance_interval=p["maintenance_interval"],
        resize_interval=p["resize_interval"],
        pop_capacity=p["pop_capacity"], materialize=False)


def _bank(cfg: TwoTierConfig, seed=7):
    rng = np.random.default_rng(seed)
    pages = rng.normal(size=(8, 1, cfg.page_size, cfg.num_kv_heads,
                             cfg.head_dim)).astype(np.float32)
    return pages, pages


def _placements(mgr):
    return (dict(mgr.slot_owner), tuple(mgr.free),
            tuple(int(q) for q in mgr.tenant_quota),
            tuple(int(u) for u in mgr.tenant_used))


def drive(mgr, trace, cfg, seed=1):
    kb, vb = _bank(cfg)
    run_events(mgr, trace, kb, vb, decode_every=0, seed=seed)
    return mgr.stats.as_dict()


def main(smoke: bool = False):
    p = SMOKE if smoke else FULL
    cfg = _mk_cfg(p)
    spec = SessionSpec(num_tenants=p["tenants"], target_live=p["live"],
                       max_pages=6)
    trace = generate_sessions(spec, p["events"], seed=1)
    assert smoke or trace.num_sessions >= 1000, trace.num_sessions

    with Timer() as t1:
        m_b = TwoTierKVManager(cfg, p["tenants"], batched=True)
        a = drive(m_b, trace, cfg)
    with Timer() as t2:
        m_s = TwoTierKVManager(cfg, p["tenants"], batched=False)
        a_seq = drive(m_s, trace, cfg)
    with Timer() as t3:
        m_l = GlobalLRUManager(cfg, p["tenants"])
        b = drive(m_l, trace, cfg)

    # gate 1: batched controller == sequential host-dict oracle, bit for bit
    assert a == a_seq, (a, a_seq)
    assert _placements(m_b) == _placements(m_s)
    # gate 2: WBWO endurance bound — exactly one host write per append
    assert a["dma_write_bytes"] == a["appends"] * cfg.page_bytes
    # gate 3: device popularity table big enough to mirror the tracker
    assert a["pop_drops"] == 0
    # gate 4: head-to-head vs push-mode global LRU
    assert a["dma_write_bytes"] < b["dma_write_bytes"], (a, b)
    assert a["hit_ratio"] >= b["hit_ratio"] - 0.1, (a, b)

    n = p["events"]
    row("serving/etica_two_tier", t1.us / n,
        f"sessions={trace.num_sessions} max_live={trace.max_live} "
        f"hit={a['hit_ratio']:.3f} dma_w={a['dma_write_bytes']} "
        f"dma_r={a['dma_read_bytes']} drops={a['pop_drops']}")
    row("serving/etica_sequential_oracle", t2.us / n,
        f"hit={a_seq['hit_ratio']:.3f} bit_identical=True")
    row("serving/global_lru_wb", t3.us / n,
        f"hit={b['hit_ratio']:.3f} dma_w={b['dma_write_bytes']} "
        f"dma_r={b['dma_read_bytes']}")
    row("serving/summary", 0.0,
        f"dma_write_reduction="
        f"{1 - a['dma_write_bytes']/max(b['dma_write_bytes'],1):.3f} "
        f"hit_delta={a['hit_ratio']-b['hit_ratio']:+.3f}")
    return a, b


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    main(smoke="--smoke" in sys.argv[1:])
