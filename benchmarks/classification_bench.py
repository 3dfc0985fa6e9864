"""Beyond-paper: IO-classification head-to-head on the scan-heavy mix.

Open-CAS-style sequential-cutoff bypass (``repro.classify.seq_cutoff``)
vs the unclassified controllers on ``SCAN_HEAVY_MIX`` — two scan
streams (``scan_mix``, ``backup_scan``) consolidated next to two
reuse-friendly victims (``hm_1``, ``src2_0``) whose working sets the
scans flush out of a push-mode cache. Three gates, in order:

  * ``class/match_all_identity`` — a single match-all class produces
    aggregate Stats **bit-identical** to ``classifier=None`` on both
    controllers (the fig15-style equality assert for the classified
    datapath);
  * ``class/chassis_*`` — single-level WB chassis (Centaur) with
    seq-cutoff: **strictly higher read-hit ratio and strictly fewer SSD
    writes** than unclassified, asserted, plus the batched==sequential
    equality of the classified path itself;
  * ``class/etica_*`` — the two-level ETICA controller with the same
    cutoff, recorded (bypass protects the DRAM level from scan churn).

Results are recorded in ``BENCH_classification.json``. ``--smoke`` runs
a CI-sized version of the same protocol, assertions included.
"""
from __future__ import annotations

import dataclasses

from repro.classify import match_all, seq_cutoff
from repro.core import EticaCache, make_centaur
from repro.traces import SCAN_HEAVY_MIX

from .common import GEO, RESIZE, SSD_CAP, Timer, aggregate_stats, \
    etica_config, row, vm_mix

CUTOFF = 48          # blocks of one run before requests go straight to disk
REQS = 8_000
SMOKE_REQS = 2_000


def _read_hit_ratio(agg: dict) -> float:
    return ((agg.get("read_hits_l1", 0.0) + agg["read_hits_l2"])
            / max(agg["reads"], 1))


def _chassis(classifier, batched=True):
    return make_centaur(SSD_CAP, len(SCAN_HEAVY_MIX), geometry=GEO,
                        resize_interval=RESIZE, sim_chunk=500,
                        batched=batched, classifier=classifier)


def _etica(classifier, batched=True):
    cfg = dataclasses.replace(etica_config("full"), batched=batched,
                              classifier=classifier)
    return EticaCache(cfg, len(SCAN_HEAVY_MIX))


def _run(build, trace):
    with Timer() as t:
        res = build().run(trace)
    return aggregate_stats(res), t


def main(smoke: bool = False) -> dict:
    reqs = SMOKE_REQS if smoke else REQS
    trace = vm_mix(SCAN_HEAVY_MIX, reqs=reqs)
    out = {}

    # gate 1: match-all class == no classifier, bit for bit, both layers
    for name, build in [("chassis", _chassis), ("etica", _etica)]:
        agg_none, _ = _run(lambda: build(None), trace)
        agg_ma, _ = _run(lambda: build(match_all()), trace)
        assert agg_none == agg_ma, (
            f"{name}: match-all classifier diverged from classifier=None:\n"
            f"  none:      {agg_none}\n  match_all: {agg_ma}")
    row("class/match_all_identity", 0.0, "stats_equal=True")

    # gate 2: WB chassis, seq-cutoff vs unclassified (strict wins)
    cutoff = seq_cutoff(CUTOFF)
    base, t_base = _run(lambda: _chassis(None), trace)
    cls_b, t_cls = _run(lambda: _chassis(cutoff), trace)
    cls_s, _ = _run(lambda: _chassis(cutoff, batched=False), trace)
    assert cls_b == cls_s, (
        f"classified chassis batched/sequential diverged:\n"
        f"  batched:    {cls_b}\n  sequential: {cls_s}")
    hit_base, hit_cls = _read_hit_ratio(base), _read_hit_ratio(cls_b)
    wr_base, wr_cls = base["cache_writes_l2"], cls_b["cache_writes_l2"]
    assert hit_cls > hit_base, (
        f"seq-cutoff did not raise the chassis read-hit ratio: "
        f"{hit_cls:.4f} <= {hit_base:.4f}")
    assert wr_cls < wr_base, (
        f"seq-cutoff did not cut chassis SSD writes: "
        f"{wr_cls:.0f} >= {wr_base:.0f}")
    out["chassis"] = dict(
        read_hit_unclassified=hit_base, read_hit_classified=hit_cls,
        ssd_writes_unclassified=wr_base, ssd_writes_classified=wr_cls,
        bypassed=cls_b.get("bypassed", 0.0))
    row("class/chassis_unclassified", t_base.us / len(trace),
        f"read_hit={hit_base:.4f} ssd_writes={wr_base:.0f}")
    row("class/chassis_seq_cutoff", t_cls.us / len(trace),
        f"read_hit={hit_cls:.4f} ssd_writes={wr_cls:.0f} "
        f"bypassed={cls_b.get('bypassed', 0):.0f} "
        f"batched_eq_sequential=True")

    # gate 3: ETICA two-level with the same cutoff (recorded)
    e_base, te_b = _run(lambda: _etica(None), trace)
    e_cls, te_c = _run(lambda: _etica(cutoff), trace)
    out["etica"] = dict(
        read_hit_unclassified=_read_hit_ratio(e_base),
        read_hit_classified=_read_hit_ratio(e_cls),
        ssd_writes_unclassified=e_base["cache_writes_l2"],
        ssd_writes_classified=e_cls["cache_writes_l2"],
        bypassed=e_cls.get("bypassed", 0.0),
        pop_drops=e_cls.get("pop_drops", 0.0))
    row("class/etica_unclassified", te_b.us / len(trace),
        f"read_hit={_read_hit_ratio(e_base):.4f} "
        f"ssd_writes={e_base['cache_writes_l2']:.0f}")
    row("class/etica_seq_cutoff", te_c.us / len(trace),
        f"read_hit={_read_hit_ratio(e_cls):.4f} "
        f"ssd_writes={e_cls['cache_writes_l2']:.0f} "
        f"bypassed={e_cls.get('bypassed', 0):.0f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    main(smoke="--smoke" in sys.argv)
