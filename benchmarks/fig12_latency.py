"""Paper Figs. 12 & 13: mean I/O latency and total hit ratio of the VMs
under ETICA-Full / ETICA-NPE / ECI-Cache at equal total cache space
(paper: 45% lower latency on average; ETICA-NPE 27%; +30% hit ratio)."""
from __future__ import annotations

import numpy as np

from repro.core import EticaCache, make_eci_cache

from .common import (DRAM_CAP, GEO, RESIZE, SSD_CAP, Timer, etica_config,
                     row, vm_mix_source)

VMS = ["hm_1", "ts_0", "usr_0", "web_3", "wdev_0", "src2_0"]


def main(streamed: bool = False):
    trace = vm_mix_source(VMS, streamed=streamed)
    out = {}
    for name, runner in [
        ("etica_full", lambda: EticaCache(etica_config("full"), len(VMS))),
        ("etica_npe", lambda: EticaCache(etica_config("npe"), len(VMS))),
        ("eci_cache", lambda: make_eci_cache(
            DRAM_CAP + SSD_CAP, len(VMS), geometry=GEO,
            resize_interval=RESIZE)),
    ]:
        with Timer() as t:
            res = runner().run(trace)
        lat = np.mean([r.mean_latency for r in res])
        clat = np.mean([r.contended_latency() for r in res])
        hit = np.mean([r.hit_ratio for r in res])
        out[name] = (lat, hit, clat)
        row(f"fig12/{name}", t.us / len(trace),
            f"mean_latency_ms={lat*1e3:.3f} "
            f"contended_ms={clat*1e3:.3f} hit_ratio={hit:.3f}")
        for vm, r in zip(VMS, res):
            row(f"fig12/{name}/{vm}", 0.0,
                f"latency_ms={r.mean_latency*1e3:.3f} hit={r.hit_ratio:.3f}")
    imp_full = 1 - out["etica_full"][0] / out["eci_cache"][0]
    imp_npe = 1 - out["etica_npe"][0] / out["eci_cache"][0]
    imp_cont = 1 - out["etica_full"][2] / out["eci_cache"][2]
    row("fig12/summary", 0.0,
        f"etica_latency_improvement={imp_full:.3f} (paper: 0.45) "
        f"npe={imp_npe:.3f} (paper: 0.27) "
        f"with_ssd_write_contention={imp_cont:.3f} "
        f"hit_gain={out['etica_full'][1]-out['eci_cache'][1]:.3f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    main(streamed="--streamed" in sys.argv)
