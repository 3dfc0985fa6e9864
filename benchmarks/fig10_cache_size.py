"""Paper Figs. 10 & 11: per-interval cache sizes estimated by URD vs
POD(RO) vs POD(WBWO), and the average size reduction (paper: POD
allocates 51.7% less on average than URD)."""
from __future__ import annotations

import numpy as np

from repro.core import Policy, demand_blocks, pod, urd
from repro.traces import make

from .common import Timer, row

WORKLOADS = ["hm_1", "proj_0", "rsrch_0", "web_3", "ts_0", "wdev_0",
             "usr_0", "src2_0"]
INTERVAL = 1_000
N_INTERVALS = 10


def main():
    total_urd = total_ro = total_wbwo = 0
    for w in WORKLOADS:
        tr = make(w, INTERVAL * N_INTERVALS, seed=1, scale=0.25)
        sizes_u, sizes_r, sizes_w = [], [], []
        with Timer() as t:
            for win in tr.intervals(INTERVAL):
                sizes_u.append(demand_blocks(urd(win)))
                sizes_r.append(demand_blocks(pod(win, Policy.RO)))
                sizes_w.append(demand_blocks(pod(win, Policy.WBWO)))
        u, r, wb = map(np.mean, (sizes_u, sizes_r, sizes_w))
        total_urd += u
        total_ro += r
        total_wbwo += wb
        row(f"fig10/{w}", t.us / N_INTERVALS,
            f"avg_urd={u:.0f} avg_pod_ro={r:.0f} avg_pod_wbwo={wb:.0f}")
    red = 1 - (total_ro + total_wbwo) / (2 * total_urd)
    row("fig11/average_reduction", 0.0,
        f"pod_vs_urd_size_reduction={red:.3f} (paper: 0.517)")
    return red


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
