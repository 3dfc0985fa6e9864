"""The comparison that decides ``correct``.

What the timed controller produced (its per-window counts and sizing
decisions, and the state it ends in) against what the plain reference
produces from the same stream. Each number compared has its own limit,
from the configuration's ``limits``:

* ``stats_diff``: per-window, per-VM integer Stats that differ (hits per
  level, SSD writes, disk reads and writes, flushes, popularity drops);
* ``alloc_diff``: per-window sizing decisions that differ (demand and
  allocation per VM and level, and ECI's write policy);
* ``state_diff``: cells of the final cache state that differ (tags,
  last-use times, dirty bits, clocks, popularity-table addresses);
* ``dram_dirty``: dirty blocks the timed run holds in DRAM, which its
  read-only policy forbids (a guarantee of the configuration);
* ``latency_gap``: the widest relative gap of a per-window, per-VM
  float32 latency sum;
* ``score_gap``: the widest gap of a final Eq. 1 popularity score, as a
  share of that VM's highest score.
"""
from __future__ import annotations

import numpy as np

DECISIONS = ("demand", "alloc", "policy", "dram_demand", "dram_alloc",
             "ssd_demand", "ssd_alloc")


def window_deltas(cumulative: list[dict]) -> list[dict]:
    """Per-window records from the controller's cumulative counts."""
    out, prev_s, prev_l = [], None, None
    for rec in cumulative:
        s, lat = rec["stats"], rec["latency"]
        d = dict(rec)
        d["stats"] = s if prev_s is None else s - prev_s
        d["latency"] = lat if prev_l is None else lat - prev_l
        prev_s, prev_l = s, lat
        out.append(d)
    return out


def _count_diff(a, b) -> int:
    if isinstance(a, list) and a and isinstance(a[0], np.ndarray):
        return sum(_count_diff(x, y) for x, y in zip(a, b))
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int(np.sum(a != b))


def compare(prog_windows: list[dict], ref_windows: list[dict],
            prog_state: dict, ref_state: dict) -> dict[str, float]:
    nums: dict[str, float] = {"stats_diff": 0, "alloc_diff": 0}
    gap = 0.0
    if len(prog_windows) != len(ref_windows):
        raise ValueError("program and reference ran different windows")
    for p, r in zip(prog_windows, ref_windows):
        nums["stats_diff"] += int(np.sum(np.rint(p["stats"]) != r["stats"]))
        for k in DECISIONS:
            if k in r:
                nums["alloc_diff"] += _count_diff(p[k], r[k])
        ref_lat = np.asarray(r["latency"], np.float64)
        live = ref_lat > 0
        gap = max(gap, float(np.max(
            np.abs(np.asarray(p["latency"])[live] - ref_lat[live])
            / ref_lat[live], initial=0.0)))
    nums["state_diff"] = sum(_count_diff(prog_state[k], ref_state[k])
                             for k in ref_state if k != "pop_val")
    if "dram_dirty" in prog_state:
        nums["dram_dirty"] = int(np.sum(prog_state["dram_dirty"]))
    nums["latency_gap"] = gap
    if "pop_val" in ref_state:
        sg = 0.0
        for pa, pv, ra, rv in zip(prog_state["pop_addr"],
                                  prog_state["pop_val"],
                                  ref_state["pop_addr"], ref_state["pop_val"]):
            common, ip, ir = np.intersect1d(pa, ra, return_indices=True)
            top = float(np.max(np.abs(rv), initial=0.0))
            if common.size and top > 0:
                sg = max(sg, float(np.max(np.abs(pv[ip] - rv[ir]))) / top)
        nums["score_gap"] = sg
    return nums


def verdict(nums: dict[str, float], limits: dict[str, float]) -> bool:
    return all(nums[k] <= limits[k] for k in nums)
