#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's compared
numbers over many seeds, and the control's.

    python3 bench/readings.py --workload <cell> --windows <n> \\
        --seeds <s1> <s2> ... [--control-seeds <c1> ...]

For each seed the timed path of a run (``bench/run.py``'s ``measure``)
drives the cell's controller over ``--windows`` resize windows, as many
as a timed run reaches, and its numbers against the plain reference are
printed. For each control seed the reference itself is put in the
program's place, computed in bfloat16, the precision below the float32
the configurations state, and compared with the float32 reference in the
same way. All in one process, so the program compiles once. One JSON
line per reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control(cell, seed: int, windows: int) -> dict:
    """The bfloat16 reference in the program's place, against the
    float32 reference, over the first ``windows`` resize windows."""
    import ml_dtypes

    from bench.lib import traffic
    from bench.lib.compare import compare
    cfg = cell.config
    stream = traffic.stream(cell.traffic, seed)
    r = cfg["resize_interval"]
    ref = cell.reference.make(cfg, cfg["num_vms"])
    low = cell.reference.make(cfg, cfg["num_vms"], ml_dtypes.bfloat16)
    for i in range(windows):
        s = stream.slice(i * r, (i + 1) * r)
        ref.run_window(s.addr, s.is_write, s.vm)
        low.run_window(s.addr, s.is_write, s.vm)
    return compare(low.windows, ref.windows, low.state(), ref.state())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import run
    from bench.lib import cell as cells
    run.use_compile_cache(jax)
    cell = cells.load(args.workload)
    devices = jax.devices()
    for seed in args.seeds:
        t = time.perf_counter()
        out = run.measure(cell, seed, 0.0, False, devices,
                          windows=args.windows)
        print(json.dumps({"workload": cell.name, "side": "program",
                          "seed": seed, "correct": out["correct"],
                          "seconds": time.perf_counter() - t,
                          "numbers": {k: c["value"] for k, c in
                                      out["checks"].items()}}), flush=True)
    for seed in args.control_seeds:
        t = time.perf_counter()
        nums = control(cell, seed, args.windows)
        print(json.dumps({"workload": cell.name, "side": "control",
                          "seed": seed,
                          "seconds": time.perf_counter() - t,
                          "numbers": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
