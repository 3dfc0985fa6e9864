#!/usr/bin/env python3
"""A traced run of one cell, read for the device-idle time under each of
the controllers' host phases and for the host syncs per resize window.

    python3 bench/host_phases.py --workload <cell> --seed <n> \\
        [--seconds 5]

``bench/run.py --trace 1`` reduces the profile to device time and gives
its metric readers no host span. This makes the same traced measurement
(``bench.run.measure``), keeps the profile's host spans as it is reduced
(``bench/lib/host_spans.py``) and prints, as the last line of standard
output, one JSON object: the traced run's result and ``host_phases``,
which holds the per-layer numbers ``host_spans.IDLE_METRICS`` and
``host_syncs_per_window`` define. Without a TPU it exits 3.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def phases(cell, seed: int, seconds: float, devices) -> dict:
    from bench import run
    from bench.lib import host_spans, trace_reduce

    got = {}
    events = trace_reduce.events

    def keep_spans(profile):
        got["spans"] = host_spans.events(profile)
        got["ev"] = events(profile)
        return got["ev"]

    trace_reduce.events = keep_spans        # measure() imports it per call
    try:
        out = run.measure(cell, seed, seconds, True, devices)
    finally:
        trace_reduce.events = events
    summary = trace_reduce.reduce(got["ev"])
    host = host_spans.attribute(got["ev"], got["spans"], summary.windows)
    if host is None:
        out["host_phases"] = None
        return out
    requests = summary.windows * cell.config["resize_interval"]
    metrics = {"host_syncs_per_window": host.syncs_per_window()}
    for name, names in host_spans.IDLE_METRICS.items():
        metrics[name] = host.idle_ms_per_kreq(names, requests)
    idle = {k: host.idle_ms_per_kreq((k,), requests) for k in host.idle_s}
    inside = sum(v for k, v in host.idle_s.items()
                 if k != host_spans.HARNESS)
    out["host_phases"] = {
        "metrics": metrics, "idle_ms_per_kreq": idle,
        "syncs": host.syncs, "windows": host.windows,
        "requests": requests,
        "idle_s": sum(host.idle_s.values()),
        "window_idle_s": summary.window_s - summary.busy_s,
        "unattributed_share_of_run_idle": (
            host.idle_s[host_spans.UNATTRIBUTED] / inside if inside else None),
    }
    log(f"host phases: {host.windows} windows, idle ms/kreq "
        + ", ".join(f"{k} {v:.6f}" for k, v in idle.items())
        + f"; syncs {host.syncs}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run
    from bench.lib import cell as cells
    cell = cells.load(args.workload)

    import jax
    run.use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"host_phases.py: JAX found no TPU (it found "
            f"{devices[0].platform}); nothing was run")
        return 3
    out = phases(cell, args.seed, args.seconds, devices)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
