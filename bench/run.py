#!/usr/bin/env python3
"""Run one benchmark cell of the cache controllers on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``: the deployment and its controller) and a
traffic mix (``bench/traffic/<name>.json``). One run:

1. set-up: generates the host stream from ``--seed``, warms every program
   shape the stream will use on a throwaway controller, and builds the
   timed controller empty (``setup_s`` ends here);
2. window: drives the timed controller's normal ``run()`` over
   consecutive resize windows of the stream, one call per window, until
   ``--seconds`` have passed; the window in progress then finishes.
   ``requests_per_s`` is every request of those windows over the time
   from the first dispatch to the last host sync;
3. check: runs the plain reference (``bench/reference/``) over the same
   windows and compares what the timed controller produced with it
   (``bench/lib/compare.py``).

With ``--trace 1`` the window runs under the JAX profiler, lasts at most
``TRACE_SECONDS`` (the device tracer keeps about 8 s of the busiest
cell's operations and drops the rest), and the run reports the cell's
per-layer metrics (``bench/metrics/<name>.py``) instead of its
end-to-end ones. The last line of standard output is one
JSON object; the compared numbers and their limits end both it and
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path keys the cache
TRACE_SECONDS = 5.0
WARMUP_WINDOWS = 2                      # the stream prefix warmed up


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache(jax) -> None:
    """Keep every compiled program in the checkout's ``.jax_cache``."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # unbounded, whatever the environment says: with a size limit JAX keeps
    # an access-time file beside each entry, and on the TPU host a lost one
    # made every later write fail, so every run compiled afresh
    jax.config.update("jax_compilation_cache_max_size", -1)


class LayerContext:
    """What a per-layer metric reader sees of a traced run."""

    def __init__(self, trace, window_compiles: int, requests: int):
        self.trace = trace
        self.window_compiles = window_compiles
        self.requests = requests        # of the resize windows traced

    def ms_per_kreq(self, patterns) -> float | None:
        if self.trace is None:
            return None
        s = self.trace.seconds_matching(patterns)
        if s is None:
            return None
        return s * 1e3 / (self.requests / 1e3)


def warmup_windows(ctl, stream, cfg, n_windows: int) -> list[int]:
    """The stream prefix plus every later window that brings a program
    shape the prefix has not: together they compile all the timed window
    can use."""
    r = cfg["resize_interval"]
    seen, picked = set(), []
    for i in range(n_windows):
        s = stream.slice(i * r, (i + 1) * r)
        sig = ctl.signature(s.vm, cfg)
        if i < WARMUP_WINDOWS or not sig <= seen:
            picked.append(i)
            seen |= sig
    return picked


def log_window_times(dt) -> None:
    """Each resize window's host time, summed up: a host stall shows as
    windows far over the median, and ``over_2x_median_s`` is the time
    they took beyond it."""
    import numpy as np
    med = float(np.median(dt))
    q90, q99 = np.quantile(dt, [0.9, 0.99])
    over = float(np.maximum(dt - 2 * med, 0).sum())
    log(f"window times: first {dt[0] * 1e3:.3f} ms, median "
        f"{med * 1e3:.3f} ms, p90 {q90 * 1e3:.3f} ms, "
        f"p99 {q99 * 1e3:.3f} ms, max {dt.max() * 1e3:.3f} ms, "
        f"{int((dt > 2 * med).sum())} over 2x median, "
        f"over_2x_median_s {over:.6f}")


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            windows: int | None = None) -> dict:
    """One run of ``cell``. ``windows``, where given, fixes the number of
    resize windows the timed controller runs in place of ``seconds``."""
    import jax
    import numpy as np

    from bench.lib import traffic
    from bench.lib.compare import compare, verdict, window_deltas
    from bench.lib.compile_meter import CompileMeter
    from bench.lib.trace_reduce import WINDOW, events, reduce

    meter = CompileMeter()
    cfg, ctl = cell.config, cell.controller
    num_vms = cfg["num_vms"]
    t = time.perf_counter()
    stream = traffic.stream(cell.traffic, seed)
    r = cfg["resize_interval"]
    n_windows = len(stream) // r
    log(f"stream: {len(stream)} requests, {n_windows} windows, "
        f"{time.perf_counter() - t:.3f} s")

    def window(i):
        s = stream.slice(i * r, (i + 1) * r)
        return s.addr, s.is_write, s.vm

    t = time.perf_counter()
    if hasattr(ctl, "warm_lengths"):
        counts = np.bincount(
            stream.vm[: n_windows * r].astype(np.int64)
            + num_vms * (np.arange(n_windows * r) // r),
            minlength=num_vms * n_windows)
        ctl.warm_lengths({int(c) for c in counts if c})
    warm = ctl.build(cfg, num_vms)
    picked = warmup_windows(ctl, stream, cfg, n_windows)
    for i in picked:
        ctl.run(warm, *window(i))
    ctl.sync(warm)
    del warm
    timed = ctl.build(cfg, num_vms)
    ctl.sync(timed)
    log(f"warm-up: windows {picked}, {time.perf_counter() - t:.3f} s, "
        f"{meter.compiles} compiles ({meter.seconds:.3f} s), "
        f"{meter.cache_hits} cache hits, {meter.cache_misses} misses")
    before = meter.compiles + meter.cache_hits
    setup_s = time.perf_counter() - T_START

    session = None
    if trace:
        from jaxlib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        session = _profiler.ProfilerSession(opts)
        seconds = min(seconds, TRACE_SECONDS)
    records, ends = [], []
    t0 = time.perf_counter()
    while True:
        if len(records) == n_windows:
            raise RuntimeError(
                f"the stream's cap of {n_windows} resize windows "
                f"({len(stream)} requests) ran out inside the window")
        with jax.profiler.TraceAnnotation(WINDOW):
            ctl.run(timed, *window(len(records)))
        records.append(ctl.observe(timed))
        ends.append(time.perf_counter())
        if (len(records) == windows if windows else
                ends[-1] - t0 >= seconds):
            break
    ctl.sync(timed)
    elapsed = time.perf_counter() - t0
    log_window_times(np.diff([t0] + ends))
    profile = session.stop_and_get_profile_data() if trace else None
    window_compiles = meter.compiles + meter.cache_hits - before
    requests = len(records) * r
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {len(records)} resize windows, {requests} requests, "
        f"{elapsed:.6f} s, {window_compiles} compiles")
    prog_state = ctl.state(timed)
    del timed

    out = {"attempted": requests, "failed": 0,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        t = time.perf_counter()
        summary = reduce(events(profile))
        del profile
        log(f"trace: {summary.windows} of {len(records)} resize windows "
            f"traced, {summary.devices} device(s), window "
            f"{summary.window_s:.6f} s, busy {summary.busy_s:.6f} s, "
            f"reduced in {time.perf_counter() - t:.3f} s")
        ctx = LayerContext(summary, window_compiles, summary.windows * r)
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(ctx)
            if v is not None:           # nothing to read: left out
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"].update(busy_s=summary.busy_s,
                             window_s=summary.window_s)
        top = sorted(summary.programs.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                            "idle_gaps": [[k, v] for k, v in summary.gaps]}
    else:
        values = {"requests_per_s": requests / elapsed, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics

    t = time.perf_counter()
    ref = cell.reference.make(cfg, num_vms)
    for i in range(len(records)):
        ref.run_window(*window(i))
    nums = compare(window_deltas(records), ref.windows, prog_state,
                   ref.state())
    log(f"reference: {len(records)} windows in "
        f"{time.perf_counter() - t:.3f} s")
    limits = cfg["limits"]
    out["correct"] = verdict(nums, limits)
    out["checks"] = {k: {"value": v, "limit": limits[k]}
                     for k, v in nums.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"run.py: no src/repro under {ROOT}: the system under test is "
            f"missing; nothing was run")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import cell as cells
    cell = cells.load(args.workload)

    import jax
    use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"run.py: JAX found no TPU (it found {devices[0].platform}); "
            f"nothing was run")
        return 3
    if len(devices) < cell.chips:
        log(f"run.py: {args.workload} needs {cell.chips} chips, JAX found "
            f"{len(devices)}; nothing was run")
        return 3
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    checks = out.pop("checks")
    out = {"correct": out.pop("correct"), **out, "checks": checks}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
