#!/usr/bin/env python3
"""Split one XLA program's device time by HLO operation, on a TPU.

    python3 tools/op_split.py --workload paper12.msr --seed 7 \\
        --windows 11 --program _maintenance_impl [--root DIR] [--out FILE]

Builds the benchmark cell's controller (``bench/``), warms every program
shape as ``bench/run.py`` does, runs the first ``--windows`` resize
windows of the stream on a fresh controller under the JAX profiler, and
sums the device time of each operation on the ``XLA Ops`` line that ran
inside an execution of a program matching ``--program``. Prints one JSON
object: the program's executions and device time, its outermost
operations grouped by kind (the HLO instruction name without its numeric
suffix), the largest operations with their HLO text, and the fused
maintenance dispatches per way bucket where the controller counts them
(over the windows up to ``--count-to``). ``--root`` runs the program and the
benchmark of another checkout (a parent commit, for a before/after
split). Exits 3 off a TPU.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from pathlib import Path

_OP = re.compile(r"%?([^ ]+?)(\.\d+)?( =|$)")


def op_kind(name: str) -> str:
    """The kind of an ``XLA Ops`` event: its HLO instruction name without
    the ``%`` and the numeric suffix (``while``, ``fusion``, a kernel's
    name), from an event named by the full instruction text."""
    m = _OP.match(name)
    return m.group(1) if m else name


def split(profile, program: str, top: int = 25) -> dict:
    """Device time of the ``program`` executions in ``profile``, by op.

    An operation that runs inside another one (the body of a ``while``)
    counts in ``top_ops`` but not in ``by_kind``, whose entries are the
    outermost operations and add up to ``ops_s``."""
    rx = re.compile(program)
    by_name, calls, nested, by_kind = {}, {}, set(), {}
    total = outer = 0.0
    runs, lines_seen = 0, set()
    for plane in profile.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        lines_seen |= set(lines)
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines.get("XLA Modules", ())
                      if rx.search(e.name))
        if not mods:
            continue
        runs += len(mods)
        total += sum(e - s for s, e in mods) * 1e-9
        starts = [s for s, _ in mods]
        inside = []
        for e in lines.get("XLA Ops", ()):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns + e.duration_ns <= mods[i][1]:
                inside.append((e.start_ns, -e.duration_ns, e.name))
        enclosing_end = 0
        for start, neg, name in sorted(inside):
            sec = -neg * 1e-9
            by_name[name] = by_name.get(name, 0.0) + sec
            calls[name] = calls.get(name, 0) + 1
            if start - neg <= enclosing_end:
                nested.add(name)
                continue
            enclosing_end = start - neg
            outer += sec
            kind = op_kind(name)
            by_kind[kind] = by_kind.get(kind, 0.0) + sec
    order = sorted(by_name, key=lambda n: -by_name[n])[:top]
    return {
        "program": program, "executions": runs, "device_s": total,
        "ops_s": outer,
        "by_kind": sorted(by_kind.items(), key=lambda kv: -kv[1]),
        "top_ops": [{"op": n[:400], "seconds": by_name[n],
                     "calls": calls[n], "nested": n in nested}
                    for n in order],
        "device_lines": sorted(lines_seen),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=11)
    ap.add_argument("--program", default="_maintenance_impl")
    ap.add_argument("--root", default=str(Path(__file__).resolve()
                                          .parents[1]))
    ap.add_argument("--out", default=None)
    ap.add_argument("--count-to", type=int, default=0,
                    help="run untraced windows up to this index after the "
                         "traced ones, for the way-bucket counts")
    args = ap.parse_args(argv)
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root), str(root / "src")]

    import jax
    import numpy as np
    from jaxlib import _profiler

    from bench.lib import cell as cells
    from bench.lib import traffic
    from bench.run import use_compile_cache, warmup_windows

    use_compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("op_split.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    cell = cells.load(args.workload)
    cfg, ctl = cell.config, cell.controller
    stream = traffic.stream(cell.traffic, args.seed)
    r = cfg["resize_interval"]
    n_windows = len(stream) // r

    def window(i):
        s = stream.slice(i * r, (i + 1) * r)
        return s.addr, s.is_write, s.vm

    if hasattr(ctl, "warm_lengths"):
        counts = np.bincount(
            stream.vm[: n_windows * r].astype(np.int64)
            + cfg["num_vms"] * (np.arange(n_windows * r) // r),
            minlength=cfg["num_vms"] * n_windows)
        ctl.warm_lengths({int(c) for c in counts if c})
    warm = ctl.build(cfg, cfg["num_vms"])
    for i in warmup_windows(ctl, stream, cfg, n_windows):
        ctl.run(warm, *window(i))
    ctl.sync(warm)
    del warm
    timed = ctl.build(cfg, cfg["num_vms"])
    ctl.sync(timed)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    session = _profiler.ProfilerSession(opts)
    for i in range(args.windows):
        ctl.run(timed, *window(i))
    ctl.sync(timed)
    out = split(session.stop_and_get_profile_data(), args.program)
    for i in range(args.windows, args.count_to):
        ctl.run(timed, *window(i))
    buckets = getattr(getattr(timed, "telemetry", None), "ways_buckets", None)
    out.update(workload=args.workload, seed=args.seed, windows=args.windows,
               root=root.name,
               ways_buckets=None if buckets is None else dict(buckets),
               counted_windows=max(args.windows, args.count_to))
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
