"""From a profiler trace to the numbers the per-layer metrics read.

The trace is the JAX profiler's ``ProfileData`` (read from the session
in memory, or from an ``.xplane.pb`` file). Its device planes
(``/device:...``) carry one line of XLA program executions (``XLA
Modules``) and one of the operations inside them (``XLA Ops``); the host
plane carries the benchmark's ``bench.window`` annotation around each
controller ``run()`` call.

* traced windows: the ``bench.window`` spans in order, up to the first
  in which no program ran on some device. Every ``run()`` call waits
  for the device inside it, so such a span means the device tracer's
  buffer was full and dropped the rest;
* window: from the first traced span's start to the last one's end;
* busy: the union of the device operations' intervals inside the window
  (the programs' intervals where a device has no operation line),
  averaged over the device planes;
* device time per program: the summed durations of its executions,
  keyed by the program name without its ``(id)`` suffix;
* idle gaps: the stretches of the window with no device operation,
  labelled by the program that last started before the gap and the next
  one to start after it, and by whether the host was inside a
  controller ``run()`` call.
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools
import re

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    programs: dict[str, float]          # device seconds per program
    gaps: list[tuple[str, float]]       # longest idle gaps, labelled
    devices: int
    windows: int                        # bench.window spans traced

    def seconds_matching(self, patterns) -> float | None:
        """Device seconds of the programs any pattern matches; ``None``
        when no such program ran in the window."""
        rx = [re.compile(p) for p in patterns]
        hit = [s for name, s in self.programs.items()
               if any(r.search(name) for r in rx)]
        return sum(hit) if hit else None


def _union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def program_name(raw: str) -> str:
    return _SUFFIX.sub("", raw.strip())


def reduce(ev: dict, top: int = 10) -> TraceSummary:
    """``ev``: ``{"windows": [(start, end)], "devices": [{"modules":
    [(name, start, end)], "ops": [(start, end)]}]}``, times in ns."""
    if not ev["windows"] or not ev["devices"]:
        raise ValueError("trace holds no window or no device plane")
    wins, spans = [], []
    for dev in ev["devices"]:       # program starts, and the latest end so far
        mods = sorted((s, e) for _, s, e in dev["modules"])
        spans.append(([s for s, _ in mods],
                      list(itertools.accumulate((e for _, e in mods), max))))
    for s, e in sorted(ev["windows"]):
        if not all((i := bisect.bisect_right(st, e)) and last[i - 1] >= s
                   for st, last in spans):
            break
        wins.append((s, e))
    if not wins:
        raise ValueError("no program ran in the first traced window")
    w0, w1 = wins[0][0], wins[-1][1]
    busy, programs, gaps = 0.0, {}, []
    inside = _union(wins)
    for dev in ev["devices"]:
        mods = [(n, max(s, w0), min(e, w1)) for n, s, e in dev["modules"]
                if e > w0 and s < w1]
        for n, s, e in mods:
            programs[program_name(n)] = (programs.get(program_name(n), 0.0)
                                         + (e - s) * 1e-9)
        ops = dev["ops"] or [(s, e) for _, s, e in mods]
        merged = _union([(max(s, w0), min(e, w1)) for s, e in ops
                         if e > w0 and s < w1])
        busy += sum(e - s for s, e in merged) * 1e-9
        idle = [(g0, g1) for g0, g1 in zip(
            [w0] + [e for _, e in merged], [s for s, _ in merged] + [w1])
            if g1 > g0]
        by_start = sorted(mods, key=lambda m: m[1])
        starts = [m[1] for m in by_start]
        for g0, g1 in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
            i = bisect.bisect_right(starts, g0) - 1
            j = bisect.bisect_left(starts, g1)
            mid = 0.5 * (g0 + g1)
            where = ("run" if any(s <= mid <= e for s, e in inside)
                     else "harness")
            before = program_name(by_start[i][0]) if i >= 0 else "start"
            after = (program_name(by_start[j][0]) if j < len(by_start)
                     else "end")
            gaps.append((f"{where}: {before} -> {after}", (g1 - g0) * 1e-9))
    n = len(ev["devices"])
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary((w1 - w0) * 1e-9, busy / n,
                        {k: v / n for k, v in programs.items()},
                        gaps[:top], n, len(wins))


def events(profile) -> dict:
    """Pull the window annotations and the device executions out of a
    ``jax.profiler.ProfileData``."""
    out = {"windows": [], "devices": []}
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] += [(e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
            if dev["modules"]:
                out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["windows"] += [(e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events if e.name == WINDOW]
    return out
