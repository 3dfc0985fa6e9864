"""From a profiler trace to the device-idle time under each host phase of
the controllers, and their host syncs per resize window.

The program marks its host work with spans on the profiler's clock
(``repro.runtime.telemetry.span`` and ``sync``), on the controller's
thread: the host line that also carries the benchmark's ``bench.window``
spans.

* ``window``: the body of one resize window's loop iteration; a
  container, not a phase;
* the phases (``PHASES``): ``ingest`` (demux into per-VM sub-traces,
  ``[V, chunk]`` blocks and their upload), ``sizing``, ``partition``,
  ``resize``, ``datapath``, ``stats``, ``maintenance``, ``telemetry``;
* ``sync:<site>``: one device-to-host read each.

In the ``bench.window`` spans that ``trace_reduce.reduce`` kept, the
device-idle time (the complement of the operations' union, computed as
``reduce`` computes it) is given to the outermost phase span over it, so
a ``sync:`` span counts with the phase it lies in and the demux before a
``window`` span counts as ``ingest``. Idle time inside ``bench.window``
under no phase is ``unattributed``; idle time between ``bench.window``
spans is ``harness``. Like ``reduce``'s busy time, each is averaged over
the device planes, so all of them together make the window less busy.
"""
from __future__ import annotations

import dataclasses

from bench.lib.trace_reduce import WINDOW, _union

CONTAINER = "window"
PHASES = ("ingest", "sizing", "partition", "resize", "datapath", "stats",
          "maintenance", "telemetry")
SYNC = "sync:"
UNATTRIBUTED, HARNESS = "unattributed", "harness"

# the per-layer metrics these spans give, and the phases each one sums:
# the once-a-window share setting, and the per-promotion-interval loop
IDLE_METRICS = {
    "ingest_idle_ms_per_kreq": ("ingest",),
    "partition_idle_ms_per_kreq": ("sizing", "partition", "resize"),
    "interval_idle_ms_per_kreq": ("datapath", "stats", "maintenance"),
    "telemetry_idle_ms_per_kreq": ("telemetry",),
}


@dataclasses.dataclass
class HostPhases:
    idle_s: dict[str, float]     # device-idle seconds per phase, and
    #                              under UNATTRIBUTED and HARNESS
    syncs: dict[str, int]        # sync: spans per site
    windows: int                 # `window` spans traced

    def syncs_per_window(self) -> float | None:
        if not self.windows:
            return None
        return sum(self.syncs.values()) / self.windows

    def idle_ms_per_kreq(self, phases, requests: int) -> float:
        return (sum(self.idle_s.get(p, 0.0) for p in phases) * 1e3
                / (requests / 1e3))


def events(profile) -> list[tuple[str, int, int]]:
    """``(name, start, end)``, in ns, of the ``bench.window`` spans and
    the program's spans on the host line that carries them."""
    keep = set(PHASES) | {CONTAINER, WINDOW}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events
                  if e.name in keep or e.name.startswith(SYNC)]
            if any(n == WINDOW for n, _, _ in ev):
                return ev
    return []


def _intersect(a, b):
    """Pieces of the sorted disjoint intervals ``a`` that lie in the
    sorted disjoint labelled intervals ``b``: ``(start, end, label)``."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e, b[j][2]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _outermost(spans):
    """The phase spans no other phase span contains, as sorted disjoint
    ``(start, end, name)``: spans of one thread nest properly."""
    out, end = [], None
    for n, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        if end is None or s >= end:
            out.append((s, e, n))
            end = e
    return out


def attribute(ev: dict, spans, windows: int) -> HostPhases | None:
    """Device-idle time per phase and syncs per site in the first
    ``windows`` ``bench.window`` spans (those ``reduce`` kept); ``ev`` is
    ``trace_reduce.events``' output, ``spans`` this module's. ``None``
    when no program span lies in them, as in a program without spans."""
    wins = sorted(ev["windows"])[:windows]
    if not wins or not ev["devices"]:
        return None
    w0, w1 = wins[0][0], wins[-1][1]
    inside = [(s, e, None) for s, e in _union(wins)]
    mine = [(n, s, e) for n, s, e in spans
            if n != WINDOW and any(a <= s and e <= b for a, b, _ in inside)]
    if not mine:
        return None
    phases = _outermost([x for x in mine if x[0] in PHASES])
    idle_s = dict.fromkeys(PHASES + (UNATTRIBUTED, HARNESS), 0.0)
    for dev in ev["devices"]:
        mods = [(s, e) for _, s, e in dev["modules"] if e > w0 and s < w1]
        ops = dev["ops"] or mods
        merged = _union([(max(s, w0), min(e, w1)) for s, e in ops
                         if e > w0 and s < w1])
        idle = [(g0, g1) for g0, g1 in zip(
            [w0] + [e for _, e in merged], [s for s, _ in merged] + [w1])
            if g1 > g0]
        run = _intersect(idle, inside)
        by_phase = _intersect(run, phases)
        for s, e, n in by_phase:
            idle_s[n] += (e - s) * 1e-9
        idle_s[HARNESS] += (sum(e - s for s, e in idle)
                            - sum(e - s for s, e, _ in run)) * 1e-9
        idle_s[UNATTRIBUTED] += (sum(e - s for s, e, _ in run)
                                 - sum(e - s for s, e, _ in by_phase)) * 1e-9
    n_dev = len(ev["devices"])
    syncs: dict[str, int] = {}
    for n, _, _ in mine:
        if n.startswith(SYNC):
            site = n[len(SYNC):]
            syncs[site] = syncs.get(site, 0) + 1
    return HostPhases({k: v / n_dev for k, v in idle_s.items()}, syncs,
                      sum(n == CONTAINER for n, _, _ in mine))
