"""The host-phase attribution (``bench/lib/host_spans.py``) on hand-made
events with hand-checked numbers, and on the small trace recorded on the
chip before the program had spans."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.lib import host_spans as hs
from bench.lib import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"

# Two bench.window spans, [0, 1000) and [1100, 1500) ns; the device runs
# [0, 50), [120, 200), [520, 600), [950, 1000) and [1300, 1500), so it is
# idle [50, 120), [200, 520), [600, 950) and [1000, 1300): 1,040 ns.
DEVICE = {"modules": [("jit_a(1)", 0, 50), ("jit_b(2)", 120, 200),
                      ("jit_c(3)", 520, 600), ("jit_a(4)", 950, 1000),
                      ("jit_b(5)", 1300, 1500)], "ops": []}
EVENTS = {"windows": [(0, 1000), (1100, 1500)], "devices": [DEVICE]}
SPANS = [
    ("bench.window", 0, 1000),
    ("ingest", 40, 100),                    # idle 50 (50-100)
    # [100, 110) idle in `window` under no phase: unattributed 10
    ("window", 100, 900),
    ("sizing", 110, 400),                   # idle 210 (110-120, 200-400)
    ("sync:decompose", 250, 300),           # counts with sizing
    ("sync:demand", 350, 360),
    ("partition", 400, 450),                # idle 50
    ("resize", 450, 500),                   # idle 50
    ("sync:resize", 480, 490),
    # [500, 520) idle under no phase: unattributed 20
    ("datapath", 520, 610),                 # idle 10 (600-610)
    ("stats", 610, 700),                    # idle 90
    ("sync:stats", 620, 690),
    ("maintenance", 700, 800),              # idle 100
    ("telemetry", 800, 850),                # idle 50
    # [850, 950) idle inside bench.window: unattributed 100
    ("bench.window", 1100, 1500),
    ("window", 1110, 1490),
    ("ingest", 1150, 1200),                 # idle 50 (1150-1200)
    ("datapath", 1200, 1400),               # idle 100 (1200-1300)
    ("sync:stats", 1400, 1410),
    # [1000, 1100) harness 100; [1100, 1150) unattributed 50
]


def test_idle_goes_to_the_outermost_phase():
    h = hs.attribute(EVENTS, SPANS, 2)
    ns = {k: round(v * 1e9, 6) for k, v in h.idle_s.items()}
    assert ns == {"ingest": 100.0, "sizing": 210.0, "partition": 50.0,
                  "resize": 50.0, "datapath": 110.0, "stats": 90.0,
                  "maintenance": 100.0, "telemetry": 50.0,
                  "unattributed": 180.0, "harness": 100.0}
    s = tr.reduce(EVENTS)
    assert sum(h.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)


def test_syncs_are_counted_per_site_and_window():
    h = hs.attribute(EVENTS, SPANS, 2)
    assert h.windows == 2
    assert h.syncs == {"decompose": 1, "demand": 1, "resize": 1, "stats": 2}
    assert h.syncs_per_window() == 2.5


def test_metrics_sum_their_phases_per_kreq():
    h = hs.attribute(EVENTS, SPANS, 2)
    req = 2000                           # 2 resize windows of 1,000
    got = {k: h.idle_ms_per_kreq(p, req) for k, p in hs.IDLE_METRICS.items()}
    assert got == pytest.approx({
        "ingest_idle_ms_per_kreq": 100e-6 / 2,
        "partition_idle_ms_per_kreq": 310e-6 / 2,
        "interval_idle_ms_per_kreq": 300e-6 / 2,
        "telemetry_idle_ms_per_kreq": 50e-6 / 2})


def test_only_the_windows_reduce_kept_count():
    h = hs.attribute(EVENTS, SPANS, 1)
    assert h.windows == 1 and sum(h.syncs.values()) == 4
    assert h.idle_s["harness"] == 0.0
    assert h.idle_s["datapath"] == pytest.approx(10e-9)


def test_nothing_to_read_without_program_spans():
    bare = [x for x in SPANS if x[0] == "bench.window"]
    assert hs.attribute(EVENTS, bare, 2) is None
    assert hs.attribute(dict(EVENTS, devices=[]), SPANS, 2) is None


CHIP = DATA / "chip_window.xplane.pb"


def test_chip_trace_without_spans_reads_as_before():
    """The recorded chip trace holds no program span: the host-phase
    reader finds nothing, and the accepted per-layer metrics read what
    they read when it was recorded."""
    import json

    from jax.profiler import ProfileData

    from bench.lib import cell as cells
    from bench.run import LayerContext
    profile = ProfileData.from_file(str(CHIP))
    ev = tr.events(profile)
    s = tr.reduce(ev)
    assert hs.events(profile) == [("bench.window", *ev["windows"][0])]
    assert hs.attribute(ev, hs.events(profile), s.windows) is None
    spec = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    ctx = LayerContext(s, 0, s.windows * 10_000)
    got = {m["name"]: cells.load("paper12.msr").metric_reader(m["name"])
           .read(ctx) for m in spec["per_layer"]}
    assert got == pytest.approx({
        "device_idle_share": 100 * (1 - 1_217_657 / 20_000_000),
        "window_compiles": 0.0, "datapath_ms_per_kreq": None,
        "sizing_ms_per_kreq": 1_204_806e-9 * 1e3 / 10,
        "maintenance_ms_per_kreq": None}, abs=1e-9)
