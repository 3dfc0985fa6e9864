"""The trace reduction (``bench/lib/trace_reduce.py``) on hand-made
events with hand-checked numbers, and on a small trace recorded on the
chip."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.lib import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"

# one window [100, 1100) ns; three program executions, four operations
EVENTS = {
    "windows": [(100, 1100)],
    "devices": [{
        "modules": [("jit_a(1)", 200, 400), ("jit_b(2)", 500, 600),
                    ("jit_a(3)", 900, 1000)],
        "ops": [(200, 300), (350, 400), (500, 600), (900, 950)],
    }],
}


def test_busy_is_the_union_of_operations():
    s = tr.reduce(EVENTS)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(300e-9)       # 100 + 50 + 100 + 50
    assert s.devices == 1 and s.windows == 1


def test_time_per_program_drops_the_id_suffix():
    s = tr.reduce(EVENTS)
    assert s.programs == pytest.approx({"jit_a": 300e-9, "jit_b": 100e-9})
    assert s.seconds_matching([r"jit_a"]) == pytest.approx(300e-9)
    assert s.seconds_matching([r"jit_a", r"_b$"]) == pytest.approx(400e-9)


def test_a_program_that_did_not_run_reads_none():
    assert tr.reduce(EVENTS).seconds_matching([r"maintenance"]) is None


def test_idle_gaps_longest_first_with_their_neighbours():
    s = tr.reduce(EVENTS, top=4)
    assert [lab for lab, _ in s.gaps[:2]] == ["run: jit_b -> jit_a",
                                              "run: jit_a -> end"]
    assert [g for _, g in s.gaps] == pytest.approx(
        [300e-9, 150e-9, 100e-9, 100e-9])
    assert {lab for lab, _ in s.gaps[2:]} == {"run: start -> jit_a",
                                              "run: jit_a -> jit_b"}


def test_windows_after_the_tracer_buffer_filled_are_left_out():
    ev = {"windows": [(100, 1100), (1200, 1500), (1600, 1900)],
          "devices": [{"modules": [("jit_a(1)", 200, 400),
                                   ("jit_b(2)", 1300, 1400)],
                       "ops": [(200, 400), (1300, 1400)]}]}
    s = tr.reduce(ev)
    assert s.windows == 2
    assert s.window_s == pytest.approx(1400e-9)
    assert s.busy_s == pytest.approx(300e-9)
    with pytest.raises(ValueError):
        tr.reduce(dict(ev, windows=[(0, 150)]))


def test_overlapping_operations_count_once_and_clip_to_the_window():
    ev = {"windows": [(0, 1000)],
          "devices": [{"modules": [("m", -50, 600)],
                       "ops": [(-50, 200), (100, 300), (250, 600)]}]}
    s = tr.reduce(ev)
    assert s.busy_s == pytest.approx(600e-9)
    assert s.programs["m"] == pytest.approx(600e-9)


# The first 20 ms of one `paper12.msr` resize window, traced on a TPU v5
# lite and cut to the device's XLA Modules / XLA Ops lines and the host's
# `bench.window` span. The numbers below were checked against a 1-ns
# timeline of the same events, built straight from `ProfileData`.
CHIP = DATA / "chip_window.xplane.pb"


def test_chip_trace_busy_programs_and_gaps():
    from jax.profiler import ProfileData
    s = tr.reduce(tr.events(ProfileData.from_file(str(CHIP))))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.020, abs=1e-12)
    assert s.busy_s == pytest.approx(1_217_657e-9, abs=1e-12)
    assert s.programs == pytest.approx({"jit__decompose_vmapped": 1_204_806e-9,
                                        "jit__where": 9_271e-9,
                                        "jit__reduce_max": 7_822e-9},
                                       abs=1e-12)
    assert [g for _, g in s.gaps] == pytest.approx(
        [2_564_173e-9, 1_526_384e-9, 1_472_335e-9, 1_450_992e-9,
         1_397_533e-9, 1_364_964e-9, 1_328_378e-9, 1_303_810e-9,
         1_229_377e-9, 1_132_228e-9], abs=1e-12)
    assert s.gaps[0][0] == "run: jit__decompose_vmapped -> jit__reduce_max"
    assert s.seconds_matching([r"_maintenance_impl"]) is None
