"""CPU rehearsal of every cell at a tiny size: the harness drives the
timed path, the plain reference agrees with it, driving ``run()`` one
resize window at a time equals one call over the whole stream, and
``correct`` comes out false for each fault a cell can have. (A cell on
one chip has no exchange between chips to leave out.)"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import run
from bench.lib import traffic
from bench.tests.tiny import CELLS, tiny_cell

DATAPATH = {"etica": "simulate_two_level_batch",
            "eci": "simulate_single_level_batch"}


def _measure(cell, seed=3, windows=3):
    return run.measure(cell, seed, 0.0, False, jax.devices(),
                       windows=windows)


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct(name):
    out = _measure(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] == 3 * 2000
    assert out["metrics"]["requests_per_s"]["value"] > 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_window_by_window_equals_one_run(name):
    cell = tiny_cell(name)
    cfg, ctl = cell.config, cell.controller
    s = traffic.stream(cell.traffic, 11)
    r = cfg["resize_interval"]
    n = 4 * r
    whole = ctl.build(cfg, cfg["num_vms"])
    ctl.run(whole, s.addr[:n], s.is_write[:n], s.vm[:n])
    parts = ctl.build(cfg, cfg["num_vms"])
    for i in range(0, n, r):
        ctl.run(parts, s.addr[i:i + r], s.is_write[i:i + r],
                s.vm[i:i + r])
    assert whole.stats == parts.stats
    a, b = ctl.state(whole), ctl.state(parts)
    for k in a:
        if isinstance(a[k], list):
            assert all(np.array_equal(x, y) for x, y in zip(a[k], b[k])), k
        else:
            assert np.array_equal(a[k], b[k]), k


def _datapath_fault(monkeypatch, cell, kind):
    from repro.core import simulator
    fname = DATAPATH[cell.config["controller"]]
    orig = getattr(simulator, fname)

    def faulty(addr, is_write, *states_and_rest, **kw):
        if kind == "half_batch":
            addr = np.asarray(addr).copy()
            addr[addr.shape[0] // 2:] = -1
        out = orig(addr, is_write, *states_and_rest, **kw)
        if kind == "state_unchanged":
            n_states = 2 if fname == "simulate_two_level_batch" else 1
            out = tuple(states_and_rest[:n_states]) + tuple(out[n_states:])
        if kind == "altered_count" and np.all(np.asarray(kw["t0"]) == 0):
            st = out[-2]
            hits = np.asarray(st.read_hits_l2).copy()
            hits[0] += 1
            out = out[:-2] + (st._replace(read_hits_l2=hits), out[-1])
        return out

    monkeypatch.setattr(simulator, fname, faulty)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "altered_count"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(monkeypatch, name, kind):
    cell = tiny_cell(name)
    _datapath_fault(monkeypatch, cell, kind)
    out = _measure(cell)
    assert not out["correct"], (kind, out["checks"])
    if kind == "altered_count":
        assert out["checks"]["stats_diff"]["value"] == 1


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_is_not_correct(name):
    """The plain reference computed in bfloat16, the precision below the
    float32 the configurations state, put in the program's place."""
    from bench.lib.compare import verdict
    from bench.readings import control
    cell = tiny_cell(name)
    nums = control(cell, 5, 3)
    assert not verdict(nums, cell.config["limits"]), nums
    assert nums["latency_gap"] > cell.config["limits"]["latency_gap"]
