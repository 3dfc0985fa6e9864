"""CPU rehearsal of ``consolidation1024.msr`` cut to 8 VMs (16 x 8 caches,
as ``tiny.py`` cuts the other cells): the harness drives ETICA's
VM-sharded path against the process-parallel reference on a one-device
mesh here and on four host devices in a child process, ``correct`` comes
out false when one shard's work goes wrong, and that reference equals
the one-process reference bit for bit."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from bench import run
from bench.lib import traffic
from bench.tests.tiny import tiny_cell

ROOT = Path(__file__).resolve().parents[2]
CELL = "consolidation1024.msr"
VMS = 8


def small_cell(shards: int):
    c = tiny_cell(CELL)
    c.config = dict(c.config, num_vms=VMS, mesh_shards=shards)
    c.traffic = dict(c.traffic, vms=c.traffic["vms"][:VMS])
    return c


def _measure(cell, seed=3, windows=3):
    return run.measure(cell, seed, 0.0, False, jax.devices(),
                       windows=windows)


def test_one_shard_is_correct():
    out = _measure(small_cell(1))
    assert out["correct"], out["checks"]
    assert out["attempted"] == 3 * 2000
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_reference_equals_one_process_reference():
    from bench.reference import etica, etica_mesh
    cfg = small_cell(1).config
    s = traffic.stream(small_cell(1).traffic, 5)
    r = cfg["resize_interval"]
    one = etica.make(cfg, VMS)
    blocks = etica_mesh.make(cfg, VMS, workers=3)       # blocks of 3, 2, 3
    for i in range(3):
        w = s.slice(i * r, (i + 1) * r)
        one.run_window(w.addr, w.is_write, w.vm)
        blocks.run_window(w.addr, w.is_write, w.vm)
    for a, b in zip(one.windows, blocks.windows):
        assert a.keys() == b.keys()
        for k in a:
            assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), k
    sa, sb = one.state(), blocks.state()
    assert sa.keys() == sb.keys()
    for k in sa:
        pairs = zip(sa[k], sb[k]) if isinstance(sa[k], list) else \
            [(sa[k], sb[k])]
        assert len(sa[k]) == len(sb[k]), k
        assert all(x.dtype == y.dtype and np.array_equal(x, y)
                   for x, y in pairs), k
    blocks.close()


# -- four host devices, in a child process -----------------------------------

SHARD = 1                       # the shard whose work a fault spoils


def _fault(kind: str, orig) -> None:
    """Spoil shard ``SHARD``'s datapath ``orig``: its rows of the window
    dropped (``rows_dropped``), or its state left as it was
    (``state_unchanged``)."""
    from repro.core import simulator

    def faulty(addr, is_write, dram, ssd, *rest, **kw):
        per = np.shape(addr)[0] // 4
        rows = slice(SHARD * per, (SHARD + 1) * per)
        if kind == "rows_dropped":
            addr = np.asarray(addr).copy()
            addr[rows] = -1
        out = orig(addr, is_write, dram, ssd, *rest, **kw)
        if kind == "state_unchanged":
            keep = jax.tree.map(
                lambda new, old: jax.device_put(
                    new.at[rows].set(old[rows]), new.sharding),
                out[:2], (dram, ssd))
            out = tuple(keep) + tuple(out[2:])
        return out

    simulator.simulate_two_level_sharded = faulty


def four_shards_main() -> None:
    """One JSON line per run: clean, then each fault."""
    from repro.core import simulator
    assert len(jax.devices()) == 4
    orig = simulator.simulate_two_level_sharded
    for kind in ("none", "state_unchanged", "rows_dropped"):
        if kind != "none":
            _fault(kind, orig)
        out = _measure(small_cell(4))
        print(json.dumps({"kind": kind, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)


def test_four_shards_correct_and_faults_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run(
        [sys.executable, "-c", "from bench.tests.test_bench_consolidation "
         "import four_shards_main; four_shards_main()"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = {r["kind"]: r for r in map(json.loads, p.stdout.splitlines())}
    assert runs["none"]["correct"], runs["none"]["checks"]
    for kind in ("state_unchanged", "rows_dropped"):
        assert not runs[kind]["correct"], (kind, runs[kind]["checks"])
        assert runs[kind]["checks"]["state_diff"]["value"] > 0, kind


def test_bfloat16_control_is_not_correct():
    """The process-parallel reference in bfloat16, the precision below the
    float32 the configuration states, in the program's place."""
    from bench.lib.compare import verdict
    from bench.readings import control
    cell = small_cell(1)
    nums = control(cell, 5, 3)
    assert not verdict(nums, cell.config["limits"]), nums
    assert nums["latency_gap"] > cell.config["limits"]["latency_gap"]
