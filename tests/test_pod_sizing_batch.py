"""ETICA's POD sizing reduced on the device equals the per-VM oracle.

``reuse.sizing_metrics_batch`` with the POD kinds (``pod_ro``,
``pod_wbwo``) gives every VM's demand and MRC hit counts in one dispatch.
They must equal, VM by VM, what ``pod_distances`` + ``demand_blocks`` +
``hit_counts_at_sizes`` give on the unpadded trace: on one device and on
a four-device mesh (forced CPU host devices, in a subprocess), over
ragged rows with an empty row and a row exactly one bucket long. And
``EticaCache``, whose batched controller sizes by that route, must hand
the partition the same demands and hit curves, log the same allocations
and end in the same state as its sequential oracle (``batched=False``) on
the tiny ``paper12.msr`` cell.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import reuse
from repro.core.policies import Policy

ROOT = Path(__file__).resolve().parents[1]
POLICIES = {"ro": Policy.RO, "wbwo": Policy.WBWO}
# the largest row sets the bucket (512), so it carries no pad; 0 is empty
LENS = (512, 0, 256, 77)
GRID = np.arange(0, 641, 40, dtype=np.int64)


def _rows():
    rng = np.random.default_rng(17)
    addrs = [rng.integers(0, 90, n).astype(np.int32) for n in LENS]
    writes = [rng.random(n) < 0.4 for n in LENS]
    return addrs, writes


def _oracle(policy):
    addrs, writes = _rows()
    demands = np.zeros(len(LENS), np.int64)
    hits = np.zeros((len(LENS), GRID.size), np.int64)
    for v, (a, w) in enumerate(zip(addrs, writes)):
        if a.size:
            r = reuse.pod_distances(a, w, policy)
            demands[v] = reuse.demand_blocks(int(r.max))
            hits[v] = reuse.hit_counts_at_sizes(r.dist, r.served, GRID)
    return demands, hits


def _batched(policy, mesh=None, telemetry=None):
    addrs, writes = _rows()
    dem, hits, _ = reuse.sizing_metrics_batch(
        addrs, writes, reuse.POD_KINDS[policy], GRID, mesh=mesh,
        telemetry=telemetry)
    return dem, hits


def mesh_main() -> None:
    """Under four forced host devices: one JSON line per policy with the
    mesh route's demands, hit counts and gathered bytes."""
    from repro.launch.mesh import make_vm_mesh
    from repro.runtime.telemetry import TelemetryRecorder
    assert len(jax.devices()) == 4
    mesh = make_vm_mesh()
    for name, policy in POLICIES.items():
        rec = TelemetryRecorder()
        dem, hits = _batched(policy, mesh, rec)
        print(json.dumps({"policy": name, "demands": dem.tolist(),
                          "hits": hits.tolist(),
                          "bytes": rec.shard_host_bytes}), flush=True)


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, 'tests'); "
         "from test_pod_sizing_batch import mesh_main; mesh_main()"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["policy"]: r for r in map(json.loads, p.stdout.splitlines())}


@pytest.mark.parametrize("devices", ["one", "mesh4"])
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_pod_sizing_batch_matches_per_vm_oracle(policy, devices, request):
    want_d, want_h = _oracle(POLICIES[policy])
    assert want_d.any() and want_h.any()          # the rows reuse blocks
    if devices == "one":
        got_d, got_h = _batched(POLICIES[policy])
    else:
        out = request.getfixturevalue("four_devices")[policy]
        got_d, got_h = np.array(out["demands"]), np.array(out["hits"])
        # the gather: int32 demand, hit counts and read count per row
        assert out["bytes"] == len(LENS) * (GRID.size + 2) * 4
    np.testing.assert_array_equal(got_d, want_d)
    np.testing.assert_array_equal(got_h, want_h)


def test_etica_batched_sizing_matches_sequential_oracle(monkeypatch):
    from bench.lib import traffic
    from bench.tests.tiny import tiny_cell
    from repro.core import EticaCache, Trace
    from repro.core import controller
    seen = []                  # what each sizing hands to the partition

    def partition(demands, curves, grid, capacity):
        seen.append((demands.copy(), curves.copy()))
        return real(demands, curves, grid, capacity)

    real = controller._partition
    monkeypatch.setattr(controller, "_partition", partition)
    cell = tiny_cell("paper12.msr")
    cfg, ctl = cell.config, cell.controller
    s = traffic.stream(cell.traffic, 2**31 + 101)
    n = 3 * cfg["resize_interval"]
    trace = Trace(addr=s.addr[:n], is_write=s.is_write[:n], vm=s.vm[:n])
    batched = ctl.build(cfg, cfg["num_vms"])
    seq = EticaCache(dataclasses.replace(batched.cfg, batched=False),
                     cfg["num_vms"])
    got = batched.run(trace)
    fed, seen[:] = seen[:], []
    want = seq.run(trace)
    assert len(fed) == len(seen) == 6
    for (d, c), (d0, c0) in zip(fed, seen):
        assert d.dtype == d0.dtype and c.dtype == c0.dtype
        np.testing.assert_array_equal(d, d0)
        np.testing.assert_array_equal(c, c0)
    assert len(batched.logs_dram) == len(seq.logs_dram) == 3
    for a, b in zip(batched.logs_dram + batched.logs_ssd,
                    seq.logs_dram + seq.logs_ssd):
        np.testing.assert_array_equal(a.demands, b.demands)
        np.testing.assert_array_equal(a.alloc, b.alloc)
    assert any(log.demands.any() for log in batched.logs_dram)
    assert any(log.demands.any() for log in batched.logs_ssd)
    assert [r.stats for r in got] == [r.stats for r in want]
    for v in range(cfg["num_vms"]):
        for x, y in ((batched.vm_dram(v), seq.vm_dram(v)),
                     (batched.vm_ssd(v), seq.vm_ssd(v))):
            for f, g in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
                np.testing.assert_array_equal(np.asarray(f), np.asarray(g))
    np.testing.assert_array_equal(batched.t, seq.t)
