"""The benchmark's traffic generator (``bench/lib/traffic.py``)."""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from bench.lib import traffic
from bench.lib.cell import BENCH

def _mix(name: str = "msr", **over) -> dict:
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    return dict(mix, **over)


@pytest.mark.parametrize("zipf", [0.0, 1.0])
def test_stream_is_deterministic_per_seed(zipf):
    mix = _mix(requests_per_vm=500, passes=2, vm_share_zipf=zipf)
    a, b = traffic.stream(mix, 2**31 + 17), traffic.stream(mix, 2**31 + 17)
    c = traffic.stream(mix, 2**31 + 18)
    for f in ("addr", "is_write", "vm"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.addr, c.addr)
    assert not np.array_equal(a.vm, c.vm)
    assert len(a) == 2 * 12 * 500


@pytest.mark.parametrize("family", sorted(traffic.load_families()))
def test_volume_equals_the_program_generator(family):
    """Every volume, the RAW step included, is draw for draw the one
    ``repro.traces.make`` generates."""
    from repro.traces import make
    spec = traffic.load_families()[family]
    addr, is_write = traffic.volume(spec, 3000, 1234, addr_offset=5 << 20)
    want = make(family, 3000, seed=1234, addr_offset=5 << 20)
    assert np.array_equal(addr, want.addr)
    assert np.array_equal(is_write, want.is_write)


@pytest.mark.parametrize("family", ["src2_0", "ts_0", "wdev_0", "usr_0"])
def test_redirected_reads_target_one_of_the_last_8_writes(family):
    spec = traffic.load_families()[family]
    addr, is_write = traffic.volume(spec, 4000, 99)
    plain, _ = traffic.volume(dataclasses.replace(spec, raw_fraction=0.0),
                              4000, 99)
    moved = np.nonzero(addr != plain)[0]
    assert moved.size > 100
    assert not is_write[moved].any()
    writes = np.nonzero(is_write)[0]
    for i in moved:
        last8 = writes[writes < i][-8:]
        assert addr[i] in addr[last8]


def test_vm_shares_follow_zipf_1_in_every_pass():
    counts = traffic.vm_counts(10_000, 12, 1.0)
    exact = 10_000 * (1.0 / np.arange(1, 13)) / np.sum(1.0 / np.arange(1, 13))
    assert counts.sum() == 10_000
    assert np.all(np.abs(counts - exact) < 1)
    assert counts[0] == 3222
    assert np.array_equal(traffic.vm_counts(10_000, 12, 0.0),
                          [834] * 4 + [833] * 8)
    mix = _mix(requests_per_vm=2000, passes=3, vm_share_zipf=1.0)
    s = traffic.stream(mix, 2**33 + 7)
    per_pass = np.bincount(s.vm.astype(np.int64) + 12 * (np.arange(len(s))
                                                         // 24_000),
                           minlength=12 * 3).reshape(3, 12)
    assert np.all(per_pass == traffic.vm_counts(24_000, 12, 1.0))


def test_window_counts_vary_as_random_arrivals_do():
    """Equal shares give each VM about 833 of every 10,000 arrivals, more
    or fewer from window to window, within a binomial's spread."""
    s = traffic.stream(_mix(passes=2), 2**32 + 5)
    per_window = np.bincount(s.vm.astype(np.int64) + 12 * (np.arange(len(s))
                                                           // 10_000),
                             minlength=12 * 48).reshape(48, 12)
    assert np.all(per_window.sum(axis=1) == 10_000)
    assert len(np.unique(per_window)) > 50
    sd = np.sqrt(10_000 * (1 / 12) * (11 / 12))
    assert 0.7 * sd < per_window.std() < 1.3 * sd
    assert np.all(np.abs(per_window - 10_000 / 12) < 6 * sd)


def test_interleave_keeps_each_vm_in_order():
    parts = [(np.arange(n, dtype=np.int32) + 1000 * v, np.arange(n) % 3 == 0)
             for v, n in enumerate((40, 80, 20))]
    got = traffic.interleave(parts, 5)
    assert np.array_equal(np.bincount(got.vm, minlength=3), [40, 80, 20])
    assert not np.array_equal(got.vm, np.sort(got.vm))
    for v, (a, w) in enumerate(parts):
        assert np.array_equal(got.addr[got.vm == v], a)
        assert np.array_equal(got.is_write[got.vm == v], w)
