"""Every Pallas entry point resolves ``interpret=None`` through
``repro.kernels.use_interpret``, and no env override can move a TPU run
off the compiled kernels."""
from __future__ import annotations

import numpy as np
import pytest

import repro.kernels as kernels
from repro.core import Geometry, Policy
from repro.core.simulator import make_cache_batch


def _state():
    return make_cache_batch(2, 4, 4)


def _maintenance_interval():
    from repro.core import popularity as pop
    from repro.kernels.maintenance import ops
    n = 8
    ops.maintenance_interval(
        _state(), pop.table_init(2, 16), np.zeros((2, n), np.int32),
        np.zeros((2, n), bool), np.arange(2 * n).reshape(2, n),
        np.array([n, 0]), np.array([2, 2]), np.array([1, 1]),
        evict_frac=0.05, decay=0.5, clean_quota=1)


def _decode(fn):
    q = np.ones((1, 2, 8), np.float32)
    pool = np.ones((2, 4, 2, 8), np.float32)
    table, lengths = np.zeros((1, 1), np.int32), np.array([3], np.int32)
    if fn == "ops":
        from repro.kernels.decode_attention.ops import decode_attention
        decode_attention(q, (pool, pool), table, lengths)
    else:
        from repro.kernels.decode_attention.kernel import \
            paged_decode_attention
        paged_decode_attention(q, pool, pool, table, lengths)


def _call(entry: str) -> None:
    from repro.kernels.maintenance import kernel as mk
    from repro.kernels.maintenance import ops as mo
    from repro.kernels.popularity import kernel as pk
    from repro.kernels.popularity import ops as po
    from repro.kernels.reuse_distance import kernel as rk
    from repro.kernels.reuse_distance import ops as ro
    st = _state()
    ways = np.array([2, 2], np.int32)
    addr = np.arange(16, dtype=np.int32) % 5
    wr = np.arange(16) % 3 == 0
    grid = np.arange(0, 33, 8, dtype=np.int32)
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    calls = {
        "maintenance.evict": lambda: mo.evict(st, [addr[:3], addr[3:5]]),
        "maintenance.promote": lambda: mo.promote(
            st, [addr[:3], addr[3:5]], ways, ways),
        "maintenance.clean": lambda: mo.clean(st, ways, 1),
        "maintenance.interval": _maintenance_interval,
        "maintenance.evict_scatter": lambda: mk.evict_scatter(
            i32(2, 8, 4), i32(2, 8, 4), i32(2, 8, 4), i32(2, 8), ts=8, qc=8),
        "maintenance.promote_scatter": lambda: mk.promote_scatter(
            i32(2, 8, 4), i32(2, 8, 4), i32(2, 8, 4), i32(2, 8), ways, ways,
            num_sets=8, ts=8, qc=8),
        "maintenance.clean_scatter": lambda: mk.clean_scatter(
            i32(2, 8, 4), i32(2, 8, 4), ways, ways, ways, ts=8),
        "reuse_distance.count_between": lambda: rk.count_between(
            i32(16), i32(16), i32(16)),
        "reuse_distance.reuse_distances": lambda: ro.reuse_distances(
            addr, wr, Policy.RO),
        "reuse_distance.sizing_reduction": lambda: ro.sizing_reduction(
            addr, wr, "urd", grid),
        "reuse_distance.sizing_metrics_batch":
            lambda: ro.sizing_metrics_batch([addr], [wr], "urd", grid),
        "popularity.popularity": lambda: pk.popularity(
            i32(16), np.ones(16, bool), addr, 5, 8.0),
        "popularity.block_popularity": lambda: po.block_popularity(
            addr, i32(16), np.ones(16, bool), 8.0),
        "decode_attention.decode_attention": lambda: _decode("ops"),
        "decode_attention.paged_decode_attention": lambda: _decode("kernel"),
    }
    calls[entry]()


ENTRY_POINTS = [
    "maintenance.evict", "maintenance.promote", "maintenance.clean",
    "maintenance.interval", "maintenance.evict_scatter",
    "maintenance.promote_scatter", "maintenance.clean_scatter",
    "reuse_distance.count_between", "reuse_distance.reuse_distances",
    "reuse_distance.sizing_reduction", "reuse_distance.sizing_metrics_batch",
    "popularity.popularity", "popularity.block_popularity",
    "decode_attention.decode_attention",
    "decode_attention.paged_decode_attention",
]


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_entry_point_resolves_interpret_through_use_interpret(entry,
                                                              monkeypatch):
    asked = []

    def spy():
        asked.append(True)
        return True          # the interpreter: this runs on CPU

    monkeypatch.setattr(kernels, "use_interpret", spy)
    _call(entry)
    assert asked, f"{entry} never asked use_interpret()"


def test_use_interpret_off_tpu_keeps_overrides(monkeypatch):
    monkeypatch.setattr(kernels, "on_tpu", lambda: False)
    monkeypatch.delenv("ETICA_PALLAS_INTERPRET", raising=False)
    assert kernels.use_interpret() is True
    monkeypatch.setenv("ETICA_PALLAS_INTERPRET", "0")
    assert kernels.use_interpret() is False
    monkeypatch.setenv("ETICA_PALLAS_INTERPRET", "1")
    assert kernels.use_interpret() is True
    assert kernels.resolve_interpret(False) is False


def test_tpu_backend_compiles_and_rejects_interpreter_override(monkeypatch):
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    monkeypatch.delenv("ETICA_PALLAS_INTERPRET", raising=False)
    assert kernels.use_interpret() is False
    monkeypatch.setenv("ETICA_PALLAS_INTERPRET", "0")
    assert kernels.use_interpret() is False
    monkeypatch.setenv("ETICA_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="ETICA_PALLAS_INTERPRET"):
        kernels.use_interpret()
    with pytest.raises(RuntimeError, match="ETICA_PALLAS_INTERPRET"):
        kernels.resolve_interpret(None)


def test_tpu_backend_rejects_jnp_sizing_override(monkeypatch):
    from repro.core.baselines import _use_kernel_sizing, urd_metric
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    monkeypatch.delenv("ETICA_SIZING_KERNEL", raising=False)
    assert _use_kernel_sizing() is True
    monkeypatch.setenv("ETICA_SIZING_KERNEL", "0")
    with pytest.raises(RuntimeError, match="ETICA_SIZING_KERNEL"):
        _use_kernel_sizing()
    with pytest.raises(RuntimeError, match="ETICA_SIZING_KERNEL"):
        urd_metric(Geometry(num_sets=8, max_ways=8)).batch(
            [np.arange(8, dtype=np.int32)], [np.zeros(8, bool)])
    monkeypatch.setattr(kernels, "on_tpu", lambda: False)
    assert _use_kernel_sizing() is False
