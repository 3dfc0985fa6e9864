"""Tests for the interval telemetry runtime (PR 9).

The load-bearing guarantees, each pinned here:

* **Bit-identity** — threading a configured :class:`TelemetryRecorder`
  through ``EticaCache``, ``PartitionedSingleLevelCache`` or
  ``TwoTierKVManager`` changes *nothing* about cache behaviour: the
  final Stats are byte-equal to a default run.
* **Zero added syncs** — the recorder only consumes host values the
  controller already fetched; the ``jax.device_get`` call count is
  identical with telemetry configured (span timing stays opt-in because
  it is the documented exception).
* **Bounded journal + JSONL spill** — memory stays O(window) while the
  spill file keeps every row; :func:`load_journal` round-trips.
* **Histogram exposition** — golden-pinned render of the cumulative
  ``_bucket``/``_sum``/``_count`` triplet and a strict parser that
  rejects the ways histogram text goes wrong.
* **Overload detection** — LBICA-style flags are exact on synthetic
  hit-ratio collapses, end to end through ``sample_cache``.
* **Live scrape** — the stdlib endpoint serves parseable exposition
  with the telemetry families present.
"""
import collections
import re
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EticaCache, EticaConfig, Geometry, interleave
from repro.core.baselines import make_eci_cache
from repro.kvcache import TwoTierConfig, TwoTierKVManager
from repro.runtime import metrics
from repro.runtime import telemetry as T
from repro.runtime.http import CONTENT_TYPE, MetricsServer
from repro.runtime.metrics import HistogramValue, Metric
from repro.runtime.telemetry import (DISPATCH_BUCKETS, Journal,
                                     OverloadConfig, SpanStats,
                                     TelemetryRecorder, load_journal,
                                     overload_flags)
from repro.traces import (SESSION_ACTIVATE, SESSION_APPEND, SESSION_END,
                          SESSION_NEW, SessionSpec, generate_sessions, make)

GEO = Geometry(num_sets=8, max_ways=16)


def _mix(num_vms=2, n=1000):
    return interleave(
        [make(name, n, seed=i, addr_offset=i * 10_000_000, scale=0.25)
         for i, name in enumerate(["hm_1", "web_3", "usr_0"][:num_vms])],
        seed=42)


def _etica_cfg(**kw):
    kw.setdefault("clean_quota", 2)
    return EticaConfig(dram_capacity=40, ssd_capacity=80,
                       geometry_dram=GEO, geometry_ssd=GEO,
                       resize_interval=600, promo_interval=200, **kw)


def _stats_dicts(res):
    return [dict(r.stats) for r in res]


# ---------------------------------------------------------------------------
# bit-identity + sync-count parity on all three controller families
# ---------------------------------------------------------------------------

class _CountingGet:
    """Wraps jax.device_get, counting calls — the sync budget meter."""

    def __init__(self, real):
        self.real, self.n = real, 0

    def __call__(self, x):
        self.n += 1
        return self.real(x)


def test_etica_bit_identity_and_zero_added_syncs(tmp_path, monkeypatch):
    trace = _mix()
    counter = _CountingGet(jax.device_get)
    monkeypatch.setattr(jax, "device_get", counter)

    base = EticaCache(_etica_cfg(), num_vms=2).run(trace)
    base_syncs = counter.n

    counter.n = 0
    rec = TelemetryRecorder(window=16, spill=tmp_path / "cache.jsonl",
                            overload=OverloadConfig(window=4))
    cache = EticaCache(_etica_cfg(telemetry=rec), num_vms=2)
    res = cache.run(trace)
    rec.journal.close()

    assert _stats_dicts(res) == _stats_dicts(base)
    assert counter.n == base_syncs, (
        "telemetry recorder added device->host syncs")
    # the journal actually recorded the run, interval by interval
    assert rec.journal.total >= 4
    cols = load_journal(tmp_path / "cache.jsonl")
    assert abs(cols["requests"].sum()
               - sum(s["reads"] + s["writes"] for s in _stats_dicts(res))
               ) < 1e-9
    # journal-backed clean_log view keeps the PR 8 cleaner semantics
    logs = cache.clean_log
    assert logs and all(isinstance(c, np.ndarray) for c in logs)
    for v in range(2):
        assert sum(int(c[v]) for c in logs) == res[v].stats["flushes"]


def test_chassis_bit_identity(tmp_path):
    trace = _mix(num_vms=3)
    base = make_eci_cache(120, 3, geometry=GEO,
                          resize_interval=600).run(trace)
    rec = TelemetryRecorder(window=8, spill=tmp_path / "eci.jsonl")
    cache = make_eci_cache(120, 3, geometry=GEO, resize_interval=600,
                           telemetry=rec)
    res = cache.run(trace)
    rec.journal.close()
    assert _stats_dicts(res) == _stats_dicts(base)
    assert rec.journal.total >= 1
    cols = load_journal(tmp_path / "eci.jsonl")
    assert cols["requests"].shape[1] == 3          # per-VM columns


SERVE_CFG = dict(page_size=8, hbm_pages=24, num_kv_heads=2, head_dim=4,
                 num_layers=1, dtype="float32", maintenance_interval=16,
                 resize_interval=64, pop_capacity=128, materialize=False)


def _replay_sessions(mgr, n_events=800):
    tr = generate_sessions(SessionSpec(num_tenants=3, target_live=48,
                                       max_pages=4, lifetime=20),
                           n_events, seed=0)
    rng = np.random.default_rng(7)
    pg = rng.normal(size=(1, mgr.cfg.page_size, mgr.cfg.num_kv_heads,
                          mgr.cfg.head_dim)).astype(np.float32)
    for i in range(len(tr)):
        kind, sid = int(tr.kind[i]), int(tr.sid[i])
        if kind == SESSION_NEW:
            mgr.new_session(sid, int(tr.tenant[i]))
        elif kind == SESSION_APPEND:
            mgr.append_page(sid, pg, pg)
        elif kind == SESSION_ACTIVATE:
            mgr.activate(sid)
            mgr.deactivate(sid)
        elif kind == SESSION_END:
            mgr.end_session(sid)
    return mgr.stats


def test_serving_bit_identity(tmp_path):
    base = _replay_sessions(
        TwoTierKVManager(TwoTierConfig(**SERVE_CFG), num_tenants=3))
    rec = TelemetryRecorder(window=32, spill=tmp_path / "serve.jsonl")
    mgr = TwoTierKVManager(TwoTierConfig(telemetry=rec, **SERVE_CFG),
                           num_tenants=3)
    stats = _replay_sessions(mgr)
    rec.journal.close()
    assert stats.as_dict() == base.as_dict()
    assert rec.journal.total >= 1
    row = rec.journal.last_row()
    assert row["quota"].shape == (3,)              # per-tenant columns
    assert row["overloaded"].shape == (3,)
    cols = load_journal(tmp_path / "serve.jsonl")
    # the journal covers activations up to the LAST maintenance tick;
    # events after it are in Stats but not yet journaled
    assert 0 < cols["requests"].sum() <= stats.activations


# ---------------------------------------------------------------------------
# journal: bounded ring, ordering, spill round-trip
# ---------------------------------------------------------------------------

def test_journal_ring_and_spill_roundtrip(tmp_path):
    spill = tmp_path / "j.jsonl"
    j = Journal(window=4, spill=spill)
    for i in range(10):
        j.append({"x": np.array([i, 2 * i]), "s": i})
    j.close()
    # bounded memory: ring buffers never grow past the window
    assert j.total == 10 and j.retained == 4
    assert j._cols["x"].shape == (4, 2)
    assert np.array_equal(j.column("x"),
                          [[6, 12], [7, 14], [8, 16], [9, 18]])
    assert np.array_equal(j.column("s"), [6, 7, 8, 9])
    assert j.last_row()["s"] == 9
    assert [r["s"] for r in j.rows()] == [6, 7, 8, 9]
    # the spill kept ALL rows, not just the retained window
    cols = load_journal(spill)
    assert np.array_equal(cols["i"], np.arange(10))
    assert cols["x"].shape == (10, 2)
    assert np.array_equal(cols["x"][-4:], j.column("x"))


def test_journal_rejects_bad_shapes_and_schemas(tmp_path):
    with pytest.raises(ValueError):
        Journal(window=0)
    j = Journal(window=4)
    j.append({"x": np.zeros(3)})
    with pytest.raises(ValueError):
        j.append({"x": np.zeros(2)})               # shape drift
    ragged = tmp_path / "ragged.jsonl"
    ragged.write_text('{"i": 0, "a": 1}\n{"i": 1, "b": 2}\n')
    with pytest.raises(ValueError):
        load_journal(ragged)
    garbled = tmp_path / "garbled.jsonl"
    garbled.write_text('{"i": 0}\nnot json\n')
    with pytest.raises(ValueError):
        load_journal(garbled)
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert load_journal(empty) == {}


# ---------------------------------------------------------------------------
# dispatch spans: opt-in timers, golden histogram exposition
# ---------------------------------------------------------------------------

def test_span_timing_opt_in():
    rec = TelemetryRecorder()                      # default: off
    assert rec.span("x") is T._NULL_SPAN
    with rec.span("x") as sp:
        sp.ready(jnp.arange(4))
    assert rec.spans == {}                         # nothing recorded

    rec = TelemetryRecorder(span_timing=True)
    with rec.span("demo") as sp:
        out = jnp.arange(8) * 2
        sp.ready(out)
    s = rec.spans["demo"]
    assert s.n == 1 and s.total > 0.0
    assert int(s.counts.sum()) == 1
    # a span body that raises records nothing
    with pytest.raises(RuntimeError):
        with rec.span("demo"):
            raise RuntimeError("boom")
    assert rec.spans["demo"].n == 1


# reading an event's arguments makes jaxlib build its type, which warns
EVENT_STATS = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")


def _session():
    from jaxlib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return _profiler.ProfilerSession(opts)


def _host_events(profile, marker):
    """(name, start_ns, end_ns, args) of every event on the host line
    that carries the ``marker`` annotation, outer spans first."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats)) for e in line.events]
            if any(n == marker for n, *_ in ev):
                return sorted(ev, key=lambda x: (x[1], -x[2]))
    raise AssertionError(f"no host line carries {marker!r}")


@EVENT_STATS
def test_recorder_span_is_the_profiler_annotation():
    session = _session()
    with T.span("outer", k=1):
        # timing off: in a session the same annotation as span(), no sync
        with TelemetryRecorder().span("quiet", level="dram") as sp:
            sp.ready(jnp.arange(4))
        rec = TelemetryRecorder(span_timing=True)
        with rec.span("timed") as sp:
            sp.ready(jnp.arange(4) + 1)
        got = T.sync("site", jnp.arange(3))
    ev = _host_events(session.stop_and_get_profile_data(), "outer")
    names = [n for n, *_ in ev if n in ("outer", "quiet", "timed",
                                         "sync:site")]
    assert names == ["outer", "quiet", "timed", "sync:site"]
    assert [a for n, *_, a in ev if n == "quiet"] == [{"level": "dram"}]
    assert rec.spans["timed"].n == 1
    assert np.array_equal(got, np.arange(3))


def _phase_sequence(spans, lo, hi):
    """Names of the outermost spans inside ``[lo, hi]``, in order."""
    out, end = [], lo
    for n, s, e, _ in spans:
        if s >= end and e <= hi:
            out.append(n)
            end = e
    return out


@EVENT_STATS
@pytest.mark.parametrize("name", ["paper12.msr", "eci12.msr"])
def test_batched_loops_span_every_phase_and_sync(monkeypatch, name):
    """The tiny benchmark cells under a profiler session: one ``window``
    span per resize window, preceded by its demux under ``ingest`` and
    holding the phases in loop order; one ``sync:`` span per
    ``jax.device_get``, each inside a phase, as many per window as the
    code's sync sites predict; Stats bit-identical to an untraced run."""
    from bench.lib import traffic
    from bench.tests.tiny import tiny_cell
    cell = tiny_cell(name)
    cfg, ctl = cell.config, cell.controller
    s = traffic.stream(cell.traffic, 2**31 + 7)
    r, windows = cfg["resize_interval"], 3
    args = (s.addr[:windows * r], s.is_write[:windows * r],
            s.vm[:windows * r])
    plain = ctl.build(cfg, cfg["num_vms"])
    ctl.run(plain, *args)

    counter = _CountingGet(jax.device_get)
    monkeypatch.setattr(jax, "device_get", counter)
    traced = ctl.build(cfg, cfg["num_vms"])
    session = _session()
    with jax.profiler.TraceAnnotation("test.run"):
        ctl.run(traced, *args)
    profile = session.stop_and_get_profile_data()
    monkeypatch.undo()
    assert traced.stats == plain.stats
    a, b = ctl.state(traced), ctl.state(plain)
    for k in a:
        if isinstance(a[k], list):
            assert all(np.array_equal(x, y) for x, y in zip(a[k], b[k])), k
        else:
            assert np.array_equal(a[k], b[k]), k

    ev = _host_events(profile, "test.run")
    phases = {"ingest", "sizing", "partition", "resize", "datapath",
              "stats", "maintenance", "telemetry"}
    spans = [x for x in ev if x[0] in phases | {"window"}
             or x[0].startswith("sync:")]
    run = next(x for x in ev if x[0] == "test.run")
    assert _phase_sequence(spans, run[1], run[2]) == ["ingest",
                                                     "window"] * windows
    wins = [x for x in spans if x[0] == "window"]
    assert [w[3]["index"] for w in wins] == list(range(windows))
    assert all(w[3]["requests"] == r for w in wins)

    syncs = [x for x in spans if x[0].startswith("sync:")]
    assert len(syncs) == counter.n
    tops = [x for x in spans if x[0] in phases]
    for n, s0, e0, _ in syncs:
        assert any(p0 <= s0 and e0 <= p1 for _, p0, p1, _ in tops), n

    etica = cfg["controller"] == "etica"
    chunk = cfg["promo_interval"] if etica else cfg["sim_chunk"]
    interval = ("datapath stats maintenance telemetry" if etica
                else "datapath stats telemetry")
    head = ("sizing partition sizing partition partition resize" if etica
            else "sizing partition resize")
    loop = re.compile(rf"{head}( (ingest )*{interval})+")
    inner = [x for x in spans if x[0] != "window"]
    for i, (_, w0, w1, _) in enumerate(wins):
        seq = _phase_sequence(inner, w0, w1)
        assert loop.fullmatch(" ".join(seq)), seq
        counts = np.bincount(args[2][i * r:(i + 1) * r],
                             minlength=cfg["num_vms"])
        chunks = -(-int(counts.max()) // chunk)
        live = int((counts > 0).sum())
        sites = collections.Counter(n for n, s0, e0, _ in syncs
                                    if w0 <= s0 and e0 <= w1)
        want = ({"sync:decompose": 2, "sync:demand": 2 * live,
                 "sync:ways": 2, "sync:resize": 1, "sync:clock": chunks,
                 "sync:stats": chunks, "sync:maintenance": chunks}
                if etica else
                {"sync:sizing": 1, "sync:ways": 1, "sync:resize": 1,
                 "sync:clock": chunks, "sync:stats": chunks})
        assert sites == want, (i, sites)


HIST_GOLDEN = """\
# HELP d_seconds dispatch wall-clock
# TYPE d_seconds histogram
d_seconds_bucket{span="x",le="0.001"} 1
d_seconds_bucket{span="x",le="0.01"} 3
d_seconds_bucket{span="x",le="0.1"} 3
d_seconds_bucket{span="x",le="+Inf"} 4
d_seconds_sum{span="x"} 0.5105
d_seconds_count{span="x"} 4
"""


def test_histogram_golden_render_and_parse():
    s = SpanStats(buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.005, 0.5):
        s.observe(v)
    assert list(s.counts) == [1, 2, 0, 1]          # per-bucket, +Inf last
    hv = HistogramValue(s.buckets, tuple(int(c) for c in s.counts),
                        float(s.total))
    m = Metric("d_seconds", "histogram", "dispatch wall-clock")
    m.add({"span": "x"}, hv)
    text = metrics.render([m])
    assert text == HIST_GOLDEN
    fams = metrics.parse_exposition(text)
    assert fams["d_seconds"]["type"] == "histogram"
    key = ("count", ("span", "x"))
    assert fams["d_seconds"]["samples"][key] == 4.0
    assert fams["d_seconds"]["samples"][
        ("bucket", ("le", "+Inf"), ("span", "x"))] == 4.0


def test_dispatch_buckets_are_pinned():
    assert DISPATCH_BUCKETS == (0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                                0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                                1.0, 2.5)


def test_histogram_render_rejections():
    ok = HistogramValue((0.1,), (1, 0), 0.05)
    with pytest.raises(ValueError):                # scalar in histogram
        metrics.render([Metric("h", "histogram", "x").add({}, 1.0)])
    with pytest.raises(ValueError):                # HistogramValue in counter
        metrics.render([Metric("h_total", "counter", "x").add({}, ok)])
    with pytest.raises(ValueError):                # reserved 'le' label
        metrics.render([Metric("h", "histogram", "x").add({"le": "1"}, ok)])
    with pytest.raises(ValueError):                # wrong counts arity
        metrics.render([Metric("h", "histogram", "x")
                        .add({}, HistogramValue((0.1, 0.2), (1, 2), 0.0))])
    with pytest.raises(ValueError):                # bounds not ascending
        metrics.render([Metric("h", "histogram", "x")
                        .add({}, HistogramValue((0.2, 0.1), (1, 2, 3), 0.0))])
    with pytest.raises(ValueError):                # negative count
        metrics.render([Metric("h", "histogram", "x")
                        .add({}, HistogramValue((0.1,), (1, -2), 0.0))])


@pytest.mark.parametrize("bad", [
    # bare sample inside a histogram family
    "# TYPE h histogram\nh 1\n",
    # bucket without the le label
    "# TYPE h histogram\nh_bucket 1\nh_sum 0\nh_count 1\n",
    # missing +Inf bucket
    '# TYPE h histogram\nh_bucket{le="0.1"} 1\nh_sum 0\nh_count 1\n',
    # bucket series not cumulative
    '# TYPE h histogram\nh_bucket{le="0.1"} 2\n'
    'h_bucket{le="+Inf"} 1\nh_sum 0\nh_count 1\n',
    # +Inf bucket disagrees with _count
    '# TYPE h histogram\nh_bucket{le="+Inf"} 1\nh_sum 0\nh_count 2\n',
    # missing _sum/_count
    '# TYPE h histogram\nh_bucket{le="+Inf"} 1\n',
])
def test_histogram_parse_rejections(bad):
    with pytest.raises(ValueError):
        metrics.parse_exposition(bad)


# ---------------------------------------------------------------------------
# overload detection: exactness on synthetic collapses
# ---------------------------------------------------------------------------

def test_overload_flags_pure_function():
    ocfg = OverloadConfig(window=8, drop=0.6, min_requests=32)
    prev_h = np.array([[80.0, 80.0]] * 4)
    prev_r = np.array([[100.0, 100.0]] * 4)
    no_pressure = np.zeros(2, bool)
    # vm0 collapses to 0.3 < 0.6 * 0.8 = 0.48 -> flagged; vm1 holds 0.7
    f = overload_flags(prev_h, prev_r, np.array([30.0, 70.0]),
                       np.array([100.0, 100.0]), no_pressure, ocfg)
    assert f.tolist() == [True, False]
    # below the request floor: no verdict even on a collapse
    f = overload_flags(prev_h, prev_r, np.array([1.0, 70.0]),
                       np.array([10.0, 100.0]), no_pressure, ocfg)
    assert f.tolist() == [False, False]
    # unqualified baseline (all prevs under the floor): no verdict
    f = overload_flags(prev_h / 10, prev_r / 10, np.array([30.0, 70.0]),
                       np.array([100.0, 100.0]), no_pressure, ocfg)
    assert f.tolist() == [False, False]
    # pressure flags regardless of ratios
    f = overload_flags(prev_h, prev_r, np.array([80.0, 80.0]),
                       np.array([100.0, 100.0]),
                       np.array([False, True]), ocfg)
    assert f.tolist() == [False, True]


def _cum(reads, hits):
    """Cumulative per-VM stats dicts from per-interval delta lists."""
    out = []
    for v in range(len(reads[0])):
        out.append({"reads": float(sum(r[v] for r in reads)),
                    "read_hits_l1": float(sum(h[v] for h in hits))})
    return out


def test_overload_through_sample_cache():
    rec = TelemetryRecorder(overload=OverloadConfig(window=4, drop=0.6,
                                                    min_requests=32))
    reads, hits = [], []
    # four healthy intervals at 0.8, then vm0 collapses to 0.3
    for delta_h in ([80, 80], [80, 80], [80, 80], [80, 80], [30, 70]):
        reads.append([100, 100])
        hits.append(delta_h)
        row = rec.sample_cache(_cum(reads, hits))
    assert row["overloaded"].tolist() == [True, False]
    assert rec.journal.column("overloaded")[:-1].sum() == 0
    # recovery interval: baseline window still holds 0.8, 0.7 passes
    reads.append([100, 100])
    hits.append([70, 70])
    row = rec.sample_cache(_cum(reads, hits))
    assert row["overloaded"].tolist() == [False, False]
    # queue pressure path: dirty occupancy pressing the allocation
    row = rec.sample_cache(_cum(reads, hits),
                           alloc_l2=[100, 100], dirty=[96, 10])
    assert row["overloaded"].tolist() == [True, False]


# ---------------------------------------------------------------------------
# exporter + live scrape
# ---------------------------------------------------------------------------

def _demo_recorder():
    rec = TelemetryRecorder(span_timing=True)
    with rec.span("demo") as sp:
        sp.ready(jnp.ones(4))
    rec.sample_cache([{"reads": 100.0, "read_hits_l1": 60.0},
                      {"reads": 50.0, "read_hits_l1": 10.0}])
    return rec


def test_collect_telemetry_families():
    rec = _demo_recorder()
    fams = metrics.parse_exposition(
        metrics.render(metrics.collect_telemetry(rec)))
    assert fams["etica_dispatch_seconds"]["type"] == "histogram"
    assert fams["etica_telemetry_intervals_total"]["samples"][()] == 1.0
    s = fams["etica_interval_requests"]["samples"]
    assert s[(("vm", "0"),)] == 100.0 and s[(("vm", "1"),)] == 50.0
    assert fams["etica_interval_hits"]["samples"][(("vm", "0"),)] == 60.0
    assert fams["etica_overloaded"]["samples"][(("vm", "1"),)] == 0.0
    assert ("count", ("span", "demo")) in \
        fams["etica_dispatch_seconds"]["samples"]


def test_live_scrape_round_trips():
    rec = _demo_recorder()
    with MetricsServer(lambda: metrics.collect_telemetry(rec)) as srv:
        base = "http://%s:%d" % srv.address
        assert srv.url == f"{base}/metrics"
        with urllib.request.urlopen(srv.url) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == CONTENT_TYPE
            body = r.read().decode()
        with urllib.request.urlopen(f"{base}/healthz") as r:
            assert r.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
        # a second scrape sees updated state: the endpoint is live
        rec.sample_cache([{"reads": 120.0, "read_hits_l1": 70.0},
                          {"reads": 60.0, "read_hits_l1": 15.0}])
        with urllib.request.urlopen(srv.url) as r:
            body2 = r.read().decode()
    fams = metrics.parse_exposition(body)
    assert fams["etica_telemetry_intervals_total"]["samples"][()] == 1.0
    assert fams["etica_dispatch_seconds"]["type"] == "histogram"
    fams2 = metrics.parse_exposition(body2)
    assert fams2["etica_telemetry_intervals_total"]["samples"][()] == 2.0
    assert fams2["etica_interval_requests"]["samples"][(("vm", "0"),)] == 20.0


def test_scrape_collector_failure_is_500_not_crash():
    def boom():
        raise RuntimeError("collector exploded")
    with MetricsServer(boom) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url)
        assert ei.value.code == 500
        # the server thread survived the failing scrape
        base = "http://%s:%d" % srv.address
        with urllib.request.urlopen(f"{base}/healthz") as r:
            assert r.read() == b"ok\n"
