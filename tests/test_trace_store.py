"""The streaming trace-store subsystem, end to end.

Covers the new ingestion layer (``repro.traces.store`` +
``repro.traces.stream``) and its controller integration:

  * exact ``Trace`` -> ``TraceStore`` -> ``Trace`` round-trips (incl.
    shard-boundary crossing, append resume, vm-less stores);
  * the MSR-Cambridge CSV and blktrace text parsers on fixture logs;
  * the stable-sort per-VM demux (``split_by_vm`` and the shard-level
    streaming demux) against the ``for_vm`` boolean-mask oracle,
    including ragged windows and VMs absent from whole windows;
  * streamed-vs-in-memory **bit-identical** aggregate Stats for both
    controllers (the acceptance bar for the whole subsystem);
  * the batched ECI policy chooser against its host-loop oracle.
"""
import numpy as np
import pytest

from repro.core import (EticaCache, EticaConfig, Geometry, Policy, Trace,
                        interleave, make_eci_cache, pad_batch, split_by_vm)
from repro.core.baselines import eci_policy
from repro.traces import (VM_ADDR_STRIDE, StreamingTraceSource, TraceStore,
                          make, make_store, parse_blktrace, parse_msr_csv,
                          window_source)
from repro.traces.store import main as store_cli

GEO = Geometry(num_sets=8, max_ways=16)


def _mixed_trace(num_vms=3, reqs=2000, workloads=("hm_1", "usr_0", "web_3")):
    return interleave(
        [make(n, reqs, seed=i, addr_offset=i * VM_ADDR_STRIDE, scale=0.25)
         for i, n in enumerate(workloads[:num_vms])], seed=0)


def _assert_trace_equal(a: Trace, b: Trace):
    assert np.array_equal(np.asarray(a.addr), np.asarray(b.addr))
    assert np.array_equal(np.asarray(a.is_write), np.asarray(b.is_write))
    if a.vm is None or b.vm is None:
        assert a.vm is None and b.vm is None
    else:
        assert np.array_equal(np.asarray(a.vm), np.asarray(b.vm))


# ---------------------------------------------------------------------------
# store round-trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shard_size", [64, 700, 10_000])
def test_store_roundtrip_exact(tmp_path, shard_size):
    trace = _mixed_trace(reqs=600)
    store = TraceStore.from_trace(tmp_path / "s", trace,
                                  shard_size=shard_size)
    assert len(store) == len(trace)
    _assert_trace_equal(store.to_trace(), trace)
    # re-open read-only: same contents, mmap-backed shards
    ro = TraceStore.open(tmp_path / "s")
    assert len(ro) == len(trace)
    assert ro.num_vms == 3 and ro.has_vm
    assert ro.num_shards == -(-len(trace) // shard_size)
    _assert_trace_equal(ro.to_trace(), trace)
    # windowed reads equal in-memory slicing
    for i, win in enumerate(ro.iter_windows(257)):
        _assert_trace_equal(win, trace[i * 257: (i + 1) * 257])


def test_store_append_resume_and_vmless(tmp_path):
    t = _mixed_trace(reqs=400)
    a, b = t[:123], t[123:]
    with TraceStore.create(tmp_path / "s", shard_size=100) as store:
        store.append(a)
    with TraceStore.open(tmp_path / "s", mode="a") as store:
        store.append(b)
    _assert_trace_equal(TraceStore.open(tmp_path / "s").to_trace(), t)

    # vm-less store: no vm column on disk, vm=None round-trip
    plain = Trace(np.asarray(t.addr), np.asarray(t.is_write))
    store = TraceStore.from_trace(tmp_path / "p", plain, shard_size=64)
    assert not store.has_vm and store.num_vms is None
    _assert_trace_equal(store.to_trace(), plain)
    with pytest.raises(ValueError):
        with TraceStore.open(tmp_path / "p", mode="a") as w:
            w.append(t)          # mixing vm-tagged into a vm-less store


def test_store_create_and_mode_guards(tmp_path):
    TraceStore.from_trace(tmp_path / "s", _mixed_trace(reqs=50))
    with pytest.raises(FileExistsError):
        TraceStore.create(tmp_path / "s")
    ro = TraceStore.open(tmp_path / "s")
    with pytest.raises(PermissionError):
        ro.append(_mixed_trace(reqs=10))


def test_unflushed_reads_rejected(tmp_path):
    """Reading past unflushed appends must fail loudly, not short-read."""
    t = _mixed_trace(reqs=50)
    store = TraceStore.create(tmp_path / "s", shard_size=1000)
    store.append(t)
    assert len(store) == len(t)      # logical length counts the buffer
    with pytest.raises(RuntimeError, match="unflushed"):
        store.to_trace()
    with pytest.raises(RuntimeError, match="unflushed"):
        store.read(0, 10)
    store.flush()
    _assert_trace_equal(store.to_trace(), t)   # flushed: reads see it all
    store.close()


# ---------------------------------------------------------------------------
# external-format parsers
# ---------------------------------------------------------------------------

MSR_FIXTURE = """\
Timestamp,Hostname,DiskNumber,Type,Offset,Size,ResponseTime
128166372003061629,hm,0,Read,8192,4096,151
128166372016382155,hm,0,Write,12288,8192,512
128166372033741215,prxy,1,Read,0,4096,426
128166372033744415,hm,0,Read,8192,512,90
not,a,real,line
"""


def test_parse_msr_csv():
    chunks = list(parse_msr_csv(MSR_FIXTURE.splitlines(), block_size=4096))
    t = Trace.concat(chunks)
    # row 2 spans blocks 3..4 (8 KiB write at offset 12 KiB)
    assert np.asarray(t.addr).tolist() == [2, 3, 4, 0, 2]
    assert np.asarray(t.is_write).tolist() == [False, True, True, False,
                                               False]
    # vm ids per (host, disk) first appearance: hm.0 -> 0, prxy.1 -> 1
    assert np.asarray(t.vm).tolist() == [0, 0, 0, 1, 0]


BLKTRACE_FIXTURE = """\
  8,16   1        1     0.000000000  1234  Q   R 8 + 8 [fio]
  8,16   1        2     0.000104000  1234  D   R 8 + 8 [fio]
  8,32   0        3     0.000221000  1235  Q  WS 16 + 16 [fio]
  8,16   1        4     0.000300000  1234  C   R 8 + 8 [0]
  8,16   1        5     0.000412000  1234  Q   W 24 + 8 [fio]
CPU0 (fio): reads queued: 1
"""


def test_parse_blktrace():
    chunks = list(parse_blktrace(BLKTRACE_FIXTURE.splitlines(),
                                 block_size=4096))
    t = Trace.concat(chunks)
    # Q events only; sectors are 512 B: 8+8 -> block 1, 16+16 -> blocks
    # 2..3, 24+8 -> block 3 (one 4 KiB block each)
    assert np.asarray(t.addr).tolist() == [1, 2, 3, 3]
    assert np.asarray(t.is_write).tolist() == [False, True, True, True]
    assert np.asarray(t.vm).tolist() == [0, 1, 1, 0]   # per-device vms


def test_store_import_cli(tmp_path, capsys):
    csv = tmp_path / "t.csv"
    csv.write_text(MSR_FIXTURE)
    assert store_cli(["import", "--format", "msr", str(csv),
                      str(tmp_path / "s"), "--shard-size", "2"]) == 0
    assert store_cli(["info", str(tmp_path / "s")]) == 0
    out = capsys.readouterr().out
    assert "imported 5 requests" in out and "num_vms=2" in out
    store = TraceStore.open(tmp_path / "s")
    assert len(store) == 5 and store.num_shards == 3


# ---------------------------------------------------------------------------
# per-VM demux: one stable sort == V boolean-mask scans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_split_by_vm_matches_for_vm(seed):
    rng = np.random.default_rng(seed)
    n, v = 500, 5
    t = Trace(rng.integers(0, 64, n).astype(np.int32),
              rng.random(n) < 0.4,
              rng.integers(0, v, n).astype(np.int32))
    subs = split_by_vm(t, v)
    for vm_id in range(v):
        _assert_trace_equal(subs[vm_id], t.for_vm(vm_id))
    # vm-less windows keep the shared-window convention
    plain = Trace(np.asarray(t.addr), np.asarray(t.is_write))
    assert all(s is plain for s in split_by_vm(plain, 3))


def test_streaming_demux_matches_split_across_shards(tmp_path):
    """Shard-level demux + window binary search == per-window split, even
    when windows straddle shard boundaries and VMs skip whole windows."""
    rng = np.random.default_rng(7)
    n, v = 1000, 4
    vm = rng.integers(0, v, n).astype(np.int32)
    vm[100:400] = 2          # VMs 0,1,3 absent for a long stretch
    t = Trace(rng.integers(0, 64, n).astype(np.int32), rng.random(n) < 0.3,
              vm)
    store = TraceStore.from_trace(tmp_path / "s", t, shard_size=333)
    src = StreamingTraceSource(TraceStore.open(tmp_path / "s"), num_vms=v,
                               window=170, chunk=50)
    wins = list(src.windows())
    ref = list(t.intervals(170))
    assert len(wins) == len(ref)
    for win, rw in zip(wins, ref):
        ref_subs = split_by_vm(rw, v)
        for a, b in zip(win.subs, ref_subs):
            _assert_trace_equal(a, b)


def test_stream_blocks_padding_ragged_and_empty_vms(tmp_path):
    """[V, chunk] blocks match pad_batch on the reference chunk lists —
    including all-empty VMs (all-pad rows) and ragged tails — with and
    without prefetch."""
    t = _mixed_trace(reqs=300)           # 900 requests, 3 VMs
    # VM 3 never appears: rectangular rows must still be emitted for it
    store = TraceStore.from_trace(tmp_path / "s", t, shard_size=256)
    for prefetch in (True, False):
        src = StreamingTraceSource(TraceStore.open(tmp_path / "s"),
                                   num_vms=4, window=400, chunk=150,
                                   prefetch=prefetch)
        for win, rw in zip(src.windows(), t.intervals(400)):
            lists = [list(s.intervals(150))
                     for s in split_by_vm(rw, 4)]
            n_chunks = max(map(len, lists), default=0)
            got = list(win.blocks())
            assert len(got) == n_chunks
            for k, (a, w, kth) in enumerate(got):
                ref_kth = [c[k] if k < len(c) else None for c in lists]
                ra, rw_ = pad_batch(ref_kth, 150)
                assert np.array_equal(np.asarray(a), ra)
                assert np.array_equal(np.asarray(w), rw_)
                assert a.shape == (4, 150)
                for ck, rk in zip(kth, ref_kth):
                    if rk is None or len(rk) == 0:
                        assert ck is None or len(ck) == 0
                    else:
                        _assert_trace_equal(ck, rk)


def test_window_source_type_errors_and_reparameterization():
    with pytest.raises(TypeError):
        window_source(object(), 2, 100, 10)
    # a pre-built source is re-parameterized to the controller's settings,
    # including prefetch
    pre = StreamingTraceSource(Trace(np.arange(4, dtype=np.int32),
                                     np.zeros(4, bool)),
                               num_vms=1, window=2, chunk=1, prefetch=True)
    src = window_source(pre, 3, 100, 10, prefetch=False)
    assert (src.num_vms, src.window, src.chunk, src.prefetch) == \
        (3, 100, 10, False)


def test_parser_int32_overflow_rejected():
    """Offsets past 2^31 blocks must fail loudly, not wrap into the
    datapath's negative-address no-op convention."""
    line = f"1,h,0,Read,{(2**31) * 4096},4096,1"
    with pytest.raises(ValueError, match="int32"):
        list(parse_msr_csv([line]))
    # corrupt negative offsets must not become pad/no-op addresses either
    with pytest.raises(ValueError, match="int32"):
        list(parse_msr_csv(["1,h,0,Read,-8192,4096,1"]))


# ---------------------------------------------------------------------------
# controllers: streamed == in-memory, bit for bit
# ---------------------------------------------------------------------------

def _etica(batched=True, prefetch=True, prefetch_depth=2):
    cfg = EticaConfig(dram_capacity=60, ssd_capacity=120, geometry_dram=GEO,
                      geometry_ssd=GEO, resize_interval=1500,
                      promo_interval=500, mode="full", batched=batched,
                      prefetch=prefetch, prefetch_depth=prefetch_depth)
    return EticaCache(cfg, 3)


def test_etica_streamed_equals_in_memory(tmp_path):
    trace = _mixed_trace(reqs=2500)
    store = TraceStore.from_trace(tmp_path / "s", trace, shard_size=1024)
    res_mem = _etica().run(trace)
    res_str = _etica().run(TraceStore.open(tmp_path / "s"))
    res_nopf = _etica(prefetch=False).run(TraceStore.open(tmp_path / "s"))
    res_seq = _etica(batched=False).run(TraceStore.open(tmp_path / "s"))
    for v in range(3):
        assert res_mem[v].stats == res_str[v].stats, v
        assert res_mem[v].stats == res_nopf[v].stats, v
        assert res_mem[v].stats == res_seq[v].stats, v
        assert np.array_equal(res_mem[v].alloc_history,
                              res_str[v].alloc_history)


def test_etica_streamed_prefetch_depths_bit_identical(tmp_path):
    """The depth-d host->device pipeline never changes results: streamed
    Stats at depths 0 (host arrays), 1 (classic double buffer) and 2
    (default) are bit-identical."""
    trace = _mixed_trace(reqs=2000)
    store = TraceStore.from_trace(tmp_path / "s", trace, shard_size=777)
    ref = _etica(prefetch_depth=0).run(TraceStore.open(tmp_path / "s"))
    for depth in (1, 2):
        res = _etica(prefetch_depth=depth).run(
            TraceStore.open(tmp_path / "s"))
        for v in range(3):
            assert ref[v].stats == res[v].stats, (depth, v)
            assert np.array_equal(ref[v].alloc_history,
                                  res[v].alloc_history), (depth, v)


def test_eci_streamed_equals_in_memory(tmp_path):
    trace = _mixed_trace(reqs=2500)
    store = TraceStore.from_trace(tmp_path / "s", trace, shard_size=900)

    def build(batched=True):
        return make_eci_cache(120, 3, geometry=GEO, resize_interval=1500,
                              sim_chunk=500, batched=batched)

    res_mem = build().run(trace)
    caches = {}
    res = {}
    for batched in (True, False):
        cache = build(batched)
        res[batched] = cache.run(TraceStore.open(tmp_path / "s"))
        caches[batched] = cache
    for v in range(3):
        assert res_mem[v].stats == res[True][v].stats, v
        assert res_mem[v].stats == res[False][v].stats, v
    # dynamic per-VM policies chosen by the batched chooser == host loop
    for log_b, log_s in zip(caches[True].logs, caches[False].logs):
        assert log_b.policies == log_s.policies


def test_generated_store_streams_like_memory(tmp_path):
    """make_store (generate-to-store) == the in-memory vm_mix recipe."""
    workloads = ["hm_1", "usr_0", "web_3"]
    store = make_store(tmp_path / "s", workloads, reqs_per_vm=1200,
                       scale=0.25, interleave_seed=0, shard_size=500)
    trace = _mixed_trace(reqs=1200, workloads=tuple(workloads))
    _assert_trace_equal(TraceStore.open(tmp_path / "s").to_trace(), trace)


# ---------------------------------------------------------------------------
# batched policy chooser == host-loop oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_eci_policy_chooser_batch_matches_ref(seed):
    chooser = eci_policy()
    rng = np.random.default_rng(seed)
    lens = [0, 1, 7, 50, 200]
    subs = [Trace(rng.integers(0, 32, n).astype(np.int32),
                  rng.random(n) < rng.random())  # varied read ratios
            for n in lens]
    reads = [s.n_reads for s in subs]
    got = chooser.batch(reads, lens)
    want = [chooser(s) if len(s) else Policy.WB for s in subs]
    assert got == want
    # threshold boundary: ratio exactly at the threshold picks RO
    assert chooser.batch([4], [5]) == [Policy.RO]      # 0.8 >= 0.8
    assert chooser.batch([3], [5]) == [Policy.WB]


def test_generators_refuse_addresses_beyond_int32():
    """An address offset that would wrap int32 is refused instead of
    producing negative (padding) addresses the datapath skips."""
    with pytest.raises(ValueError, match="int32"):
        make("hm_1", 100, seed=0, addr_offset=2**31 - 50)
    with pytest.raises(ValueError, match="int32"):
        make("scan_mix", 400, seed=0, addr_offset=2**31 - 50)


def test_consolidation_mix_of_1024_vms_has_no_negative_addresses():
    """The benchmark mix holds 1024 VMs at the consolidation stride: every
    address is a real (non-negative) block below the 2^30 bound."""
    import benchmarks.common as bench
    from benchmarks.fig15_vm_scaling import WORKLOADS

    names = (WORKLOADS * (1024 // len(WORKLOADS) + 1))[:1024]
    mix = bench.vm_mix(names, reqs=20)
    addr = np.asarray(mix.addr)
    assert addr.min() >= 0 and addr.max() < 2**30
    assert np.unique(np.asarray(mix.vm)).size == 1024
