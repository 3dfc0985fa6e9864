"""Churn-driven serving with the ETICA two-tier KV manager.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --events 2000 --tenants 4 --live 256 [--manager lru]

A session arrival/churn stream (`repro.traces.generate_sessions`: zipf
popularity, bursty batch residency, bounded lifetimes) drives the
manager's full lifecycle — arrivals, activations (tier-1 residency via
the POD/popularity controller), KV-page appends (WBWO commits), and
retirements — at serving population sizes, not a fixed handful of
sessions. KV pages are *real*: one prefill of the reduced model fills a
bank of pages from its first attention layer's cache, and decode steps
run real paged attention against the HBM pool. Prints hit ratio / DMA
traffic / latency — the serving analogs of the paper's hit-ratio /
SSD-write / latency metrics.

Managers: ``etica`` (batched controller), ``etica-seq`` (the host-dict
sequential oracle — same decisions, slower), ``lru`` (global LRU +
write-back baseline).

Observability: ``--metrics-port N`` starts the stdlib scrape endpoint
(`repro.runtime.http.MetricsServer`; 0 picks an ephemeral port, printed
at startup) serving live ``/metrics`` from the manager's counters and
telemetry journal; ``--journal PATH`` spills one JSONL row per
maintenance interval (read it back with ``tools/run_report.py``);
``--spans`` enables the dispatch wall-clock histograms (adds
``block_until_ready`` syncs — off by default).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import paged_decode_ref
from repro.kvcache import GlobalLRUManager, TwoTierConfig, TwoTierKVManager
from repro.models import model as M
from repro.traces import (SESSION_ACTIVATE, SESSION_APPEND, SESSION_END,
                          SESSION_NEW, SessionSpec, generate_sessions)


def kv_page_bank(cfg, kv_cfg: TwoTierConfig, bank: int, seed: int):
    """A bank of real KV pages: prefill the reduced model once over
    ``bank`` pages' worth of random tokens and slice its first attention
    layer's cache into ``[1, page_size, heads, dim]`` pages. Falls back
    to gaussian pages for frontends whose prefill needs extra modalities
    (encdec/vision) — the manager only moves bytes either way."""
    ps = kv_cfg.page_size
    rng = np.random.default_rng(seed)
    if cfg.is_encdec or getattr(cfg, "frontend", None) == "vision":
        pages = rng.normal(size=(bank, 1, ps, kv_cfg.num_kv_heads,
                                 kv_cfg.head_dim)).astype(np.float32)
        return pages, pages
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (1, bank * ps), 0, cfg.vocab_size)
    _, cache = M.prefill(params, cfg, {"tokens": toks}, cache_len=bank * ps)
    k_leaf = v_leaf = None
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            cache["layers"])[0]:
        name = getattr(path[-1], "key", getattr(path[-1], "name", ""))
        if np.ndim(leaf) == 5 and np.shape(leaf)[2] == bank * ps:
            if name == "k" and k_leaf is None:
                k_leaf = np.asarray(leaf[0], np.float32)   # [1, S, Hkv, D]
            elif name == "v" and v_leaf is None:
                v_leaf = np.asarray(leaf[0], np.float32)
    assert k_leaf is not None and v_leaf is not None, "no attention cache"
    if (k_leaf.shape[2], k_leaf.shape[3]) != (kv_cfg.num_kv_heads,
                                              kv_cfg.head_dim):
        raise ValueError("kv geometry mismatch between model and pool")
    split = lambda a: np.stack([a[:, i * ps:(i + 1) * ps]
                                for i in range(bank)])
    return split(k_leaf), split(v_leaf)


def run_events(mgr, trace, k_bank, v_bank, *, decode_every: int = 0,
               seed: int = 0, check_ref: bool = False):
    """Replay a SessionTrace through a manager; optionally run a real
    paged-attention decode step every ``decode_every``-th activation.
    ``check_ref`` also compares each decode step with the pure-jnp
    oracle (``decode_attention/ref.py``, f32 matmuls) and raises on a
    mismatch."""
    rng = np.random.default_rng(seed)
    bank = k_bank.shape[0]
    n_act = 0
    for i in range(len(trace)):
        kind, sid = int(trace.kind[i]), int(trace.sid[i])
        if kind == SESSION_NEW:
            mgr.new_session(sid, int(trace.tenant[i]))
        elif kind == SESSION_APPEND:
            j = sid % bank
            mgr.append_page(sid, k_bank[j], v_bank[j])
        elif kind == SESSION_ACTIVATE:
            pt = mgr.activate(sid)
            n_act += 1
            if decode_every and n_act % decode_every == 0:
                h, d = mgr.cfg.num_kv_heads, mgr.cfg.head_dim
                q = jnp.asarray(rng.normal(size=(1, h, d)), jnp.float32)
                lengths = jnp.asarray([mgr.sessions[sid].length], jnp.int32)
                pool = (mgr.k_pool[0], mgr.v_pool[0])
                table = jnp.asarray(pt[None, :])
                out = decode_attention(q, pool, table, lengths)
                assert bool(jnp.all(jnp.isfinite(out)))
                if check_ref:
                    with jax.default_matmul_precision("highest"):
                        want = paged_decode_ref(q, *pool, table, lengths)
                    np.testing.assert_allclose(np.asarray(out),
                                               np.asarray(want),
                                               rtol=1e-4, atol=1e-4)
            mgr.deactivate(sid)
        elif kind == SESSION_END:
            mgr.end_session(sid)
    return mgr.stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--events", type=int, default=2000)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--live", type=int, default=256,
                    help="target concurrent sessions")
    ap.add_argument("--hbm-pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-pages", type=int, default=6,
                    help="per-session KV budget (pages)")
    ap.add_argument("--manager", choices=["etica", "etica-seq", "lru"],
                    default="etica")
    ap.add_argument("--decode-every", type=int, default=8,
                    help="real paged-attention decode each Nth activation "
                         "(0 = controller only)")
    ap.add_argument("--no-materialize", action="store_true",
                    help="skip device page pools (implies no decode) — "
                         "controller-scale runs")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live /metrics + /healthz on this port "
                         "(0 = ephemeral; off when omitted)")
    ap.add_argument("--journal", default=None,
                    help="spill the per-interval telemetry journal to "
                         "this JSONL path")
    ap.add_argument("--spans", action="store_true",
                    help="time the fused dispatches into the "
                         "etica_dispatch_seconds histogram (adds "
                         "block_until_ready syncs)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.get_reduced(args.arch)
    recorder = None
    if args.metrics_port is not None or args.journal or args.spans:
        from repro.runtime.telemetry import TelemetryRecorder
        recorder = TelemetryRecorder(spill=args.journal,
                                     span_timing=args.spans)
    kv_cfg = TwoTierConfig(
        page_size=args.page_size, hbm_pages=args.hbm_pages,
        num_kv_heads=max(cfg.num_kv_heads, 1),
        head_dim=max(cfg.head_dim, 8), num_layers=1, dtype="float32",
        materialize=not args.no_materialize, telemetry=recorder)
    if args.manager == "lru":
        mgr = GlobalLRUManager(kv_cfg, args.tenants)
    else:
        mgr = TwoTierKVManager(kv_cfg, args.tenants,
                               batched=args.manager == "etica")

    server = None
    if args.metrics_port is not None:
        from repro.runtime import metrics as metrics_mod
        from repro.runtime.http import MetricsServer

        def _collect():
            out = []
            if isinstance(mgr, TwoTierKVManager):
                out += metrics_mod.collect_serving(mgr)
                out += metrics_mod.collect_telemetry(
                    mgr.telemetry, prefix="etica_serving", label="tenant")
            return out

        server = MetricsServer(_collect, port=args.metrics_port)
        host, port = server.start()
        print(f"metrics: http://{host}:{port}/metrics")

    spec = SessionSpec(num_tenants=args.tenants, target_live=args.live,
                       max_pages=args.max_pages)
    trace = generate_sessions(spec, args.events, seed=args.seed)
    k_bank, v_bank = kv_page_bank(cfg, kv_cfg, bank=8, seed=args.seed)

    t0 = time.time()
    decode_every = 0 if args.no_materialize else args.decode_every
    stats = run_events(mgr, trace, k_bank, v_bank,
                       decode_every=decode_every, seed=args.seed)
    wall = time.time() - t0
    s = stats.as_dict()
    print(f"manager={args.manager} events={args.events} "
          f"sessions={trace.num_sessions} max_live={trace.max_live} "
          f"wall={wall:.1f}s")
    for k, v in s.items():
        print(f"  {k:18s} {v:,.3f}" if isinstance(v, float) else
              f"  {k:18s} {v:,}")
    if recorder is not None and recorder.journal.total:
        last = recorder.journal.last_row()
        flagged = [str(t) for t, f in enumerate(last["overloaded"]) if f]
        print(f"  telemetry: {recorder.journal.total} interval rows"
              + (f", journal -> {args.journal}" if args.journal else "")
              + (f", overloaded tenants: {','.join(flagged)}"
                 if flagged else ""))
    if server is not None:
        # interactive runs keep the endpoint alive for a final scrape;
        # programmatic callers (argv passed in) get it shut down cleanly
        if argv is None:
            print(f"scrape still live at {server.url} (ctrl-c to exit)")
            try:
                import signal
                signal.pause()
            except (KeyboardInterrupt, AttributeError):
                pass
        server.stop()
    return s


if __name__ == "__main__":
    main()
