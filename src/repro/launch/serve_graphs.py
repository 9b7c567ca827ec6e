"""Graph-property serving launcher: replay synthetic request traffic through
the segment-streaming inference engine (serve/engine.py).

    PYTHONPATH=src python -m repro.launch.serve_graphs \
        --requests 64 --unique 24 --duplicate-rate 0.5 --window 8

Reports p50/p99 request latency, throughput, cross-request cache hit-rate,
and encode-kernel launch counts.  ``--check-parity`` verifies a sample of
engine predictions against the one-shot batch encoder and exits nonzero on
mismatch; ``--min-hit-rate`` turns the hit-rate into an assertion — both are
what the CI serve-smoke job runs.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache


def build_engine(args):
    from repro.serve import ServeConfig, ServeEngine

    cfg = ServeConfig(
        backbone=args.backbone,
        use_pallas=args.use_pallas,
        max_seg_nodes=args.max_seg_nodes,
        cache_capacity=args.cache_capacity,
        cache_enabled=not args.no_cache,
        table_device_rows=args.table_device_rows,
        evict_policy=args.evict_policy,
        wb_threshold=args.wb_threshold,
        stale_forecast=args.stale_forecast,
        stream_chunk=args.stream_chunk,
    )
    return ServeEngine(cfg, seed=args.seed)


def check_parity(engine, graphs, atol: float) -> float:
    """Engine predictions vs the one-shot batch encoder (training-style
    padding, every segment encoded in one flat batch)."""
    from repro.core import gst as G
    from repro.graphs.batching import segment_dataset
    from repro.graphs.gnn import encode_segments
    from repro.graphs.partition import partition_graph

    worst = 0.0
    for g in graphs:
        res = engine.process([g], window=1)[0]
        segs = partition_graph(len(g.x), g.edges, engine.cfg.max_seg_nodes,
                               engine.cfg.partition, engine.cfg.partition_seed)
        ds = segment_dataset([g], engine.cfg.max_seg_nodes,
                             method=engine.cfg.partition,
                             seed=engine.cfg.partition_seed)
        si = {k: jnp.asarray(v[0]) for k, v in ds.seg_inputs(np.array([0])).items()}
        h = encode_segments(engine.params, engine.gnn_cfg, si)[:len(segs)]
        ref = G.head_apply(engine.head, h.mean(axis=0), "mlp")
        worst = max(worst, float(np.abs(res.pred - np.asarray(ref)).max()))
    if worst > atol:
        raise SystemExit(f"PARITY FAIL: engine vs one-shot max diff {worst:.3e} "
                         f"> atol {atol:.1e}")
    return worst


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--unique", type=int, default=24)
    ap.add_argument("--duplicate-rate", type=float, default=0.5)
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--backbone", default="sage", choices=["gcn", "sage", "gps"])
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--cache-capacity", type=int, default=512)
    ap.add_argument("--table-device-rows", type=int, default=None,
                    help="cap device-resident cache rows; cold entries "
                         "spill to a host-RAM tier and fault back on hit "
                         "instead of being re-encoded (store/tiered.py). "
                         "Default: all cache rows on device")
    ap.add_argument("--evict-policy", default="lru",
                    choices=["lru", "stale-first"],
                    help="device-tier eviction policy under "
                         "--table-device-rows: pure LRU or age-aware "
                         "stale-first (evict stale-and-cold rows before "
                         "fresh-and-hot ones)")
    ap.add_argument("--wb-threshold", type=float, default=0.0,
                    help="delta-gated write-back under --table-device-rows: "
                         "skip the host-tier emb write for spilled rows "
                         "whose embedding moved less than this (max-abs) "
                         "while device-resident. 0 = gate off, bit-exact")
    ap.add_argument("--stale-forecast", action="store_true",
                    help="back the cache's tiered store with the online "
                         "per-row velocity forecaster (store/forecast.py); "
                         "a no-op for the offline replay, whose cache rows "
                         "never drift — train-while-serve plumbing")
    ap.add_argument("--popularity", type=float, default=0.0,
                    help="repeat-request skew: P(graph) ∝ "
                         "times_served**popularity over distinct seen "
                         "graphs (0 = uniform, 1 = rich-get-richer)")
    ap.add_argument("--max-seg-nodes", type=int, default=64)
    ap.add_argument("--stream-chunk", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=4,
                    help="requests replayed first to absorb jit compiles "
                         "(stats are reset afterwards; cache is NOT reset, "
                         "pass --cold-cache to flush it)")
    ap.add_argument("--cold-cache", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-parity", action="store_true")
    ap.add_argument("--parity-atol", type=float, default=1e-5)
    ap.add_argument("--min-hit-rate", type=float, default=None)
    from repro.obs import Obs, add_obs_args
    add_obs_args(ap)
    args = ap.parse_args(argv)
    if args.use_pallas and args.backbone == "gps":
        print("[serve_graphs] --use-pallas: gps has no fused kernel; "
              "encoding on the jnp reference path")

    from repro.serve import TrafficConfig, make_request_stream

    engine = build_engine(args)
    tc = TrafficConfig(n_unique=args.unique, n_requests=args.requests,
                       duplicate_rate=args.duplicate_rate,
                       popularity=args.popularity, seed=args.seed)
    stream = make_request_stream(tc)
    obs = Obs.from_args(args, run="serve_graphs",
                        backbone=args.backbone, requests=args.requests,
                        window=args.window)

    try:
        return _run(args, engine, stream, obs)
    finally:
        # the tiered store owns a write-back thread — release it even when
        # the parity / hit-rate gates raise SystemExit
        engine.close()
        obs.close()


def _run(args, engine, stream, obs):
    if args.warmup:
        engine.process(stream[:args.warmup], window=args.window)
        engine.reset_stats()
        # warmup compiles/misses must not count against the SLO gates
        obs.registry.reset()
        if args.cold_cache and engine.cache is not None:
            engine.cache.flush()  # cold contents, warm compile caches

    # replay window-by-window (behaviorally identical to one process()
    # call, which windows internally) so the JSONL stream gets one
    # per-window delta tick
    for wi, w0 in enumerate(range(0, len(stream), args.window)):
        engine.process(stream[w0:w0 + args.window], window=args.window)
        if obs.should_tick(wi):
            obs.tick(step=wi,
                     requests_done=min(w0 + args.window, len(stream)))
    s = engine.stats.summary()
    obs.close(serve=s)

    print(f"[serve_graphs] backend={jax.default_backend()} "
          f"backbone={args.backbone} pallas={args.use_pallas} "
          f"cache={'off' if args.no_cache else 'on'}")
    print(f"  requests          {s['n_requests']}  ({s['n_segments']} segments)")
    print(f"  throughput        {s['throughput_req_s']:.1f} req/s")
    print(f"  latency p50/p99   {s['latency_p50_ms']:.1f} / {s['latency_p99_ms']:.1f} ms")
    print(f"  encode launches   {s['encode_launches']} "
          f"({s['encoded_segments']} segments encoded, "
          f"{s['pallas_launches']} pallas kernel launches)")
    if s.get("truncated_nodes") or s.get("truncated_edges"):
        print(f"  TRUNCATED         {s['truncated_nodes']} nodes, "
              f"{s['truncated_edges']} edges dropped by catch-all "
              f"bucket overflow (repro.obs.gate fails on this)")
    if s["cache"]:
        c = s["cache"]
        print(f"  cache             hit-rate {c['hit_rate']:.2f} "
              f"({c['hits']} hits / {c['misses']} misses), "
              f"{c['size']}/{c['capacity']} slots, "
              f"{c['evictions']} evictions, "
              f"age mean/max {c['age_mean_steps']:.1f}/{c['age_max_steps']} steps")
        st = c.get("store", {})
        if st:
            print(f"  store             [{st['backend']}] device rows "
                  f"{st['occupancy']}/{st['device_rows']} "
                  f"(of {st['n_rows']} total), tier hit-rate "
                  f"{st['hit_rate']:.2f}, {st['evictions']} spills, "
                  f"{st['migration_bytes'] / 1024:.1f} KiB migrated")

    if args.check_parity:
        worst = check_parity(engine, stream[:3], args.parity_atol)
        print(f"  parity            OK (max |engine - one-shot| = {worst:.2e})")
    if args.min_hit_rate is not None:
        hr = s["cache"].get("hit_rate", 0.0) if s["cache"] else 0.0
        if hr <= args.min_hit_rate:
            raise SystemExit(f"HIT-RATE FAIL: {hr:.3f} <= {args.min_hit_rate}")
        print(f"  hit-rate check    OK ({hr:.2f} > {args.min_hit_rate})")
    return s


if __name__ == "__main__":
    main()
