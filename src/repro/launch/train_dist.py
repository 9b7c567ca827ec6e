"""Distributed GST training launcher (data-parallel shard_map).

Runs Algorithm 1/2 over a 1-D data mesh with the row-sharded historical
table and the async host→device segment pipeline:

    # 8 forced host devices, complete method, async double buffering
    PYTHONPATH=src python -m repro.launch.train_dist \
        --devices 8 --variant gst_efd --backbone sage --epochs 5

    # synchronous feeder baseline on the same trace
    PYTHONPATH=src python -m repro.launch.train_dist \
        --devices 8 --feeder sync --epochs 5

    # owner-direct table exchange, capacity planned over the schedules
    PYTHONPATH=src python -m repro.launch.train_dist \
        --devices 8 --exchange bucketed --epochs 5

    # lookahead prefetch: batch k+1's exchange lookup dispatched while
    # step k runs, write-back patched (bit-exact at f32 payloads)
    PYTHONPATH=src python -m repro.launch.train_dist \
        --devices 8 --prefetch-lookups --epochs 5

``--devices N`` forces an N-device host via XLA_FLAGS when jax has not
initialized yet (CPU development / CI; on a real TPU slice leave it unset
to use the attached devices).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _force_device_count(n: int) -> None:
    if "jax" in sys.modules:
        return  # too late — use whatever is attached
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def main(argv=None):
    """Train, print per-epoch progress, and return ``{"epoch_losses":
    [last train loss of each epoch], "finetune_loss": float | None,
    "metric": final eval metric, "state": final TrainState}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="data-parallel width (forces an N-device host on "
                         "CPU when jax is not yet initialized)")
    ap.add_argument("--dataset", default="malnet", choices=["malnet"])
    ap.add_argument("--backbone", default="sage", choices=["gcn", "sage"])
    ap.add_argument("--variant", default="gst_efd")
    ap.add_argument("--n-graphs", type=int, default=64)
    ap.add_argument("--max-seg-nodes", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--finetune-epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--keep-prob", type=float, default=0.5)
    ap.add_argument("--num-sampled", type=int, default=1,
                    help="segments sampled for backprop per graph (S)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--feeder", default="async", choices=["async", "sync"],
                    help="host→device pipeline: async double buffering "
                         "(default) or the synchronous baseline")
    ap.add_argument("--depth", type=int, default=2,
                    help="async pipeline depth (in-flight device batches)")
    ap.add_argument("--exchange", default="ring",
                    choices=["ring", "alltoall", "bucketed", "auto"],
                    help="table-exchange strategy (dist/exchange.py): the "
                         "D-hop ppermute ring, full-buffer all_to_all "
                         "dissemination, owner-direct bucketed routing, or "
                         "auto = fewest analytic bytes per step at this "
                         "shard count")
    ap.add_argument("--exchange-cap", type=int, default=None,
                    help="bucketed only: per-(device, owner) bucket "
                         "capacity.  Default: planned host-side over the "
                         "run's precomputed id schedules "
                         "(exchange.plan_capacity — the tightest safe cap)")
    ap.add_argument("--payload-dtype", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="wire format for embedding payloads crossing the "
                         "exchange collectives (exchange.PayloadCodec): "
                         "f32 = identity (bit-exact), bf16, or int8 with a "
                         "per-row scale; write-backs use stochastic "
                         "rounding.  --exchange=auto re-picks the min-"
                         "bytes strategy at this dtype")
    ap.add_argument("--prefetch-lookups", action="store_true",
                    help="hide the exchange: dispatch batch k+1's table "
                         "lookup as its own collective while step k's "
                         "compute runs (dist.make_prefetch_lookup), and "
                         "restore read-after-write correctness with the "
                         "fused write-back patch "
                         "(exchange.update_sampled_patch).  Bit-exact vs "
                         "the inline exchange at --payload-dtype f32; "
                         "bounded-error under bf16/int8 like the inline "
                         "path.  Train loop only — refresh/finetune/eval "
                         "stay inline")
    ap.add_argument("--patch-cap", type=int, default=None,
                    help="bucketed + --prefetch-lookups only: per-(device, "
                         "consumer) bucket capacity of the patch hop.  "
                         "Default: planned host-side over the train "
                         "schedules (exchange.plan_patch_capacity)")
    ap.add_argument("--table-device-rows", type=int, default=None,
                    help="cap on device-resident historical-table rows "
                         "(total, split over shards; clamped up so every "
                         "shard can pin one batch).  The rest spill to a "
                         "host-RAM tier with async write-back.  Default: "
                         "whole table on device")
    ap.add_argument("--evict-policy", default="lru",
                    choices=["lru", "stale-first"],
                    help="tiered-store device-tier eviction policy under "
                         "--table-device-rows (store/slots.py)")
    ap.add_argument("--wb-threshold", type=float, default=0.0,
                    help="delta-gated write-back admission under "
                         "--table-device-rows: skip the host-tier emb "
                         "write for evicted rows whose embedding moved "
                         "less than this (max-abs) while resident "
                         "(store/writeback.delta_gate).  0 = gate off, "
                         "bit-exact store")
    ap.add_argument("--sed-age-weighting", type=float, default=0.0,
                    help="λ of the exp(-λ·age) staleness decay folded into "
                         "the stale branch of Eq.-1 η (use_sed+use_table "
                         "variants; ages read exactly through the exchange "
                         "collective).  0 = off, bit-exact to the "
                         "unweighted step")
    ap.add_argument("--stale-forecast", action="store_true",
                    help="extrapolate stale host-tier rows forward by "
                         "their age on fault-in via the online per-row "
                         "velocity forecaster (store/forecast.py); needs "
                         "--table-device-rows")
    # repro.obs is jax-free, so this is safe before _force_device_count
    from repro.obs import (Obs, StalenessProbe, add_obs_args,
                           record_exchange_bytes, record_prefetch_exchange)
    from repro.obs.trace import span
    add_obs_args(ap)
    args = ap.parse_args(argv)

    if args.devices:
        _force_device_count(args.devices)
    # first jax import of the CLI: it must follow the device-count override
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import dist as DT
    from repro.core import gst as G
    from repro.core.embedding_table import init_table
    from repro.dist import exchange as EXC
    from repro.dist import pipeline as DP
    from repro.dist import table as dtbl
    from repro.graphs import data as D
    from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
    from repro.optim import make_optimizer

    n_dev = args.devices or jax.device_count()
    if args.batch_size % n_dev:
        ap.error(f"--batch-size {args.batch_size} must be divisible by the "
                 f"device count {n_dev}")
    if args.epochs < 1:
        ap.error("--epochs must be >= 1")
    if args.n_graphs < args.batch_size:
        ap.error(f"--n-graphs {args.n_graphs} yields an empty drop-last "
                 f"epoch at --batch-size {args.batch_size}")

    graphs = D.make_malnet_like(n_graphs=args.n_graphs, seed=args.seed)
    ds, spec = DP.segment_dataset_shared(graphs, args.max_seg_nodes,
                                         seed=args.seed)
    var = G.VARIANTS[args.variant]
    cfg = GNNConfig(backbone=args.backbone, n_feat=graphs[0].x.shape[1],
                    hidden=args.hidden, use_pallas=args.use_pallas)
    enc = make_encode_fn(cfg)
    key = jax.random.key(args.seed)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), args.hidden, 5, "mlp")
    opt = make_optimizer("adam", lr=args.lr)
    state = G.TrainState(bb, head, opt.init((bb, head)),
                         init_table(ds.n, ds.j_max, args.hidden),
                         jnp.zeros((), jnp.int32))

    mesh = DT.make_dist_mesh(n_dev)
    device_rows = None
    if args.table_device_rows is not None:
        # every shard must be able to pin one batch's rows at once; the
        # prefetch lane keeps lookahead batches pinned (store.begin
        # pin=True, released after their step), so it needs room for the
        # in-flight window too: the running step, the prefetched next
        # batch, and up to --depth feeder batches begun ahead of them
        window = 1 if not args.prefetch_lookups else (
            2 if args.feeder == "sync" else args.depth + 2)
        device_rows = max(args.table_device_rows,
                          window * n_dev * args.batch_size)

    # precompute every id schedule up front (same rng draw order as the
    # former per-epoch draws, so traces are unchanged): the bucketed
    # exchange sizes its per-owner buckets host-side over the WHOLE run
    # (exchange.plan_capacity) before any step is built
    rng = np.random.default_rng(args.seed + 3)
    train_scheds = [DP.epoch_ids(ds, args.batch_size, rng=rng)
                    for _ in range(args.epochs)]
    refresh_sched = DP.epoch_ids(ds, args.batch_size, rng=rng, shuffle=False)
    ft_scheds = [DP.epoch_ids(ds, args.batch_size, rng=rng)
                 for _ in range(args.finetune_epochs)] \
        if var.finetune_head else []
    eval_sched = DP.epoch_ids(ds, args.batch_size, rng=rng, shuffle=False)

    # owner histograms are identical in graph-row and tiered slot space
    # (a row's slot stays on its owner shard), so capacity planned on
    # graph ids is exact for either table the step sees
    rows_per_shard = dtbl.rows_per_shard(ds.n, n_dev)
    exchange_batches = [ids for sched in
                        (*train_scheds, refresh_sched, *ft_scheds)
                        for ids in sched]
    need_cap = EXC.plan_capacity(exchange_batches, num_shards=n_dev,
                                 rows=rows_per_shard)
    cap = args.exchange_cap
    if cap is None:
        cap = need_cap
    elif cap < need_cap:
        ap.error(f"--exchange-cap {cap} is below the {need_cap} rows one "
                 "owner bucket needs for this run's schedules — the "
                 "bucketed exchange would silently truncate writes")
    b_local = args.batch_size // n_dev
    exchange = args.exchange
    if exchange == "auto":
        exchange = EXC.select_exchange(n_dev, b_local, ds.j_max,
                                       args.num_sampled, args.hidden,
                                       cap=cap,
                                       payload_dtype=args.payload_dtype)
    patch_cap = None
    if args.prefetch_lookups and exchange == "bucketed":
        # the patch hop routes this batch's write-backs to the shards
        # holding the NEXT batch's prefetched buffer — plan its bucket
        # capacity over consecutive pairs of each train epoch's schedule
        # (same graph-id/slot-space equivalence as plan_capacity above)
        need_patch = max(EXC.plan_patch_capacity(sched, num_shards=n_dev,
                                                 rows=rows_per_shard)
                         for sched in train_scheds)
        patch_cap = args.patch_cap
        if patch_cap is None:
            patch_cap = need_patch
        elif patch_cap < need_patch:
            ap.error(f"--patch-cap {patch_cap} is below the {need_patch} "
                     "rows one consumer bucket needs for this run's "
                     "schedules — the patch hop would silently drop "
                     "write-back repairs")
    ctx = DT.make_context(mesh, ds.n, device_rows=device_rows,
                          exchange=exchange,
                          exchange_cap=cap if exchange == "bucketed"
                          else None,
                          payload_dtype=args.payload_dtype,
                          prefetch=args.prefetch_lookups,
                          patch_cap=patch_cap)
    store = DT.make_dist_store(ctx, ds.j_max, args.hidden,
                               evict_policy=args.evict_policy,
                               wb_threshold=args.wb_threshold,
                               stale_forecast=args.stale_forecast)
    state = DT.device_state(ctx, state, store=store)
    step = DT.make_dist_train_step(enc, opt, var, ctx=ctx,
                                   keep_prob=args.keep_prob,
                                   num_sampled=args.num_sampled,
                                   use_pallas=args.use_pallas,
                                   sed_decay=args.sed_age_weighting)
    eval_step = DT.make_dist_eval_step(enc, ctx=ctx,
                                       use_pallas=args.use_pallas)
    ex_model = EXC.make_exchange(exchange, axis_name=DT.AXIS,
                                 num_shards=ctx.num_shards,
                                 rows=ctx.table_rows, cap=ctx.exchange_cap,
                                 payload_dtype=ctx.payload_dtype,
                                 patch_cap=ctx.patch_cap)
    xbytes = ex_model.train_step_bytes(b_local, ds.j_max, args.num_sampled,
                                       args.hidden, use_table=var.use_table)
    pxbytes = ex_model.prefetch_train_step_bytes(
        b_local, ds.j_max, args.num_sampled, args.hidden,
        use_table=var.use_table)
    print(f"[dist] devices={ctx.num_shards} rows/shard={ctx.rows_per_shard} "
          f"device-rows/shard={ctx.table_rows} "
          f"bucket={spec.key} feeder={args.feeder} "
          f"exchange={exchange} (payload={ex_model.payload_dtype}, "
          f"{xbytes / 1024:.1f} KiB/step/device"
          + (f", cap={cap}" if exchange == "bucketed" else "")
          + (f", prefetch {pxbytes / 1024:.1f} KiB"
             + (f", patch-cap={ctx.patch_cap}"
                if exchange == "bucketed" else "")
             if args.prefetch_lookups else "") + ")")

    obs = Obs.from_args(args, run="train_dist", variant=args.variant,
                        devices=ctx.num_shards, exchange=exchange,
                        payload_dtype=ex_model.payload_dtype,
                        epochs=args.epochs, batch_size=args.batch_size)
    probe = StalenessProbe(keep_prob=args.keep_prob,
                           num_sampled=args.num_sampled,
                           seg_valid=ds.seg_valid,
                           sed_decay=args.sed_age_weighting,
                           forecast=args.stale_forecast)

    try:
        # monotone per-begin counter, same clock the jitted steps write
        # ages with — the stale-first refresh hint for rows a train/
        # refresh step is about to rewrite (finetune only READS the
        # table, so its put passes no hint)
        step_counter = {"t": 0}

        def _put(b, counting, pin=False):
            # route graph ids -> store device rows on the feeder thread, so the
            # host-tier gather + staging device_put overlap with the running
            # step; the consumer commits the staged migration in order below
            hint = None
            if counting:
                hint = step_counter["t"]
                step_counter["t"] += 1
            prep = store.begin(np.asarray(b.graph_ids), step=hint, pin=pin)
            return prep, DT.shard_batch(ctx, b._replace(graph_ids=prep.slots))

        def put(b):
            return _put(b, True)

        def put_pinned(b):
            # prefetch train loop: lookahead batches stay pinned on the
            # device tier (later begins may not evict them) until the
            # driver releases them after their step is dispatched
            return _put(b, True, pin=True)

        def put_readonly(b):
            return _put(b, False)

        def print_store_line():
            s = store.stats()
            if ctx.device_rows_per_shard is not None:
                gate = (f", delta-gate skipped {s['wb_skipped_rows']} rows "
                        f"({s['wb_skipped_bytes'] / 1024:.1f} KiB)"
                        if s.get("wb_threshold", 0.0) > 0.0 else "")
                print(f"  store [{s['backend']}] device rows {s['device_rows']}/"
                      f"{s['n_rows']}  hit-rate {s['hit_rate']:.2f} "
                      f"({s['misses']} faults), {s['evictions']} evictions, "
                      f"{s['migration_bytes'] / 1024:.1f} KiB migrated, "
                      f"occupancy {s['occupancy']}{gate}", flush=True)

        if args.prefetch_lookups:
            prefetch_fn = DT.make_prefetch_lookup(ctx)
            bsh = DT.batch_sharding(ctx)
            sentinel = ctx.num_shards * ctx.table_rows

            def prefetch_dispatch(item):
                # runs at lane pull time, BEFORE the previous item's step
                # is launched: commit the staged migration, then dispatch
                # the lookup collective so it executes (same stream) ahead
                # of the donating step that would overwrite the table
                nonlocal state
                prep, batch = item
                with span("train.commit"):
                    state = state._replace(
                        table=store.commit(state.table, prep))
                return prefetch_fn(state.table, batch.graph_ids)

        def run_epoch_inline(epoch, feeder):
            nonlocal state
            losses = []
            for prep, batch in feeder:
                with span("train.commit"):
                    state = state._replace(
                        table=store.commit(state.table, prep))
                with span("train.step", epoch=epoch):
                    state, m = step(state, batch, jax.random.PRNGKey(epoch))
                record_exchange_bytes(exchange, ex_model.payload_dtype,
                                      xbytes)
                losses.append(m["loss"])
            return losses, feeder.stats

        def run_epoch_prefetch(epoch, feeder):
            nonlocal state
            lane = DP.PrefetchLane(feeder, prefetch_dispatch)
            rng = jax.random.PRNGKey(epoch)
            losses, pref = [], None
            for (prep, batch), cur_h, nxt, nxt_h in lane:
                if pref is None:
                    pref = cur_h   # first batch: nothing patched it yet
                if nxt is not None:
                    nprep, nbatch = nxt
                    next_ids, next_pair = nbatch.graph_ids, nxt_h
                    dest = EXC.consumer_shards(
                        np.asarray(prep.slots), np.asarray(nprep.slots),
                        num_shards=ctx.num_shards, rows=ctx.table_rows)
                else:
                    # epoch tail: sentinel consumers — the patch no-ops
                    # into a throwaway zero buffer
                    B = args.batch_size
                    next_ids = jax.device_put(
                        np.full((B,), sentinel, np.int32), bsh)
                    next_pair = (
                        jax.device_put(np.zeros((B, ds.j_max, args.hidden),
                                                np.float32), bsh),
                        jax.device_put(np.zeros((B, ds.j_max), bool), bsh))
                    dest = np.full((B,), ctx.num_shards, np.int32)
                patched_rows = int((dest != ctx.num_shards).sum())
                dest_dev = jax.device_put(np.asarray(dest, np.int32), bsh)
                with span("train.step", epoch=epoch):
                    state, m, pref = step(state, batch,
                                          rng, pref, next_pair,
                                          next_ids, dest_dev)
                store.release(prep)
                # exchange.bytes.* stays the run's total-traffic family
                # (prefetch moves the same bytes earlier; bucketed adds
                # its patch hop), exchange.prefetch.* is the lane's own
                record_exchange_bytes(exchange, ex_model.payload_dtype,
                                      pxbytes)
                record_prefetch_exchange(exchange, ex_model.payload_dtype,
                                         pxbytes, patched_rows)
                losses.append(m["loss"])
            return losses, lane.stats

        t_start = time.perf_counter()
        last_stats = None
        epoch_losses = []
        run_epoch = (run_epoch_prefetch if args.prefetch_lookups
                     else run_epoch_inline)
        for epoch, sched in enumerate(train_scheds):
            feeder = DP.make_feeder(
                args.feeder, ds, sched,
                put_pinned if args.prefetch_lookups else put,
                depth=args.depth)
            losses, last_stats = run_epoch(epoch, feeder)
            epoch_losses.append(float(losses[-1]))
            print(f"epoch {epoch}: loss={epoch_losses[-1]:.4f} "
                  f"host_blocked={last_stats.host_blocked_ms_per_batch:.2f} "
                  f"ms/batch", flush=True)
            # resident rows rewritten this epoch re-report their true
            # device-plane ages to the eviction bookkeeping (no-op under
            # plain LRU)
            store.refresh_ages(state.table)
            if obs.enabled:
                # per-epoch observability: staleness probe over the merged
                # table view + registry delta() — PER-EPOCH rates, not the
                # cumulative counters the old store line reported
                store.publish_counters()
                stale = probe.observe(store, state.table, step_counter["t"])
                d = (obs.tick(step=step_counter["t"], epoch=epoch,
                              loss=float(losses[-1]),
                              staleness=stale) or {}).get("delta") \
                    or obs.registry.delta()
                print(f"  obs epoch {epoch}: faults {d.get('store.faults', 0):.0f} "
                      f"evictions {d.get('store.evictions', 0):.0f} "
                      f"exch KiB {sum(v for k, v in d.items() if k.startswith('exchange.bytes.')) / 1024:.1f} "
                      f"row-age p99 {stale['row_age_steps']['p99']:.0f} steps "
                      f"sed-drop {stale['sed_drop_rate']:.3f}", flush=True)
        print_store_line()

        finetune_loss = None
        if var.finetune_head:
            refresh = DT.make_dist_refresh_step(enc, ctx=ctx)
            for prep, batch in DP.make_feeder("sync", ds, refresh_sched, put):
                state = state._replace(table=store.commit(state.table, prep))
                state = refresh(state, batch)
            ft_opt = make_optimizer("adam", lr=args.lr * 0.5)
            state = state._replace(
                opt_state=DT.replicate(ctx, ft_opt.init(jax.device_get(state.head))))
            ft = DT.make_dist_finetune_step(ft_opt, ctx=ctx,
                                            use_pallas=args.use_pallas)
            m = None
            for sched in ft_scheds:
                for prep, batch in DP.make_feeder(
                        args.feeder, ds, sched, put_readonly,
                        depth=args.depth):
                    state = state._replace(table=store.commit(state.table, prep))
                    state, m = ft(state, batch)
            if m is not None:
                finetune_loss = float(m["loss"])
                print(f"finetune: loss={finetune_loss:.4f}")

        # eval never reads the table — no store routing (a begun-but-uncommitted
        # migration would corrupt residency bookkeeping)
        metrics = []
        for batch in DP.make_feeder("sync", ds, eval_sched,
                                    lambda b: DT.shard_batch(ctx, b)):
            metrics.append(float(eval_step(state, batch)["metric"]))
        # surface any failed async write-back BEFORE reporting success
        store.flush_writebacks()
        wall = time.perf_counter() - t_start
        metric = float(np.mean(metrics))
        print(f"[dist] done in {wall:.1f}s — train metric "
              f"{metric:.3f}, host blocked "
              f"{last_stats.host_blocked_ms_per_batch:.2f} ms/batch "
              f"({args.feeder})")
        print_store_line()
        if obs.enabled:
            store.publish_counters()
            probe.observe_store_counters(store.counters.as_dict())
        obs.close(wall_s=wall, train_metric=metric)
    finally:
        store.close()   # stop the write-back thread even on error
        obs.close()
    return {"epoch_losses": epoch_losses, "finetune_loss": finetune_loss,
            "metric": metric, "state": state}


if __name__ == "__main__":
    main()
