"""Training launcher — both tracks, CPU-runnable at reduced scale.

Examples:
    # paper track: GST+EFD on synthetic MalNet with a SAGE backbone
    PYTHONPATH=src python -m repro.launch.train --track graph \
        --backbone sage --variant gst_efd --epochs 30

    # sequence track: GST+EFD property training with a reduced assigned arch
    PYTHONPATH=src python -m repro.launch.train --track seq \
        --arch internlm2-1.8b --reduced --steps 200

    # plain-LM objective (the non-GST baseline of the framework)
    PYTHONPATH=src python -m repro.launch.train --track lm \
        --arch olmo-1b --reduced --steps 100
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, reduced as reduce_cfg
from repro.core import gst as G
from repro.data.tokens import doc_batch_iterator, make_lm_stream, make_property_docs
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.obs import Obs, StalenessProbe, add_obs_args
from repro.obs.trace import span
from repro.optim import cosine_schedule, make_optimizer
from repro.store import DeviceStore, TieredStore


def train_graph(args, obs):
    from repro.graphs.experiment import run_experiment
    if args.use_pallas and args.backbone == "gps":
        print("[graph] --use-pallas: gps has no fused kernel; encoding on "
              "the jnp reference path")
    r = run_experiment(
        dataset=args.dataset, backbone=args.backbone, variant=args.variant,
        n_graphs=args.n_graphs, epochs=args.epochs,
        finetune_epochs=args.finetune_epochs, keep_prob=args.keep_prob,
        seed=args.seed, use_pallas=args.use_pallas,
        table_device_rows=args.table_device_rows,
        evict_policy=args.evict_policy,
        wb_threshold=args.wb_threshold,
        sed_age_weighting=args.sed_age_weighting,
        stale_forecast=args.stale_forecast, obs=obs)
    print(f"[graph/{args.dataset}] {args.backbone} {args.variant}"
          f"{' [pallas]' if args.use_pallas else ''}: "
          f"train={r.train_metric:.3f} test={r.test_metric:.3f} "
          f"{r.ms_per_iter:.1f} ms/iter")
    if r.store_stats and args.table_device_rows:
        s = r.store_stats
        print(f"  store [{s['backend']}] device rows {s['device_rows']}/"
              f"{s['n_rows']}  hit-rate {s['hit_rate']:.2f} "
              f"({s['hits']} hits / {s['misses']} faults), "
              f"{s['evictions']} evictions, "
              f"{s['migration_bytes'] / 1024:.1f} KiB migrated")
    return r


def train_seq(args, obs):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg)
    J, L = cfg.gst_num_segments, args.seg_len
    docs = make_property_docs(n_docs=args.n_docs, n_segments=J, seg_len=L,
                              vocab=cfg.vocab_size,
                              n_topics=cfg.gst_num_classes, seed=args.seed)
    key = jax.random.key(args.seed)
    params = model.init(key)
    head = G.head_init(jax.random.fold_in(key, 1), cfg.d_model,
                       cfg.gst_num_classes, "mlp")
    opt = make_optimizer("adamw", lr=args.lr, weight_decay=0.01)
    # the (n_docs, J, d_model) table sits behind the embedding store —
    # --table-device-rows caps how many doc rows stay in device memory
    store = (TieredStore(args.n_docs, J, cfg.d_model,
                         device_rows=max(args.table_device_rows,
                                         args.batch_size),
                         evict_policy=args.evict_policy,
                         wb_threshold=args.wb_threshold,
                         stale_forecast=args.stale_forecast)
             if args.table_device_rows
             else DeviceStore(args.n_docs, J, cfg.d_model))
    state = G.TrainState(params, head, opt.init((params, head)),
                         store.init_device_table(),
                         jnp.zeros((), jnp.int32))

    def encode(backbone, seg_inputs):
        return model.encode_segment(backbone, seg_inputs)

    # donate the state so the device-tier table updates in place
    step = jax.jit(G.make_train_step(
        encode, opt, G.VARIANTS[args.variant], keep_prob=args.keep_prob,
        use_pallas=args.use_pallas, sed_decay=args.sed_age_weighting),
        donate_argnums=(0,))
    try:
        rng = np.random.default_rng(args.seed)
        probe = StalenessProbe(keep_prob=args.keep_prob, num_sampled=1,
                               sed_decay=args.sed_age_weighting,
                               forecast=args.stale_forecast)
        it = 0
        t0 = time.time()
        while it < args.steps:
            for tup in doc_batch_iterator(docs, args.batch_size, rng=rng):
                # step hint: the train step about to WRITE these rows —
                # feeds stale-first scoring and the stale-row forecaster
                table, slots = store.prepare(state.table, np.asarray(tup[2]),
                                             step=it)
                state = state._replace(table=table)
                batch = G.GSTBatch({"tokens": jnp.asarray(tup[0]["tokens"])},
                                   jnp.asarray(tup[1]), jnp.asarray(slots),
                                   jnp.asarray(tup[3]))
                with span("train.step", step=it):
                    state, m = step(state, batch, jax.random.key(it))
                it += 1
                if it % args.log_every == 0:
                    print(f"step {it}: loss={float(m['loss']):.4f} "
                          f"acc={float(m['metric']):.3f} "
                          f"({(time.time()-t0)/it*1e3:.0f} ms/step)", flush=True)
                    if obs.enabled:
                        store.publish_counters()
                        stale = probe.observe(store, state.table, it)
                        obs.tick(step=it, loss=float(m["loss"]),
                                 staleness=stale)
                if it >= args.steps:
                    break
        # surface any failed async write-back BEFORE reporting success
        store.flush_writebacks()
        if args.table_device_rows:
            s = store.stats()
            print(f"store [{s['backend']}] device rows {s['device_rows']}/"
                  f"{s['n_rows']}  hit-rate {s['hit_rate']:.2f}, "
                  f"{s['evictions']} evictions, "
                  f"{s['migration_bytes'] / 1024:.1f} KiB migrated")
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, it, {"backbone": state.backbone,
                                                "head": state.head})
    finally:
        store.close()   # stop the write-back thread even on error
    return state


def train_lm(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg)
    data = make_lm_stream(args.n_docs, args.seg_len + 1, cfg.vocab_size,
                          seed=args.seed)
    params = model.init(jax.random.key(args.seed))
    opt = make_optimizer("adamw", lr=args.lr,
                         schedule=cosine_schedule(args.lr, args.steps, 10))
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(p):
            h, aux = model.forward_with_aux(p, {"tokens": tokens[:, :-1]})
            logits = model.logits(p, h)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
            return jnp.mean(nll) + 1e-2 * aux
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, _ = opt.update(params, grads, opt_state)
        return params, opt_state, loss

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for it in range(args.steps):
        ids = rng.integers(0, len(data), args.batch_size)
        params, opt_state, loss = step(params, opt_state, jnp.asarray(data[ids]))
        if (it + 1) % args.log_every == 0:
            print(f"step {it+1}: lm_loss={float(loss):.4f} "
                  f"({(time.time()-t0)/(it+1)*1e3:.0f} ms/step)", flush=True)
    return params


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--track", default="graph", choices=["graph", "seq", "lm"])
    # graph track
    ap.add_argument("--dataset", default="malnet", choices=["malnet", "tpugraphs"])
    ap.add_argument("--backbone", default="sage", choices=["gcn", "sage", "gps"])
    ap.add_argument("--n-graphs", type=int, default=100)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--finetune-epochs", type=int, default=10)
    # shared
    ap.add_argument("--variant", default="gst_efd", choices=list(G.VARIANTS))
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the hot path through the fused Pallas kernels "
                         "(batched segment_spmm + sed_pool; interpret mode "
                         "when not on TPU)")
    ap.add_argument("--keep-prob", type=float, default=0.5)
    ap.add_argument("--table-device-rows", type=int, default=None,
                    help="cap device-resident historical-table rows; the "
                         "rest spill to a host-RAM tier (store/tiered.py). "
                         "Clamped up to the batch size. Default: whole "
                         "table on device")
    ap.add_argument("--wb-threshold", type=float, default=0.0,
                    help="delta-gated write-back under --table-device-rows: "
                         "skip the host-tier emb write for evicted rows "
                         "whose embedding moved less than this (max-abs) "
                         "while resident (store/writeback.delta_gate). "
                         "0 = gate off, bit-exact store")
    ap.add_argument("--evict-policy", default="lru",
                    choices=["lru", "stale-first"],
                    help="tiered-store eviction policy under "
                         "--table-device-rows (stale_first scores by the "
                         "row's true last-write step)")
    ap.add_argument("--sed-age-weighting", type=float, default=0.0,
                    help="λ of the exp(-λ·age) staleness decay folded into "
                         "the stale branch of Eq.-1 η (graph track, "
                         "use_sed+use_table variants). 0 = off, bit-exact "
                         "to the unweighted step")
    ap.add_argument("--stale-forecast", action="store_true",
                    help="extrapolate stale host-tier rows forward by their "
                         "age on fault-in via the online per-row velocity "
                         "forecaster (store/forecast.py); needs "
                         "--table-device-rows")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    # seq/lm track
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seg-len", type=int, default=64)
    ap.add_argument("--n-docs", type=int, default=64)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    add_obs_args(ap)
    args = ap.parse_args()
    obs = Obs.from_args(args, run="train", track=args.track,
                        variant=args.variant)
    try:
        if args.track == "graph":
            train_graph(args, obs)
        elif args.track == "seq":
            train_seq(args, obs)
        else:
            train_lm(args)
    finally:
        obs.close()


if __name__ == "__main__":
    main()
