"""End-to-end experiment driver for the paper-faithful graph track.

Runs one (dataset, backbone, variant) cell of the paper's tables on the
synthetic MalNet-like / TpuGraphs-like datasets: GST training (Algorithm 1/2)
with optional head-finetuning phase, returning train/test metrics and
wall-clock per-iteration time (Table 3 analogue).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gst as G
from repro.graphs import batching as Bt
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.obs import StalenessProbe, get_registry, probe_jit, span
from repro.optim import make_optimizer
from repro.store import DeviceStore, TieredStore


@dataclass
class ExperimentResult:
    variant: str
    backbone: str
    train_metric: float
    test_metric: float
    ms_per_iter: float
    use_pallas: bool = False
    finetuned: bool = False      # whether the Algorithm-2 head-finetuning
                                 # phase (lines 11-18) actually ran
    curve: List[Dict] = field(default_factory=list)
    store_stats: Optional[Dict] = None   # residency counters (store/)


def _to_batch(seg_inputs, seg_valid, ids, labels) -> G.GSTBatch:
    """The batch put on the device, as one ``feeder.put`` span.  A
    resident handle (``batching.ResidentSegments``) passes as it is, for
    the step to gather its segments from, and counts as a
    ``feeder.step_gathers``."""
    with span("feeder.put"):
        if isinstance(seg_inputs, Bt.ResidentSegments):
            reg = get_registry()
            if reg.enabled:
                reg.inc("feeder.step_gathers")
        else:
            seg_inputs = {k: jnp.asarray(v) for k, v in seg_inputs.items()}
        return G.GSTBatch(seg_inputs, jnp.asarray(seg_valid),
                          jnp.asarray(ids), jnp.asarray(labels))


def _step_temp_bytes(step, state: G.TrainState, batch: G.GSTBatch,
                     key) -> int:
    """Temp bytes of ``step`` compiled for these arguments (which may be
    shapes)."""
    compiled = step.lower(state, batch, key).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def _place_datasets(step, state: G.TrainState, datasets,
                    batch_size: int) -> None:
    """Decides the feeder's residency (``SegmentedDataset.resident``)
    where the training step is known: before its first batch, each
    dataset reserves the compiled step's temp bytes, one gathered batch
    and the bytes of the datasets after it.  A backend that reports no
    memory places them without compiling."""
    if Bt._device_free_bytes() is None:
        return
    ds = datasets[0]
    shape = lambda a: jax.ShapeDtypeStruct((batch_size,) + a.shape[1:],
                                           a.dtype)
    batch = G.GSTBatch({k: shape(getattr(ds, k)) for k in Bt.SEG_FIELDS},
                       shape(ds.seg_valid),
                       jax.ShapeDtypeStruct((batch_size,), jnp.int32),
                       shape(ds.labels))
    reserve = (_step_temp_bytes(step, state, batch, jax.random.key(0))
               + batch_size * ds.seg_nbytes // ds.n)
    for i, d in enumerate(datasets):
        d.resident(reserve + sum(e.seg_nbytes for e in datasets[i + 1:]))


def run_step(step, state: G.TrainState, batch: G.GSTBatch, key):
    """One training step of the jitted ``step``: its dispatch under a
    ``train.step`` span, then the wait for its loss under a child
    ``train.wait``.  Returns ``(state, metrics)`` with the loss ready."""
    with span("train.step"):
        state, m = step(state, batch, key)
        with span("train.wait"):
            jax.block_until_ready(m["loss"])
    return state, m


def run_experiment(
    *,
    dataset: str = "malnet",          # malnet | tpugraphs
    backbone: str = "sage",           # gcn | sage | gps
    variant: str = "gst_efd",
    n_graphs: int = 80,
    max_seg_nodes: int = 64,
    partition: str = "bfs",
    epochs: int = 30,
    finetune_epochs: int = 10,
    batch_size: int = 8,
    hidden: int = 64,
    lr: float = 5e-3,
    keep_prob: float = 0.5,
    num_sampled: int = 1,
    seed: int = 0,
    test_frac: float = 0.25,
    record_curve: bool = False,
    use_pallas: bool = False,
    table_device_rows: Optional[int] = None,
    evict_policy: str = "lru",
    wb_threshold: float = 0.0,
    sed_age_weighting: float = 0.0,   # λ of the stale-branch exp(-λ·age)
                                      # decay in Eq. 1 (0 = off, bit-exact)
    stale_forecast: bool = False,     # extrapolate stale host rows forward
                                      # on fault-in (store/forecast.py)
    obs=None,                         # optional repro.obs.Obs bundle: gets a
                                      # per-epoch tick + staleness probe
) -> ExperimentResult:
    var = G.VARIANTS[variant]
    if dataset == "malnet":
        graphs = D.make_malnet_like(n_graphs=n_graphs, seed=seed)
        loss_kind, head_mode, agg, n_out = "ce", "mlp", "mean", 5
    else:
        graphs = D.make_tpugraphs_like(n_graphs=n_graphs, seed=seed)
        # paper §5.3: per-segment runtime, F' = sum; normalize targets
        loss_kind, head_mode, agg, n_out = "pairwise_hinge", "segment_sum", "sum", 1
        lab = np.asarray([g.label for g in graphs], np.float32)
        mu, sd = lab.mean(), lab.std() + 1e-6
        for g in graphs:
            g.label = float((g.label - mu) / sd)

    n_test = int(len(graphs) * test_frac)
    rng = np.random.default_rng(seed + 17)
    perm = rng.permutation(len(graphs))
    test_graphs = [graphs[i] for i in perm[:n_test]]
    train_graphs = [graphs[i] for i in perm[n_test:]]

    ds = Bt.segment_dataset(train_graphs, max_seg_nodes, method=partition, seed=seed)
    ds_test = Bt.segment_dataset(test_graphs, max_seg_nodes, method=partition,
                                 seed=seed, j_max=ds.j_max, e_max=ds.e_max)

    cfg = GNNConfig(backbone=backbone, n_feat=graphs[0].x.shape[1],
                    hidden=hidden, use_pallas=use_pallas)
    enc = make_encode_fn(cfg)
    key = jax.random.key(seed)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), hidden, n_out, head_mode)
    opt = make_optimizer("adam", lr=lr)
    # the historical table lives behind the embedding store: fully
    # device-resident by default, or a bounded LRU of hot rows over a
    # host-RAM tier when table_device_rows caps device residency —
    # bit-identical either way (tests/test_store.py)
    store = (TieredStore(ds.n, ds.j_max, hidden,
                         device_rows=max(table_device_rows, batch_size),
                         evict_policy=evict_policy,
                         wb_threshold=wb_threshold,
                         stale_forecast=stale_forecast)
             if table_device_rows else DeviceStore(ds.n, ds.j_max, hidden))
    state = G.TrainState(bb, head, opt.init((bb, head)),
                         store.init_device_table(),
                         jnp.zeros((), jnp.int32))

    # TrainState is donated through the hot steps so the (n, J, d) embedding
    # table scatters in-place instead of copying the largest array each iter.
    # probe_jit hooks each jit entry point into the obs.memory probe
    # (--mem-probe): compiled memory/cost stats per (site, shape signature),
    # a no-op branch when probing is off
    step = probe_jit("train.step", jax.jit(G.make_train_step(
        enc, opt, var, num_sampled=num_sampled, keep_prob=keep_prob,
        head_mode=head_mode, loss_kind=loss_kind, agg=agg,
        use_pallas=use_pallas, sed_decay=sed_age_weighting),
        donate_argnums=(0,)))
    eval_step = probe_jit("train.eval", jax.jit(
        G.make_eval_step(enc, head_mode=head_mode, loss_kind=loss_kind,
                         agg=agg, use_pallas=use_pallas)))
    refresh = probe_jit("train.refresh", jax.jit(
        G.make_refresh_step(enc), donate_argnums=(0,)))

    def evaluate(ds_, st):
        ms, ws = [], []
        for tup in Bt.batch_iterator(ds_, batch_size, rng=np.random.default_rng(0),
                                     shuffle=False):
            m = eval_step(st, _to_batch(*tup))
            ms.append(float(m["metric"]))
            ws.append(tup[1].shape[0])
        return float(np.average(ms, weights=ws)) if ms else float("nan")

    # host-side mirror of state.step: the step hint handed to the store on
    # write paths (train/refresh), so stale-first scoring and the stale-row
    # forecaster see the TRUE step without a device sync per batch
    step_counter = {"t": 0}

    def route(tup, step=None):
        """Map the batch's graph ids onto device rows through the store
        (migrating tiers as needed) — identity under the DeviceStore."""
        nonlocal state
        table, slots = store.prepare(state.table, tup[2], step=step)
        state = state._replace(table=table)
        return jnp.asarray(slots)

    def routed(tup, step=None):
        return _to_batch(*tup)._replace(graph_ids=route(tup, step=step))

    if table_device_rows:   # a table capped on the device: data off it too
        ds.keep_on_host()
        ds_test.keep_on_host()
    else:
        _place_datasets(step, state, [ds, ds_test], batch_size)

    # the store owns a write-back thread when tiered — release it even
    # when training raises (try/finally), keeping repeated runs leak-free
    try:
        curve = []
        iter_times = []
        brng = np.random.default_rng(seed + 3)
        last_train = 0.0
        probe = StalenessProbe(keep_prob=keep_prob, num_sampled=num_sampled,
                               seg_valid=ds.seg_valid,
                               sed_decay=sed_age_weighting,
                               forecast=stale_forecast)
        for epoch in range(epochs):
            ep_metrics = []
            for tup in Bt.batch_iterator(ds, batch_size, rng=brng):
                batch = _to_batch(*tup)
                # the timed region includes the tier migration — it IS part of
                # the step cost of a capped-capacity table (bench_store.py)
                t0 = time.perf_counter()
                # replaces state.table before the step sees it; the hint is
                # the step about to WRITE these rows
                slots = route(tup, step=step_counter["t"])
                state, m = run_step(step, state,
                                    batch._replace(graph_ids=slots),
                                    jax.random.key(epoch))
                step_counter["t"] += 1
                iter_times.append(time.perf_counter() - t0)
                ep_metrics.append(float(m["metric"]))
            last_train = float(np.mean(ep_metrics))
            # resident rows refreshed by this epoch's writes re-report their
            # true device-plane ages to the eviction bookkeeping (no-op
            # under plain LRU)
            store.refresh_ages(state.table)
            stale = None
            if get_registry().enabled:
                store.publish_counters()
                stale = probe.observe(store, state.table,
                                      int(jax.device_get(state.step)))
            if obs is not None:
                obs.tick(step=int(jax.device_get(state.step)), epoch=epoch,
                         train=last_train, staleness=stale)
            if record_curve:
                curve.append({"epoch": epoch, "train": last_train,
                              "test": evaluate(ds_test, state)})

        # ---- head finetuning phase (Algorithm 2 lines 11-18) -----------------
        # Runs for BOTH head modes: the MLP graph head and the TpuGraphs
        # per-segment scalar head finetune from the refreshed table.
        finetuned = False
        if var.finetune_head:
            for tup in Bt.batch_iterator(ds, batch_size, rng=brng, shuffle=False):
                # refresh WRITES every requested row at the current step
                batch = routed(tup, step=step_counter["t"])
                state = refresh(state, batch)
            ft_opt = make_optimizer("adam", lr=lr * 0.5)
            state = state._replace(opt_state=ft_opt.init(state.head))
            ft_step = probe_jit("train.finetune", jax.jit(G.make_finetune_step(
                ft_opt, head_mode=head_mode, loss_kind=loss_kind, agg=agg,
                use_pallas=use_pallas), donate_argnums=(0,)))
            for fe in range(finetune_epochs):
                for tup in Bt.batch_iterator(ds, batch_size, rng=brng):
                    batch = routed(tup)
                    state, m = ft_step(state, batch)
                    finetuned = True
                if record_curve:
                    curve.append({"epoch": epochs + fe, "train": float(m["metric"]),
                                  "test": evaluate(ds_test, state)})
            state = state._replace(opt_state=opt.init((state.backbone, state.head)))

        store.flush_writebacks()
        store_stats = store.stats()
    finally:
        store.close()
    # skip the first few compile-laden iterations in the timing
    ms_per_iter = float(np.median(iter_times[3:]) * 1e3) if len(iter_times) > 4 else float("nan")
    return ExperimentResult(
        variant=variant, backbone=backbone,
        train_metric=last_train,
        test_metric=evaluate(ds_test, state),
        ms_per_iter=ms_per_iter, use_pallas=use_pallas,
        finetuned=finetuned, curve=curve, store_stats=store_stats)
