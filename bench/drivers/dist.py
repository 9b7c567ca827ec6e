"""The data-parallel training loop, built from the program's own pieces
exactly as its driver builds them.

The loop of ``launch/train_dist.py::main`` at its command line's defaults
(async feeder of depth 2, ring exchange, f32 payloads, no lookahead
prefetch, the whole table on the devices): ``make_dist_mesh`` ->
``make_context`` -> ``make_dist_store`` -> ``device_state`` ->
``make_dist_train_step`` -> ``make_feeder``, one epoch's feeder after
another.  The feeder's thread gathers each batch on the host and puts it
on the mesh, sharded on the batch axis; the historical table is sharded
by rows and reached through the ring exchange.  As in ``main``, the loop
does not wait for a step before the next: ``block`` does.  The protocol
a driver keeps is in ``harness/spec.py``; the batches recorded for the
reference are gathered from the dataset by the schedule's ids, not taken
from the feeder.
"""
from __future__ import annotations

import argparse
import itertools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness.proof import (avals, host_batch, recorded, require_off,
                           tree_nbytes)
from repro import dist as DT
from repro.core import gst as G
from repro.core.embedding_table import init_table
from repro.dist import pipeline as DP
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.optim import make_optimizer

FEEDER, DEPTH = "async", 2
# train_dist's defaults that keep its plain path: any other value of these
# takes a path this driver does not build
PLAIN = {"feeder": FEEDER, "depth": DEPTH, "exchange": "ring",
         "exchange_cap": None, "payload_dtype": "f32",
         "prefetch_lookups": False, "patch_cap": None,
         "table_device_rows": None, "wb_threshold": 0.0,
         "sed_age_weighting": 0.0, "stale_forecast": False}


class _Parsed(Exception):
    pass


def cli_defaults() -> Dict:
    """The defaults of ``launch/train_dist.py``'s command line, read
    without running it: its parser is built inside ``main``, which is
    stopped at ``parse_args``."""
    from repro.launch import train_dist
    got: Dict = {}
    parse = argparse.ArgumentParser.parse_args

    def grab(self, args=None, namespace=None):
        got.update(vars(parse(self, [])))
        raise _Parsed

    argparse.ArgumentParser.parse_args = grab
    try:
        train_dist.main([])
    except _Parsed:
        pass
    finally:
        argparse.ArgumentParser.parse_args = parse
    return got


class Driver:
    """Mesh, sharded store, encoder, optimizer and the jitted step, built
    once."""

    def __init__(self, cfg: Dict, traffic: Dict, ds, devices):
        self.cfg, self.traffic, self.ds = cfg, traffic, ds.segmented
        self.devices = devices
        self.B, self.S = traffic["batch_size"], traffic["num_sampled"]
        require_off("launch/train_dist.py", cli_defaults(), PLAIN)
        if self.B % len(devices):
            raise SystemExit(f"batch {self.B} does not divide over "
                             f"{len(devices)} devices")
        self.gnn = GNNConfig(backbone=cfg["backbone"], n_feat=cfg["n_feat"],
                             hidden=cfg["hidden"], n_pre=cfg["n_pre"],
                             n_mp=cfg["n_mp"], n_post=cfg["n_post"],
                             use_pallas=cfg["use_pallas"])
        self.opt = make_optimizer(cfg["optimizer"], lr=cfg["lr"],
                                  max_grad_norm=cfg["max_grad_norm"])
        self.ctx = DT.make_context(DT.make_dist_mesh(len(devices)), ds.n,
                                   exchange=PLAIN["exchange"],
                                   payload_dtype=PLAIN["payload_dtype"])
        self.store = DT.make_dist_store(self.ctx, ds.j_max, cfg["hidden"])
        self.step = DT.make_dist_train_step(
            make_encode_fn(self.gnn), self.opt, G.VARIANTS[cfg["variant"]],
            ctx=self.ctx, keep_prob=cfg["keep_prob"], num_sampled=self.S,
            use_pallas=cfg["use_pallas"], head_mode=cfg["head"],
            loss_kind=cfg["loss"], agg=cfg["agg"])
        self.h2d_bytes = 0          # host bytes put on the devices per step
        self.proof: Dict = {}
        self.last = None            # (batch, key) of the latest step
        self.spans: Optional[List] = None

    def start(self, weight_key: int, batch_seed: int, rng_seed: int):
        cfg, ds = self.cfg, self.ds
        bb, head = jax.jit(lambda k: (
            gnn_init(k, self.gnn),
            G.head_init(jax.random.fold_in(k, 1), cfg["hidden"],
                        cfg["n_out"], cfg["head"])))(
            jax.random.key(weight_key))
        state = G.TrainState(bb, head, jax.jit(self.opt.init)((bb, head)),
                             init_table(ds.n, ds.j_max, cfg["hidden"]),
                             jnp.zeros((), jnp.int32))
        self.state = DT.device_state(self.ctx, state, store=self.store)
        self.batch_seed, self.rng_seed = batch_seed, rng_seed

    def lower_temp_bytes(self) -> int:
        """XLA temp bytes of the compiled step at this cell's shapes, on
        each device."""
        batch, key = self.last
        compiled = self.step.lower(avals(self.state), avals(batch),
                                   key).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def steps(self):
        """Yields after each step; records the first steps."""
        return recorded(self, self._steps(),
                        lambda: tuple(DT.host_table(self.ctx,
                                                    self.state.table)))

    def _batch(self, ids):
        """The batch of graphs ``ids`` as the reference reads it, gathered
        from the dataset on the host."""
        return {**host_batch(self.ds, ids),
                "batch_pos": np.arange(len(ids), dtype=np.int32)}

    def _put(self, counter):
        def put(b):
            prep = self.store.begin(np.asarray(b.graph_ids),
                                    step=next(counter))
            if not self.h2d_bytes:
                self.h2d_bytes = tree_nbytes(b)
            return prep, DT.shard_batch(self.ctx,
                                        b._replace(graph_ids=prep.slots))
        return put

    def _steps(self):
        brng = np.random.default_rng(self.batch_seed)
        put = self._put(itertools.count())
        for epoch in itertools.count():
            sched = DP.epoch_ids(self.ds, self.B, rng=brng)
            key = jax.random.PRNGKey(self.rng_seed + epoch)
            feeder = iter(DP.make_feeder(FEEDER, self.ds, sched, put,
                                         depth=DEPTH))
            try:
                for ids in sched:
                    t0 = time.perf_counter()
                    prep, batch = next(feeder)
                    t1 = time.perf_counter()
                    self.state = self.state._replace(
                        table=self.store.commit(self.state.table, prep))
                    self.state, m = self.step(self.state, batch, key)
                    if self.spans is not None:
                        self.spans += [(t0, t1, "bench.batch"),
                                       (t1, time.perf_counter(),
                                        "bench.step")]
                    self.last = (batch, key)
                    yield (lambda ids=ids: self._batch(ids)), key, m
            finally:
                feeder.close()

    def block(self):
        jax.block_until_ready(self.state)

    def close(self):
        self.state = self.last = None
        self.store.close()
