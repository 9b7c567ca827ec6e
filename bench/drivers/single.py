"""The single-chip training loop, built from the program's own pieces
exactly as its driver builds them.

The loop of ``graphs/experiment.py::run_experiment`` (what
``launch/train.py`` runs): ``_to_batch`` -> ``store.prepare`` (row
routing) -> the donated, jitted ``core/gst.py::make_train_step`` ->
``block_until_ready`` on the loss, one epoch of ``batch_iterator`` after
another.  The protocol a driver keeps is in ``harness/spec.py``; the
batches recorded for the reference are gathered from the dataset's host
arrays by the yielded ids, not taken from the batch the step was fed.
"""
from __future__ import annotations

import inspect
import itertools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness.proof import (avals, host_batch, recorded, require_off,
                           tree_nbytes)
from repro.core import gst as G
from repro.graphs import batching as Bt
from repro.graphs import experiment as EX
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.optim import make_optimizer


class Driver:
    """Encoder, optimizer and the jitted step, built once."""

    def __init__(self, cfg: Dict, traffic: Dict, ds, devices):
        self.cfg, self.traffic, self.ds = cfg, traffic, ds.segmented
        self.devices = devices
        self.B, self.S = traffic["batch_size"], traffic["num_sampled"]
        sig = inspect.signature(EX.run_experiment).parameters
        require_off("graphs/experiment.py::run_experiment",
                    {k: p.default for k, p in sig.items()},
                    {"table_device_rows": None, "sed_age_weighting": 0.0,
                     "stale_forecast": False})
        self.gnn = GNNConfig(backbone=cfg["backbone"], n_feat=cfg["n_feat"],
                             hidden=cfg["hidden"], n_pre=cfg["n_pre"],
                             n_mp=cfg["n_mp"], n_post=cfg["n_post"],
                             use_pallas=cfg["use_pallas"])
        self.opt = make_optimizer(cfg["optimizer"], lr=cfg["lr"],
                                  max_grad_norm=cfg["max_grad_norm"])
        from repro.store import DeviceStore
        self.store = DeviceStore(ds.n, ds.j_max, cfg["hidden"])
        self.step = jax.jit(G.make_train_step(
            make_encode_fn(self.gnn), self.opt, G.VARIANTS[cfg["variant"]],
            num_sampled=self.S, keep_prob=cfg["keep_prob"],
            head_mode=cfg["head"], loss_kind=cfg["loss"], agg=cfg["agg"],
            use_pallas=cfg["use_pallas"]), donate_argnums=(0,))
        self.h2d_bytes = 0          # host bytes put on the device per step
        self.proof: Dict = {}
        self.last = None            # (batch, key) of the latest step
        self.spans: Optional[List] = None

    def start(self, weight_key: int, batch_seed: int, rng_seed: int):
        cfg = self.cfg
        bb, head = jax.jit(lambda k: (
            gnn_init(k, self.gnn),
            G.head_init(jax.random.fold_in(k, 1), cfg["hidden"],
                        cfg["n_out"], cfg["head"])))(
            jax.random.key(weight_key))
        self.state = G.TrainState(bb, head, jax.jit(self.opt.init)((bb, head)),
                                  self.store.init_device_table(),
                                  jnp.zeros((), jnp.int32))
        self.batch_seed, self.rng_seed = batch_seed, rng_seed

    def lower_temp_bytes(self) -> int:
        """XLA temp bytes of the compiled step at this cell's shapes."""
        batch, key = self.last
        compiled = self.step.lower(avals(self.state), avals(batch),
                                   key).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def steps(self):
        """Yields after each step; records the first steps."""
        return recorded(self, self._steps(), lambda: tuple(
            np.asarray(a) for a in jax.device_get(self.state.table)))

    def _steps(self):
        brng = np.random.default_rng(self.batch_seed)
        t = 0
        for epoch in itertools.count():
            key = jax.random.key(self.rng_seed + epoch)
            batches = Bt.batch_iterator(self.ds, self.B, rng=brng)
            while True:
                t0 = time.perf_counter()
                tup = next(batches, None)
                if tup is None:
                    break
                batch = EX._to_batch(*tup)
                table, slots = self.store.prepare(self.state.table, tup[2],
                                                  step=t)
                self.state = self.state._replace(table=table)
                batch = batch._replace(graph_ids=jnp.asarray(slots))
                if not self.h2d_bytes:
                    self.h2d_bytes = tree_nbytes(tup) + np.asarray(
                        slots).nbytes
                t1 = time.perf_counter()
                self.state, m = self.step(self.state, batch, key)
                jax.block_until_ready(m["loss"])
                if self.spans is not None:
                    self.spans += [(t0, t1, "bench.batch"),
                                   (t1, time.perf_counter(), "bench.step")]
                self.last = (batch, key)
                t += 1
                yield (lambda ids=tup[2]: host_batch(self.ds, ids)), key, m

    def block(self):
        jax.block_until_ready(self.state)

    def close(self):
        self.state = self.last = None
