"""The training loop the window drives, built from the program's own pieces
exactly as its driver builds them.

``single``: the loop of ``graphs/experiment.py::run_experiment`` (what
``launch/train.py`` runs): ``_to_batch`` -> ``store.prepare`` (row
routing) -> the donated, jitted ``core/gst.py::make_train_step`` ->
``block_until_ready`` on the loss, one epoch of ``batch_iterator`` after
another.

The harness owns only the loop, its timing and its host spans.  A driver is
built once (the jitted step); ``start`` gives it fresh weights, state and
batch order from a seed, and ``steps`` yields once per step.  The first
steps after ``start`` are recorded for the reference.  While ``spans`` is a
list, each step appends ``(start, end, name)`` by ``time.perf_counter`` for
its batch build (``bench.batch``) and its step (``bench.step``).
"""
from __future__ import annotations

import inspect
import itertools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gst as G
from repro.graphs import batching as Bt
from repro.graphs import experiment as EX
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.optim import make_optimizer

PROOF_STEPS = 3
ADAM_BETA1 = 0.9     # optim/adamw.py's default, which make_optimizer keeps


def _require_off(where: str, values: Dict, off: Dict) -> None:
    """The harness reproduces the driver's plain path; a default that
    switches on another path needs the harness to follow it."""
    for k, v in off.items():
        if values[k] != v:
            raise SystemExit(f"{where}: default {k}={values[k]!r} takes a "
                             f"path this harness does not drive (expects "
                             f"{v!r})")


def tree_nbytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


def _avals(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def _host(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32),
                                  jax.device_get(tree))


class SingleDriver:
    """Encoder, optimizer and the jitted step, built once."""

    def __init__(self, cfg: Dict, traffic: Dict, ds, devices):
        self.cfg, self.traffic, self.ds = cfg, traffic, ds
        self.devices = devices
        self.B, self.S = traffic["batch_size"], traffic["num_sampled"]
        sig = inspect.signature(EX.run_experiment).parameters
        _require_off("graphs/experiment.py::run_experiment",
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
        compiled = self.step.lower(_avals(self.state), _avals(batch),
                                   key).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def steps(self):
        """Yields after each step; records the first ``PROOF_STEPS``."""
        pr = self.proof = {"losses": [], "batches": [], "rngs": [],
                           "params0": _host((self.state.backbone,
                                             self.state.head))}
        for i, (host, key, m) in enumerate(self._steps()):
            if i < PROOF_STEPS:
                pr["losses"].append(float(m["loss"]))
                pr["batches"].append(host)
                pr["rngs"].append(key)
                if i == 0:   # Adam's first moment after one step
                    pr["grads1"] = jax.tree_util.tree_map(
                        lambda mu: mu / (1 - ADAM_BETA1),
                        _host(self.state.opt_state["mu"]))
                    pr["params1"] = _host((self.state.backbone,
                                           self.state.head))
                if i == PROOF_STEPS - 1:
                    pr["params"] = _host((self.state.backbone,
                                          self.state.head))
                    pr["table"] = tuple(np.asarray(a) for a in
                                        jax.device_get(self.state.table))
            yield m

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
                seg, sv, ids, labels = tup
                yield ({**seg, "seg_valid": sv, "ids": ids, "labels": labels},
                       key, m)

    def block(self):
        jax.block_until_ready(self.state)

    def close(self):
        self.state = self.last = None


DRIVERS = {"single": SingleDriver}
