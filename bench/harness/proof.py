"""What a driver records for the comparison, and the helpers drivers share.

A driver records its first ``PROOF_STEPS`` steps (``harness/check.py``
compares three) as host copies: the batches and rng keys it fed, each
step's loss, the parameters before the first step, after it and after the
last, the first gradient as Adam got it (its first moment after one step
over ``1 - ADAM_BETA1``) and the historical table after the last step.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

PROOF_STEPS = 3
ADAM_BETA1 = 0.9     # optim/adamw.py's default, which make_optimizer keeps


def require_off(where: str, values: Dict, off: Dict) -> None:
    """The harness reproduces the driver's plain path; a default that
    switches on another path needs the harness to follow it."""
    for k, v in off.items():
        if values[k] != v:
            raise SystemExit(f"{where}: default {k}={values[k]!r} takes a "
                             f"path this harness does not drive (expects "
                             f"{v!r})")


def tree_nbytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


def avals(tree):
    """Shapes, types and shardings of a tree's arrays, for ``lower``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def host(tree):
    """A float32 host copy of a tree of arrays."""
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32),
                                  jax.device_get(tree))


def host_batch(ds, ids) -> Dict:
    """The batch of examples ``ids`` as the reference reads it, gathered
    from the dataset's host arrays: not what the code under test fed its
    step, so that a wrong row there shows as a gap."""
    return {**ds.seg_inputs(ids), "seg_valid": ds.seg_valid[ids],
            "ids": ids, "labels": ds.labels[ids]}


def recorded(driver, steps, table):
    """Yield the metrics of ``steps``, an iterator of ``(batch, key,
    metrics)`` that advances ``driver.state`` by one step each, and record
    the first ``PROOF_STEPS`` in ``driver.proof``.  ``batch()`` builds the
    step's batch as the reference reads it; ``table()`` is a host copy of
    the driver's table."""
    params = lambda: host((driver.state.backbone, driver.state.head))
    pr = driver.proof = {"losses": [], "batches": [], "rngs": [],
                         "params0": params()}
    for i, (batch, key, m) in enumerate(steps):
        if i < PROOF_STEPS:
            pr["losses"].append(float(m["loss"]))
            pr["batches"].append(batch())
            pr["rngs"].append(key)
            if i == 0:   # Adam's first moment after one step
                pr["grads1"] = jax.tree_util.tree_map(
                    lambda mu: mu / (1 - ADAM_BETA1),
                    host(driver.state.opt_state["mu"]))
                pr["params1"] = params()
            if i == PROOF_STEPS - 1:
                pr["params"] = params()
                pr["table"] = table()
        yield m
