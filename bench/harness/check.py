"""The comparison that decides ``correct``: the program's first three steps
against the plain reference's, number by number.  ``numbers`` reads all of
the following; a cell's limits file names the ones compared, each with its
limit.

* ``loss_gap``: the largest relative gap between the two losses over the
  three steps; ``loss1_gap``: the first step's alone.
* ``grad_gap``: the first gradient as the optimizer got it; per leaf, the
  gap between the two norms over the larger of the reference leaf's norm
  and the median leaf's; the worst leaf.  ``grad_elem_gap``: the median,
  over the elements of the leaves that move, of each element's relative
  gap.
* ``change_gap``: the same for the norm of each leaf's change over the
  three steps; ``change_med_gap``: the median leaf's gap.  Leaves whose
  reference gradient is under a thousandth of the median leaf's move under
  Adam by round-off alone and are left out.
* ``change_elem_gap``: the median, over the elements of those leaves, of
  the relative gap between each element's change and the reference's.
  Adam turns a gradient element near zero into a full step of either sign,
  so the elements whose sign rounding flips differ by up to twice the
  learning rate; the median element does not see them.
* ``step1_elem_gap``: the same for the first step's change alone.  That
  step moves each element by the learning rate times the sign of its
  gradient, so it is exact wherever the sign agrees, at any matmul
  precision, and it shows how parameters are stored and updated.
* ``table_gap``: the largest gap between a written table entry and the
  reference's, over the largest reference entry; ``table1_gap``: the same
  over the entries the first step wrote.
* ``table_mismatch``: table slots whose written flag or step of writing
  differs from the reference's; exact.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import numpy as np


def _leaves(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in flat}


def _norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree).items()}


def _gaps(prog: Dict[str, float], ref: Dict[str, float], keys):
    floor = float(np.median(list(ref.values())))
    return [abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30) for k in keys]


def _table_gap(prog, ref, where) -> float:
    if not where.any():
        return math.inf
    scale = max(float(np.max(np.abs(ref[where]))), 1e-30)
    return float(np.max(np.abs(prog[where].astype(np.float64)
                               - ref[where]))) / scale


def _elem_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    nz = ref != 0
    return float(np.median(np.abs(prog[nz] - ref[nz]) / np.abs(ref[nz])))


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref``: records of three steps, as the drivers'
    ``proof`` and ``reference.run`` give them."""
    losses = [abs(p - r) / max(abs(r), 1e-12)
              for p, r in zip(prog["losses"], ref["losses"])]
    g_ref, g_prog = _norms(ref["grads1"]), _norms(prog["grads1"])
    floor = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= 1e-3 * floor]
    delta = lambda r, to="params": jax.tree_util.tree_map(
        lambda a, b: a - b, r[to], r["params0"])
    d_prog, d_ref = _leaves(delta(prog)), _leaves(delta(ref))
    change = _gaps({k: float(np.linalg.norm(v)) for k, v in d_prog.items()},
                   {k: float(np.linalg.norm(v)) for k, v in d_ref.items()},
                   moving)
    elem_gap = lambda dp, dr: _elem_gap(
        np.concatenate([dp[k].ravel() for k in moving]),
        np.concatenate([dr[k].ravel() for k in moving]))
    emb_p, age_p, init_p = prog["table"]
    emb_r, age_r, init_r = ref["table"]
    written, init_p = init_r.astype(bool), init_p.astype(bool)
    both = written & init_p
    return {"loss_gap": float(np.max(losses)), "loss1_gap": losses[0],
            "grad_gap": float(np.max(_gaps(g_prog, g_ref, g_ref))),
            "grad_elem_gap": elem_gap(_leaves(prog["grads1"]),
                                      _leaves(ref["grads1"])),
            "change_gap": float(np.max(change)),
            "change_med_gap": float(np.median(change)),
            "change_elem_gap": elem_gap(d_prog, d_ref),
            "step1_elem_gap": elem_gap(_leaves(delta(prog, "params1")),
                                       _leaves(delta(ref, "params1"))),
            "table_gap": _table_gap(emb_p, emb_r, both),
            "table1_gap": _table_gap(emb_p, emb_r, both & (age_r == 0)),
            "table_mismatch": float(np.sum(init_p != written)
                                    + np.sum(both & (age_p != age_r)))}


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number the limits name is finite and within its limit."""
    return all(math.isfinite(values[k]) and values[k] <= lim
               for k, lim in limits.items())
