"""Plain reference of the timed GST-EFD train step, in straightforward jnp.

It imports nothing of the program.  It builds its own weights from the same
seed with the same ``jax.random`` calls as the published GraphGym layer
structure, and keeps its own historical table.  It follows the paper's
Algorithm 1/2 step with Stale Embedding Dropout (Eq. 1), as the program
states it: sample S segments per graph (Gumbel top-k over the valid ones),
encode them (pre MLP, SAGE mean aggregation, post MLP, PReLU, mean pool),
fill the other slots from the table (zero weight where never written),
weight by eta, pool, apply the head and the loss, clip the gradients to a
global norm, take an Adam step, and write the fresh embeddings back.

``dtype`` float32 runs at HIGHEST matmul precision (the reference);
bfloat16 runs everything in bfloat16 (the control).  Random draws stay in
float32 in both, so both see the same segments and drops.

``fault`` plants a fault in the reference put in the program's place:
``half_batch`` (loss over the first half of the batch only), ``altered``
(the write-back lands on the next segment's slot), ``frozen`` (parameters
left as they are after the first step, while the optimizer state and the
table go on) and ``beta2`` (Adam's second moment decays with
``FAULT_BETA2`` for 0.999).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

FAULT_BETA2 = 0.5


def _dense(key, d_in, d_out):
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out))
            / math.sqrt(d_in))


def init_params(key, cfg: Dict):
    """(backbone, head) with the program's tree layout and key schedule."""
    if cfg["backbone"] != "sage" or cfg["head"] != "segment_sum":
        raise ValueError("the reference has a SAGE backbone and a summed "
                         "per-segment head only")
    d, n_pre, n_mp, n_post = (cfg["hidden"], cfg["n_pre"], cfg["n_mp"],
                              cfg["n_post"])
    keys = jax.random.split(key, n_pre + n_mp + n_post + 1)
    a = lambda: {"a": jnp.full((1,), 0.25, jnp.float32)}
    p = {"pre": [], "mp": [], "post": []}
    d_in = cfg["n_feat"]
    for i in range(n_pre):
        p["pre"].append({"w": _dense(keys[i], d_in, d), "b": jnp.zeros((d,)),
                         "prelu": a()})
        d_in = d
    for i in range(n_mp):
        k1, k2, _, _ = jax.random.split(keys[n_pre + i], 4)
        p["mp"].append({"w_self": _dense(k1, d, d), "w_nbr": _dense(k2, d, d),
                        "prelu": a()})
    for i in range(n_post):
        p["post"].append({"w": _dense(keys[n_pre + n_mp + i], d, d),
                          "b": jnp.zeros((d,)), "prelu": a()})
    k1, _ = jax.random.split(jax.random.fold_in(key, 1))
    return p, {"w": _dense(k1, d, 1), "b": jnp.zeros((1,))}


def _prelu(lp, x):
    return jnp.where(x >= 0, x, lp["prelu"]["a"] * x)


def _encode(p, x, edges, ev, nv):
    """One padded segment -> its embedding."""
    m = x.shape[0]
    src, dst = edges[:, 0], edges[:, 1]
    h = x
    for lp in p["pre"]:
        h = _prelu(lp, h @ lp["w"] + lp["b"])
    h = h * nv[:, None]
    deg = jnp.maximum(jax.ops.segment_sum(ev, dst, num_segments=m), 1)
    for lp in p["mp"]:
        nbr = jax.ops.segment_sum(h[src] * ev[:, None], dst, num_segments=m)
        nbr = nbr / deg[:, None]
        h = _prelu(lp, h @ lp["w_self"] + nbr @ lp["w_nbr"]) * nv[:, None]
    for lp in p["post"]:
        h = _prelu(lp, h @ lp["w"] + lp["b"])
    h = h * nv[:, None]
    return jnp.sum(h, 0) / jnp.maximum(jnp.sum(nv), 1)


def _draws(rng, t, seg_valid, S):
    """Sampled segment indices (B, S) and SED uniforms (B, J)."""
    B, J = seg_valid.shape
    r_sample, r_sed = jax.random.split(jax.random.fold_in(rng, t))
    g = jax.random.gumbel(r_sample, (B, J))
    u = jax.random.uniform(r_sed, (B, J))
    _, idx = jax.lax.top_k(jnp.where(seg_valid > 0, g, -jnp.inf), S)
    return idx, u


def _loss(trainable, cfg, batch, idx, eta, base, rows):
    backbone, head = trainable
    B = batch["seg_valid"].shape[0]
    b = jnp.arange(B)[:, None]
    take = lambda a: a[b, idx].reshape((-1,) + a.shape[2:])
    h_s = jax.vmap(lambda x, e, ev, nv: _encode(backbone, x, e, ev, nv))(
        take(batch["x"]), take(batch["edges"]), take(batch["edge_valid"]),
        take(batch["node_valid"])).reshape(B, idx.shape[1], -1)
    h_comb = base.at[b, idx].set(h_s)
    sv = batch["seg_valid"]
    scal = (h_comb @ head["w"] + head["b"])[..., 0]
    preds = jnp.sum(scal * eta, -1)
    if cfg["agg"] == "mean":
        preds = preds / jnp.maximum(jnp.sum(sv, -1), 1)
    y = batch["labels"]
    pair = rows[:, None] * rows[None, :]
    gt = (y[:, None] > y[None, :]).astype(preds.dtype) * pair
    dy = preds[:, None] - preds[None, :]
    loss = jnp.sum(gt * jnp.maximum(0, 1 - dy)) / jnp.maximum(jnp.sum(gt), 1)
    return loss, h_s


def make_step(cfg: Dict, traffic: Dict, dtype, fault: Optional[str] = None):
    """``step(trainable, opt, table, batch, rng, t) -> (loss, grads,
    trainable, opt, table)``, jitted; ``grads`` are clipped, as Adam gets
    them."""
    S, p_keep, lr = traffic["num_sampled"], cfg["keep_prob"], cfg["lr"]
    b1, eps, max_norm = 0.9, 1e-8, cfg["max_grad_norm"]
    b2 = FAULT_BETA2 if fault == "beta2" else 0.999

    def step(trainable, opt, table, batch, rng, t):
        emb, age, init = table
        batch = {k: (v.astype(dtype) if v is not None and k in
                     ("x", "edge_valid", "node_valid", "seg_valid") else v)
                 for k, v in batch.items()}
        sv, ids = batch["seg_valid"], batch["ids"]
        B, J = sv.shape
        idx, u = _draws(rng, t, sv, S)
        fresh = jnp.sum(jax.nn.one_hot(idx, J, dtype=dtype), 1) * sv
        stale_valid = sv * init[ids].astype(dtype)
        J_i = jnp.sum(sv, -1, keepdims=True)
        drop = (u > p_keep).astype(dtype)
        eta = (fresh * (p_keep + (1 - p_keep) * J_i / S)
               + sv * (1 - fresh) * (1 - drop) * stale_valid) * sv
        rows = jnp.ones((B,), dtype)
        if fault == "half_batch":
            rows = (jnp.arange(B) < B // 2).astype(dtype)
        (loss, h_s), grads = jax.value_and_grad(_loss, has_aux=True)(
            trainable, cfg, batch, idx, eta, emb[ids], rows)
        leaves = jax.tree_util.tree_leaves(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1, max_norm / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype),
                                       grads)
        n = opt["count"] + 1
        bc1, bc2 = 1 - b1 ** n.astype(jnp.float32), 1 - b2 ** n.astype(
            jnp.float32)
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                    opt["mu"], grads)
        nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                    opt["nu"], grads)
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / bc1.astype(dtype)) / (
                jnp.sqrt(v / bc2.astype(dtype)) + eps),
            trainable, mu, nu)
        if fault == "frozen":
            new = jax.tree_util.tree_map(
                lambda a, b: jnp.where(t >= 1, a, b), trainable, new)
        trainable = new
        w_idx = (idx + 1) % J if fault == "altered" else idx
        rb = jnp.broadcast_to(ids[:, None], idx.shape)
        table = (emb.at[rb, w_idx].set(jax.lax.stop_gradient(h_s)),
                 age.at[rb, w_idx].set(t), init.at[rb, w_idx].set(True))
        return loss, grads, trainable, {"count": n, "mu": mu, "nu": nu}, table

    return jax.jit(step)


def run(cfg: Dict, traffic: Dict, seed_key: int, n_rows: int, j_max: int,
        batches: List[Dict], rngs: List, dtype=jnp.float32,
        fault: Optional[str] = None, step=None) -> Dict:
    """Drive the reference through ``batches`` from its seeded initial
    state; returns host copies of what the comparison reads.  ``step``
    reuses a ``make_step`` of the same settings."""
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        trainable = init_params(jax.random.key(seed_key), cfg)
        trainable = jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                           trainable)
        zeros = lambda x: jnp.zeros_like(x)
        opt = {"count": jnp.zeros((), jnp.int32),
               "mu": jax.tree_util.tree_map(zeros, trainable),
               "nu": jax.tree_util.tree_map(zeros, trainable)}
        table = (jnp.zeros((n_rows, j_max, cfg["hidden"]), dtype),
                 jnp.zeros((n_rows, j_max), jnp.int32),
                 jnp.zeros((n_rows, j_max), bool))
        step = step or make_step(cfg, traffic, dtype, fault)
        p0 = jax.device_get(trainable)
        losses, grads1 = [], None
        for t, (batch, rng) in enumerate(zip(batches, rngs)):
            loss, grads, trainable, opt, table = step(
                trainable, opt, table, batch, rng, t)
            losses.append(float(loss))
            if t == 0:
                grads1 = jax.device_get(grads)
                params1 = jax.device_get(trainable)
        f32 = lambda tree: jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32), tree)
        return {"losses": losses, "grads1": f32(grads1), "params0": f32(p0),
                "params1": f32(params1),
                "params": f32(jax.device_get(trainable)),
                "table": tuple(np.asarray(jax.device_get(a)) for a in table)}
