#!/usr/bin/env python3
"""Bring-up smoke test: drive the GST main path once on a TPU, in one process.

    python chip_smoke.py              # one chip: train, fused-vs-reference, serve
    python chip_smoke.py --chips 4    # four chips: data-parallel training only

One chip runs three phases through the entry points a user calls:

  train   ``graphs.experiment.run_experiment`` (what ``launch/train.py``
          wraps): malnet, sage, gst_efd, fused Pallas path, hidden 128 (one
          full lane tile, the kernels' d_blk), m = 64 so graphs span ~20
          segments and the historical table and SED do real work.  Every
          probed program must hold as many Mosaic kernels
          (``tpu_custom_call``) as its jaxpr holds ``pallas_call``s: fewer
          means a kernel ran in interpret mode or fell back to the reference.
  parity  one train step and one ``encode_segments`` on the same batch and
          initial state, fused path vs the jnp reference, both at HIGHEST
          matmul precision and both at the default; maxima are checked
          against the tolerances below.
  serve   ``launch.serve_graphs.main`` replays requests on the fused path
          with ``--check-parity`` (engine vs one-shot encoder).

``--chips 4`` runs only ``launch.train_dist.main``: four devices under the
ring, alltoall and bucketed exchanges must give identical per-epoch losses,
and the row-sharded table must span four devices; then four devices and one,
both at HIGHEST matmul precision, must agree within ``DIST_RTOL``.

The script exits non-zero, and prints no result line, unless JAX finds a TPU.
Its last line is the JSON result ``{"ok": true, "device": {...}}``.  Times it
prints are set-up and compile times, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# --- phase settings ----------------------------------------------------------
TRAIN = dict(dataset="malnet", backbone="sage", variant="gst_efd",
             use_pallas=True, hidden=128, max_seg_nodes=64, n_graphs=96,
             epochs=2, finetune_epochs=1)
SERVE_ARGV = ["--use-pallas", "--check-parity", "--requests", "24",
              "--unique", "8", "--warmup", "4"]
DIST_ARGV = ["--hidden", "128", "--max-seg-nodes", "64", "--n-graphs", "64",
             "--batch-size", "8", "--use-pallas", "--epochs", "2",
             "--finetune-epochs", "1"]

# --- tolerances (max |fused - reference| / max |reference|) ------------------
# HIGHEST on both sides: XLA's matmuls take f32 products, and Mosaic runs the
# kernels' f32 dots in f32 too (v5e: embeddings agree to 9.9e-8), so what
# remains is the order of f32 accumulation: the one-hot SpMM sums each edge
# block of <= 256 messages on the MXU, the reference sums them one by one.
# 1e-5 is ~100 f32 ulps of the result.
KERNEL_TOL = 1e-5
# Default precision, as users run: XLA feeds each f32 matmul through one bf16
# pass (operands rounded to 2^-9).  Fused and reference take the same passes,
# but a value that differs by f32 round-off between them can round to the
# neighbouring bf16 value; against HIGHEST every operand rounds.  The encoder
# and loss chain ~10 matmul stages (pre, 2 x (2 kernel dots + 2 dense), post,
# 2 head), each adding up to 2^-8: the bound is 10 * 2^-8.
DEFAULT_TOL = 10 * 2.0 ** -8
# Adam's first step moves every parameter by ~lr * g / |g|, so updated
# parameters are compared as the share of entries whose step differs by more
# than lr / 2: an entry whose gradient sits within rounding noise of zero may
# step the other way.  At HIGHEST that noise is f32 round-off and a flip is a
# rare event; at default precision it is bf16 rounding of every gradient.
KERNEL_FLIP_SHARE = 1e-4
DEFAULT_FLIP_SHARE = 0.05
# Four devices vs one, both at HIGHEST: the gradient pmean sums shards in
# another order than one device sums the batch (f32 round-off ~1e-7; 1.1e-7
# on 4 forced CPU devices), and 16 Adam steps amplify it through near-zero
# gradients as above.  At default precision the bf16 passes flip Adam steps
# as in the parity phase and the runs drift apart (v5e: 1.1e-3 after 16
# steps), which says nothing about the sharding: the comparison runs at
# HIGHEST.
DIST_RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} device_kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found "
                         f"{info['platform']}); refusing to run on it")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {info['count']}")
    return info


def _kernel_census():
    """A memory probe that, instead of memory stats, records for every probed
    jit entry point the Mosaic kernels in its compiled program and the
    ``pallas_call`` eqns in its jaxpr."""
    from repro.kernels.ops import count_pallas_calls
    from repro.obs.memory import MemoryProbe

    class KernelCensus(MemoryProbe):
        def __init__(self):
            super().__init__()
            self.census = {}

        def observe_call(self, site, jitted, args, kwargs):
            if site in self.census:
                return
            hlo = jitted.lower(*args, **kwargs).compile().as_text()
            self.census[site] = (
                hlo.count('custom_call_target="tpu_custom_call"'),
                count_pallas_calls(jitted, *args, **kwargs))

    return KernelCensus()


def phase_train() -> None:
    from repro.graphs.experiment import run_experiment
    from repro.obs.memory import set_probe

    census = _kernel_census()
    prev = set_probe(census)
    try:
        r = run_experiment(**TRAIN)
    finally:
        set_probe(prev)
    log(f"[train] train={r.train_metric!r} test={r.test_metric!r} "
        f"finetuned={r.finetuned}")
    for site, (mosaic, pallas) in sorted(census.census.items()):
        log(f"[train] {site}: tpu_custom_call={mosaic} pallas_call={pallas}")
    if not (math.isfinite(r.train_metric) and math.isfinite(r.test_metric)):
        raise SystemExit("train: non-finite metrics")
    if not r.finetuned:
        raise SystemExit("train: the finetune phase did not run")
    if census.census["train.step"][1] == 0:
        raise SystemExit("train.step: no pallas_call on the fused path")
    for site, (mosaic, pallas) in census.census.items():
        if mosaic != pallas:
            raise SystemExit(f"{site}: {mosaic} Mosaic kernels for {pallas} "
                             "pallas_calls — a kernel ran in interpret mode "
                             "or fell back to the reference")


def _rel_err(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def phase_parity() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gst as G
    from repro.core.embedding_table import init_table
    from repro.graphs import batching as Bt
    from repro.graphs import data as D
    from repro.graphs.gnn import GNNConfig, encode_segments, gnn_init, \
        make_encode_fn
    from repro.optim import make_optimizer

    hidden, lr = TRAIN["hidden"], 5e-3
    graphs = D.make_malnet_like(n_graphs=TRAIN["n_graphs"], seed=0)
    ds = Bt.segment_dataset(graphs, TRAIN["max_seg_nodes"])
    seg, seg_valid, ids, labels = next(Bt.batch_iterator(
        ds, 8, rng=np.random.default_rng(0), shuffle=False))
    batch = G.GSTBatch({k: jnp.asarray(v) for k, v in seg.items()},
                       jnp.asarray(seg_valid), jnp.asarray(ids),
                       jnp.asarray(labels))
    flat = {k: jnp.asarray(v.reshape((-1,) + v.shape[2:]))
            for k, v in seg.items()}
    log(f"[parity] J_max={ds.j_max} e_max={ds.e_max} "
        f"segments/batch={flat['x'].shape[0]}")

    def run(use_pallas: bool):
        cfg = GNNConfig(backbone="sage", n_feat=graphs[0].x.shape[1],
                        hidden=hidden, use_pallas=use_pallas)
        key = jax.random.key(0)
        bb = gnn_init(key, cfg)
        head = G.head_init(jax.random.fold_in(key, 1), hidden, 5, "mlp")
        opt = make_optimizer("adam", lr=lr)
        state = G.TrainState(bb, head, opt.init((bb, head)),
                             init_table(ds.n, ds.j_max, hidden),
                             jnp.zeros((), jnp.int32))
        step = jax.jit(G.make_train_step(
            make_encode_fn(cfg), opt, G.VARIANTS["gst_efd"],
            use_pallas=use_pallas))
        emb = jax.jit(lambda p, s: encode_segments(p, cfg, s))(bb, flat)
        new, m = step(state, batch, jax.random.key(0))
        delta = jax.tree.map(lambda a, b: a - b,
                             (new.backbone, new.head), (bb, head))
        leaves = jax.tree.leaves(delta)
        return (np.asarray(emb), float(m["loss"]),
                np.concatenate([np.ravel(x) for x in leaves]))

    with jax.default_matmul_precision("highest"):
        ref_hi, fused_hi = run(False), run(True)
    ref, fused = run(False), run(True)
    for name, got, want, tol, flip_tol in (
            ("fused vs reference, both HIGHEST", fused_hi, ref_hi,
             KERNEL_TOL, KERNEL_FLIP_SHARE),
            ("fused vs reference, both default", fused, ref,
             DEFAULT_TOL, DEFAULT_FLIP_SHARE),
            ("fused default vs reference HIGHEST", fused, ref_hi,
             DEFAULT_TOL, DEFAULT_FLIP_SHARE)):
        e_emb = _rel_err(got[0], want[0])
        e_loss = abs(got[1] - want[1]) / max(abs(want[1]), 1e-30)
        e_step = float(np.abs(got[2] - want[2]).max())
        flips = float(np.mean(np.abs(got[2] - want[2]) > lr / 2))
        log(f"[parity] {name}: emb {e_emb!r} loss {e_loss!r} (tol {tol!r}); "
            f"param step max |diff| {e_step!r}, flipped share {flips!r} "
            f"(tol {flip_tol!r})")
        if not (e_emb <= tol and e_loss <= tol and flips <= flip_tol):
            raise SystemExit(f"parity: {name} outside tolerance")


def phase_serve() -> None:
    from repro.launch import serve_graphs

    s = serve_graphs.main(SERVE_ARGV)
    log(f"[serve] pallas_launches={s['pallas_launches']} "
        f"encode_launches={s['encode_launches']} "
        f"requests={s['n_requests']}")
    if s["pallas_launches"] <= 0:
        raise SystemExit("serve: no Pallas kernel launches")


def phase_dist() -> None:
    import jax
    import numpy as np

    from repro.launch import train_dist

    def train(devices: str, exchange: str, precision: str):
        t0 = time.perf_counter()
        with jax.default_matmul_precision(precision):
            r = train_dist.main(["--devices", devices, "--exchange", exchange]
                                + DIST_ARGV)
        log(f"[dist] devices={devices} exchange={exchange} "
            f"precision={precision} epoch_losses={r['epoch_losses']!r} "
            f"finetune_loss={r['finetune_loss']!r} metric={r['metric']!r} "
            f"(set-up + compile + run {time.perf_counter() - t0:.1f}s)")
        return r

    runs = {ex: train("4", ex, "default")
            for ex in ("ring", "alltoall", "bucketed")}
    for leaf in runs["ring"]["state"].table:
        shards = leaf.addressable_shards
        devices = {s.device.id for s in shards}
        blocks = {str(s.index) for s in shards}
        log(f"[dist] table leaf {leaf.shape}: devices {sorted(devices)}, "
            f"{len(blocks)} distinct row blocks")
        if len(devices) != 4 or len(blocks) != 4:
            raise SystemExit("dist: the table is not row-sharded over 4 "
                             "devices")
    for exchange in ("alltoall", "bucketed"):
        if runs[exchange]["epoch_losses"] != runs["ring"]["epoch_losses"]:
            raise SystemExit(f"dist: {exchange} losses differ from ring "
                             "(the f32 exchange is pure row selection)")

    four = train("4", "ring", "highest")["epoch_losses"]
    one = train("1", "ring", "highest")["epoch_losses"]
    rel = float(np.max(np.abs(np.subtract(four, one)) / np.abs(one)))
    log(f"[dist] 4 vs 1 device at HIGHEST: max relative epoch-loss diff "
        f"{rel!r} (tol {DIST_RTOL!r})")
    if rel > DIST_RTOL:
        raise SystemExit("dist: 4 devices disagree with 1")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the data-parallel phase on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    log(f"[setup] compile cache: {use_compile_cache()}")
    device = device_check(args.chips)
    phases = ([phase_dist] if args.chips == 4
              else [phase_train, phase_parity, phase_serve])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"[setup] {phase.__name__} done in "
            f"{time.perf_counter() - t0:.1f}s (compile included)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
