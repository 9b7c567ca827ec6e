"""Readings that the correctness limits are set from, on the chip.

    python bench/readings.py --workload <cell> --seeds 12 --out <file.jsonl>

One process, one compile.  For each of ``--seeds`` seeds the program's
driver runs its first three steps exactly as a benchmark run does, and the
comparison reads every number of ``harness/check.py`` against the float32
reference (kind ``program``: the lower readings).  On the first
``--others`` seeds it also reads the control, the reference computed in
bfloat16 in the program's place (kind ``control``), and the reference with
each of ``reference.py``'s faults planted (kinds ``half_batch``,
``altered``, ``frozen`` and ``beta2``): the upper readings.  A step that
returns its state unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
construction and is not run.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_011)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import run as R
    from harness import spec as SPEC
    cell = SPEC.load(args.workload)
    jax = R._configure_jax(cell.bench)
    import jax.numpy as jnp
    from harness import check, dataset, drivers, reference

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"needs {cell.chips} TPU chip(s)")
    devices = devices[:cell.chips]
    cfg, traffic = cell.config, cell.traffic
    ds = dataset.for_config(cfg, cell.bench / ".cache" / "data")
    driver = drivers.DRIVERS[traffic["driver"]](cfg, traffic, ds, devices)
    faults = ["half_batch", "altered", "frozen", "beta2"]
    steps = {"program": reference.make_step(cfg, traffic, jnp.float32),
             "control": reference.make_step(cfg, traffic, jnp.bfloat16)}
    for f in faults:
        steps[f] = reference.make_step(cfg, traffic, jnp.float32, f)
    out = open(args.out, "w")
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        wkey, bseed, rseed = R.seeds(seed)
        driver.start(wkey, bseed, rseed)
        gen = driver.steps()
        for _ in range(drivers.PROOF_STEPS):
            next(gen)
        gen.close()
        proof = driver.proof
        ref_args = (cfg, traffic, wkey, ds.n, ds.j_max, proof["batches"],
                    proof["rngs"])
        ref = reference.run(*ref_args, step=steps["program"])
        rows = [("program", check.numbers(proof, ref))]
        if i < args.others:
            rows.append(("control", check.numbers(reference.run(
                *ref_args, dtype=jnp.bfloat16, step=steps["control"]), ref)))
            for f in faults:
                rows.append((f, check.numbers(reference.run(
                    *ref_args, fault=f, step=steps[f]), ref)))
        for kind, nums in rows:
            line = json.dumps({"cell": cell.name, "kind": kind, "seed": seed,
                               **nums})
            out.write(line + "\n")
            print(line, flush=True)
        print(f"seed {seed}: {time.time() - t0:.1f} s", file=sys.stderr)
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
