"""A tiny run is correct, and its control is not: the reference computed in
bfloat16 in the program's place fails the limits."""
import json

import jax.numpy as jnp
import pytest

from conftest import BENCH

import run as R
from harness import check, drivers, reference
from harness import spec as SPEC

CELLS = ["tpugraphs-tiny.train.tiny-single"]
# the limits the control must fail, set from chip readings
REAL_LIMITS = "tpugraphs-sage.train.encoder-heavy"


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct(tiny_bench, cell):
    res = R.run_cell(SPEC.load(cell, tiny_bench), 3000000007, 0.3,
                     trace=False, require_tpu=False)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_graphs_per_s", "step_temp_mib",
                                   "setup_s"}


def test_tiny_traced_run_reads_its_layers(tiny_bench):
    """The traced path end to end on the CPU: the profiler, the reduction
    and the readers.  The CPU trace has no TPU plane, so the device
    readers find nothing and leave their metrics out."""
    res = R.run_cell(SPEC.load(CELLS[0], tiny_bench), 3000000013, 0.3,
                     trace=True, require_tpu=False)
    assert res["correct"] is True
    assert {"h2d_mib_per_step.train", "seg_fill.train",
            "mfu.train"} <= set(res["metrics"])
    assert "step_device_ms.train" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(tiny_bench, cell):
    c = SPEC.load(cell, tiny_bench)
    from harness import dataset
    import jax
    cfg, traffic = c.config, c.traffic
    ds = dataset.for_config(cfg, tiny_bench / ".cache" / "data")
    d = drivers.DRIVERS[traffic["driver"]](cfg, traffic, ds,
                                           jax.devices()[:1])
    wkey, bseed, rseed = R.seeds(3000000029)
    d.start(wkey, bseed, rseed)
    gen = d.steps()
    for _ in range(drivers.PROOF_STEPS):
        next(gen)
    gen.close()
    args = (cfg, traffic, wkey, ds.n, ds.j_max, d.proof["batches"],
            d.proof["rngs"])
    ref = reference.run(*args)
    assert check.verdict(check.numbers(d.proof, ref), c.limits)
    control = check.numbers(reference.run(*args, dtype=jnp.bfloat16), ref)
    limits = json.loads(
        (BENCH / "limits" / f"{REAL_LIMITS}.json").read_text())
    assert not check.verdict(control, limits)
    d.close()


def test_dataset_is_the_programs_segmentation(tiny_bench):
    """The cached dataset is what one ``segment_dataset`` call of the
    program gives, and a second load reads it back unchanged."""
    import numpy as np
    from harness import dataset
    from repro.graphs import batching as Bt
    cfg = SPEC.load(CELLS[0], tiny_bench).config
    cache = tiny_bench / ".cache" / "data-check"
    ds = dataset.for_config(cfg, cache)
    data = cfg["data"]
    want = Bt.segment_dataset(
        dataset._generate({**data, "n_graphs": cfg["n_graphs"],
                           "n_feat": cfg["n_feat"]}),
        data["max_seg_nodes"], method=data["partition"], seed=data["seed"])
    again = dataset.for_config(cfg, cache)
    assert len(list(cache.glob("*.npz"))) == 1
    for got in (ds, again):
        assert (got.j_max, got.m_max, got.e_max) == (want.j_max, want.m_max,
                                                     want.e_max)
        for f in dataset.FIELDS:
            np.testing.assert_array_equal(getattr(got, f), getattr(want, f))
