"""A tiny run is correct, and its control is not: the reference computed in
bfloat16 in the program's place fails the limits, and so does the
reference with each of its faults planted.  The four-chip cell runs on
four forced host devices in a child process."""
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, CELLS, in_four_devices

import run as R
from harness import check
from harness import spec as SPEC
from harness.proof import PROOF_STEPS

# the limits the control must fail, set from chip readings: those of the
# real cell on as many chips
REAL_LIMITS = {1: "tpugraphs-sage.train.encoder-heavy",
               4: "malnet-sage.train.large-graphs.dp4"}


def tiny_run_is_correct(bench, chips):
    res = R.run_cell(SPEC.load(CELLS[chips], bench), 3000000007, 0.3,
                     trace=False, require_tpu=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == chips
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_graphs_per_s", "step_temp_mib",
                                   "setup_s"}


@pytest.mark.parametrize("chips", [1, 4])
def test_tiny_run_is_correct(tiny_bench, chips):
    if chips == 1:
        tiny_run_is_correct(tiny_bench, chips)
    else:
        in_four_devices("test_bench_correct", "tiny_run_is_correct", chips)


def test_tiny_traced_run_reads_its_layers(tiny_bench):
    """The traced path end to end on the CPU: the profiler, the reduction
    and the readers.  The CPU trace has no TPU plane, so the device
    readers find nothing and leave their metrics out."""
    res = R.run_cell(SPEC.load(CELLS[1], tiny_bench), 3000000013, 0.3,
                     trace=True, require_tpu=False)
    assert res["correct"] is True
    assert {"h2d_mib_per_step.train", "seg_fill.train",
            "mfu.train"} <= set(res["metrics"])
    assert "step_device_ms.train" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def control_fails(bench, chips):
    c = SPEC.load(CELLS[chips], bench)
    cfg, traffic = c.config, c.traffic
    data = c.dataset.build(cfg, bench / ".cache" / "data")
    d = c.driver.Driver(cfg, traffic, data, jax.devices()[:chips])
    wkey, bseed, rseed = R.seeds(3000000029)
    d.start(wkey, bseed, rseed)
    gen = d.steps()
    for _ in range(PROOF_STEPS):
        next(gen)
    gen.close()
    args = (cfg, traffic, wkey, data.n, data.j_max, d.proof["batches"],
            d.proof["rngs"])
    ref = c.reference.run(*args)
    values = check.numbers(d.proof, ref)
    assert tuple(values) == check.NUMBERS
    assert check.verdict(values, c.limits)
    control = check.numbers(c.reference.run(*args, dtype=jnp.bfloat16), ref)
    limits = json.loads(
        (BENCH / "limits" / f"{REAL_LIMITS[chips]}.json").read_text())
    assert not check.verdict(control, limits), control
    for fault in c.reference.FAULTS:
        if fault == "exchange" and chips == 1:
            continue            # one shard holds every row: no exchange
        planted = check.numbers(c.reference.run(*args, fault=fault,
                                                shards=chips), ref)
        assert not check.verdict(planted, c.limits), (fault, planted)
    d.close()


@pytest.mark.parametrize("chips", [1, 4])
def test_control_fails(tiny_bench, chips):
    if chips == 1:
        control_fails(tiny_bench, chips)
    else:
        in_four_devices("test_bench_correct", "control_fails", chips)


def test_dataset_is_the_programs_segmentation(tiny_bench):
    """The cached dataset is what one ``segment_dataset`` call of the
    program gives, and a second load reads it back unchanged."""
    import numpy as np
    from repro.graphs import batching as Bt
    c = SPEC.load(CELLS[1], tiny_bench)
    cfg, graphs = c.config, c.dataset
    cache = tiny_bench / ".cache" / "data-check"
    ds = graphs.build(cfg, cache).segmented
    data = cfg["data"]
    want = Bt.segment_dataset(
        graphs.generate(graphs.params(cfg)),
        data["max_seg_nodes"], method=data["partition"], seed=data["seed"])
    again = graphs.build(cfg, cache).segmented
    assert len(list(cache.glob("*.npz"))) == 1
    for got in (ds, again):
        assert (got.j_max, got.m_max, got.e_max) == (want.j_max, want.m_max,
                                                     want.e_max)
        for f in graphs.FIELDS:
            np.testing.assert_array_equal(getattr(got, f), getattr(want, f))
