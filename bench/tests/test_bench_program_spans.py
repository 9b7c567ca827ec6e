"""The program's own spans in the benchmark's loop, and the readers of the
per-layer metrics built on them: on hand-made intervals, on the tiny CPU
cell, and on a recorded chip step."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH

import program_spans as PS
import run as R
import trace_reduce as TR
from harness import spec as SPEC
from repro.graphs import batching as Bt
from repro.graphs import experiment as EX
from repro.obs import Tracer, null_tracer, set_tracer

CELL = "tpugraphs-tiny.train.tiny-single"
STEP_SPANS = ["feeder.assemble", "feeder.put", "store.prepare", "train.step",
              "train.wait"]
NEW_READERS = ["feeder_assemble_ms.train", "feeder_put_ms.train",
               "step_dispatch_ms.train", "step_wait_ms.train",
               "idle_outside_spans.train"]


def _read(metric, run):
    return SPEC.reader(BENCH, metric)(run)


def _trace(program_spans=True):
    """One chip, window [0, 10] s, two steps: the driver's spans with the
    program's nested inside them.  The device's gaps start where no span
    is open."""
    ops = {0: [(0.0, 1.0, "fusion.1"), (4.2, 9.0, "fusion.2"),
               (9.5, 10.0, "fusion.3")]}
    spans = [(1.0, 4.0, "bench.batch"), (4.0, 9.0, "bench.step")]
    if program_spans:
        spans += [(1.2, 2.5, "feeder.assemble"), (2.5, 3.6, "feeder.put"),
                  (3.6, 3.8, "store.prepare"), (4.05, 8.6, "train.step"),
                  (4.1, 8.5, "train.wait")]
    return TR.Trace(ops, {0: []}, {0: []}, sorted(spans), (0.0, 10.0))


def test_span_seconds_and_idle_outside_program_spans():
    tr = _trace()
    assert PS.span_s(tr, "feeder.assemble") == pytest.approx(1.3)
    assert PS.span_s(tr, "train.step") == pytest.approx(4.55)
    assert PS.span_s(tr, "feeder.wait") is None
    # idle 1-4.2 and 9-9.5; under no program span: 1-1.2, 3.8-4.05, 9-9.5
    assert PS.idle_outside_s(tr) == pytest.approx(0.95)
    assert PS.idle_outside_s(_trace(program_spans=False)) is None
    overlapping = TR.Trace({}, {}, {}, [(0.0, 2.0, "a"), (1.0, 3.0, "a")],
                           (0.0, 3.0))
    assert PS.span_s(overlapping, "a") == pytest.approx(3.0)
    assert PS.idle_outside_s(overlapping) is None        # no device


def test_idle_gaps_go_to_program_spans_inside_driver_spans():
    gaps = dict(TR.idle_gaps(_trace()))
    assert gaps == pytest.approx({
        "feeder.assemble": 1.3, "feeder.put": 1.1, "store.prepare": 0.2,
        "bench.batch": 0.4, "bench.step": 0.05, "train.step": 0.05,
        "train.wait": 0.1, "unattributed": 0.5})


def test_readers_on_hand_made_intervals():
    run = SimpleNamespace(trace=_trace(), steps=2)
    got = {m: _read(m, run) for m in NEW_READERS}
    assert got == pytest.approx({
        "feeder_assemble_ms.train": 650.0, "feeder_put_ms.train": 550.0,
        "step_dispatch_ms.train": 75.0, "step_wait_ms.train": 2200.0,
        "idle_outside_spans.train": 100.0 * 0.95 / 3.7})
    bare = SimpleNamespace(trace=_trace(program_spans=False), steps=2)
    assert all(_read(m, bare) is None for m in NEW_READERS)
    assert _read("feeder_put_ms.train", SimpleNamespace(
        trace=_trace(), steps=0)) is None


def _three_steps(driver, ds, seed):
    """Three steps of the benchmark's single-chip loop, through the
    program's pieces: batch_iterator, _to_batch, store.prepare, run_step."""
    wkey, bseed, rseed = R.seeds(seed)
    driver.start(wkey, bseed, rseed)
    state, key = driver.state, jax.random.key(rseed)
    batches = Bt.batch_iterator(ds, driver.B,
                                rng=np.random.default_rng(bseed))
    for t in range(3):
        tup = next(batches)
        batch = EX._to_batch(*tup)
        table, slots = driver.store.prepare(state.table, tup[2], step=t)
        state = state._replace(table=table)
        state, _ = EX.run_step(driver.step, state,
                               batch._replace(graph_ids=jnp.asarray(slots)),
                               key)
    return jax.device_get(state)


def test_tiny_cell_steps_emit_the_program_spans(tiny_bench):
    c = SPEC.load(CELL, tiny_bench)
    data = c.dataset.build(c.config, tiny_bench / ".cache" / "data")
    driver = c.driver.Driver(c.config, c.traffic, data, jax.devices()[:1])
    ds = data.segmented
    seed = 3000000041
    off = _three_steps(driver, ds, seed)
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        on = _three_steps(driver, ds, seed)
    finally:
        set_tracer(prev)
    spans = sorted(s for s in tracer.spans() if s[2] != "jit.compile")
    assert [n for _, _, n, _ in spans] == STEP_SPANS * 3
    parents = {n: p for _, _, n, p in spans}
    assert parents == {"feeder.assemble": None, "feeder.put": None,
                       "store.prepare": None, "train.step": None,
                       "train.wait": "train.step"}
    for (s0, e0, n0, _), (s1, e1, n1, _) in zip(spans, spans[1:]):
        if n1 == "train.wait":
            assert s0 <= s1 <= e1 <= e0        # nested in its train.step
        else:
            assert e0 <= s1                    # one after another
    # the tracer leaves the state bit for bit as it was
    leaves_off = jax.tree_util.tree_leaves(off)
    leaves_on = jax.tree_util.tree_leaves(on)
    assert len(leaves_off) == len(leaves_on)
    for a, b in zip(leaves_off, leaves_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(null_tracer()) == 0
    driver.close()


def _recorded(stem):
    import json
    data = BENCH / "testdata"
    spans = json.loads((data / f"{stem}.spans.json").read_text())
    return TR.load(data / f"{stem}.xplane.pb.gz", spans)


def test_recorded_chip_step_with_program_spans():
    """One traced step of the TpuGraphs cell on a v5e with the program's
    tracer installed: its spans nest inside the driver's, add up to them,
    and take the device's idle time; nothing compiles in the window."""
    tr = _recorded("tpugraphs-sage.train.encoder-heavy.1step.program")
    names = [n for _, _, n in tr.spans]
    assert sorted(names) == sorted(["bench.batch", "bench.step"]
                                   + STEP_SPANS)
    idle = TR.window_s(tr) - TR.busy_s(tr)
    gaps = dict(TR.idle_gaps(tr))
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert {"feeder.assemble", "feeder.put", "train.wait"} <= set(gaps)
    program = sum(PS.span_s(tr, n) for n in STEP_SPANS[:4])
    driver = PS.span_s(tr, "bench.batch") + PS.span_s(tr, "bench.step")
    assert program == pytest.approx(driver, rel=0.02)
    run = SimpleNamespace(trace=tr, steps=1)
    got = {m: _read(m, run) for m in NEW_READERS}
    assert got == pytest.approx({
        "feeder_assemble_ms.train": 467.34, "feeder_put_ms.train": 4.3077,
        "step_dispatch_ms.train": 0.9568, "step_wait_ms.train": 50.487,
        "idle_outside_spans.train": 6.42}, rel=1e-3)
    # the step's device work starts only as its wait ends: the wait is
    # for the batch's host-to-device copies, not for the 3.5 ms of work
    (step,) = [s for s in tr.spans if s[2] == "train.step"]
    first_op = min(s for evs in tr.ops.values() for s, _, _ in evs)
    assert first_op - step[0] > 0.04


def test_readers_unchanged_on_the_first_recording():
    """The device readers read the first recording (driver spans only) as
    they always have; the program-span readers find nothing there."""
    import json
    tr = _recorded("tpugraphs-sage.train.encoder-heavy.1step")
    cfg = json.loads((BENCH / "configs" / "tpugraphs-sage.json").read_text())
    traffic = json.loads(
        (BENCH / "workloads" / "train.encoder-heavy.json").read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())
    run = SimpleNamespace(trace=tr, steps=1, cfg=cfg, traffic=traffic,
                          chips=1, peak=peaks["TPU v5 lite"],
                          stats={"nodes": 55.0, "edges": 150.0,
                                 "segments": 140.0})
    got = {m: _read(m, run) for m in [
        "step_device_ms.train", "device_idle_share.train",
        "segment_spmm_roofline", "sed_pool_roofline"]}
    assert got == pytest.approx({
        "step_device_ms.train": 3.4727310000036704,
        "device_idle_share.train": 99.38345875054895,
        "segment_spmm_roofline": 1.4217498915652498,
        "sed_pool_roofline": 70.0565453954694}, rel=1e-9)
    assert all(_read(m, run) is None for m in NEW_READERS)
