"""The trace reduction on hand-made intervals and on a recorded chip trace."""
from pathlib import Path

import pytest

import trace_reduce as TR

DATA = Path(__file__).resolve().parents[1] / "testdata"


def _trace():
    # two chips, window [0, 10] s
    spmm = "jvp_jit_batched_neighbor_sum__.2"
    ops = {0: [(0.0, 2.0, "fusion.1"), (1.0, 3.0, spmm),
               (5.5, 6.5, "fusion.3")],
           1: [(0.0, 4.0, spmm), (7.0, 8.0, "all-reduce.2")]}
    kernels = {0: [(1.0, 3.0, spmm)], 1: [(0.0, 4.0, spmm)]}
    async_ops = {0: [(5.0, 6.0, "all-reduce-start.2")], 1: []}
    spans = [(3.0, 5.0, "bench.batch"), (4.5, 9.0, "bench.step")]
    return TR.Trace(ops, kernels, async_ops, spans, (0.0, 10.0))


def test_interval_algebra():
    assert TR.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert TR.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert TR.subtract([(0, 2)], [(0, 5)]) == []


def test_busy_kernel_and_exposed():
    tr = _trace()
    # chip 0 busy 0-3 and 5.5-6.5 = 4 s (the async all-reduce does not
    # count); chip 1 0-4 and 7-8 = 5 s
    assert TR.busy_s(tr) == pytest.approx(4.5)
    assert TR.window_s(tr) == 10.0
    t, n = TR.kernel_s(tr, "batched_neighbor_sum")
    assert (t, n) == (pytest.approx(3.0), 1)
    assert TR.kernel_s(tr, "sed_aggregate") == (0.0, 0)
    # chip 0: async all-reduce 5-6 overlapped by fusion from 5.5: 0.5 s
    # exposed; chip 1: 1 s exposed
    assert TR.has_collectives(tr)
    assert TR.exposed_collective_s(tr) == pytest.approx(0.75)
    assert TR.op_name("%fusion.14 = f32[8] fusion(%a), kind=kLoop") == \
        "fusion.14"


def test_idle_gaps_attributed_to_innermost_span():
    gaps = dict(TR.idle_gaps(_trace()))
    # chip 0 idle 3-5.5 and 6.5-10; chip 1 idle 4-7 and 8-10
    # batch 3-5 takes 3-4.5 on chip 0 and 4-4.5 on chip 1 (step starts
    # later, so it wins from 4.5): (1.5 + 0.5) / 2
    assert gaps["bench.batch"] == pytest.approx(1.0)
    # step 4.5-9: chip 0 4.5-5.5 and 6.5-9 = 3.5; chip 1 4.5-7, 8-9 = 3.5
    assert gaps["bench.step"] == pytest.approx(3.5)
    # the rest: chip 0 9-10, chip 1 9-10
    assert gaps["unattributed"] == pytest.approx(1.0)


def test_recorded_chip_trace():
    """One traced train step of the TpuGraphs cell on a v5e, as the chip's
    profiler wrote it with the host tracer off, and the driver's spans by
    the host clock: four SpMM kernels (two layers, forward and backward)
    and one SED pooling kernel between two ``bench_mark`` runs."""
    import json
    stem = "tpugraphs-sage.train.encoder-heavy.1step"
    spans = json.loads((DATA / f"{stem}.spans.json").read_text())
    tr = TR.load(DATA / f"{stem}.xplane.pb.gz", spans)
    assert list(tr.ops) == [0]
    assert TR.window_s(tr) == pytest.approx(0.5633, abs=1e-3)
    assert TR.busy_s(tr) == pytest.approx(3.473e-3, rel=1e-2)
    t, calls = TR.kernel_s(tr, "batched_neighbor_sum")
    assert calls == 4 and t == pytest.approx(1.278e-3, rel=1e-2)
    assert TR.kernel_s(tr, "sed_aggregate")[1] == 1
    assert len(tr.kernels[0]) == 5
    assert not TR.has_collectives(tr)
    assert [op for op, _ in TR.top_ops(tr, 2)] == ["copy.91", "fusion.14"]
    # the spans are placed at the first bench_mark's start on the device
    assert tr.spans[0][0] == pytest.approx(tr.window[0] + spans[0][0])
    gaps = dict(TR.idle_gaps(tr))
    assert set(gaps) == {"bench.step", "bench.batch", "unattributed"}
    assert gaps["bench.batch"] == pytest.approx(0.4732, rel=1e-2)
    idle = TR.window_s(tr) - TR.busy_s(tr)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_exchange_and_feeder_readers():
    """``exchange_exposed_ms.train`` reads the exposed collective time per
    step; ``feeder_blocked_ms.train`` the program's feeder counters.  Both
    find nothing where there is nothing to read."""
    from types import SimpleNamespace

    from harness import spec as SPEC
    bench = DATA.parent
    exposed = SPEC.reader(bench, "exchange_exposed_ms.train")
    blocked = SPEC.reader(bench, "feeder_blocked_ms.train")
    # 0.75 s exposed (above) over 5 steps
    run = SimpleNamespace(trace=_trace(), steps=5, counters={
        "feeder.batches": 4.0, "feeder.host_blocked_ms": 10.0})
    assert exposed(run) == pytest.approx(150.0)
    assert blocked(run) == pytest.approx(2.5)
    tr = _trace()
    quiet = TR.Trace({0: tr.ops[0][:2]}, tr.kernels, {0: []}, tr.spans,
                     tr.window)
    assert exposed(SimpleNamespace(trace=quiet, steps=5)) is None
    assert blocked(SimpleNamespace(counters={})) is None
    assert blocked(SimpleNamespace(counters={
        "feeder.device_gathers": 3.0})) is None
