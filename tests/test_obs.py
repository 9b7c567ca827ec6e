"""Observability spine (src/repro/obs/) — ISSUE 7 contract.

  * the registry is thread-safe (store publishes from the feeder,
    write-back, and consumer threads at once), rejects kind collisions,
    and its delta()/reset() give honest per-interval rates;
  * the disabled path is a true no-op AND invisible to jit: the jaxpr of
    the gst_efd train step is identical with telemetry installed or not
    (the host-side-only rule that keeps --metrics off zero-cost);
  * summarize() is the one percentile implementation — histogram
    percentiles agree with numpy's within the bucket resolution;
  * spans recorded from multiple threads export structurally valid
    Chrome-trace JSON (validate_chrome_trace);
  * the StalenessProbe row-age histogram is bit-consistent with
    store.snapshot() ages once write-backs are flushed;
  * store.publish_counters mirrors the counter dict into the registry
    exactly once per increment, surviving the counters-reset idiom;
  * the serve engine publishes latency and prediction-staleness;
  * Obs round-trips meta/tick/summary through the JSONL stream and
    restores the process-wide globals on close.
"""
import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gst as G
from repro.dist import pipeline as DP
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.obs import (MetricsRegistry, NullRegistry, Obs, StalenessProbe,
                       dict_delta, get_registry, get_tracer, null_registry,
                       sed_age_bound, sed_drop_stats, set_registry, summarize,
                       validate_chrome_trace, wb_skip_rate)
from repro.obs.gate import GateFailure, require_families
from repro.obs.metrics import Histogram, exponential_buckets
from repro.obs.trace import NullTracer, Tracer, null_tracer, set_tracer
from repro.optim import make_optimizer
from repro.store import StoreCounters, TieredStore

HID = 8


@pytest.fixture(scope="module")
def dataset():
    graphs = D.make_malnet_like(n_graphs=24, seed=0)
    ds, _ = DP.segment_dataset_shared(graphs, 16, seed=0)
    return ds


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test starts and ends with the null registry/tracer installed
    (the process default) — no cross-test telemetry bleed."""
    set_registry(null_registry())
    set_tracer(null_tracer())
    yield
    set_registry(null_registry())
    set_tracer(null_tracer())


def _state(ds):
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=HID)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, 5, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    from repro.core import embedding_table as tbl
    return enc, opt, G.TrainState(bb, head, opt.init((bb, head)),
                                  tbl.init_table(ds.n, ds.j_max, HID),
                                  jnp.zeros((), jnp.int32))


def _batch(ds, ids):
    return jax.tree_util.tree_map(jnp.asarray, DP._assemble(ds, ids))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_metric_kinds_and_collisions():
    reg = MetricsRegistry()
    reg.inc("store.faults", 3, unit="rows")
    reg.inc("store.faults", 2)
    reg.set("store.occupancy", 7)
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0), unit="ms")
    h.observe(1.5)
    snap = reg.snapshot()
    assert snap["store.faults"]["value"] == 5
    assert snap["store.faults"]["type"] == "counter"
    assert snap["store.occupancy"]["value"] == 7
    assert snap["lat"]["count"] == 1
    # a name is one kind forever — silent shadowing would corrupt deltas
    with pytest.raises(TypeError):
        reg.set("store.faults", 1)
    with pytest.raises(TypeError):
        reg.histogram("store.occupancy")


def test_registry_thread_safety():
    reg = MetricsRegistry()
    N_THREADS, N_OPS = 8, 500

    def work(t):
        h = reg.histogram("h", buckets=tuple(float(2 ** i) for i in range(8)))
        for i in range(N_OPS):
            reg.inc("c")                       # get-or-create under race
            h.observe(float(i % 100))
    threads = [threading.Thread(target=work, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.get("c").value == N_THREADS * N_OPS
    assert reg.get("h").count == N_THREADS * N_OPS


def test_histogram_percentiles_within_bucket_resolution():
    rng = np.random.default_rng(0)
    data = rng.exponential(scale=20.0, size=5000)
    buckets = exponential_buckets(0.1, 2.0, 20)
    h = Histogram("x", buckets=buckets)
    h.observe_many(data)
    for q in (50, 99):
        exact = float(np.percentile(data, q))
        approx = h.percentile(q)
        # within the containing bucket: the bucket's full width is the
        # resolution bound
        idx = np.searchsorted(buckets, exact)
        lo = buckets[idx - 1] if idx > 0 else 0.0
        hi = buckets[idx] if idx < len(buckets) else data.max()
        assert lo <= approx <= hi + 1e-9, (q, exact, approx, lo, hi)


def test_summarize_list_and_histogram_agree():
    data = list(np.linspace(1.0, 400.0, 777))
    h = Histogram("x", buckets=exponential_buckets(0.5, 2.0, 16))
    h.observe_many(data)
    s_list, s_hist = summarize(data), summarize(h)
    assert s_list["count"] == s_hist["count"] == 777
    assert s_list["min"] == s_hist["min"] and s_list["max"] == s_hist["max"]
    assert np.isclose(s_list["mean"], s_hist["mean"])
    # percentiles agree to bucket resolution (factor-2 ladder)
    assert s_hist["p50"] / s_list["p50"] < 2.0
    assert s_list["p50"] / s_hist["p50"] < 2.0


def test_delta_and_reset_semantics():
    reg = MetricsRegistry()
    reg.inc("c", 10)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    d1 = reg.delta()
    assert d1["c"] == 10 and d1["h.count"] == 1
    reg.inc("c", 3)
    d2 = reg.delta()
    assert d2["c"] == 3 and d2["h.count"] == 0   # only the interval's change
    reg.reset()                                   # a fresh run phase:
    assert reg.get("c") is None                   # metrics AND marks drop
    reg.inc("c", 2)
    assert reg.delta()["c"] == 2                  # no stale baseline
    assert dict_delta({"a": 5, "b": 1}, {"a": 2}) == {"a": 3, "b": 1}


def test_null_registry_is_noop_and_shared():
    reg = NullRegistry()
    assert not reg.enabled
    reg.inc("x", 5)
    reg.set("y", 2)
    reg.histogram("z").observe(1.0)
    assert reg.snapshot() == {} and reg.summary() == {}
    # handles are shared singletons — no allocation on the disabled path
    assert reg.counter("a") is reg.histogram("b")
    assert null_registry() is null_registry()


# ---------------------------------------------------------------------------
# disabled-path invariant: telemetry never touches the jaxpr
# ---------------------------------------------------------------------------


def test_train_step_jaxpr_identical_with_obs_installed(dataset):
    """The host-side-only rule, asserted: installing a live registry +
    tracer changes NOTHING inside jit — same jaxpr, bit for bit."""
    ds = dataset
    enc, opt, state = _state(ds)
    step = G.make_train_step(enc, opt, G.VARIANTS["gst_efd"], keep_prob=0.5)
    batch = _batch(ds, np.arange(4, dtype=np.int64))
    rng = jax.random.PRNGKey(0)

    baseline = str(jax.make_jaxpr(step)(state, batch, rng))
    obs = Obs(metrics=True, trace_out="unused.json", install=True)
    try:
        assert get_registry() is obs.registry and get_registry().enabled
        instrumented = str(jax.make_jaxpr(step)(state, batch, rng))
    finally:
        obs.uninstall()
    assert instrumented == baseline


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_tracer_multithreaded_export_is_valid_chrome_trace(tmp_path):
    tr = Tracer()
    set_tracer(tr)
    gate = threading.Barrier(3)   # overlap lifetimes: distinct thread ids

    def worker():
        gate.wait()
        for i in range(5):
            with tr.span("feeder.assemble", batch=i):
                pass
    threads = [threading.Thread(target=worker, name=f"w{k}")
               for k in range(3)]
    for t in threads:
        t.start()
    with tr.span("train.step", epoch=0):
        with tr.span("store.commit"):
            pass
    for t in threads:
        t.join()

    path = tmp_path / "trace.json"
    tr.export(str(path))
    payload = json.loads(path.read_text())
    assert validate_chrome_trace(payload) == []
    evs = payload["traceEvents"]
    names = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
    assert {"w0", "w1", "w2"} <= names           # thread_name metadata
    xs = [e for e in evs if e.get("ph") == "X"]
    assert len(xs) == 3 * 5 + 2
    assert all(e["dur"] >= 1 for e in xs)
    # spans from 4 distinct threads landed in one stream
    assert len({e["tid"] for e in xs}) == 4


def test_span_parents_across_nesting_and_threads():
    """A span's parent is the span open on its own thread when it began;
    another thread's open spans are not its parents."""
    tr = Tracer()
    set_tracer(tr)
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with tr.span("feeder.assemble"):
            inside.set()
            release.wait(timeout=10)
            with tr.span("feeder.put"):
                pass
    t = threading.Thread(target=worker)
    with tr.span("train.step"):
        t.start()
        assert inside.wait(timeout=10)
        with tr.span("train.wait"):
            release.set()
            t.join(timeout=10)
    assert not t.is_alive()
    with tr.span("store.prepare"):
        pass
    parents = {name: parent for _, _, name, parent in tr.spans()}
    assert parents == {"feeder.put": "feeder.assemble",
                       "feeder.assemble": None, "train.wait": "train.step",
                       "train.step": None, "store.prepare": None}


def test_spans_on_perf_counter_seconds():
    import time
    tr = Tracer()
    set_tracer(tr)
    t0 = time.perf_counter()
    with tr.span("outer"):
        time.sleep(0.01)
        t_mid = time.perf_counter()
        with tr.span("inner"):
            pass
    t1 = time.perf_counter()
    (si, ei, ni, pi), (so, eo, no, po) = tr.spans()   # in order of ending
    assert (ni, pi, no, po) == ("inner", "outer", "outer", None)
    assert t0 <= so <= t_mid <= si <= ei <= eo <= t1
    assert eo - so >= 0.01


def test_export_records_its_clock(tmp_path):
    """``ts`` counts microseconds from ``otherData``'s epoch on the named
    clock, so an exported span lands where ``spans()`` puts it."""
    tr = Tracer()
    set_tracer(tr)
    with tr.span("train.step"):
        pass
    payload = json.loads(Path(tr.export(str(tmp_path / "t.json")))
                         .read_text())
    assert validate_chrome_trace(payload) == []
    other = payload["otherData"]
    assert other["clock"] == "time.perf_counter"
    (ev,) = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    ((s, e, _, _),) = tr.spans()
    assert other["epoch_s"] + ev["ts"] * 1e-6 == pytest.approx(s, abs=2e-6)
    assert ev["dur"] * 1e-6 == pytest.approx(e - s, abs=2e-6)


def test_compile_is_a_span_under_the_open_span():
    tr = Tracer()
    set_tracer(tr)
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.ones((3, 5), np.float32)       # host inputs: nothing else compiles
    with tr.span("train.step"):
        f(x).block_until_ready()
    f(x).block_until_ready()                       # cached: no compile
    compiles = [s for s in tr.spans() if s[2] == "jit.compile"]
    assert len(compiles) == 1 and compiles[0][3] == "train.step"
    (step,) = [s for s in tr.spans() if s[2] == "train.step"]
    assert step[0] <= compiles[0][0] <= compiles[0][1] <= step[1]
    (ev,) = [e for e in tr.events() if e["name"] == "jit.compile"]
    assert "<lambda>" in ev["args"]["fun_name"]
    f(np.ones((4, 5), np.float32)).block_until_ready()   # a new shape
    assert sum(s[2] == "jit.compile" for s in tr.spans()) == 2


def test_null_tracer_records_nothing(dataset):
    """Under the NullTracer the program's spans and compiles go nowhere,
    not even to a tracer installed earlier."""
    from repro.graphs import batching as Bt
    from repro.graphs import experiment as EX
    old = Tracer()
    set_tracer(old)
    set_tracer(null_tracer())
    enc, opt, state = _state(dataset)
    step = jax.jit(G.make_train_step(enc, opt, G.VARIANTS["gst_efd"],
                                     keep_prob=0.5))
    tup = next(Bt.batch_iterator(dataset, 4, rng=np.random.default_rng(0)))
    EX.run_step(step, state, EX._to_batch(*tup), jax.random.key(0))
    assert len(old) == 0 and old.spans() == []
    assert null_tracer().spans() == [] and len(null_tracer()) == 0


def test_null_tracer_refuses_export():
    nt = NullTracer()
    assert nt.span("x") is nt.span("y")          # one shared no-op span
    assert len(nt) == 0
    with pytest.raises(RuntimeError):
        nt.export("/tmp/never.json")


def test_validate_chrome_trace_catches_breakage():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 10, "dur": 1, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5, "dur": -1, "pid": 1, "tid": 1},
        {"name": "c", "ph": "E", "ts": 20, "pid": 1, "tid": 1},
    ]}
    problems = validate_chrome_trace(bad)
    assert any("monotonic" in p for p in problems)
    assert any("bad dur" in p for p in problems)
    assert any("E without matching B" in p for p in problems)


# ---------------------------------------------------------------------------
# staleness
# ---------------------------------------------------------------------------


def test_sed_drop_stats_hand_case():
    seg_valid = np.array([[1, 1, 1, 0]])
    init = np.array([[True, True, False, False]])
    s = sed_drop_stats(seg_valid, init, num_sampled=1, keep_prob=0.5)
    # 3 valid slots, 2 initialized, 1 fresh -> 1 SED-eligible stale slot
    assert s["valid_segments"] == 3
    assert s["sed_eligible"] == 1
    assert s["sed_dropped_expected"] == 0.5
    assert np.isclose(s["sed_drop_rate"], 0.5 / 3)


def test_sed_age_bound_formula():
    b = sed_age_bound(j_max=4, num_sampled=1, steps_per_epoch=10, safety=2.0)
    assert np.isclose(b, np.log(100.0) * 4 * 10 * 2.0)
    # more sampling -> fresher rows -> tighter bound
    assert sed_age_bound(j_max=4, num_sampled=2, steps_per_epoch=10) < b


def test_staleness_histogram_bit_consistent_with_snapshot(dataset):
    """ages_init (the probe's view, host tier included) must agree with
    the flushed snapshot() ages — the histogram built from either is
    identical bucket for bucket."""
    ds = dataset
    enc, opt, state = _state(ds)
    cap = max(-(-ds.n // 4), 4)
    store = TieredStore(ds.n, ds.j_max, HID, device_rows=cap)
    state = state._replace(table=store.init_device_table())
    step = jax.jit(G.make_train_step(enc, opt, G.VARIANTS["gst_efd"],
                                     keep_prob=0.5))
    try:
        rng = np.random.default_rng(0)
        for t in range(6):
            ids = rng.choice(ds.n, size=4, replace=False).astype(np.int64)
            table, slots = store.prepare(state.table, ids)
            state = state._replace(table=table)
            state, _ = step(state, _batch(ds, ids)._replace(
                graph_ids=jnp.asarray(slots)), jax.random.PRNGKey(t))
        store.flush_writebacks()
        step_now = int(jax.device_get(state.step))

        probe_live = StalenessProbe(seg_valid=ds.seg_valid,
                                    registry=MetricsRegistry())
        live = probe_live.observe(store, state.table, step_now)
        snap = store.snapshot(state.table)
        probe_snap = StalenessProbe(seg_valid=ds.seg_valid,
                                    registry=MetricsRegistry())
        again = probe_snap.observe_ages(np.asarray(snap.age),
                                        np.asarray(snap.initialized),
                                        step_now)
        h1 = probe_live.registry.get("staleness.row_age").snapshot()
        h2 = probe_snap.registry.get("staleness.row_age").snapshot()
        assert h1["counts"] == h2["counts"] and h1["count"] == h2["count"]
        assert live["row_age_steps"] == again["row_age_steps"]
        assert live["init_fraction"] > 0
        assert h1["count"] > 0, "training must have initialized rows"
    finally:
        store.close()


# ---------------------------------------------------------------------------
# store publication
# ---------------------------------------------------------------------------


def test_store_publish_counters_mirrors_and_survives_reset(dataset):
    ds = dataset
    enc, opt, state = _state(ds)
    cap = max(-(-ds.n // 4), 4)
    store = TieredStore(ds.n, ds.j_max, HID, device_rows=cap)
    state = state._replace(table=store.init_device_table())
    step = jax.jit(G.make_train_step(enc, opt, G.VARIANTS["gst_efd"],
                                     keep_prob=0.5))
    reg = MetricsRegistry()
    set_registry(reg)
    try:
        rng = np.random.default_rng(1)
        for t in range(4):
            ids = rng.choice(ds.n, size=4, replace=False).astype(np.int64)
            table, slots = store.prepare(state.table, ids)
            state = state._replace(table=table)
            state, _ = step(state, _batch(ds, ids)._replace(
                graph_ids=jnp.asarray(slots)), jax.random.PRNGKey(t))
        store.flush_writebacks()
        store.publish_counters()
        c = store.counters
        snap = reg.snapshot()
        assert snap["store.lookups"]["value"] == c.lookups
        assert snap["store.faults"]["value"] == c.misses
        assert snap["store.evictions"]["value"] == c.evictions
        assert snap["store.bytes_h2d"]["value"] == c.bytes_h2d
        # publishing again without new work is a no-op (diff-publish)
        store.publish_counters()
        assert reg.snapshot()["store.lookups"]["value"] == c.lookups
        # the counters-reset idiom (bench_store, cache.flush) re-baselines:
        # registry values stay cumulative, no double count, no negatives
        before = reg.snapshot()["store.lookups"]["value"]
        store.counters = StoreCounters()
        store.publish_counters()
        assert reg.snapshot()["store.lookups"]["value"] == before
        assert wb_skip_rate({"evictions": 10, "wb_skipped_rows": 4}) == 0.4
    finally:
        store.close()


# ---------------------------------------------------------------------------
# serve publication
# ---------------------------------------------------------------------------


def test_serve_engine_publishes_latency_and_prediction_staleness():
    from repro.serve import (ServeConfig, ServeEngine, TrafficConfig,
                             make_request_stream)
    reg = MetricsRegistry()
    set_registry(reg)
    cfg = ServeConfig(backbone="sage", hidden=32, max_seg_nodes=32,
                      cache_capacity=128, cache_enabled=True, stream_chunk=4)
    engine = ServeEngine(cfg, seed=0)
    try:
        tc = TrafficConfig(n_unique=3, n_requests=8, duplicate_rate=0.7,
                           seed=3)
        engine.process(make_request_stream(tc), window=4)
        snap = reg.snapshot()
        assert snap["serve.requests"]["value"] == 8
        assert snap["serve.latency_ms"]["count"] == 8
        ps = snap["serve.prediction_staleness"]
        assert ps["count"] > 0, "duplicate traffic must read cached rows"
        # engine-local histogram and registry histogram see the same events
        assert engine.stats.latency.count == 8
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# export / lifecycle / gate
# ---------------------------------------------------------------------------


def test_obs_jsonl_roundtrip_and_uninstall(tmp_path):
    out = tmp_path / "obs.jsonl"
    obs = Obs(metrics_out=str(out), trace_out=str(tmp_path / "t.json"))
    assert get_registry() is obs.registry
    obs.exporter.meta(run="unit")
    obs.registry.inc("store.faults", 4)
    with get_tracer().span("train.step"):
        pass
    rec = obs.tick(step=1, epoch=0)
    assert rec["delta"]["store.faults"] == 4
    obs.registry.inc("store.faults", 1)
    summary = obs.close(wall_s=1.0)
    assert summary["metrics"]["store.faults"] == 5

    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert [l["type"] for l in lines] == ["meta", "tick", "summary"]
    assert lines[1]["step"] == 1 and lines[1]["delta"]["store.faults"] == 4
    assert lines[2]["n_ticks"] == 1
    # close() exported the trace and restored the process globals
    trace = json.loads((tmp_path / "t.json").read_text())
    assert validate_chrome_trace(trace) == []
    assert not get_registry().enabled and not get_tracer().enabled
    assert obs.close() is None                   # idempotent


def test_obs_disabled_is_null(tmp_path):
    obs = Obs()          # no flags: everything off
    assert not obs.enabled
    assert isinstance(obs.registry, NullRegistry)
    assert obs.tick(step=0) is None
    assert obs.close() is None


def test_gate_require_families_prefix_match():
    summary = {"metrics": {"staleness.row_age": {"count": 3},
                           "exchange.bytes.ring.f32": 100}}
    names = require_families(
        summary, ("staleness.row_age", "exchange.bytes."), "t.jsonl")
    assert names == ["exchange.bytes.ring.f32", "staleness.row_age"]
    with pytest.raises(GateFailure):
        require_families(summary, ("serve.latency_ms",), "t.jsonl")
