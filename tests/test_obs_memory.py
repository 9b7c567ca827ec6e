"""Memory observability (src/repro/obs/memory.py) and GST's constant-memory claim.

  * the probe captures compiled memory/cost stats once per (site, shape
    signature) and only counts calls afterwards;
  * measure-on-the-side: the traced jaxpr of the gst_efd train step is
    bit-identical with the probe installed or not, and the probed wrapper
    returns exactly what the raw jitted callable returns;
  * the streaming encoder's compiled temp bytes are chunk-count-
    independent and >= the jaxpr-walk max_intermediate_bytes bound (the
    serve-side constant-memory claim, measured not argued);
  * Chrome-trace "C" counter events interleaved with spans from multiple
    threads export as a valid monotonic trace, and the validator rejects
    malformed counter events;
  * the tiered store's host-tier byte gauge equals snapshot() nbytes;
  * when memory_analysis is unavailable the probe degrades to the
    accounting-only mode instead of raising;
  * the train step's compiled temp bytes stay flat while the graphs grow
    (every constant-memory variant, and the step that gathers from the
    resident copy), and the full-graph control grows;
  * Obs --mem-probe writes the per-site memory event ahead of the final
    summary record and restores the global probe on close.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gst as G
from repro.dist import pipeline as DP
from repro.graphs import batching as Bt
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.kernels.ops import max_intermediate_bytes
from repro.obs import (MemoryProbe, MetricsRegistry, NullProbe, Obs,
                       get_probe, get_registry, null_probe, null_registry,
                       null_tracer, probe_jit, set_probe, set_registry,
                       set_tracer, shape_signature, tree_nbytes,
                       validate_chrome_trace)
from repro.obs.trace import Tracer
from repro.optim import make_optimizer
from repro.roofline.analysis import (compiled_memory_stats,
                                     device_peak_bytes)
from repro.serve.engine import graph_to_chunks, make_stream_encoder
from repro.serve.buckets import default_ladder
from repro.store import TieredStore

HID = 8


@pytest.fixture(scope="module")
def dataset():
    graphs = D.make_malnet_like(n_graphs=16, seed=0)
    ds, _ = DP.segment_dataset_shared(graphs, 16, seed=0)
    return ds


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test starts and ends with the null registry/tracer/probe
    installed (the process defaults) — no cross-test telemetry bleed."""
    set_registry(null_registry())
    set_tracer(null_tracer())
    set_probe(null_probe())
    yield
    set_registry(null_registry())
    set_tracer(null_tracer())
    set_probe(null_probe())


def _state(ds, hidden=HID):
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=hidden)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), hidden, 5, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    from repro.core import embedding_table as tbl
    return enc, opt, G.TrainState(bb, head, opt.init((bb, head)),
                                  tbl.init_table(ds.n, ds.j_max, hidden),
                                  jnp.zeros((), jnp.int32))


def _batch(ds, ids):
    return jax.tree_util.tree_map(jnp.asarray, DP._assemble(ds, ids))


# ---------------------------------------------------------------------------
# capture + dedup
# ---------------------------------------------------------------------------


def test_probe_capture_keyed_by_shape_signature():
    probe = MemoryProbe()
    set_probe(probe)
    reg = MetricsRegistry()
    set_registry(reg)
    f = probe_jit("t.add", jax.jit(lambda a, b: a + b))

    x4, x8 = jnp.ones((4,)), jnp.ones((8,))
    f(x4, x4)
    f(x4, x4)          # same signature: counted, not re-measured
    f(x8, x8)          # new signature: second record
    recs = {(r["site"], r["signature"]): r for r in probe.records()}
    assert len(recs) == 2
    sig4 = shape_signature(((x4, x4), {}))
    assert recs[("t.add", sig4)]["calls"] == 2
    r = recs[("t.add", sig4)]
    assert r["mode"] == "compiled"
    assert r["peak_bytes"] > 0 and r["temp_bytes"] >= 0
    assert r["cost"] is not None and r["cost"]["flops"] >= 0
    # gauges landed in the registry under the site name
    snap = reg.snapshot()
    assert snap["mem.device.peak_bytes.t.add"]["value"] > 0
    assert "mem.device.temp_bytes.t.add" in snap


def test_signature_distinguishes_dtype_and_shape():
    a = jnp.ones((2, 3), jnp.float32)
    b = jnp.ones((2, 3), jnp.int32)
    assert shape_signature(a) != shape_signature(b)
    assert shape_signature(a) != shape_signature(jnp.ones((3, 2)))
    assert shape_signature({"x": a}) == shape_signature({"x": a})


def test_tree_nbytes_counts_numpy_and_jax_leaves():
    host = {"x": np.zeros((4, 2), np.float32), "i": np.zeros((4,), np.int64)}
    assert tree_nbytes(host) == 4 * 2 * 4 + 4 * 8
    assert tree_nbytes(jnp.zeros((8,), jnp.float32)) == 32


def test_null_probe_and_passthrough():
    assert not NullProbe().enabled
    assert get_probe() is null_probe()
    jitted = jax.jit(lambda x: x * 2)
    f = probe_jit("t.mul", jitted)
    # attribute passthrough: AOT entry points still reachable
    assert f.lower(jnp.ones((2,))).compile() is not None
    # disabled probe records nothing
    f(jnp.ones((2,)))
    assert get_probe().records() == []


# ---------------------------------------------------------------------------
# measure-on-the-side: jaxpr identity + result identity
# ---------------------------------------------------------------------------


def test_train_step_jaxpr_identical_with_probe_installed(dataset):
    ds = dataset
    enc, opt, state = _state(ds)
    step_fn = G.make_train_step(enc, opt, G.VARIANTS["gst_efd"],
                                keep_prob=0.5)
    batch = _batch(ds, np.arange(4, dtype=np.int64))
    rng = jax.random.PRNGKey(0)

    baseline = str(jax.make_jaxpr(step_fn)(state, batch, rng))
    obs = Obs(mem_probe=True, install=True)
    try:
        assert get_probe() is obs.probe and get_probe().enabled
        probed = probe_jit("train.step", jax.jit(step_fn))
        _, m = probed(state, batch, rng)
        jax.block_until_ready(m["loss"])
        instrumented = str(jax.make_jaxpr(step_fn)(state, batch, rng))
        assert [r["site"] for r in obs.probe.records()] == ["train.step"]
    finally:
        obs.uninstall()
    assert instrumented == baseline


def test_probed_results_identical_to_raw(dataset):
    ds = dataset
    enc, opt, state = _state(ds)
    step = jax.jit(G.make_eval_step(enc))
    batch = _batch(ds, np.arange(4, dtype=np.int64))
    raw = step(state, batch)
    set_probe(MemoryProbe())
    probed = probe_jit("t.eval", step)(state, batch)
    np.testing.assert_array_equal(np.asarray(raw["loss"]),
                                  np.asarray(probed["loss"]))


# ---------------------------------------------------------------------------
# streaming constant-memory claim, measured
# ---------------------------------------------------------------------------


def test_streaming_temp_flat_across_chunk_counts_and_bounded():
    cfg = GNNConfig(backbone="sage", n_feat=8, hidden=HID)
    bb = gnn_init(jax.random.key(0), cfg)
    head = G.head_init(jax.random.key(1), HID, 5, "mlp")
    g = D.make_malnet_like(n_graphs=1, seed=0)[0]
    spec = default_ladder(16)[-1]
    base = graph_to_chunks(g, spec, 2, partition_max_nodes=16)
    stream = make_stream_encoder(cfg)

    temps, bounds = [], []
    chunks = base
    for _ in range(3):           # C, 2C, 4C chunks of identical shape
        dev = {k: jnp.asarray(v) for k, v in chunks.items()}
        mem = compiled_memory_stats(
            stream.lower(bb, head, dev).compile())
        if mem is None:
            pytest.skip("memory_analysis unavailable on this backend")
        temps.append(mem["temp_size_in_bytes"])
        bounds.append(int(max_intermediate_bytes(stream, bb, head, dev)))
        chunks = {k: np.concatenate([v, v]) for k, v in chunks.items()}

    assert len(set(temps)) == 1, f"stream temp grew with chunks: {temps}"
    assert all(t >= b for t, b in zip(temps, bounds)), (temps, bounds)
    assert len(set(bounds)) == 1   # the accounting bound is flat too


def test_device_peak_model_consistent():
    mem = {"argument_size_in_bytes": 100, "output_size_in_bytes": 40,
           "temp_size_in_bytes": 10, "alias_size_in_bytes": 30}
    assert device_peak_bytes(mem) == 120
    assert device_peak_bytes({}) == 0


# ---------------------------------------------------------------------------
# counter events in the trace
# ---------------------------------------------------------------------------


def test_counter_and_span_interleave_exports_valid_trace(tmp_path):
    tr = Tracer()
    set_tracer(tr)
    gate = threading.Barrier(3)

    def worker():
        gate.wait()
        for i in range(20):
            with tr.span("w.step", i=i):
                tr.counter("mem.bytes", staged=float(i * 100))

    threads = [threading.Thread(target=worker) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        payload = json.load(f)
    assert validate_chrome_trace(payload) == []
    phases = {ev["ph"] for ev in payload["traceEvents"]}
    assert "C" in phases and "X" in phases


def test_validator_rejects_malformed_counter_events():
    base = {"name": "c", "ph": "C", "ts": 1, "pid": 1, "tid": 1}
    ok = {**base, "args": {"bytes": 42.0}}
    assert validate_chrome_trace({"traceEvents": [ok]}) == []
    no_args = dict(base)
    assert validate_chrome_trace({"traceEvents": [no_args]})
    empty = {**base, "args": {}}
    assert validate_chrome_trace({"traceEvents": [empty]})
    non_numeric = {**base, "args": {"bytes": "lots"}}
    assert validate_chrome_trace({"traceEvents": [non_numeric]})
    boolean = {**base, "args": {"bytes": True}}
    assert validate_chrome_trace({"traceEvents": [boolean]})


def test_counter_requires_numeric_series():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.counter("c", label="not-a-number")
    tr.counter("c", a=1, label="ignored")   # numeric subset recorded
    (ev,) = tr.events()
    assert ev["args"] == {"a": 1.0}


# ---------------------------------------------------------------------------
# host-side tracking
# ---------------------------------------------------------------------------


def test_host_tier_gauge_matches_snapshot_nbytes(dataset):
    ds = dataset
    probe = MemoryProbe()
    set_probe(probe)
    set_registry(MetricsRegistry())
    store = TieredStore(ds.n, ds.j_max, HID, device_rows=4)
    try:
        table = store.init_device_table()
        table, _ = store.prepare(table, np.arange(4, dtype=np.int64))
        store.publish_counters()
        snap = store.snapshot(table)
        want = sum(int(np.asarray(x).nbytes) for x in snap)
        assert probe.host_bytes()["store.host_tier"] == want
        assert store.host_tier_bytes() == want
        reg_snap = get_registry().snapshot()
        assert reg_snap["mem.host.store.host_tier_bytes"]["value"] == want
    finally:
        store.close()


def test_feeder_staging_bytes_published(dataset):
    ds = dataset
    probe = MemoryProbe()
    set_probe(probe)
    set_registry(MetricsRegistry())
    sched = [np.arange(4, dtype=np.int64)]
    feeder = DP.SyncSegmentFeeder(ds, sched, lambda h: h)
    batches = list(feeder)
    assert len(batches) == 1
    assert probe.host_bytes()["feeder.staging"] == tree_nbytes(batches[0])


# ---------------------------------------------------------------------------
# accounting-only degrade (no memory_analysis on the backend)
# ---------------------------------------------------------------------------


class _NoMemCompiled:
    def memory_analysis(self):
        return None

    def cost_analysis(self):
        return {"flops": 3.0, "bytes accessed": 7.0}


class _NoMemLowered:
    def compile(self):
        return _NoMemCompiled()


class _NoMemJit:
    def lower(self, *args, **kwargs):
        return _NoMemLowered()

    def __call__(self, *args, **kwargs):
        return args


def test_probe_degrades_to_accounting_without_memory_analysis():
    probe = MemoryProbe(accounting_fallback=False)
    set_probe(probe)
    f = probe_jit("t.nomem", _NoMemJit())
    f(jnp.ones((2,)))
    (rec,) = probe.records()
    assert rec["mode"] == "accounting"
    assert "peak_bytes" not in rec          # nothing fabricated
    assert rec["cost"] == {"flops": 3.0, "bytes_accessed": 7.0}


def test_probe_survives_uncompilable_entry_point():
    class _Boom:
        def lower(self, *a, **k):
            raise RuntimeError("no lowering for you")

        def __call__(self, *a, **k):
            return 42

    probe = MemoryProbe()
    set_probe(probe)
    assert probe_jit("t.boom", _Boom())() == 42   # the call still runs
    (rec,) = probe.records()
    assert rec["mode"] == "error"


def test_probe_raises_on_tpu_instead_of_degrading(monkeypatch):
    class _Boom(_NoMemJit):
        def lower(self, *a, **k):
            raise RuntimeError("no lowering for you")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    set_probe(MemoryProbe())
    with pytest.raises(RuntimeError, match="no lowering"):
        probe_jit("t.boom", _Boom())(jnp.ones((2,)))
    with pytest.raises(RuntimeError, match="memory_analysis"):
        probe_jit("t.nomem", _NoMemJit())(jnp.ones((2,)))


# ---------------------------------------------------------------------------
# train-step constant-memory claim, measured
# ---------------------------------------------------------------------------

# SAGE at hidden 32, segments of at most 32 nodes, one batch of 4 graphs;
# the graphs grow from 2 to 8 communities, so J grows at a fixed segment
# shape
SWEEP_COMMS = ((2, 3), (8, 9))
SWEEP_HID, SWEEP_SEG_NODES, SWEEP_GRAPHS = 32, 32, 4


@pytest.fixture(scope="module")
def size_sweep():
    return [Bt.segment_dataset(
        D.make_malnet_like(n_graphs=SWEEP_GRAPHS, seed=0, comm_range=comm),
        SWEEP_SEG_NODES, method="bfs", seed=0) for comm in SWEEP_COMMS]


def _train_step_temp_bytes(ds, variant: str, resident: bool) -> int:
    """Temp (activation and workspace) bytes of the compiled train step
    of ``variant`` on one batch of every graph of ``ds``; ``resident``
    feeds it a ``ResidentSegments`` handle, from which the step gathers."""
    enc, opt, state = _state(ds, hidden=SWEEP_HID)
    ids = np.arange(ds.n)
    seg_inputs = (ds.device_seg_inputs(ids) if resident else
                  {k: jnp.asarray(v) for k, v in ds.seg_inputs(ids).items()})
    batch = G.GSTBatch(seg_inputs, jnp.asarray(ds.seg_valid[ids]),
                       jnp.asarray(ids, jnp.int32),
                       jnp.asarray(ds.labels[ids]))
    step = jax.jit(G.make_train_step(enc, opt, G.VARIANTS[variant],
                                     keep_prob=0.5),
                   donate_argnums=(0,))
    mem = compiled_memory_stats(
        step.lower(state, batch, jax.random.key(0)).compile())
    if mem is None:
        pytest.skip("memory_analysis unavailable on this backend")
    return mem["temp_size_in_bytes"]


@pytest.mark.parametrize("variant,resident,flat", [
    ("gst_e", False, True),
    ("gst_ef", False, True),
    ("gst_ed", False, True),
    ("gst_efd", False, True),
    ("gst_one", False, True),
    ("gst_efd", True, True),      # the step gathers from the resident copy
    ("full", False, False),       # the control: every segment backprops
])
def test_train_step_temp_flat_across_graph_size(size_sweep, variant,
                                                resident, flat):
    """GST's constant-memory claim: with the segment budget fixed, the
    train step's temp bytes stay within 1.25x while the graphs (and J)
    grow, and the full-graph control grows at least 2x over the same
    sizes, so that the sweep has teeth."""
    small, large = size_sweep
    assert large.j_max >= 2 * small.j_max, (small.j_max, large.j_max)
    temps = [_train_step_temp_bytes(ds, variant, resident)
             for ds in size_sweep]
    ratio = temps[1] / temps[0]
    if flat:
        assert ratio <= 1.25, (variant, small.j_max, large.j_max, temps)
    else:
        assert ratio >= 2.0, (variant, small.j_max, large.j_max, temps)


# ---------------------------------------------------------------------------
# Obs lifecycle
# ---------------------------------------------------------------------------


def test_obs_mem_probe_writes_memory_event_before_summary(tmp_path):
    out = str(tmp_path / "obs.jsonl")
    obs = Obs(mem_probe=True, metrics_out=out)
    assert get_probe() is obs.probe
    f = probe_jit("t.sq", jax.jit(lambda x: x * x))
    f(jnp.ones((4,)))
    obs.close()
    assert get_probe() is null_probe()     # global restored
    with open(out) as fh:
        records = [json.loads(line) for line in fh]
    assert records[-1]["type"] == "summary"
    (mem_ev,) = [r for r in records if r.get("event") == "memory"]
    assert [r["site"] for r in mem_ev["records"]] == ["t.sq"]
    assert mem_ev["records"][0]["mode"] == "compiled"
    assert "mem.device.peak_bytes.t.sq" in records[-1]["metrics"]
