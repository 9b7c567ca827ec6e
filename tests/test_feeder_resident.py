"""The feeder's resident dataset (graphs/batching.py).

  * every batch on the resident path is a ``ResidentSegments`` handle
    whose gather equals the host gather ``seg_inputs(ids)`` bit for bit,
    over shuffled epochs and an unshuffled pass, and the host fields are
    the host path's; its ``take(idx)`` equals ``gather_segments`` of the
    host gather, in every layout a field can have on the device;
  * steps fed the handle give the host dicts' losses, parameters and
    table bit for bit in every variant; the ``gst_efd`` step gathers no
    whole batch, and a donated step leaves the resident arrays alive;
  * the copy is placed once per dataset, across epochs and iterators
    (one ``feeder.place`` span), and ``feeder.resident_mib`` follows
    the live copies;
  * the residency rule reads the device's free memory less what the step
    needs beside the copy, is decided once, and sends every batch down
    the host path when it says no; ``run_experiment`` reserves its
    compiled step, one batch and the test split, and keeps the data on
    the host when the table is capped on the device;
  * ``feeder.device_gathers`` and ``feeder.host_gathers`` count each batch
    on its own path, and ``feeder.step_gathers`` each handle put in a
    batch;
  * a short ``run_experiment`` gives the same metrics on either path.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gst as G
from repro.core.embedding_table import init_table
from repro.graphs import batching as Bt
from repro.graphs import data as D
from repro.graphs import experiment as EX
from repro.graphs.experiment import run_experiment
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.optim import make_optimizer
from repro.obs import (MetricsRegistry, null_registry, null_tracer,
                       set_registry, set_tracer)
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _clean_globals():
    set_registry(null_registry())
    set_tracer(null_tracer())
    yield
    set_registry(null_registry())
    set_tracer(null_tracer())


def _dataset(kind="segments"):
    if kind == "whole":
        return Bt.whole_graph_dataset(D.make_malnet_like(n_graphs=10, seed=1))
    graphs = (D.make_tpugraphs_like(n_graphs=10, seed=2) if kind == "ranking"
              else D.make_malnet_like(n_graphs=10, seed=0))
    return Bt.segment_dataset(graphs, 16)


def _seg_bytes(ds):
    return sum(getattr(ds, k).nbytes for k in Bt.SEG_FIELDS)


def _refuse(monkeypatch):
    """Observed free memory below twice the dataset's bytes."""
    monkeypatch.setattr(Bt, "_device_free_bytes", lambda: 0)


def _passes(ds, seed=5):
    """Two shuffled epochs from one generator, then one unshuffled pass."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(2):
        out += list(Bt.batch_iterator(ds, 3, rng=rng))
    out += list(Bt.batch_iterator(ds, 3, rng=rng, shuffle=False))
    return out


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("kind", ["segments", "ranking", "whole"])
def test_device_gather_is_the_host_gather_bit_for_bit(kind, monkeypatch):
    ds = _dataset(kind)
    dev = _passes(ds)
    assert ds.resident() is not None
    host_ds = _dataset(kind)
    _refuse(monkeypatch)
    host = _passes(host_ds)
    assert host_ds.resident() is None
    assert len(dev) == len(host) == 2 * (ds.n // 3) + ds.n // 3
    for (seg, sv, ids, lab), (hseg, hsv, hids, hlab) in zip(dev, host):
        assert isinstance(seg, Bt.ResidentSegments)
        assert all(isinstance(v, jax.Array) for v in seg.values())
        assert all(isinstance(v, np.ndarray) for v in hseg.values())
        assert set(seg) == set(hseg) == set(Bt.SEG_FIELDS)
        for k, v in ds.seg_inputs(ids).items():
            _same_bits(seg[k], v)
            _same_bits(hseg[k], v)
        for a, b in ((sv, hsv), (ids, hids), (lab, hlab)):
            assert isinstance(a, np.ndarray)
            _same_bits(a, b)


def test_dataset_is_placed_once_across_epochs_and_iterators():
    reg = MetricsRegistry()
    set_registry(reg)
    tracer = Tracer()
    set_tracer(tracer)
    ds = _dataset()
    _passes(ds)
    first = ds.resident()
    list(Bt.batch_iterator(ds, 4, rng=np.random.default_rng(1)))
    assert ds.resident() is first
    names = [n for _, _, n, _ in tracer.spans()]
    assert names.count("feeder.place") == 1
    assert names.count("feeder.assemble") == 3 * 3 + 10 // 4
    assert reg.get("feeder.resident_mib").value == _seg_bytes(ds) / 2**20
    assembles = [e for e in tracer.events() if e["name"] == "feeder.assemble"]
    assert all(e["args"]["device"] is True for e in assembles)
    del ds, first                          # the gauge drops with the copy
    gc.collect()
    assert reg.get("feeder.resident_mib").value == 0


@pytest.mark.parametrize("spare", [-1, 0])
def test_residency_rule_is_half_the_free_memory(spare, monkeypatch):
    """Resident when the arrays take at most half the free memory left
    beside one gathered batch."""
    ds = _dataset()
    assert ds.seg_nbytes == _seg_bytes(ds)
    free = 2 * _seg_bytes(ds) + 3 * _seg_bytes(ds) // ds.n + spare
    monkeypatch.setattr(Bt, "_device_free_bytes", lambda: free)
    seg = next(Bt.batch_iterator(ds, 3, rng=np.random.default_rng(0)))[0]
    resident = spare >= 0
    assert (ds.resident() is not None) == resident
    assert isinstance(seg, Bt.ResidentSegments if resident else dict)
    assert isinstance(seg["x"], jax.Array if resident else np.ndarray)


def test_residency_is_decided_once_per_dataset(monkeypatch):
    ds = _dataset()
    _refuse(monkeypatch)
    next(Bt.batch_iterator(ds, 3, rng=np.random.default_rng(0)))
    monkeypatch.setattr(Bt, "_device_free_bytes", lambda: None)
    seg = next(Bt.batch_iterator(ds, 3, rng=np.random.default_rng(0)))[0]
    assert ds.resident() is None
    assert isinstance(seg["x"], np.ndarray)
    assert Bt._device_free_bytes() is None and _dataset().resident()


def test_gathers_are_counted_on_their_own_path(monkeypatch):
    reg = MetricsRegistry()
    set_registry(reg)
    _passes(_dataset())                       # 3 + 3 + 3 batches
    assert reg.get("feeder.device_gathers").value == 9
    assert reg.get("feeder.host_gathers") is None
    _refuse(monkeypatch)
    list(Bt.batch_iterator(_dataset(), 4, rng=np.random.default_rng(0)))
    assert reg.get("feeder.device_gathers").value == 9
    assert reg.get("feeder.host_gathers").value == 10 // 4


def test_run_experiment_is_the_same_on_either_path(monkeypatch):
    kw = dict(dataset="tpugraphs", backbone="sage", variant="gst_efd",
              n_graphs=16, max_seg_nodes=24, epochs=2, finetune_epochs=1,
              batch_size=4, hidden=8, record_curve=True)
    reg = MetricsRegistry()
    set_registry(reg)
    dev = run_experiment(**kw)
    assert reg.get("feeder.host_gathers") is None
    n_dev = reg.get("feeder.device_gathers").value
    assert reg.get("feeder.step_gathers").value == n_dev
    _refuse(monkeypatch)
    host = run_experiment(**kw)
    assert reg.get("feeder.device_gathers").value == n_dev
    assert reg.get("feeder.host_gathers").value == n_dev
    assert reg.get("feeder.step_gathers").value == n_dev
    assert dev.finetuned and host.finetuned
    assert (dev.train_metric, dev.test_metric) == (host.train_metric,
                                                   host.test_metric)
    assert dev.curve == host.curve


def test_device_gather_refuses_ids_out_of_range():
    ds = _dataset()
    for ids in ([0, ds.n], [-1, 2]):
        with pytest.raises(IndexError):
            ds.device_seg_inputs(np.asarray(ids))


_EXP = dict(dataset="tpugraphs", backbone="sage", variant="gst_efd",
            n_graphs=16, max_seg_nodes=24, epochs=1, finetune_epochs=1,
            batch_size=4, hidden=8)


def _spy_reserves(monkeypatch):
    """The ``reserve_bytes`` of each dataset's deciding ``resident`` call,
    with the dataset's bytes."""
    calls = []
    orig = Bt.SegmentedDataset.resident

    def resident(self, reserve_bytes=0):
        if self._device is None:
            calls.append((self.seg_nbytes, self.n, reserve_bytes))
        return orig(self, reserve_bytes)
    monkeypatch.setattr(Bt.SegmentedDataset, "resident", resident)
    return calls


@pytest.mark.parametrize("temp", [0, 2**31])
def test_run_experiment_reserves_the_compiled_step(temp, monkeypatch):
    """Free memory that holds the data alone (2**30 bytes, twice over) but
    not beside a step of 2**31 temp bytes sends every batch to the host."""
    reg = MetricsRegistry()
    set_registry(reg)
    monkeypatch.setattr(Bt, "_device_free_bytes", lambda: 2**30)
    monkeypatch.setattr(EX, "_step_temp_bytes", lambda *a: temp)
    calls = _spy_reserves(monkeypatch)
    run_experiment(**_EXP)
    (train, n, r_train), (test, _, r_test) = calls
    assert 2 * (train + test) < 2**30
    batch = 4 * train // n
    assert (r_train, r_test) == (temp + batch + test, temp + batch)
    used = "feeder.host_gathers" if temp else "feeder.device_gathers"
    unused = "feeder.device_gathers" if temp else "feeder.host_gathers"
    assert reg.get(used).value > 0 and reg.get(unused) is None


def test_run_experiment_compiles_the_step_for_its_temp_bytes(monkeypatch):
    """Where the device reports its memory, the reserve holds the compiled
    step's temp bytes."""
    monkeypatch.setattr(Bt, "_device_free_bytes", lambda: 2**40)
    temps = []
    real = EX._step_temp_bytes
    monkeypatch.setattr(EX, "_step_temp_bytes",
                        lambda *a: temps.append(real(*a)) or temps[-1])
    calls = _spy_reserves(monkeypatch)
    run_experiment(**_EXP)
    assert len(temps) == 1 and temps[0] > 0
    assert calls[1][2] == temps[0] + 4 * calls[0][0] // calls[0][1]


def test_capped_table_keeps_the_data_on_the_host():
    reg = MetricsRegistry()
    set_registry(reg)
    run_experiment(**_EXP, table_device_rows=4)
    assert reg.get("feeder.host_gathers").value > 0
    assert reg.get("feeder.device_gathers") is None


def test_rows_of_128_where_an_example_fills_them():
    """Each field's examples go to the device in rows of 128 where their
    elements fill whole rows, else as they are; the gather gives back
    ``seg_inputs`` bit for bit either way."""
    rng = np.random.default_rng(0)
    n, J, m, F, E = 6, 4, 8, 16, 5
    ds = Bt.SegmentedDataset(
        rng.normal(size=(n, J, m, F)).astype(np.float32),
        rng.integers(0, m, size=(n, J, E, 2)).astype(np.int32),
        rng.random((n, J, E)).astype(np.float32),
        rng.random((n, J, m)).astype(np.float32),
        np.ones((n, J), np.float32), np.arange(n, dtype=np.int32), J, m, E)
    dev = ds.resident()
    assert dev["x"].shape == (n, J * m * F // 128, 128)
    assert all(dev[k].shape == getattr(ds, k).shape
               for k in ("edges", "edge_valid", "node_valid"))
    ids = np.asarray([5, 0, 5, 2])
    got = ds.device_seg_inputs(ids)
    for k, v in ds.seg_inputs(ids).items():
        _same_bits(got[k], v)


# Each field's example fills whole rows of 128, and each segment too (x);
# the example fills rows but a segment does not (edges, node_valid); or
# the example fills none and stays as it is (edge_valid).
_LAYOUTS = {"x": "segment rows", "edges": "example rows",
            "edge_valid": "as is", "node_valid": "example rows"}


def _layout_dataset(n=6, J=16, m=8, F=16, E=12):
    rng = np.random.default_rng(0)
    return Bt.SegmentedDataset(
        rng.normal(size=(n, J, m, F)).astype(np.float32),
        rng.integers(0, m, size=(n, J, E, 2)).astype(np.int32),
        rng.random((n, J, E)).astype(np.float32),
        rng.random((n, J, m)).astype(np.float32),
        np.ones((n, J), np.float32), np.arange(n, dtype=np.int32), J, m, E)


@pytest.mark.parametrize("field", list(_LAYOUTS),
                         ids=[f"{k}, {v}" for k, v in _LAYOUTS.items()])
def test_take_and_whole_are_the_host_gather_bit_for_bit(field):
    """``take(idx)`` is ``gather_segments`` of the host gather and
    ``whole()`` the host gather, bit for bit, whatever the field's layout
    on the device; the last segment of an example is among those taken,
    and so are a negative index and one out of range."""
    ds = _layout_dataset()
    a, shape = ds.resident()[field], getattr(ds, field).shape
    size = int(np.prod(shape[2:]))
    rows = {"segment rows": size % 128 == 0,
            "example rows": size % 128 and size * ds.j_max % 128 == 0,
            "as is": size * ds.j_max % 128 != 0}
    assert rows[_LAYOUTS[field]]
    assert a.shape == (shape if _LAYOUTS[field] == "as is"
                       else (ds.n, size * ds.j_max // 128, 128))
    ids = np.asarray([5, 0, 5, 2])
    handle = ds.device_seg_inputs(ids)
    host = ds.seg_inputs(ids)
    _same_bits(handle.whole()[field], host[field])
    _same_bits(handle[field], host[field])
    take = jax.jit(lambda h, i: h.take(i))
    want = jax.jit(G.gather_segments)
    rng = np.random.default_rng(1)
    for _ in range(3):
        idx = rng.integers(0, ds.j_max, (len(ids), 3)).astype(np.int32)
        idx[0, 0] = ds.j_max - 1
        idx[1, 1], idx[2, 2] = -1, ds.j_max
        got = take(handle, jnp.asarray(idx))
        _same_bits(got[field], want({k: jnp.asarray(v)
                                     for k, v in host.items()},
                                    jnp.asarray(idx))[field])


def _gnn_state(ds, variant, hidden=8):
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=hidden)
    opt = make_optimizer("adam", lr=1e-2)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), hidden, 5, "mlp")
    state = G.TrainState(bb, head, opt.init((bb, head)),
                         init_table(ds.n, ds.j_max, hidden),
                         jnp.zeros((), jnp.int32))
    step = jax.jit(G.make_train_step(make_encode_fn(cfg), opt,
                                     G.VARIANTS[variant], num_sampled=2),
                   donate_argnums=(0,))
    return state, step


def _three_steps(ds, variant):
    state, step = _gnn_state(ds, variant)
    losses = []
    rng = np.random.default_rng(3)
    batches = (tup for _ in range(2)
               for tup in Bt.batch_iterator(ds, 4, rng=rng))
    for t, tup in zip(range(3), batches):
        state, m = step(state, EX._to_batch(*tup), jax.random.key(t))
        losses.append(np.asarray(m["loss"]))
    return losses, jax.device_get(state)


@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_steps_fed_the_handle_are_the_host_steps(variant, monkeypatch):
    """Three steps fed the resident handle and three fed host dicts give
    the same losses, parameters and table, bit for bit: ``full`` and
    ``gst`` read ``whole()``, the rest ``take``."""
    ds = _dataset()
    dev = _three_steps(ds, variant)
    assert ds.resident() is not None
    host_ds = _dataset()
    _refuse(monkeypatch)
    host = _three_steps(host_ds, variant)
    assert host_ds.resident() is None
    for a, b in zip(*map(jax.tree_util.tree_leaves, (dev, host))):
        _same_bits(a, b)


def _shapes_made(jaxpr):
    """The shapes of every value an equation of ``jaxpr`` makes, nested
    jaxprs included."""
    out = set()
    for eqn in jaxpr.eqns:
        out |= {tuple(v.aval.shape) for v in eqn.outvars}
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _shapes_made(sub)
    return out


@pytest.mark.parametrize("variant", ["gst_efd", "gst"])
def test_gst_efd_step_gathers_no_whole_batch(variant):
    """The ``gst_efd`` step fed the handle makes no value of the whole
    batch's ``x`` shape (B, J, m, F); ``gst``, which encodes every
    segment, does (the control)."""
    ds = _dataset()
    state, step = _gnn_state(ds, variant)
    tup = next(Bt.batch_iterator(ds, 4, rng=np.random.default_rng(0)))
    jaxpr = jax.make_jaxpr(step)(state, EX._to_batch(*tup),
                                 jax.random.key(0))
    whole = (4,) + ds.x.shape[1:]
    assert (whole in _shapes_made(jaxpr.jaxpr)) == (variant == "gst")


def test_resident_arrays_outlive_a_donated_step():
    """The step donates its state only: after it the resident arrays are
    alive and hold the dataset."""
    ds = _dataset()
    state, step = _gnn_state(ds, "gst_efd")
    tup = next(Bt.batch_iterator(ds, 4, rng=np.random.default_rng(0)))
    old = state.table.emb
    state, m = step(state, EX._to_batch(*tup), jax.random.key(0))
    jax.block_until_ready(m["loss"])
    assert old.is_deleted()
    for k, a in ds.resident().items():
        assert not a.is_deleted()
        _same_bits(np.asarray(a).reshape(getattr(ds, k).shape),
                   getattr(ds, k))


def test_step_gathers_count_resident_batches_only(monkeypatch):
    """``_to_batch`` counts each handle as a ``feeder.step_gathers`` and
    passes it on as it is; a host batch is put on the device uncounted."""
    reg = MetricsRegistry()
    set_registry(reg)
    ds = _dataset()
    for tup in Bt.batch_iterator(ds, 4, rng=np.random.default_rng(0)):
        assert EX._to_batch(*tup).seg_inputs is tup[0]
    assert reg.get("feeder.step_gathers").value == 10 // 4
    _refuse(monkeypatch)
    for tup in Bt.batch_iterator(_dataset(), 4,
                                 rng=np.random.default_rng(0)):
        batch = EX._to_batch(*tup)
        assert all(isinstance(v, jax.Array)
                   for v in batch.seg_inputs.values())
    assert reg.get("feeder.step_gathers").value == 10 // 4
    assert reg.get("feeder.host_gathers").value == 10 // 4
