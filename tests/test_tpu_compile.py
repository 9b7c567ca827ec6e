"""Compile rehearsals for a described TPU v5e, with no chip attached.

The main path's Pallas kernels at real widths (hidden 128, the kernels'
d_blk) and one fused train step are lowered and compiled by the TPU
compiler.  Interpret mode cannot catch a tiling or VMEM refusal; this can.
Nothing runs: a pass says the chip's compiler accepts the program, and the
Mosaic kernel count says no kernel was left to interpret mode.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import gst as G
from repro.core.embedding_table import init_table
from repro.graphs import batching as Bt
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.kernels import ops
from repro.kernels import quant
from repro.kernels.sed_pool import sed_pool
from repro.kernels.segment_spmm import segment_spmm_batched
from repro.optim import make_optimizer

HIDDEN = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
    # a TPU program written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mosaic_kernels(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("mode", ["fwd", "grad"])
@pytest.mark.parametrize("m", [64, 256, 1024])
def test_segment_spmm_batched_compiles(one_chip, m, mode):
    """Every n_blk the VMEM budget picks lowers, up to the m = 1024 the
    kernel's docstring promises; the grad adds the transposed SpMM."""
    N, e = 16, 8 * m
    args = (_spec(one_chip, (N, m, HIDDEN), jnp.float32),
            _spec(one_chip, (N, e), jnp.int32),
            _spec(one_chip, (N, e), jnp.int32),
            _spec(one_chip, (N, e), jnp.float32))

    def fwd(h, src, dst, w):
        return segment_spmm_batched(h, src, dst, w, interpret=False)

    if mode == "fwd":
        assert _mosaic_kernels(fwd, *args) == 1
    else:
        grad = jax.value_and_grad(lambda *a: fwd(*a).sum(), argnums=(0, 3))
        assert _mosaic_kernels(grad, *args) == 2


@pytest.mark.parametrize("aged", [False, True])
def test_sed_pool_compiles(one_chip, aged):
    B, J = 8, 20
    masks = [_spec(one_chip, (B, J), jnp.float32) for _ in range(3)]
    args = [_spec(one_chip, (B, J, HIDDEN), jnp.float32)] + masks
    if aged:
        args.append(_spec(one_chip, (B, J), jnp.int32))

    def pool(h, valid, fresh, drop, ages=None):
        return sed_pool(h, valid, fresh, drop, keep_prob=0.5, num_sampled=1,
                        ages=ages, decay=0.1 if aged else 0.0,
                        interpret=False)

    assert _mosaic_kernels(pool, *args) == 1


@pytest.mark.parametrize("dtype,op", [
    ("bf16", "pack"), ("bf16", "pack_stochastic"), ("bf16", "unpack"),
    ("int8", "pack"), ("int8", "pack_stochastic"), ("int8", "unpack"),
])
def test_quant_compiles(one_chip, dtype, op):
    """Exchange payload rows (rows, J, hidden): pack with round-to-nearest
    or stochastic rounding from explicit random bits, and unpack."""
    shape = (64, 20, HIDDEN)
    x = _spec(one_chip, shape, jnp.float32)
    if op == "pack":
        fn = lambda x: quant.quantize_rows(x, dtype, None, use_pallas=True,
                                           interpret=False)
        args = (x,)
    elif op == "pack_stochastic":
        fn = lambda x, b: quant.quantize_rows(x, dtype, b, use_pallas=True,
                                              interpret=False)
        args = (x, _spec(one_chip, shape, jnp.uint32))
    else:
        values = _spec(one_chip, shape,
                       jnp.bfloat16 if dtype == "bf16" else jnp.int8)
        parts = ((values,) if dtype == "bf16"
                 else (values, _spec(one_chip, (shape[0],), jnp.float32)))
        fn = lambda p: quant.dequantize_rows(p, dtype, use_pallas=True,
                                             interpret=False)
        args = (parts,)
    assert _mosaic_kernels(fn, *args) == 1


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernel wrappers to their compiled path, as the chip's
    backend check does.  Trace caches are cleared on both sides so no
    interpret-mode trace is reused here and none of these leaks out."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def test_gst_efd_train_step_compiles(one_chip, compiled_kernels):
    """One gst_efd sage step at hidden 128, m 64: each pallas_call of its
    jaxpr (two MP layers forward and backward, one sed_pool) is a Mosaic
    kernel in the compiled program."""
    graphs = D.make_malnet_like(n_graphs=8, seed=0)
    ds = Bt.segment_dataset(graphs, max_seg_nodes=64)
    seg, seg_valid, ids, labels = next(Bt.batch_iterator(
        ds, 8, rng=np.random.default_rng(0), shuffle=False))
    cfg = GNNConfig(backbone="sage", n_feat=graphs[0].x.shape[1],
                    hidden=HIDDEN, use_pallas=True)
    opt = make_optimizer("adam", lr=5e-3)

    def init():
        key = jax.random.key(0)
        bb = gnn_init(key, cfg)
        head = G.head_init(jax.random.fold_in(key, 1), HIDDEN, 5, "mlp")
        return G.TrainState(bb, head, opt.init((bb, head)),
                            init_table(ds.n, ds.j_max, HIDDEN),
                            jnp.zeros((), jnp.int32))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    state = on_chip(jax.eval_shape(init))
    batch = on_chip(G.GSTBatch(seg, seg_valid, ids, labels))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    step = G.make_train_step(make_encode_fn(cfg), opt, G.VARIANTS["gst_efd"],
                             use_pallas=True)
    n_pallas = ops.count_pallas_calls(step, state, batch, key)
    assert n_pallas == 5
    assert _mosaic_kernels(step, state, batch, key) == n_pallas


def _entry_users(hlo: str, op_name: str):
    """The output element counts of every instruction of the entry
    computation that reads the parameter named ``op_name``, and that
    parameter's element count."""
    entry = hlo[hlo.index("\nENTRY"):]
    quoted = op_name.replace("'", "\\'")      # as the HLO text quotes it
    param = re.search(r"\n\s*(%\S+) = \w+\[([\d,]*)\][^\n]*parameter\(\d+\)"
                      r"[^\n]*op_name=\"" + re.escape(quoted) + "\"", entry)
    name, dims = param.group(1), param.group(2)
    size = lambda d: math.prod(int(x) for x in d.split(",") if x)
    users = []
    for line in entry.splitlines():
        rhs = line.partition(" = ")[2]
        if re.search(re.escape(name) + r"[,)]", rhs) and "parameter(" not in rhs:
            out = rhs[:rhs.index("(%")]      # the shapes before the operands
            users.append(max(size(d) for d in re.findall(r"\[([\d,]*)\]", out)))
    return users, size(dims)


def test_gst_efd_step_reads_resident_rows_only(one_chip, compiled_kernels):
    """The gst_efd step fed the feeder's handle, compiled for a v5e at the
    TpuGraphs widths (m 256, F 140, hidden 128): every instruction that
    reads the resident ``x`` makes at most one example's worth of
    elements (a relayout, reshape or convert of the whole array would
    make all of them), and the step needs less scratch than one gathered
    batch of ``x``."""
    n, J, m, F, E, B = 6, 24, 256, 140, 64, 4
    z = lambda *s: np.zeros(s, np.float32)
    ds = Bt.SegmentedDataset(z(n, J, m, F), np.zeros((n, J, E, 2), np.int32),
                             z(n, J, E), z(n, J, m), z(n, J),
                             z(n), J, m, E)
    handle = ds.device_seg_inputs(np.arange(B))
    cfg = GNNConfig(backbone="sage", n_feat=F, hidden=HIDDEN, use_pallas=True)
    opt = make_optimizer("adam", lr=5e-3, max_grad_norm=1.0)

    def init():
        key = jax.random.key(0)
        bb = gnn_init(key, cfg)
        head = G.head_init(jax.random.fold_in(key, 1), HIDDEN, 1,
                           "segment_sum")
        return G.TrainState(bb, head, opt.init((bb, head)),
                            init_table(n, J, HIDDEN), jnp.zeros((), jnp.int32))

    on_chip = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    state = on_chip(jax.eval_shape(init))
    batch = on_chip(G.GSTBatch(handle, z(B, J), np.zeros(B, np.int32), z(B)))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    step = G.make_train_step(make_encode_fn(cfg), opt, G.VARIANTS["gst_efd"],
                             num_sampled=2, head_mode="segment_sum",
                             loss_kind="pairwise_hinge", agg="sum",
                             use_pallas=True)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch, key).compile()
    users, size = _entry_users(compiled.as_text(),
                               "batch.seg_inputs.arrays['x']")
    assert users and max(users) <= size // n
    assert compiled.memory_analysis().temp_size_in_bytes < B * J * m * F * 4
