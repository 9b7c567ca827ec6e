"""A run with the timed path broken underneath comes out not correct: the
step returns its state unchanged, the loss leaves half the batch out, the
table write-back lands on the wrong segment, the parameters stop moving
after the first step, Adam's second moment decays at the wrong rate, the
feeder gathers the wrong rows, or (on four forced host devices, in a
child process) the data-parallel step returns its state unchanged or
leaves half the batch out, or the exchange between the shards of the
table is left out."""
import functools

import jax
import jax.numpy as jnp
import pytest

from conftest import CELLS, in_four_devices

import run as R
from harness import spec as SPEC
from repro.core import embedding_table as tbl
from repro.core import gst as G

CELL = CELLS[1]


def _unchanged(monkeypatch, bench):
    orig = G.make_train_step

    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch, rng):
            _, m = step(state, batch, rng)
            return state, m
        return broken
    monkeypatch.setattr(G, "make_train_step", make)


def _half_batch(monkeypatch, bench):
    for name in ("ce_loss", "pairwise_hinge_loss"):
        orig = getattr(G, name)
        monkeypatch.setattr(G, name, lambda p, y, orig=orig: orig(
            p[:p.shape[0] // 2], y[:y.shape[0] // 2]))


def _altered(monkeypatch, bench):
    orig = tbl.update_sampled

    def update(table, ids, seg_idx, h_new, step, **kw):
        J = table.emb.shape[1]
        return orig(table, ids, (seg_idx + 1) % J, h_new, step, **kw)
    monkeypatch.setattr(tbl, "update_sampled", update)


def _frozen(monkeypatch, bench):
    """Optimizer state and table go on; the parameters keep their first
    step's values."""
    orig = G.make_train_step

    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch, rng):
            new, m = step(state, batch, rng)
            keep = lambda old, nu: jax.tree_util.tree_map(
                lambda o, n: jnp.where(state.step >= 1, o, n), old, nu)
            return new._replace(backbone=keep(state.backbone, new.backbone),
                                head=keep(state.head, new.head)), m
        return broken
    monkeypatch.setattr(G, "make_train_step", make)


def _beta2(monkeypatch, bench):
    driver = SPEC.module(bench, "drivers", "single")
    monkeypatch.setattr(driver, "make_optimizer", functools.partial(
        driver.make_optimizer, b2=0.5))


def _shifted_rows(monkeypatch, bench):
    """The feeder's device gather hands the step each example's neighbour
    in the dataset, while the ids and labels stay right."""
    from repro.graphs import batching as Bt
    orig = Bt._take_rows

    def take(arrays, ids, shapes):
        n = next(iter(arrays.values())).shape[0]
        return orig(arrays, (ids + 1) % n, shapes)
    monkeypatch.setattr(Bt, "_take_rows", take)


@pytest.mark.parametrize(
    "fault", [_unchanged, _half_batch, _altered, _frozen, _beta2,
              _shifted_rows],
    ids=["unchanged", "half_batch", "altered", "frozen", "beta2",
         "shifted_rows"])
def test_fault_is_not_correct(tiny_bench, monkeypatch, fault):
    fault(monkeypatch, tiny_bench)
    res = R.run_cell(SPEC.load(CELL, tiny_bench), 3000000041, 0.2,
                     trace=False, require_tpu=False)
    assert res["correct"] is False
    assert list(res)[-1] == "checks"


def exchange_left_out(bench):
    """Each shard answers and writes only the table rows it owns: rows
    another shard owns read as never written, and their write-backs are
    dropped."""
    from repro.dist import exchange as EXC

    def local(self, graph_ids):
        me = jax.lax.axis_index(self.axis_name)
        return graph_ids // self.rows == me, graph_ids - me * self.rows

    def lookup(self, table, graph_ids):
        mine, row = local(self, graph_ids)
        e, i = tbl.lookup(table, jnp.clip(row, 0, self.rows - 1))
        return (jnp.where(mine[:, None, None], e, 0),
                jnp.where(mine[:, None], i, False))

    def update_sampled(self, table, graph_ids, seg_idx, h_new, step):
        mine, row = local(self, graph_ids)
        return tbl.update_sampled(table, jnp.where(mine, row, self.rows),
                                  seg_idx, h_new, step, mode="drop")

    EXC.RingExchange.lookup = lookup
    EXC.RingExchange.update_sampled = update_sampled
    res = R.run_cell(SPEC.load(CELLS[4], bench), 3000000043, 0.2,
                     trace=False, require_tpu=False)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["table_mismatch"]["value"] > 0


def test_exchange_left_out_is_not_correct():
    in_four_devices("test_bench_faults", "exchange_left_out")


def dist_fault(bench, name):
    """The program's step, broken by one of the faults above, under the
    data-parallel driver of the four-chip cell."""
    faults = {"unchanged": _unchanged, "half_batch": _half_batch}
    with pytest.MonkeyPatch.context() as mp:
        faults[name](mp, bench)
        res = R.run_cell(SPEC.load(CELLS[4], bench), 3000000047, 0.2,
                         trace=False, require_tpu=False)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_dist_fault_is_not_correct(fault):
    in_four_devices("test_bench_faults", "dist_fault", fault)
