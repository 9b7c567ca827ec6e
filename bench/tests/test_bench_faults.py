"""A run with the timed path broken underneath comes out not correct: the
step returns its state unchanged, the loss leaves half the batch out, the
table write-back lands on the wrong segment, the parameters stop moving
after the first step, or Adam's second moment decays at the wrong rate."""
import functools

import jax
import jax.numpy as jnp
import pytest

import run as R
from harness import drivers
from harness import spec as SPEC
from repro.core import embedding_table as tbl
from repro.core import gst as G

CELL = "tpugraphs-tiny.train.tiny-single"


def _unchanged(monkeypatch):
    orig = G.make_train_step

    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch, rng):
            _, m = step(state, batch, rng)
            return state, m
        return broken
    monkeypatch.setattr(G, "make_train_step", make)


def _half_batch(monkeypatch):
    for name in ("ce_loss", "pairwise_hinge_loss"):
        orig = getattr(G, name)
        monkeypatch.setattr(G, name, lambda p, y, orig=orig: orig(
            p[:p.shape[0] // 2], y[:y.shape[0] // 2]))


def _altered(monkeypatch):
    orig = tbl.update_sampled

    def update(table, ids, seg_idx, h_new, step, **kw):
        J = table.emb.shape[1]
        return orig(table, ids, (seg_idx + 1) % J, h_new, step, **kw)
    monkeypatch.setattr(tbl, "update_sampled", update)


def _frozen(monkeypatch):
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


def _beta2(monkeypatch):
    monkeypatch.setattr(drivers, "make_optimizer", functools.partial(
        drivers.make_optimizer, b2=0.5))


@pytest.mark.parametrize(
    "fault", [_unchanged, _half_batch, _altered, _frozen, _beta2],
    ids=["unchanged", "half_batch", "altered", "frozen", "beta2"])
def test_fault_is_not_correct(tiny_bench, monkeypatch, fault):
    fault(monkeypatch)
    res = R.run_cell(SPEC.load(CELL, tiny_bench), 3000000041, 0.2,
                     trace=False, require_tpu=False)
    assert res["correct"] is False
    assert list(res)[-1] == "checks"
