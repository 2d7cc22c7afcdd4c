"""`correct` comes out false when the timed path is broken underneath: a
whole run at a tiny size on the CPU, once per fault the cells can have.
One chip, so there is no exchange between chips to leave out."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core
from repro.core import operator as core_operator
from repro.core import tiered
from bench import run

from bench.tests.small import SMALL, small_cell


def _state_unchanged(monkeypatch):
    """The operator's step hands its input back: A X = X."""
    monkeypatch.setattr(core_operator.GraphOperator, "matmat",
                        lambda self, x: x)


def _half_left_out(monkeypatch):
    """The SpMM computes the first half of the rows and leaves the rest."""
    orig = core_operator.GraphOperator.matmat

    def half(self, x):
        y = orig(self, x)
        return y.at[y.shape[0] // 2:].set(0.0)
    monkeypatch.setattr(core_operator.GraphOperator, "matmat", half)


def _answer_altered(monkeypatch):
    """The largest eigenvalue is altered by 1e-4 where the solve returns it."""
    orig = repro.core.solve

    def solve(*a, **kw):
        res = orig(*a, **kw)
        lam = np.array(res.eigenvalues, np.float64)
        lam[0] += 1e-4
        res.eigenvalues = lam
        return res
    monkeypatch.setattr(repro.core, "solve", solve)


def _store_block_altered(monkeypatch):
    """A block read back from the slow tier comes back scaled by 1.001."""
    orig = tiered.TieredStore.get
    calls = {"n": 0}

    def get(self, name):
        val = orig(self, name)
        calls["n"] += 1
        if calls["n"] % 7 == 0 and self.tier_of(name) == tiered.HOST:
            return val * jnp.float32(1.001)
        return val
    monkeypatch.setattr(tiered.TieredStore, "get", get)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered,
          "store_block_altered": _store_block_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    cell = small_cell(name)
    out = run.execute(cell, 2 ** 31 + 7, 0.3, False, impl="ref",
                      graph_overrides=SMALL[name])
    assert out["correct"] is False and out["failed"] >= 1
