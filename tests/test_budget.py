"""The store's device budget is the one rule for where subspace blocks
live: whole Krylov–Schur solves on the friendster configuration's R-MAT
shape at 2^11 ids, at budgets of 2x, 1x, 1/2 and one block of the
subspace. The budget moves bytes between tiers and nothing else."""
import json
import os

import numpy as np
import pytest

from bench import graphs
from repro.core import GraphOperator, MultiVector, TieredStore, solve
from repro.core import stream
from repro.core import tiered
from repro.core.tiered import DEVICE
from repro.graphs import pack_tiles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, NB, NEV = 4, 8, 8
SHARES = {"2x": 2.0, "1x": 1.0, "half": 0.5, "one-block": 1 / NB}


class _CheckedStore(TieredStore):
    """Records, after every put, how far the resident bytes exceed the
    budget plus the pinned entries (0 when within)."""

    def __init__(self, budget):
        super().__init__(device_budget_bytes=budget)
        self.put_excess = 0

    def pinned_bytes(self):
        return sum(self._entries[n].nbytes for n in self._pinned
                   if self.tier_of(n) == DEVICE)

    def put(self, name, value, **kw):
        super().put(name, value, **kw)
        over = self.device_bytes() - self.device_budget - self.pinned_bytes()
        self.put_excess = max(self.put_excess, over)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "bench", "configs", "friendster.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def op(cfg):
    n, rows, cols, vals = graphs.rmat({**cfg["graph"], "log2n": 11},
                                      cfg["graph_seed"])
    pk = cfg["packing"]
    return GraphOperator(pack_tiles(n, n, rows, cols, vals,
                                    block_shape=tuple(pk["block_shape"]),
                                    min_block_nnz=pk["min_block_nnz"]),
                         impl="ref")


@pytest.fixture(scope="module")
def runs(cfg, op):
    subspace = op.n * B * NB * 4
    out = {}
    for key, share in SHARES.items():
        store = _CheckedStore(int(subspace * share))
        accs = []          # (resident, pinned, accumulator) bytes at alloc
        in_compress = []
        compress, matmul_init = MultiVector.compress, stream._Matmul.__init__

        def compress_(self, *a, **kw):
            in_compress.append(True)
            try:
                return compress(self, *a, **kw)
            finally:
                in_compress.pop()

        def matmul_init_(self, small, row_offsets, out_widths, alpha, n_,
                         impl):
            if in_compress:
                accs.append((store.device_bytes(), store.pinned_bytes(),
                             sum(out_widths) * n_ * 4))
            matmul_init(self, small, row_offsets, out_widths, alpha, n_,
                        impl)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(MultiVector, "compress", compress_)
            mp.setattr(stream._Matmul, "__init__", matmul_init_)
            res = solve(op, NEV, method="krylov_schur", which="LA",
                        tol=cfg["tol"], max_iters=cfg["max_restarts"],
                        block_size=B, num_blocks=NB, store=store,
                        impl="ref", seed=7)
        out[key] = (res, store, accs)
    return out


@pytest.mark.parametrize("key", list(SHARES))
def test_eigenpairs_bit_identical_across_budgets(runs, key):
    ref, res = runs["2x"][0], runs[key][0]
    assert res.converged and res.n_restarts > 0
    np.testing.assert_array_equal(res.eigenvalues, ref.eigenvalues)
    np.testing.assert_array_equal(res.eigenvectors, ref.eigenvectors)
    assert res.n_ops == ref.n_ops


@pytest.mark.parametrize("key", list(SHARES))
def test_resident_bytes_stay_within_budget_after_every_put(runs, key):
    assert runs[key][1].put_excess <= 0


@pytest.mark.parametrize("key", list(SHARES))
def test_compress_accumulator_fits_beside_resident_blocks(runs, key):
    """The store makes room before compress allocates its accumulators;
    where they alone exceed the budget, only the pinned newest block
    stays beside them."""
    store, accs = runs[key][1], runs[key][2]
    assert len(accs) == runs[key][0].n_restarts
    for resident, pinned, acc in accs:
        assert (resident + acc <= store.device_budget
                or resident == pinned), (resident, pinned, acc)


def test_host_bytes_read_fall_as_the_budget_grows(runs):
    read = [runs[k][1].stats.host_bytes_read
            for k in sorted(SHARES, key=SHARES.get)]
    assert read == sorted(read, reverse=True)
    assert read[0] > read[-1] == 0


def test_whole_subspace_budget_evicts_nothing(runs):
    st = runs["2x"][1].stats
    assert st.evict_bytes == 0 and st.cache_misses == 0
    assert runs["one-block"][1].stats.evict_bytes > 0


def test_default_budget_is_half_the_device_memory(monkeypatch):
    class Dev:
        def memory_stats(self):
            return {"bytes_limit": 16_000_000_000}
    assert TieredStore().device_budget == tiered.default_device_budget()
    monkeypatch.setattr(tiered.jax, "local_devices", lambda: [Dev()])
    assert TieredStore().device_budget == 8_000_000_000
    assert TieredStore(device_budget_bytes=0).device_budget == 0


def test_default_store_spills_a_subspace_past_its_budget(cfg, op, runs,
                                                         monkeypatch):
    """solve() without a store takes the default budget: a subspace
    larger than it spills to the slow tier, as with an explicit one."""
    monkeypatch.setattr(tiered, "default_device_budget",
                        lambda: op.n * B * 4)            # one block
    res = solve(op, NEV, method="krylov_schur", which="LA", tol=cfg["tol"],
                max_iters=cfg["max_restarts"], block_size=B, num_blocks=NB,
                impl="ref", seed=7)
    one_block = runs["one-block"][0]
    np.testing.assert_array_equal(res.eigenvalues, one_block.eigenvalues)
    assert res.io_stats["host_bytes_read"] > 0
    assert res.io_stats == one_block.io_stats
