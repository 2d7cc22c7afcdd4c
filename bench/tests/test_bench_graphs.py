"""The benchmark's own generators: shape, symmetry, seeding."""
import json
import os

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components

from bench import graphs, run

RMAT = {"log2n": 12, "edge_samples_per_vertex": 22,
        "a": 0.57, "b": 0.19, "c": 0.19}
KNN = {"log2n": 13, "band_halfwidth": 400, "nnz_per_row": 194}


def _matrix(n, r, c, v):
    return sp.csr_matrix((v.astype(np.float64), (r, c)), shape=(n, n))


@pytest.mark.parametrize("gen,params", [(graphs.rmat, RMAT),
                                        (graphs.knn_band, KNN)])
def test_symmetric_normalized_connected_no_duplicates(gen, params):
    n, r, c, v = gen(params, 7)
    key = r.astype(np.int64) * n + c
    assert np.unique(key).size == key.size and not np.any(r == c)
    a = _matrix(n, r, c, v)
    assert abs(a - a.T).max() == 0
    assert connected_components(a, directed=False)[0] == 1
    top = np.linalg.eigvalsh(a.toarray())[-1]
    assert top == pytest.approx(1.0, abs=1e-5)


def test_knn_rows_hold_194_nonzeros_within_a_band():
    n, r, c, _ = graphs.knn_band(KNN, 3)
    hw = KNN["band_halfwidth"]
    assert np.abs(r - c).max() <= hw
    deg = np.bincount(r, minlength=n)[hw:-hw]        # rows the band covers
    assert deg.mean() == pytest.approx(194, rel=0.02)
    assert deg.std() < 30                             # no power law


@pytest.mark.parametrize("name", ["friendster", "knn"])
def test_configuration_graph_has_the_published_degree(name):
    cfg = json.load(open(os.path.join(run.ROOT, "bench", "configs",
                                      f"{name}.json")))
    n, r, *_ = graphs.generate(cfg)
    want = cfg.get("nnz_per_vertex") or cfg["nnz_per_row"]
    assert r.size / n == pytest.approx(want, rel=0.01)


@pytest.mark.parametrize("gen,params", [(graphs.rmat, RMAT),
                                        (graphs.knn_band, KNN)])
def test_seed_fixes_the_graph_and_large_seeds_work(gen, params):
    big = 2 ** 31 + 12345
    a, b, c = gen(params, big), gen(params, big), gen(params, big + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
    assert a[1].size != c[1].size or not np.array_equal(a[2], c[2])


def test_cache_gives_back_the_generated_graph(tmp_path):
    cfg = {"name": "knn", "generator": "knn_band", "graph": KNN,
           "graph_seed": 5}
    made = graphs.generate(cfg, str(tmp_path))
    (kept,) = os.listdir(tmp_path)
    again = graphs.generate(cfg, str(tmp_path))
    assert made[0] == again[0]
    assert all(np.array_equal(x, y) for x, y in zip(made[1:], again[1:]))
    other = graphs.generate({**cfg, "graph_seed": 6}, str(tmp_path))
    assert len(os.listdir(tmp_path)) == 2
    assert not np.array_equal(other[2], made[2])
    assert all(np.array_equal(x, y) for x, y in
               zip(graphs.generate(cfg)[1:], made[1:]))
