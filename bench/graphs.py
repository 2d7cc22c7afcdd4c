"""Seeded graph generators of the benchmark's configurations.

The benchmark owns its inputs: these are copies of the program's
generators (`repro.graphs.synth`, the friendster cut of `chip_smoke.py`),
written for set-up speed with the same structure, so a later change to
the program cannot change what is measured. Each returns the normalized
adjacency D^-1/2 A D^-1/2 of a symmetric graph as COO arrays
(n, rows int32, cols int32, vals float32) with no duplicate entries.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components


def normalize(n: int, rows, cols, vals):
    """D^-1/2 A D^-1/2, degrees summed in float64 (bincount, not add.at)."""
    deg = np.bincount(rows, weights=vals, minlength=n)
    dinv = np.zeros(n)
    np.divide(1.0, np.sqrt(deg), out=dinv, where=deg > 0)
    return (vals * dinv[rows] * dinv[cols]).astype(np.float32)


def rmat(p: dict, seed: int):
    """Symmetric R-MAT graph reduced to its largest connected component.

    `p`: log2n, edge_samples_per_vertex (undirected samples drawn per
    vertex id, before duplicates and self loops are dropped), a, b, c.
    R-MAT leaves many ids without an edge, and a normalized adjacency has
    eigenvalue 1 once per component, so only the largest component has a
    top spectrum worth solving for.
    """
    n = 1 << int(p["log2n"])
    m = int(p["edge_samples_per_vertex"] * n)
    rng = np.random.default_rng(seed)
    pa, pb, pc = p["a"], p["a"] + p["b"], p["a"] + p["b"] + p["c"]
    r = np.zeros(m, np.int64)
    c = np.zeros(m, np.int64)
    for _ in range(int(p["log2n"])):
        u = rng.random(m, dtype=np.float32)
        r = 2 * r + (u >= pb)
        c = 2 * c + (((u >= pa) & (u < pb)) | (u >= pc))
    keep = r != c
    key = np.unique(np.concatenate([r[keep] * n + c[keep],
                                    c[keep] * n + r[keep]]))
    r, c = key // n, key % n
    ones = np.ones(r.size, np.float32)
    _, lab = connected_components(
        sp.csr_matrix((ones, (r, c)), shape=(n, n)), directed=False)
    big = lab == np.argmax(np.bincount(lab))
    new_id = np.cumsum(big) - 1
    live = big[r]
    n_lcc = int(big.sum())
    r = new_id[r[live]].astype(np.int32)
    c = new_id[c[live]].astype(np.int32)
    return n_lcc, r, c, normalize(n_lcc, r, c, ones[live])


def knn_band(p: dict, seed: int):
    """Symmetric near-banded kNN distance graph with weights in [0.5, 1).

    Vertex i links to i+d for each offset d in 1..band_halfwidth with
    probability nnz_per_row / (2 band_halfwidth), and the link is
    mirrored with the same weight: a band of +-band_halfwidth columns,
    degrees binomial around nnz_per_row (no power law), exact symmetry
    and no duplicates by construction, so nothing needs sorting here.
    """
    n = 1 << int(p["log2n"])
    hw = int(p["band_halfwidth"])
    thresh = np.uint16(round(p["nnz_per_row"] / (2 * hw) * (1 << 16)))
    rng = np.random.default_rng(seed)
    rs, cs = [], []
    step = max(1, (1 << 25) // hw)
    for i0 in range(0, n, step):
        cnt = min(step, n - i0)
        u = rng.integers(0, 1 << 16, size=(cnt, hw), dtype=np.uint16)
        i, d = np.nonzero(u < thresh)
        i = i.astype(np.int32) + i0
        j = i + d.astype(np.int32) + 1
        ok = j < n
        rs.append(i[ok])
        cs.append(j[ok])
    r = np.concatenate(rs)
    c = np.concatenate(cs)
    w = rng.random(r.size, dtype=np.float32) * 0.5 + 0.5
    rows = np.concatenate([r, c])
    cols = np.concatenate([c, r])
    vals = np.concatenate([w, w])
    return n, rows, cols, normalize(n, rows, cols, vals)


GENERATORS = {"rmat": rmat, "knn_band": knn_band}


def generate(config: dict, cache_dir: str | None = None):
    """The configuration's graph: (n, rows, cols, vals). Its seed is part
    of the configuration (`graph_seed`): a deployment solves one data set,
    and the benchmark's --seed draws the start blocks.

    With `cache_dir` the arrays are kept there, keyed by the generator,
    its parameters and the seed, and later calls read them back instead
    of generating them again (generating is most of a large graph's
    set-up, reading it back a second or two)."""
    gen = GENERATORS[config["generator"]]
    if cache_dir is None:
        return gen(config["graph"], config["graph_seed"])
    key = json.dumps([config["generator"], config["graph"],
                      config["graph_seed"]], sort_keys=True)
    path = os.path.join(cache_dir, "{}-{}.npz".format(
        config.get("name", "graph"),
        hashlib.sha256(key.encode()).hexdigest()[:16]))
    if os.path.exists(path):
        with np.load(path) as z:
            return int(z["n"]), z["rows"], z["cols"], z["vals"]
    n, rows, cols, vals = gen(config["graph"], config["graph_seed"])
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, n=n, rows=rows, cols=cols, vals=vals)
        # on the disk before set-up ends, not written back in the window
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return n, rows, cols, vals
