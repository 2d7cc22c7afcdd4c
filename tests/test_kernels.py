"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs import rmat_graph, pack_tiles
from repro.graphs.synth import to_dense
from repro.kernels import ops
from repro.kernels.spmm_ref import spmm_ref
from repro.kernels.spmm_tile import spmm_blocksparse


@pytest.mark.parametrize("n,nnz,bm,k", [
    (256, 2000, 16, 4), (512, 4000, 32, 8), (300, 1500, 16, 2),
    (1024, 8000, 64, 1),
])
def test_spmm_kernel_vs_ref(n, nnz, bm, k, rng):
    r, c, v = rmat_graph(n, nnz, seed=n, symmetric=True)
    tm = pack_tiles(n, n, r, c, v, block_shape=(bm, bm), min_block_nnz=1)
    brs = jnp.asarray(ops.block_rows_from_ptr(np.asarray(tm.row_ptr)))
    mask = jnp.asarray(ops.empty_row_mask(np.asarray(tm.row_ptr), bm))
    x = jnp.asarray(rng.standard_normal((tm.shape[1], k)), jnp.float32)
    y_ref = spmm_ref(jnp.asarray(tm.blocks), jnp.asarray(tm.block_cols),
                     brs, tm.n_block_rows, x)
    y_pal = spmm_blocksparse(jnp.asarray(tm.blocks),
                             jnp.asarray(tm.block_cols), brs, x,
                             n_block_rows=tm.n_block_rows, interpret=True)
    y_pal = jnp.where(mask[:, None], y_pal, 0.0)
    y_ref = jnp.where(mask[:, None], y_ref, 0.0)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_dtypes(dtype, rng):
    n, bm = 256, 16
    r, c, v = rmat_graph(n, 1500, seed=9, symmetric=True)
    tm = pack_tiles(n, n, r, c, v, block_shape=(bm, bm), min_block_nnz=1)
    brs = jnp.asarray(ops.block_rows_from_ptr(np.asarray(tm.row_ptr)))
    x = jnp.asarray(rng.standard_normal((tm.shape[1], 4)), dtype)
    blocks = jnp.asarray(tm.blocks, dtype)
    y_ref = spmm_ref(blocks, jnp.asarray(tm.block_cols), brs,
                     tm.n_block_rows, x)
    y_pal = spmm_blocksparse(blocks, jnp.asarray(tm.block_cols), brs, x,
                             n_block_rows=tm.n_block_rows, interpret=True)
    mask = ops.empty_row_mask(np.asarray(tm.row_ptr), bm)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_pal)[mask], np.asarray(y_ref)[mask],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("max_blocks", [ops.MAX_PREFETCH_BLOCKS, 7])
def test_spmm_full_hybrid_vs_dense(rng, monkeypatch, max_blocks):
    # max_blocks=7 splits the block stream into segments that share
    # boundary block rows, as a stream longer than SMEM holds does
    monkeypatch.setattr(ops, "MAX_PREFETCH_BLOCKS", max_blocks)
    ops.spmm_blocks.clear_cache()
    n = 600
    r, c, v = rmat_graph(n, 5000, seed=7, symmetric=True)
    tm = pack_tiles(n, n, r, c, v, block_shape=(16, 16), min_block_nnz=2)
    assert tm.nblocks > 3 * max_blocks or max_blocks > tm.nblocks
    x = rng.standard_normal((tm.shape[1], 4)).astype(np.float32)
    x[n:] = 0
    for impl in ("ref", "interpret"):
        y = ops.spmm(tm, jnp.asarray(x), impl=impl)
        np.testing.assert_allclose(np.asarray(y)[:n],
                                   to_dense(n, r, c, v) @ x[:n],
                                   rtol=1e-4, atol=1e-4)
    ops.spmm_blocks.clear_cache()


@pytest.mark.parametrize("n,m,b,ri", [
    (1024, 24, 4, 256), (512, 8, 8, 128), (768, 64, 2, 256), (256, 4, 1, 64),
    (1500, 24, 4, None), (1031, 8, 4, None),
])
def test_tsgemm_sweep(n, m, b, ri, rng):
    a = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    small = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    c0 = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    want = 1.5 * np.asarray(a) @ np.asarray(small) + 0.5 * np.asarray(c0)
    for impl in ("ref", "interpret"):
        out = ops.tsgemm(a, small, alpha=1.5, beta=0.5, c0=c0, impl=impl,
                         row_interval=ri if impl != "ref" else None)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,m,b,ri", [
    (1024, 24, 4, 256), (512, 16, 16, 512), (640, 8, 2, 128),
    (1500, 24, 4, None), (1031, 16, 2, None),
])
def test_gram_sweep(n, m, b, ri, rng):
    a = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    want = 2.0 * np.asarray(a).T @ np.asarray(bb)
    for impl in ("ref", "interpret"):
        out = ops.gram(a, bb, alpha=2.0, impl=impl,
                       row_interval=ri if impl != "ref" else None)
        np.testing.assert_allclose(np.asarray(out), want, rtol=3e-4, atol=3e-4)


def test_pick_row_interval():
    from repro.kernels.ops import ROW_ALIGN, _pick_row_interval
    assert _pick_row_interval(1024) == 512
    assert _pick_row_interval(300, cap=128) == 128
    assert _pick_row_interval(100) == 104
    for n in (1, 7, 100, 1031, 1500, 1 << 22):
        assert _pick_row_interval(n) % ROW_ALIGN == 0
