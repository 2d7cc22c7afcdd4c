"""jit'd public wrappers around the Pallas kernels, with CPU fallbacks.

Each op dispatches to the Pallas kernel on TPU (or in interpret mode when
forced) and to the pure-jnp oracle otherwise, so the rest of the framework
calls one function everywhere. `use_pallas()` picks the default from the
backend; tests override via the explicit `impl=` argument.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.tiles import TiledMatrix
from repro.kernels import spmm_ref as _spmm_ref
from repro.kernels import tsgemm_ref as _tsgemm_ref
from repro.kernels import gram_ref as _gram_ref
from repro.kernels.spmm_tile import spmm_blocksparse
from repro.kernels.tsgemm import tsgemm as _tsgemm_pallas
from repro.kernels.gram import gram as _gram_pallas

Impl = Literal["auto", "pallas", "interpret", "ref"]


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: Impl) -> str:
    if impl == "auto":
        return "pallas" if use_pallas() else "ref"
    return impl


# ---------------------------------------------------------------------------
# SpMM
# ---------------------------------------------------------------------------

def block_rows_from_ptr(row_ptr: np.ndarray) -> np.ndarray:
    """Flatten the CSR row_ptr into per-block block-row ids."""
    return np.repeat(np.arange(row_ptr.shape[0] - 1, dtype=np.int32),
                     np.diff(row_ptr))


def empty_row_mask(row_ptr: np.ndarray, bm: int) -> np.ndarray:
    """Boolean (n_rows,) mask — True where the block row has any blocks."""
    return np.repeat(np.diff(row_ptr) > 0, bm)


# The kernel scalar-prefetches its whole block index (two int32 per block)
# into SMEM, which holds 1 MiB on a v5e; longer block streams run as
# segments of at most this many blocks.
MAX_PREFETCH_BLOCKS = 1 << 16


@functools.partial(jax.jit, static_argnames=("n_block_rows", "impl"))
def spmm_blocks(blocks, block_cols, block_rows, row_mask, x,
                *, n_block_rows: int, impl: Impl = "auto"):
    """Block-sparse part of SpMM. row_mask zeroes never-visited output rows."""
    mode = _resolve(impl)
    if mode == "ref":
        return _spmm_ref.spmm_ref(blocks, block_cols, block_rows,
                                  n_block_rows, x)
    bm = blocks.shape[1]
    row_of = jnp.arange(n_block_rows * bm) // bm
    y = jnp.zeros((n_block_rows * bm, x.shape[1]), jnp.float32)
    for s0 in range(0, blocks.shape[0], MAX_PREFETCH_BLOCKS):
        s1 = min(s0 + MAX_PREFETCH_BLOCKS, blocks.shape[0])
        part = spmm_blocksparse(blocks[s0:s1], block_cols[s0:s1],
                                block_rows[s0:s1], x,
                                n_block_rows=n_block_rows,
                                interpret=(mode == "interpret"))
        # block rows are sorted, so a segment writes exactly the rows
        # between its first and last block row; the rest is garbage
        span = (row_of >= block_rows[s0]) & (row_of <= block_rows[s1 - 1])
        y = y + jnp.where(span[:, None], part, 0.0)
    return jnp.where(row_mask[:, None], y, 0.0)


def spmm(tm: TiledMatrix, x: jnp.ndarray, *, impl: Impl = "auto") -> jnp.ndarray:
    """Full SpMM: block-sparse path + COO side-path. Host-side convenience
    (device arrays are created per call — the performance path keeps arrays
    resident and calls spmm_blocks/coo parts directly)."""
    brs = jnp.asarray(block_rows_from_ptr(np.asarray(tm.row_ptr)))
    mask = jnp.asarray(empty_row_mask(np.asarray(tm.row_ptr), tm.block_shape[0]))
    y = spmm_blocks(jnp.asarray(tm.blocks), jnp.asarray(tm.block_cols), brs,
                    mask, x, n_block_rows=tm.n_block_rows, impl=impl)
    if tm.coo_vals.size:
        y = y + _spmm_ref.coo_spmm_ref(jnp.asarray(tm.coo_rows),
                                       jnp.asarray(tm.coo_cols),
                                       jnp.asarray(tm.coo_vals), x, tm.shape[0])
    return y


# ---------------------------------------------------------------------------
# TAS dense ops
# ---------------------------------------------------------------------------

# A row block must be a multiple of the f32 sublane tile (8 rows) unless it
# spans the whole array, so any n reaches the kernels padded with zero rows.
ROW_ALIGN = 8


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_row_interval(n: int, cap: int = 512) -> int:
    """Row interval: `cap`, or n rounded up to ROW_ALIGN when that is less."""
    return min(cap, _round_up(n, ROW_ALIGN))


def _pad_rows(x, n_pad: int):
    if x.shape[0] == n_pad:
        return x
    return jnp.pad(x, ((0, n_pad - x.shape[0]), (0, 0)))


def tsgemm(a, b, *, alpha=1.0, beta=0.0, c0=None, impl: Impl = "auto",
           row_interval: int | None = None):
    """C = alpha*A@B + beta*C0 (MvTimesMatAddMv)."""
    mode = _resolve(impl)
    if mode == "ref":
        return _tsgemm_ref.tsgemm_ref(a, b, alpha=alpha, beta=beta, c0=c0)
    n = a.shape[0]
    ri = row_interval or _pick_row_interval(n)
    n_pad = _round_up(n, ri)
    if c0 is None:
        c0 = jnp.zeros((n_pad, b.shape[1]), jnp.float32)
        beta = 0.0
    out = _tsgemm_pallas(_pad_rows(a, n_pad), b, _pad_rows(c0, n_pad),
                         alpha, beta, row_interval=ri,
                         interpret=(mode == "interpret"))
    return out[:n]


def gram(a, b, *, alpha=1.0, impl: Impl = "auto",
         row_interval: int | None = None):
    """G = alpha*A^T@B (MvTransMv). Zero pad rows add nothing to G."""
    mode = _resolve(impl)
    if mode == "ref":
        return _gram_ref.gram_ref(a, b, alpha=alpha)
    ri = row_interval or _pick_row_interval(a.shape[0])
    n_pad = _round_up(a.shape[0], ri)
    return _gram_pallas(_pad_rows(a, n_pad), _pad_rows(b, n_pad), alpha,
                        row_interval=ri, interpret=(mode == "interpret"))
