"""Pallas TPU kernel: block-sparse SpMM  Y = A @ X.

TPU adaptation of the paper's §3.3 semi-external-memory SpMM. The sparse
matrix is a stream of dense (bm×bn) blocks living in slow memory (HBM — the
"SSD" of the chip-level hierarchy); the Pallas grid walks the block stream in
block-row-major order ("tile rows"), double-buffering block fetches into VMEM
(BlockSpec pipelining == the paper's async I/O + buffer pool), while the
dense TAS operand X is gathered per block via a *scalar-prefetched* block
index — the in-memory "matrix index" of §3.3.1.

Accumulation uses the revisiting-output trick: blocks of one block row are
contiguous in the stream, so the output tile stays resident in VMEM across
the whole row and is flushed exactly once (minimizing writes to slow memory —
the DWPD discipline, §3.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


def _spmm_kernel(rows_ref, cols_ref, a_ref, x_ref, y_ref):
    """One grid step: multiply one sparse block with its X block.

    rows_ref/cols_ref: scalar-prefetch (nb,) int32 — block row/col ids.
    a_ref: (1, bm, bn) VMEM — the streamed sparse block.
    x_ref: (bn, k)     VMEM — gathered rows of X for this block column.
    y_ref: (bm, k)     VMEM f32 — output tile, revisited across the row.
    """
    i = pl.program_id(0)
    prev = rows_ref[jnp.maximum(i - 1, 0)]
    is_first = jnp.logical_or(i == 0, rows_ref[i] != prev)

    acc = jnp.dot(a_ref[0], x_ref[...], preferred_element_type=jnp.float32,
                  precision=mxu_precision(a_ref.dtype))

    @pl.when(is_first)
    def _init():
        y_ref[...] = acc

    @pl.when(jnp.logical_not(is_first))
    def _accum():
        y_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("n_block_rows", "interpret"))
def spmm_blocksparse(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                     block_rows: jnp.ndarray, x: jnp.ndarray,
                     *, n_block_rows: int, interpret: bool = False
                     ) -> jnp.ndarray:
    """Y = A @ X for a block-sparse A.

    blocks:     (nb, bm, bn)  — dense non-empty blocks, block-row-major.
    block_cols: (nb,) int32
    block_rows: (nb,) int32   — must be non-decreasing.
    x:          (n_block_cols*bn, k)
    returns     (n_block_rows*bm, k) float32. Output rows of *empty* block
    rows are garbage — callers mask them (see ops.empty_row_mask).
    """
    nb, bm, bn = blocks.shape
    k = x.shape[-1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda i, rows, cols: (i, 0, 0)),
            pl.BlockSpec((bn, k), lambda i, rows, cols: (cols[i], 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i, rows, cols: (rows[i], 0)),
    )
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_block_rows * bm, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="spmm_blocksparse",
    )(block_rows, block_cols, blocks, x)
