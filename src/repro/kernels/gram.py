"""Pallas TPU kernel: Gram / projection  G = alpha * A^T @ B.

Anasazi's MvTransMv (Table 1, op3) — the reorthogonalization hot spot (the
paper: >90% of runtime when computing many eigenvalues). Both TAS operands
stream through VMEM one row interval per grid step; the (m×b) result tile is
grid-accumulated in VMEM and flushed once — the paper's two-phase
"per-row-interval partial + aggregate" parallelization (§3.4.2) collapses
into the revisited-output accumulation on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


def _gram_kernel(a_ref, b_ref, alpha_ref, out_ref):
    i = pl.program_id(0)
    acc = jnp.dot(a_ref[...].T, b_ref[...], preferred_element_type=jnp.float32,
                  precision=mxu_precision(a_ref.dtype))

    @pl.when(i == 0)
    def _init():
        out_ref[...] = alpha_ref[0] * acc

    @pl.when(i != 0)
    def _accum():
        out_ref[...] += alpha_ref[0] * acc


@functools.partial(jax.jit, static_argnames=("row_interval", "interpret"))
def gram(a: jnp.ndarray, b: jnp.ndarray, alpha: float | jnp.ndarray = 1.0,
         *, row_interval: int = 512, interpret: bool = False) -> jnp.ndarray:
    """G = alpha * A^T @ B with A:(n,m), B:(n,b); n % row_interval == 0."""
    n, m = a.shape
    bcols = b.shape[1]
    assert n % row_interval == 0, (n, row_interval)
    grid = (n // row_interval,)
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_interval, m), lambda i: (i, 0)),
            pl.BlockSpec((row_interval, bcols), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((m, bcols), lambda i: (0, 0)),
    )
    return pl.pallas_call(
        _gram_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, bcols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gram",
    )(a, b, alpha)
