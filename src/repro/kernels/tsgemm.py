"""Pallas TPU kernel: tall-skinny GEMM  C = alpha*A@B + beta*C0.

This is Anasazi's MvTimesMatAddMv (Table 1, op1) — the subspace-update GEMM.
The TAS operand A streams through VMEM one row interval (tm rows) per grid
step (the paper's §3.4.3 row-interval streaming); the small B matrix stays
VMEM-resident across the whole grid (the paper keeps it in RAM). The row
interval is the unit of parallelism and of I/O, exactly as in §3.4.2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision


def _tsgemm_kernel(a_ref, b_ref, c0_ref, alpha_ref, beta_ref, out_ref):
    alpha = alpha_ref[0]
    beta = beta_ref[0]
    acc = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
                  precision=mxu_precision(a_ref.dtype))
    out_ref[...] = alpha * acc + beta * c0_ref[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("row_interval", "interpret"))
def tsgemm(a: jnp.ndarray, b: jnp.ndarray, c0: jnp.ndarray,
           alpha: float | jnp.ndarray = 1.0, beta: float | jnp.ndarray = 0.0,
           *, row_interval: int = 512, interpret: bool = False) -> jnp.ndarray:
    """C = alpha*A@B + beta*C0 with A:(n,m), B:(m,b), C0:(n,b); n % row_interval == 0."""
    n, m = a.shape
    bcols = b.shape[1]
    assert n % row_interval == 0, (n, row_interval)
    grid = (n // row_interval,)
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)
    beta = jnp.asarray(beta, jnp.float32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_interval, m), lambda i: (i, 0)),
            pl.BlockSpec((m, bcols), lambda i: (0, 0)),
            pl.BlockSpec((row_interval, bcols), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((row_interval, bcols), lambda i: (i, 0)),
    )
    return pl.pallas_call(
        _tsgemm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, bcols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="tsgemm",
    )(a, b, c0, alpha, beta)
