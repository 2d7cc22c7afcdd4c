"""Pallas TPU kernel: flash attention (online softmax), one head.

The §Roofline baseline's dominant memory term for prefill cells is the
(S×S) score traffic of unfused attention. This kernel never materializes
scores beyond a (bq × bk) VMEM tile: the classic running-max/denominator
recurrence (Rabe-Staats / FlashAttention), with the kv dimension as the
sequential ('arbitrary') grid axis and VMEM scratch carrying the state.

HBM traffic drops from O(S²) to O(S·d + S²/vmem-resident-tiles) — for
llama-vision prefill_32k this removes ~60 % of the memory term (the
projected §Perf endgame; the kernel is TPU-target, validated here in
interpret mode, while the portable q-chunked scan remains the default).
Heads/batch map via vmap in ops.flash_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, sm_scale: float, block_q: int,
                  block_k: int, n_kv_blocks: int):
    qi = pl.program_id(0)
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # whole kv block strictly in the future → skip work (masking keeps
        # correctness; pl.when keeps the flops/bytes off the hot path)
        run = qi * block_q + block_q - 1 >= kj * block_k

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + kj * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_single(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """One head: q (Sq, d), k/v (Sk, d) → (Sq, d)."""
    sq, d = q.shape
    sk = k.shape[0]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    sm_scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Batched heads: q (B, H, Sq, d), k/v (B, H, Sk, d)."""
    fn = functools.partial(flash_attention_single, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return jax.vmap(jax.vmap(fn))(q, k, v)
