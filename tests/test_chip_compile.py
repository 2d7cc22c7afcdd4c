"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles each kernel for
a chip that is described, not attached, and refuses what the chip would
refuse (misaligned blocks, VMEM overuse). Widths are the solve's: 64x64
SpMM blocks, a b=4 block against an m=32 subspace, at n = 2^22 rows and
at an n (1500) with no divisor that is a multiple of 8, which reaches the
kernels only through the zero-row padding in `kernels/ops.py`, and a
block stream whose index does not fit the chip's SMEM at once.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cholqr, stream
from repro.kernels import ops as kops
from repro.kernels.spmm_tile import spmm_blocksparse

M, B = 32, 4          # subspace width b*NB and block size b
BLOCK = 64            # SpMM block edge


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_blocksparse_compiles(one_chip, dtype):
    n_block_rows = (1 << 22) // BLOCK
    nb = 1 << 16
    _compile(
        lambda blocks, cols, rows, x: spmm_blocksparse(
            blocks, cols, rows, x, n_block_rows=n_block_rows),
        _sds((nb, BLOCK, BLOCK), dtype, one_chip),
        _sds((nb,), jnp.int32, one_chip),
        _sds((nb,), jnp.int32, one_chip),
        _sds((n_block_rows * BLOCK, B), dtype, one_chip))


def test_spmm_blocks_compiles_past_smem(one_chip):
    # a served 2^18-vertex job packs ~160k blocks: a 1.3 MB block index,
    # more than the chip's 1 MiB SMEM, so the wrapper runs segments
    n_block_rows = (1 << 18) // BLOCK
    nb = 5 << 15
    _compile(
        lambda blocks, cols, rows, mask, x: kops.spmm_blocks(
            blocks, cols, rows, mask, x, n_block_rows=n_block_rows,
            impl="pallas"),
        _sds((nb, BLOCK, BLOCK), jnp.float32, one_chip),
        _sds((nb,), jnp.int32, one_chip),
        _sds((nb,), jnp.int32, one_chip),
        _sds((n_block_rows * BLOCK,), jnp.bool_, one_chip),
        _sds((n_block_rows * BLOCK, B), jnp.float32, one_chip))


@pytest.mark.parametrize("n", [1 << 22, 1500])
def test_gram_compiles(one_chip, n):
    _compile(lambda a, b: kops.gram(a, b, impl="pallas"),
             _sds((n, M), jnp.float32, one_chip),
             _sds((n, B), jnp.float32, one_chip))


@pytest.mark.parametrize("n", [1 << 22, 1500])
def test_tsgemm_compiles(one_chip, n):
    _compile(lambda a, b, c0: kops.tsgemm(a, b, alpha=-1.0, beta=1.0, c0=c0,
                                          impl="pallas"),
             _sds((n, M), jnp.float32, one_chip),
             _sds((M, B), jnp.float32, one_chip),
             _sds((n, B), jnp.float32, one_chip))


@pytest.mark.parametrize("n", [1 << 19, 1500])
def test_subspace_visits_compile(one_chip, n):
    """A pass's compiled visits at the knn cell's widths (a 128-column
    subspace compressed into 16 output blocks): each is one program with
    its kernels inside, and the donated accumulators and w alias the
    outputs, so a visit holds no second copy of them."""
    blk = _sds((n, B), jnp.float32, one_chip)
    accs = (blk,) * 16
    compiled = stream._matmul_visit.lower(
        blk, _sds((128, 64), jnp.float32, one_chip), 8, accs, 1.0,
        impl="pallas").compile()
    assert compiled.as_text().count("tpu_custom_call") >= 16
    assert compiled.memory_analysis().alias_size_in_bytes >= 16 * n * B * 4
    compiled = stream._project_visit.lower(blk, blk, impl="pallas").compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().alias_size_in_bytes >= n * B * 4
    assert "tpu_custom_call" in cholqr.lower(blk, impl="pallas").compile(
        ).as_text()
