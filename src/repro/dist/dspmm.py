"""Sharded semi-external-memory SpMM + fused eigensolver expansion step.

This is the distributed layer of the paper's design (§3.2–3.4) mapped onto
a (pod, data, model) jax mesh:

  * The sparse graph is packed into a 2D grid of *edge panels*
    (`pack_edge_panels`): panel (g, m) holds the edges whose destination row
    lives in row group g and whose source column lives in column group m.
    Panels are the streamed operand — the paper's SSD-resident tiles; here
    they shard over every device, spec `edge_spec`.
  * The dense vector subspace X stays sharded over all devices
    (`vector_spec`) — the paper's in-fast-memory TAS. One SpMM gathers each
    column group's rows over the row axes (the panel's column working set),
    contracts the local panel, and reduce-scatters partial rows over the
    "model" axis. Per device that moves n_pad/M·b gathered + n_pad/R·b
    reduced floats — the minimized-vector-I/O discipline of §3.3.
  * `build_eigen_step` fuses SpMM -> CGS2 block orthogonalization against
    the cached subspace V -> CholQR2, returning (q_new, h, r) with
    A·x = V·h + q_new·r exactly (the Krylov expansion invariant).
  * `build_eigen_step_compressed` is the I/O-compressed variant (§3.4's
    "compact external format" theme): edge endpoints are delta-encoded
    against per-CHUNK bases and packed into one uint32 (16+16 bits), edge
    values and the dense operands travel as bfloat16 — 6 bytes/edge instead
    of 12 — while all accumulation stays float32.

The per-panel contraction is gather/scatter jnp (portable: CPU tests and
SPMD partitioning both handle it); `panel_to_blocks` bridges a packed panel
to the Pallas block-sparse kernel in `kernels/spmm_tile.py` for the
TPU-resident panel contraction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist import layout
from repro.dist.compress import compressed_psum_pod

# a TPU rounds an f32 matmul's operands to bf16 by default (2e-3 relative
# error on a v5e); the orthogonalization needs full f32 products
HIGHEST = jax.lax.Precision.HIGHEST

# Edge-stream chunk: compressed panels delta-encode endpoints against one
# (row, col) base per CHUNK edges, and panel lengths pad to a CHUNK multiple
# so the streaming grid is uniform. Consumed by launch/dryrun.py sizing.
CHUNK = 4096

_MASK16 = np.uint32(0xFFFF)


# ------------------------------------------------------------------ specs
def row_axes(mesh) -> tuple:
    """Mesh axes forming the R row groups (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def edge_spec(mesh) -> P:
    """Spec for (R, M, e_loc) panel arrays: one (1,1,e_loc) panel/device."""
    return P(row_axes(mesh), "model", None)


def vector_spec(mesh) -> P:
    """Spec for (n_pad, b) vector blocks: rows sharded over all devices."""
    return P(tuple(mesh.axis_names), None)


def _groups(mesh) -> tuple[int, int]:
    r = int(np.prod([mesh.shape[a] for a in row_axes(mesh)]))
    return r, int(mesh.shape["model"])


# ------------------------------------------------------------- panel pack
def pack_edge_panels(n_pad: int, rows, cols, vals, *, r_groups: int,
                     m_groups: int, e_loc: int | None = None):
    """Partition permuted COO edges into the (R, M) panel grid.

    rows/cols are *positions* (already through `vertex_permutation`).
    Returns (panel_cols, panel_rows, panel_vals, e_loc), each array of shape
    (r_groups, m_groups, e_loc):

      panel_rows: destination row local to the row group's contiguous block
      panel_cols: source row local to the column group's gathered buffer
      panel_vals: edge weights; padding slots carry value 0 (and repeat the
                  panel's last endpoint so compressed delta bases stay tight)

    Every edge lands in exactly one panel — edge count and value mass are
    conserved (asserted by tests/test_dist_layout.py). Panel interiors are
    sorted by (row, col) so output-tile revisits are consecutive (the
    paper's block-row-major stream order) and compressed chunk deltas small.
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    assert rows.shape == cols.shape == vals.shape
    g = layout.row_group_of(rows, n_pad, r_groups)
    m = layout.col_group_of(cols, n_pad, r_groups, m_groups)
    r_loc = layout.local_row(rows, n_pad, r_groups)
    c_loc = layout.local_col(cols, n_pad, r_groups, m_groups)

    panel = g * m_groups + m
    order = np.lexsort((c_loc, r_loc, panel))
    panel, r_loc, c_loc, vals = (a[order] for a in (panel, r_loc, c_loc,
                                                    vals))
    counts = np.bincount(panel, minlength=r_groups * m_groups)
    need = int(counts.max()) if counts.size else 1
    if e_loc is None:
        e_loc = max(need, 1)
    assert need <= e_loc, f"panel overflow: {need} edges > e_loc={e_loc}"

    pr = np.zeros((r_groups * m_groups, e_loc), dtype=np.int32)
    pc = np.zeros_like(pr)
    pv = np.zeros((r_groups * m_groups, e_loc), dtype=np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for p in range(r_groups * m_groups):
        lo, hi = starts[p], starts[p + 1]
        k = hi - lo
        pr[p, :k], pc[p, :k] = r_loc[lo:hi], c_loc[lo:hi]
        if 0 < k < e_loc:  # pad by repeating the last endpoint, weight 0
            pr[p, k:], pc[p, k:] = pr[p, k - 1], pc[p, k - 1]
        pv[p, :k] = vals[lo:hi]
    shape3 = (r_groups, m_groups, e_loc)
    return (pc.reshape(shape3), pr.reshape(shape3), pv.reshape(shape3),
            e_loc)


def pack_compressed_panels(pc: np.ndarray, pr: np.ndarray, pv: np.ndarray,
                           *, chunk: int = CHUNK):
    """Delta-encode packed panels into the 6-byte/edge streaming format.

    Per CHUNK-edge chunk, endpoints are stored as uint16 offsets from the
    chunk's (min row, min col) base: packed = row_off << 16 | col_off
    (uint32), bases interleave [r0, c0, r1, c1, ...] (int32), values cast
    to bfloat16. Returns (packed, bases, vals_bf16) with shapes
    (R, M, e_pad), (R, M, 2·n_chunks), (R, M, e_pad); e_pad rounds e_loc up
    to a chunk multiple (padding repeats each panel's last edge, weight 0).

    Size bound + sub-tile re-basing: sub-tile deltas must fit 16 bits, so a
    sub-tile's rows may span at most 65536 panel rows and its columns 65536
    panel columns. Panels are (row, col)-sorted, so the row span of `chunk`
    consecutive edges is small, but the column span of one dense row can
    reach the panel width n_pad/M, which exceeds 2^16 on sparse meshes.
    When the requested chunk overflows, the chunk is re-based at sub-tile
    granularity: each chunk splits into 2^k equal sub-tiles, each carrying
    its own (row, col) base, with k the smallest power that fits every
    delta (worst case sub-tile = 1 edge, which always fits). e_pad stays a
    multiple of `chunk` — only the bases array grows. The stream is
    self-describing: consumers recover the effective sub-tile length as
    `2 * e_pad // bases.shape[-1]` (see `_unpack_edges`), so the packed
    format needs no side channel.
    """
    import ml_dtypes
    r_groups, m_groups, e_loc = pc.shape
    e_pad = -(-e_loc // chunk) * chunk
    if e_pad != e_loc:
        reps = e_pad - e_loc
        pc = np.concatenate([pc, np.repeat(pc[..., -1:], reps, -1)], -1)
        pr = np.concatenate([pr, np.repeat(pr[..., -1:], reps, -1)], -1)
        pv = np.concatenate([pv, np.zeros(pc.shape[:2] + (reps,),
                                          pv.dtype)], -1)
    sub = chunk
    while True:
        n_sub = e_pad // sub
        rc = pr.reshape(r_groups, m_groups, n_sub, sub)
        cc = pc.reshape(r_groups, m_groups, n_sub, sub)
        base_r = rc.min(-1)
        base_c = cc.min(-1)
        off_r = (rc - base_r[..., None]).astype(np.int64)
        off_c = (cc - base_c[..., None]).astype(np.int64)
        if not off_r.size or max(off_r.max(), off_c.max()) <= 0xFFFF:
            break
        assert sub > 1, "1-edge sub-tile cannot overflow a 16-bit delta"
        # re-base at finer sub-tile granularity; an odd sub drops straight
        # to 1 so every sub in the sequence divides e_pad
        sub = sub // 2 if sub % 2 == 0 else 1
    packed = ((off_r.astype(np.uint32) << np.uint32(16))
              | off_c.astype(np.uint32)).reshape(r_groups, m_groups, e_pad)
    bases = np.stack([base_r, base_c], axis=-1).reshape(
        r_groups, m_groups, 2 * n_sub).astype(np.int32)
    return packed, bases, pv.astype(ml_dtypes.bfloat16)


def _unpack_edges(packed, bases):
    """Inverse of pack_compressed_panels for one device's (e_pad,) stream.

    The sub-tile length is recovered from the array shapes (the stream is
    self-describing), so sub-tiled re-based streams decode transparently.
    """
    n_sub = bases.shape[0] // 2
    sub = packed.shape[0] // n_sub
    b2 = bases.reshape(n_sub, 2)
    off = packed.reshape(n_sub, sub)
    pr = (off >> np.uint32(16)).astype(jnp.int32) + b2[:, :1]
    pc = (off & _MASK16).astype(jnp.int32) + b2[:, 1:]
    return pr.reshape(-1), pc.reshape(-1)


# ---------------------------------------------------------- local kernels
def _panel_spmm(pc, pr, pv, x_loc, *, mesh, n_pad: int, b: int):
    """Per-device SpMM body (inside shard_map): y_loc = (A @ x)_shard.

    1. all-gather this column group's x rows over the row axes (the panel's
       column working set, n_pad/M rows),
    2. contract the local edge panel with gather + segment scatter-add
       (f32 accumulation regardless of stream dtype),
    3. reduce-scatter partial output rows over the model axis so each
       device ends holding exactly its own n_pad/(R·M) shard.
    """
    r_groups, m_groups = _groups(mesh)
    x_m = jax.lax.all_gather(x_loc, row_axes(mesh), axis=0, tiled=True)
    contrib = pv.astype(jnp.float32)[:, None] * x_m[pc].astype(jnp.float32)
    y_g = jnp.zeros((n_pad // r_groups, b), jnp.float32).at[pr].add(contrib)
    return jax.lax.psum_scatter(y_g, "model", scatter_dimension=0,
                                tiled=True)


def _cgs2_cholqr2(w_loc, v_loc, axes, *, b: int, nb_v: int,
                  pod_compressed: bool = False):
    """Classical Gram-Schmidt (2 passes) against V + CholQR (2 passes).

    w_loc: (s, b) f32 shard of A·x. v_loc: (nb_v, s, b) shard of the cached
    subspace. Returns (q_loc, h, r) with the exact factorization
    w = V·h + q·r; h accumulates both CGS passes, r composes both CholQR
    triangles. All b×b / (nb_v·b)×b reductions psum over every mesh axis
    (optionally int8-compressed across the pod axis — the paper's
    compressed cross-rack reduction).
    """
    def allsum(z):
        if pod_compressed and "pod" in axes:
            rest = tuple(a for a in axes if a != "pod")
            z = jax.lax.psum(z, rest)
            shape = z.shape
            return compressed_psum_pod(z.reshape(-1), "pod").reshape(shape)
        return jax.lax.psum(z, axes)

    vf = v_loc.astype(jnp.float32)
    w = w_loc
    h = jnp.zeros((nb_v, b, b), jnp.float32)
    for _ in range(2):  # CGS2: the second pass scrubs f32 cancellation
        hi = allsum(jnp.einsum("jnk,nl->jkl", vf, w, precision=HIGHEST))
        w = w - jnp.einsum("jnk,jkl->nl", vf, hi, precision=HIGHEST)
        h = h + hi
    r = jnp.eye(b, dtype=jnp.float32)
    q = w
    for _ in range(2):  # CholQR2
        gram = allsum(jnp.matmul(q.T, q, precision=HIGHEST))
        ell = jnp.linalg.cholesky(gram)
        q = jax.scipy.linalg.solve_triangular(ell, q.T, lower=True).T
        r = jnp.matmul(ell.T, r, precision=HIGHEST)
    return q, h.reshape(nb_v * b, b), r


# ------------------------------------------------------------------ build
def build_dspmm(mesh, *, n_pad: int, e_loc: int, b: int):
    """Jitted y = A @ x over packed panels: fn(pc, pr, pv, x) -> y.

    pc/pr/pv: (R, M, e_loc) from pack_edge_panels, x/y: (n_pad, b) f32.
    """
    del e_loc  # shapes carry it; kept in the signature as the panel contract

    def local(pc, pr, pv, x_loc):
        return _panel_spmm(pc[0, 0], pr[0, 0], pv[0, 0], x_loc,
                           mesh=mesh, n_pad=n_pad, b=b)

    es, vs = edge_spec(mesh), vector_spec(mesh)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(es, es, es, vs),
                             out_specs=vs, check_vma=False))


def build_eigen_step(mesh, *, n_pad: int, e_loc: int, b: int, nb_v: int,
                     pod_compressed: bool = False):
    """Fused Krylov expansion: fn(pc, pr, pv, vstack, x) -> (q_new, h, r).

    vstack: (nb_v, n_pad, b) — the cached subspace V as stacked blocks
    (V[:, j·b+k] = vstack[j, :, k]). Invariants (tested):
      q_newᵀ q_new = I,  Vᵀ q_new = 0,  A·x = V·h + q_new·r.
    """
    del e_loc
    axes = tuple(mesh.axis_names)

    def local(pc, pr, pv, v_loc, x_loc):
        w = _panel_spmm(pc[0, 0], pr[0, 0], pv[0, 0], x_loc,
                        mesh=mesh, n_pad=n_pad, b=b)
        return _cgs2_cholqr2(w, v_loc, axes, b=b, nb_v=nb_v,
                             pod_compressed=pod_compressed)

    es, vs = edge_spec(mesh), vector_spec(mesh)
    vstack_spec = P(None, axes, None)
    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(es, es, es, vstack_spec, vs),
        out_specs=(vs, P(None, None), P(None, None)), check_vma=False))


def build_eigen_step_compressed(mesh, *, n_pad: int, e_loc: int, b: int,
                                nb_v: int, chunk: int = CHUNK,
                                pod_compressed: bool = False):
    """Compressed-stream expansion step (6 bytes/edge, bf16 vectors).

    Returns (fn, n_chunks, e_pad); fn(packed, bases, vals_bf16,
    vstack_bf16, x_bf16) -> (q_new, h, r) in f32. Matches the baseline step
    to bf16 input-rounding tolerance (accumulation stays f32). `chunk` here
    only sizes the declared shapes: if pack_compressed_panels re-based a
    stream at a finer sub-tile (wide panels), pass the effective sub-tile
    length `2 * e_pad // bases.shape[-1]` instead — the runtime unpack is
    shape-driven either way.
    """
    e_pad = -(-e_loc // chunk) * chunk
    n_chunks = e_pad // chunk
    axes = tuple(mesh.axis_names)

    def local(packed, bases, pv, v_loc, x_loc):
        pr, pc = _unpack_edges(packed[0, 0], bases[0, 0])
        w = _panel_spmm(pc, pr, pv[0, 0], x_loc, mesh=mesh, n_pad=n_pad,
                        b=b)
        return _cgs2_cholqr2(w, v_loc, axes, b=b, nb_v=nb_v,
                             pod_compressed=pod_compressed)

    es, vs = edge_spec(mesh), vector_spec(mesh)
    vstack_spec = P(None, axes, None)
    fn = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(es, es, es, vstack_spec, vs),
        out_specs=(vs, P(None, None), P(None, None)), check_vma=False))
    return fn, n_chunks, e_pad


# ------------------------------------------- kernels-layer bridge (TPU)
def panel_to_blocks(pr, pc, pv, n_rows: int, n_cols: int, *, bm: int,
                    bn: int):
    """Re-tile one packed panel into the block-sparse stream that
    kernels/spmm_tile.py consumes on TPU.

    Returns (blocks, block_cols, block_rows): dense (bm, bn) images of the
    non-empty blocks in block-row-major order (block_rows non-decreasing —
    the revisiting-output contract of spmm_blocksparse).
    """
    pr = np.asarray(pr, np.int64)
    pc = np.asarray(pc, np.int64)
    pv = np.asarray(pv, np.float32)
    live = pv != 0
    pr, pc, pv = pr[live], pc[live], pv[live]
    assert n_rows % bm == 0 and n_cols % bn == 0
    br, bc = pr // bm, pc // bn
    key = br * (n_cols // bn) + bc
    uniq, inv = np.unique(key, return_inverse=True)
    blocks = np.zeros((max(len(uniq), 1), bm, bn), np.float32)
    np.add.at(blocks, (inv, pr % bm, pc % bn), pv)
    block_rows = (uniq // (n_cols // bn)).astype(np.int32)
    block_cols = (uniq % (n_cols // bn)).astype(np.int32)
    if not len(uniq):
        block_rows = np.zeros(1, np.int32)
        block_cols = np.zeros(1, np.int32)
    return blocks, block_cols, block_rows


def panel_spmm_blocksparse(pr, pc, pv, x_panel, n_rows: int, *, bm: int = 8,
                           bn: int = 8, interpret: bool):
    """Panel contraction through the Pallas tile kernel (reference bridge).

    x_panel: (n_cols, k) column working set for this panel. Used by tests
    to pin the panel format to the kernels layer; production TPU panels
    call spmm_blocksparse directly with pre-tiled streams.
    """
    from repro.kernels.spmm_tile import spmm_blocksparse
    n_cols = x_panel.shape[0]
    blocks, bcols, brows = panel_to_blocks(pr, pc, pv, n_rows, n_cols,
                                           bm=bm, bn=bn)
    y = spmm_blocksparse(jnp.asarray(blocks), jnp.asarray(bcols),
                         jnp.asarray(brows), jnp.asarray(x_panel),
                         n_block_rows=n_rows // bm, interpret=interpret)
    # rows in empty block rows are uninitialized by contract — mask them
    # (select, not multiply: uninitialized VMEM can be NaN/Inf on TPU)
    mask = np.zeros(n_rows // bm, bool)
    mask[brows] = True
    return np.where(np.repeat(mask, bm)[:, None], np.asarray(y), 0.0)
