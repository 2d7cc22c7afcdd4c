"""DistOperator — the sharded SEM-SpMM step driven by the core restart loop.

This is the end-to-end seam of the paper (§3 + §4): `core.eigsh` owns the
Krylov–Schur restart logic and the out-of-core subspace bookkeeping, while
the actual numerical work of one expansion — SpMM over the edge panels,
CGS2 block orthogonalization against V, CholQR2 — runs as ONE fused
`shard_map`ped program on the device mesh (`dspmm.build_eigen_step`).

The split of residencies mirrors the paper exactly:

  * the *edge panels* are packed once at construction
    (`pack_edge_panels`, optionally also the 6-byte/edge compressed stream
    via `pack_compressed_panels`) and live device-sharded, one (1,1,e_loc)
    panel per device — the streamed-from-SSD operand of §3.3;
  * the *subspace history* V is held device-sharded as a (nb_v, n_pad, b)
    stack (`vector_spec` rows over every device) and is consumed in place
    by the fused step — the paper's "recent matrix cached in fast memory";
  * the core loop's `MultiVector` remains the system of record: every
    appended block is also written to the TieredStore (spillable to the
    SAFS page files), and restart compression / eigenvector
    materialization stream it back — "subspace on SSD".

`eigsh` discovers the fused path through the declared `fused_expand`
capability (`core.operator.capabilities`; the legacy
`supports_fused_expand` attribute is kept for external callers) and calls
`fused_expand(v, q)` instead of separate
matmat/mv_trans_mv/mv_times_mat/cholqr calls; the device shard cache is
reconciled against `MultiVector.block_names()`, so restarts (which replace
every block) and fresh solves rebuild it transparently.

Options measured by `benchmarks/bench_dist_e2e.py`:

  * `pod_compressed=True` — int8-compressed cross-pod reductions inside
    CGS2/CholQR2 (`compress.compressed_psum_pod`); the bench records the
    per-restart eigenvalue deviation so error accumulation over full
    restart cycles is a number, not a guess;
  * `compressed=True` — the 6-byte/edge delta-encoded panel stream with
    bfloat16 values/operands (accumulation stays f32).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.dist import layout
from repro.dist.dspmm import (CHUNK, _groups, build_dspmm, build_eigen_step,
                              build_eigen_step_compressed, edge_spec,
                              pack_compressed_panels, pack_edge_panels,
                              vector_spec)
from repro.obs import trace


def default_mesh(devices=None) -> jax.sharding.Mesh:
    """A (pod, data, model) mesh over the available devices: pod stays 1,
    model takes a factor of 2 when the device count is even. Explicit
    meshes (e.g. (2,2,2) in the forced-host tests) take precedence."""
    devices = list(jax.devices() if devices is None else devices)
    nd = len(devices)
    model = 2 if nd % 2 == 0 and nd > 1 else 1
    return jax.make_mesh((1, nd // model, model), ("pod", "data", "model"),
                         devices=devices)


class DistOperator:
    """LinearOperator over the shard_mapped panel SpMM, with the fused
    SpMM+CGS2/CholQR2 expansion hook that `core.eigsh` dispatches to.

    Vertices are permuted (`layout.vertex_permutation`) and padded
    (`layout.padded_n`); the operator works in *position* space of size
    `self.n = n_pad`. `nat_to_pad` / `pad_to_nat` map natural-vertex
    vectors in and out (padding rows are zero rows of A, contributing
    eigenvalue 0 — harmless for the paper's "LM"/"LA" workloads).
    """

    # legacy attribute kept for external callers; solvers dispatch on the
    # declared capability set below (core.operator.capabilities)
    supports_fused_expand = True

    def capabilities(self) -> frozenset:
        from repro.core.operator import CAP_FUSED_EXPAND
        return frozenset({CAP_FUSED_EXPAND})

    def __init__(self, n: int, rows, cols, vals, *, mesh=None,
                 compressed: bool = False, pod_compressed: bool = False,
                 chunk: int = CHUNK):
        mesh = mesh if mesh is not None else default_mesh()
        # Auto axes: the next block comes back on one device and is
        # re-sharded by device_put, which an Explicit-axis mesh (the
        # jax.make_mesh default) refuses for a committed array
        self.mesh = Mesh(mesh.devices, mesh.axis_names,
                         axis_types=(AxisType.Auto,) * mesh.devices.ndim)
        r_groups, m_groups = _groups(self.mesh)
        self.n_logical = int(n)
        self.n = layout.padded_n(n, r_groups, m_groups)
        self.perm = layout.vertex_permutation(self.n, r_groups, m_groups)
        self.compressed = bool(compressed)
        self.pod_compressed = bool(pod_compressed)

        rows = np.asarray(rows)
        cols = np.asarray(cols)
        pc, pr, pv, self.e_loc = pack_edge_panels(
            self.n, self.perm[rows], self.perm[cols], vals,
            r_groups=r_groups, m_groups=m_groups)
        edge_sh = NamedSharding(self.mesh, edge_spec(self.mesh))
        # uncompressed panels always live: matmat (residual checks, the
        # non-fused fallback) contracts them even when the fused step
        # streams the compressed format
        self._pc = jax.device_put(jnp.asarray(pc), edge_sh)
        self._pr = jax.device_put(jnp.asarray(pr), edge_sh)
        self._pv = jax.device_put(jnp.asarray(pv), edge_sh)
        self._packed = self._bases = self._vbf16 = None
        if self.compressed:
            packed, bases, vbf16 = pack_compressed_panels(pc, pr, pv,
                                                          chunk=chunk)
            self._packed = jax.device_put(jnp.asarray(packed), edge_sh)
            self._bases = jax.device_put(jnp.asarray(bases), edge_sh)
            self._vbf16 = jax.device_put(jnp.asarray(vbf16), edge_sh)
        self._vec_sh = NamedSharding(self.mesh, vector_spec(self.mesh))
        self._home = self.mesh.devices.flat[0]
        self._vstack_sh = NamedSharding(
            self.mesh, P(None, tuple(self.mesh.axis_names), None))
        self._spmm: Dict[int, object] = {}       # b -> jitted SpMM
        self._steps: Dict[tuple, object] = {}    # (nb_v, b) -> jitted step
        self._names: List[str] = []              # mirrored block names
        # (nb_v, n_pad, b) device-sharded subspace stack, in the dtype the
        # fused step consumes: f32, or bf16 for the compressed stream —
        # holding an f32 master alongside would triple the device bytes
        # the compressed mode exists to save
        self._vstack: Optional[jnp.ndarray] = None
        self.n_fused_steps = 0
        # per-compiled-program collective wire bytes (trace attribution;
        # computed lazily and only while tracing — lowering costs a
        # compile)
        self._coll_bytes: Dict[tuple, Optional[dict]] = {}

    # ------------------------------------------------------- vertex maps
    def nat_to_pad(self, x: np.ndarray) -> np.ndarray:
        """Scatter natural-vertex rows into permuted padded positions."""
        out = np.zeros((self.n,) + x.shape[1:], np.float32)
        out[self.perm[:self.n_logical]] = x
        return out

    def pad_to_nat(self, x) -> np.ndarray:
        """Gather natural-vertex rows out of a padded position vector."""
        return np.asarray(x)[self.perm[:self.n_logical]]

    # -------------------------------------------------- trace attribution
    def _collectives(self, key: tuple, fn, args) -> Optional[dict]:
        """Per-device collective wire bytes of one compiled program
        (`utils.hlo_analysis.collective_bytes` over the optimized HLO),
        cached per (kind, nb_v, b) key. Only consulted while tracing; any
        lowering/compile failure degrades to None, never to a solve
        error."""
        if key in self._coll_bytes:
            return self._coll_bytes[key]
        try:
            from repro.utils.hlo_analysis import collective_bytes
            txt = fn.lower(*args).compile().as_text()
            out = collective_bytes(txt, int(self.mesh.devices.size))
        except Exception:
            out = None
        self._coll_bytes[key] = out
        return out

    # ----------------------------------------------------------- matmat
    def matmat(self, x: jnp.ndarray) -> jnp.ndarray:
        b = int(x.shape[1])
        fn = self._spmm.get(b)
        if fn is None:
            fn = self._spmm[b] = build_dspmm(self.mesh, n_pad=self.n,
                                             e_loc=self.e_loc, b=b)
        with trace.span("operator.matmat", op="DistOperator", k=b,
                        n=self.n) as sp:
            args = (self._pc, self._pr, self._pv,
                    jnp.asarray(x, jnp.float32))
            if trace.active() is not None:
                coll = self._collectives(("spmm", b), fn, args)
                if coll is not None:
                    sp.set(collective_bytes=coll.get("total", 0.0))
            return fn(*args)

    # ------------------------------------------------------- fused step
    def _step(self, nb_v: int, b: int):
        key = (nb_v, b)
        fn = self._steps.get(key)
        if fn is None:
            if self.compressed:
                fn, _, _ = build_eigen_step_compressed(
                    self.mesh, n_pad=self.n, e_loc=self.e_loc, b=b,
                    nb_v=nb_v, pod_compressed=self.pod_compressed)
            else:
                fn = build_eigen_step(self.mesh, n_pad=self.n,
                                      e_loc=self.e_loc, b=b, nb_v=nb_v,
                                      pod_compressed=self.pod_compressed)
            self._steps[key] = fn
        return fn

    def _sync_vstack(self, v, q: jnp.ndarray) -> None:
        """Reconcile the device-sharded subspace stack with the
        MultiVector's blocks. Common case (one append) extends the stack
        with q's shard; any other change (restart compression replaced
        every block, a fresh solve) rebuilds from the store — the only
        point where subspace bytes cross from the SSD tier back to the
        device mesh."""
        names = v.block_names()
        dt = jnp.bfloat16 if self.compressed else jnp.float32
        qs = jax.device_put(jnp.asarray(q, jnp.float32),
                            self._vec_sh).astype(dt)
        if (self._vstack is not None and len(names) >= 1
                and self._names == names[:-1]):
            stack = jnp.concatenate([self._vstack, qs[None]], axis=0)
        else:
            blocks = [jax.device_put(jnp.asarray(v.block(i), jnp.float32),
                                     self._vec_sh).astype(dt)
                      for i in range(v.nblocks - 1)] + [qs]
            stack = jnp.stack(blocks, axis=0)
        self._vstack = jax.device_put(stack, self._vstack_sh)
        self._names = names

    def fused_expand(self, v, q: jnp.ndarray):
        """One combined SpMM + CGS2 + CholQR2 expansion (q already appended
        to v by the caller). Returns (q_next, h_col, r_next) with the exact
        invariant A·q = V·h_col + q_next·r_next, V including q."""
        b = int(q.shape[1])
        with trace.span("operator.fused_expand", op="DistOperator",
                        k=b) as sp:
            self._sync_vstack(v, q)
            nb_v = self._vstack.shape[0]
            step = self._step(nb_v, b)
            panels = ((self._packed, self._bases, self._vbf16)
                      if self.compressed else (self._pc, self._pr, self._pv))
            args = panels + (self._vstack, self._vstack[-1])
            sp.set(nb_v=nb_v)
            if trace.active() is not None:
                coll = self._collectives(("step", nb_v, b), step, args)
                if coll is not None:
                    sp.set(collective_bytes=coll.get("total", 0.0))
            q_next, h, r = step(*args)
            self.n_fused_steps += 1
            # the core loop's MultiVector algebra (restart compression,
            # Ritz vectors) runs the single-device kernels, and a Pallas
            # kernel cannot be partitioned over the mesh: hand the next
            # block back on one device (`_sync_vstack` re-shards it)
            return jax.device_put(q_next, self._home), h, r

    def reset_subspace(self) -> None:
        """Drop the mirrored device shards (before reusing the operator
        for an unrelated solve)."""
        self._names = []
        self._vstack = None


def e2e_mesh() -> jax.sharding.Mesh:
    """Mesh for the end-to-end drivers (example + bench share it so the
    two cannot drift): a multi-pod (2, d, 2) layout when the device count
    allows one — exercising the pod axis the compressed reductions target
    — else whatever `default_mesh` can build (down to 1 device)."""
    nd = len(jax.devices())
    if nd % 4 == 0 and nd >= 4:
        return jax.make_mesh((2, nd // 4, 2), ("pod", "data", "model"))
    return default_mesh()


def pod_compressed_deviation(n: int, rows, cols, vals, w_reference, *,
                             mesh, nev: int, block_size: int,
                             max_restarts: int = 3, tol: float = 1e-9,
                             impl: str = "ref") -> list:
    """Per-restart eigenvalue deviation of the `pod_compressed=True` solve
    against a reference spectrum — the ROADMAP's "measure error
    accumulation over full Krylov iterations" number, shared by the bench,
    the e2e example and the parity tests so the methodology cannot drift.

    Deviation is compared by |λ|: "LM" keeps the top magnitudes, and a
    power-law graph's near-±pairs make the smallest kept magnitude's sign
    an arbitrary tie — a signed comparison would report the tie, not the
    compression error. `tol` defaults far below the int8 reduction floor
    so exactly `max_restarts` full cycles are measured.
    """
    from repro.core.krylov_schur import eigsh
    w_abs = np.sort(np.abs(np.asarray(w_reference)))
    devs: list = []

    def cb(k, theta, res):
        devs.append(float(np.abs(np.sort(np.abs(theta)) - w_abs).max()))

    dop = DistOperator(n, rows, cols, vals, mesh=mesh, pod_compressed=True)
    eigsh(dop, nev, block_size=block_size, tol=tol,
          max_restarts=max_restarts, impl=impl, callback=cb)
    return devs
