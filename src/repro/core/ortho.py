"""Block (re)orthogonalization — step (1) of Algorithm 1.

The paper identifies reorthogonalization (MvTransMv + MvTimesMatAddMv) as
the dominant cost when computing many eigenvalues (>90% of SEM runtime).
We provide the TPU-native primitives:

  * cholqr  — CholeskyQR2: Gram → Cholesky → triangular solve, twice.
              This is THE tall-skinny QR for TPUs (two MXU GEMMs + a tiny
              b×b factorization, all one compiled program) replacing
              Householder QR.
  * svqb    — Stathopoulos–Wu SVQB, rank-revealing fallback when the block
              is numerically rank deficient.
  * bcgs2   — block Gram–Schmidt (×2) of a new block against an
              out-of-core MultiVector basis. fused=True (default) runs
              each pass as ONE streamed subspace read
              (`MultiVector.project_out`: h_i = V_iᵀw and w ← w − V_i h_i
              in the same block visit), so CGS2 costs 2 reads of the
              on-SSD subspace; fused=False keeps the textbook
              MvTransMv + MvTimesMatAddMv pair per pass (4 reads) — the
              paper's unfused I/O pattern, retained for parity testing
              and the bench_subspace_io before/after column (§3.4.3:
              minimizing passes over the subspace is the whole game).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.multivector import MultiVector
from repro.kernels import ops as kops
from repro.obs import trace

# a TPU rounds an f32 matmul's operands to bf16 by default (2e-3 relative
# error on a v5e); the orthogonalization needs full f32 products
HIGHEST = jax.lax.Precision.HIGHEST


def _robust_cholesky(g: jnp.ndarray) -> jnp.ndarray:
    """Shifted Cholesky with escalating shifts (rank-deficient guards):
    computes candidates at increasing regularization and keeps the first
    NaN-free one — branch-free, so it stays jittable."""
    eye = jnp.eye(g.shape[0], dtype=g.dtype)
    tr = jnp.trace(g) / g.shape[0] + 1e-30
    l = jnp.linalg.cholesky(g + 1e-7 * tr * eye)
    for shift in (1e-4, 1e-1):
        cand = jnp.linalg.cholesky(g + shift * tr * eye)
        bad = jnp.any(jnp.isnan(l))
        l = jnp.where(bad, cand, l)
    return l


@functools.partial(jax.jit, static_argnames=("impl", "iters"))
def cholqr(x: jnp.ndarray, *, impl: kops.Impl = "auto", iters: int = 2
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """CholeskyQR² — returns (Q, R) with Q orthonormal, X = Q R.

    Shifted-Cholesky guards ill-conditioning: G + eps*tr(G)*I, with
    escalating shifts on (near-)rank-deficient blocks. One compiled
    program (one host dispatch) for every iteration.
    """
    r_total = jnp.eye(x.shape[1], dtype=jnp.float32)
    q = x
    for _ in range(iters):
        g = kops.gram(q, q, impl=impl)
        l = _robust_cholesky(g)
        r = l.T
        q = jax.scipy.linalg.solve_triangular(l, q.T, lower=True).T
        r_total = jnp.matmul(r, r_total, precision=HIGHEST)
    return q, r_total


def svqb_transform(x: jnp.ndarray, *, impl: kops.Impl = "auto",
                   tol: float = 1e-10) -> Tuple[jnp.ndarray, int]:
    """The SVQB basis transform T (b×b) with Q = X @ T orthonormal on the
    numerical range of X; returns (T, numerical_rank). Rank-deficient
    directions map to zero columns of Q.

    Exposed separately from `svqb` so callers can co-apply the SAME
    transform to a parallel image of the block: LOBPCG maintains AS
    algebraically (AX ← AX·T whenever X ← X·T), which keeps the A-images
    exact without any extra operator applies."""
    g = kops.gram(x, x, impl=impl)
    d = jnp.sqrt(jnp.clip(jnp.diag(g), 1e-30, None))
    dinv = 1.0 / d
    gs = g * dinv[:, None] * dinv[None, :]
    w, v = jnp.linalg.eigh(gs)
    keep = w > tol * jnp.max(w)
    winv = jnp.where(keep, 1.0 / jnp.sqrt(jnp.clip(w, 1e-30, None)), 0.0)
    t = (dinv[:, None] * v) * winv[None, :]
    return t, int(jnp.sum(keep))


def svqb(x: jnp.ndarray, *, impl: kops.Impl = "auto", tol: float = 1e-10
         ) -> Tuple[jnp.ndarray, int]:
    """SVQB orthonormalization; returns (Q, numerical_rank). Rank-deficient
    directions are replaced by zero columns (caller refreshes them)."""
    t, rank = svqb_transform(x, impl=impl, tol=tol)
    return kops.tsgemm(x, t, impl=impl), rank


def bcgs2(basis: MultiVector, w: jnp.ndarray, *, impl: kops.Impl = "auto",
          fused: bool = True
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Orthogonalize block W against the out-of-core basis V, twice, then
    orthonormalize within the block (CholQR).

    Returns (Q, H, R):  W = V @ H + Q @ R,  VᵀQ = 0,  QᵀQ = I.
    H is (m, b) — the projection coefficients (Krylov H entries). This is
    the ONE convention: H = h1 + h2 including the second-pass correction,
    so the Krylov invariant holds with the returned H exactly.

    I/O per pass: fused=True streams the basis once (`project_out` — the
    Gram and the AXPY update share the block visit; block-MGS order, so
    W = V·h + w stays exact by telescoping); fused=False streams it twice
    (MvTransMv then MvTimesMatAddMv — classical CGS order). Both yield
    the same Q/H/R to rounding; CGS2's second pass wipes the O(eps·κ)
    first-pass difference either way.
    """
    with trace.span("ortho.bcgs2", blocks=basis.nblocks):
        if basis.nblocks == 0:
            q, r = cholqr(w, impl=impl)
            h = jnp.zeros((0, w.shape[1]), jnp.float32)
            return q, h, r
        if fused:
            h1, w = basis.project_out(w)          # one streamed read
            h2, w = basis.project_out(w)          # second pass (CGS2)
        else:
            h1 = basis.mv_trans_mv(w)             # VᵀW
            w = w - basis.mv_times_mat(h1)        # W -= V (VᵀW)
            h2 = basis.mv_trans_mv(w)
            w = w - basis.mv_times_mat(h2)
        q, r = cholqr(w, impl=impl)
        return q, h1 + h2, r


def ortho_error(q: jnp.ndarray) -> float:
    """‖QᵀQ − I‖_max — test invariant."""
    g = q.T @ q
    return float(jnp.max(jnp.abs(g - jnp.eye(g.shape[0], dtype=g.dtype))))
