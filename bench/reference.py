"""The plain reference the benchmark holds the program to.

Two parts, neither of which imports the program or takes anything it made:

* float64 checks on the host, built from the benchmark's own edge arrays:
  the top eigenvalues by ARPACK, and each returned pair's true residual
  ||Ax - theta x|| / ||x|| and Rayleigh quotient x'Ax / x'x;
* `krylov_schur`, a straightforward block Krylov-Schur (thick-restart
  block Lanczos, CGS2 + CholQR2, Rayleigh-Ritz in float64) over the whole
  subspace in device memory, with every product computed at a stated
  precision. At "highest" it is full float32, as the configuration
  states; at "high" every product is the three-pass bfloat16 split that
  a TPU's `Precision.HIGH` computes. Run at "high" in the program's
  place it is the control that `correct` has to refuse.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as sla

PRECISIONS = ("highest", "high")
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ float64 side
def csr(n: int, rows, cols, vals) -> sp.csr_matrix:
    return sp.csr_matrix((np.asarray(vals, np.float64), (rows, cols)),
                         shape=(n, n))


def top_eigenvalues(a: sp.csr_matrix, nev: int) -> np.ndarray:
    """The `nev` algebraically largest eigenvalues of `a`, descending."""
    n = a.shape[0]
    vals = sla.eigsh(a, k=nev, which="LA", tol=1e-12,
                     ncv=min(n - 1, max(64, 4 * nev)), v0=np.ones(n),
                     return_eigenvectors=False)
    return np.sort(vals)[::-1]


def pair_checks(a: sp.csr_matrix, theta, vecs):
    """Float64 true residual ||Ax - theta x|| / ||x|| and Rayleigh quotient
    of each column of `vecs` (rows past a.shape[0] are padding and must
    be zero). Returns (resid, rq)."""
    x = np.asarray(vecs, np.float64)
    pad, x = x[a.shape[0]:], x[:a.shape[0]]
    theta = np.asarray(theta, np.float64)
    ax = a @ x
    nrm = np.linalg.norm(x, axis=0)
    resid = np.linalg.norm(ax - x * theta[None, :], axis=0) / nrm
    rq = np.einsum("ij,ij->j", x, ax) / nrm ** 2
    # a padded row that is not zero is an answer the matrix cannot give
    resid = resid + np.linalg.norm(pad, axis=0) / nrm
    return resid, rq


# ------------------------------------------------------------- products
def _bf16(x):
    # an explicit rounding op: XLA may drop a bfloat16 round trip of
    # converts as "excess precision", and did on a v5e
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def mul(a, b, precision: str):
    """Elementwise a*b: exact float32, or the three-pass bfloat16 split."""
    if precision == "highest":
        return a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    return ah * bh + (ah * bl + al * bh)


def mm(a, b, precision: str):
    """a @ b: float32 at HIGHEST, or the sum of three bfloat16 products
    (each exact in float32), which is what Precision.HIGH computes."""
    def dot(x, y):
        return jnp.matmul(x, y, precision=_HIGHEST)
    if precision == "highest":
        return dot(a, b)
    ah, al = _split(a)
    bh, bl = _split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


# ------------------------------------------------------------------ SpMM
class EllSpmm:
    """Y = A X from the edge arrays: the first `width` entries of each row
    as a row-padded ELL slab (gathers only), the rest of the row as
    sorted COO through a segment sum."""

    def __init__(self, n: int, rows, cols, vals, *, precision: str,
                 n_pad: int | None = None, width: int | None = None):
        order = np.argsort(rows, kind="stable")
        r, c, v = rows[order], cols[order], vals[order]
        deg = np.bincount(r, minlength=n)
        if width is None:       # the 99th percentile of the degrees
            width = int(np.percentile(deg, 99))
        start = np.concatenate([[0], np.cumsum(deg)[:-1]])
        slot = np.arange(r.size) - start[r]
        ell = slot < width
        self.n = n
        self.n_pad = n_pad or n
        self.precision = precision
        ec = np.zeros((n, width), np.int32)
        ev = np.zeros((n, width), np.float32)
        ec[r[ell], slot[ell]] = c[ell]
        ev[r[ell], slot[ell]] = v[ell]
        self.ell_cols = jnp.asarray(ec)
        self.ell_vals = jnp.asarray(ev)
        self.coo = tuple(jnp.asarray(z) for z in (r[~ell], c[~ell],
                                                  v[~ell]))
        self._apply = jax.jit(self._spmm)

    def _spmm(self, ell_cols, ell_vals, coo, x):
        prec = self.precision
        xs = x[:self.n]

        def slab(acc, cv):
            cj, vj = cv
            return acc + mul(vj[:, None], xs[cj], prec), None
        y, _ = jax.lax.scan(slab, jnp.zeros_like(xs),
                            (ell_cols.T, ell_vals.T))
        r, c, v = coo
        if r.shape[0]:
            y = y + jax.ops.segment_sum(mul(v[:, None], xs[c], prec), r,
                                        num_segments=self.n,
                                        indices_are_sorted=True)
        return jnp.pad(y, ((0, self.n_pad - self.n), (0, 0)))

    def __call__(self, x):
        return self._apply(self.ell_cols, self.ell_vals, self.coo, x)


# -------------------------------------------------------- Krylov-Schur
@dataclasses.dataclass
class RefResult:
    eigenvalues: np.ndarray
    eigenvectors: np.ndarray
    residuals: np.ndarray
    n_restarts: int
    n_ops: int
    converged: bool


def _cholqr2(w, precision):
    """Q, R with W = Q R, two CholQR passes, Cholesky in float64."""
    r_tot = np.eye(w.shape[1])
    for _ in range(2):
        g = np.asarray(mm(w.T, w, precision), np.float64)
        r = np.linalg.cholesky(g + 1e-300 * np.eye(g.shape[0])).T
        w = mm(w, jnp.asarray(np.linalg.inv(r), jnp.float32), precision)
        r_tot = r @ r_tot
    return w, r_tot


def krylov_schur(spmm, n: int, *, nev: int, block_size: int,
                 num_blocks: int, tol: float, max_restarts: int,
                 which: str = "LA", seed: int = 0,
                 precision: str = "highest") -> RefResult:
    """Block Krylov-Schur for the `nev` largest (LA) or largest-magnitude
    (LM) eigenpairs, with the program's restart shape: subspace
    m = b NB, thick restart onto max(ceil(nev/b)+1, NB/2) blocks."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    b = block_size
    m_max = b * num_blocks
    k_keep = min(max(-(-nev // b) + 1, num_blocks // 2) * b, m_max - b)
    basis = jnp.zeros((n, m_max), jnp.float32)

    @jax.jit
    def cgs2(basis, w):
        h1 = mm(basis.T, w, precision)
        w = w - mm(basis, h1, precision)
        h2 = mm(basis.T, w, precision)
        return w - mm(basis, h2, precision), h1 + h2

    q, _ = _cholqr2(jax.random.normal(jax.random.PRNGKey(seed), (n, b),
                                      jnp.float32), precision)
    h = np.zeros((m_max, m_max))
    k, n_ops, converged = 0, 0, False
    restart = 0
    for restart in range(max_restarts):
        while k + b <= m_max:
            basis = basis.at[:, k:k + b].set(q)
            w = spmm(q)
            n_ops += 1
            w, hcol = cgs2(basis, w)
            hcol = np.asarray(hcol, np.float64)[:k + b]
            q, r_next = _cholqr2(w, precision)
            h[:k + b, k:k + b] = hcol
            h[k:k + b, :k + b] = hcol.T
            k += b
        theta, y = np.linalg.eigh(h[:k, :k])
        order = (np.argsort(-theta) if which == "LA"
                 else np.argsort(-np.abs(theta)))
        theta, y = theta[order], y[:, order]
        res = np.linalg.norm(r_next @ y[-b:, :], axis=0)
        theta_out, res_out, y_out = theta[:nev], res[:nev], y[:, :nev]
        if np.all(res[:nev] <= tol * np.maximum(1.0, np.abs(theta[:nev]))):
            converged = True
            break
        keep = jnp.asarray(y[:, :k_keep], jnp.float32)
        basis = jnp.zeros_like(basis).at[:, :k_keep].set(
            mm(basis[:, :k], keep, precision))
        h = np.zeros_like(h)
        h[np.arange(k_keep), np.arange(k_keep)] = theta[:k_keep]
        k = k_keep
        y_out = np.eye(k, nev)
    vecs = mm(basis[:, :k], jnp.asarray(y_out, jnp.float32), precision)
    return RefResult(theta_out, np.asarray(vecs), res_out, restart, n_ops,
                     converged)
