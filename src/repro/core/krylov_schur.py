"""Block Krylov–Schur (thick-restart) eigensolver — the paper's driver.

For symmetric operators the Krylov–Schur method of Stewart [21] reduces to
thick-restart block Lanczos: maintain a Krylov decomposition

    A V = V H + Q S eᵀ_last-block ,   H = Vᵀ A V  (symmetric, m×m)

expand the subspace block-by-block (semi-external SpMM + out-of-core CGS2
reorthogonalization), and at m = b·NB restart by compressing V onto the k
best Ritz vectors (one big out-of-core GEMM, `MultiVector.compress`) with
H collapsing to diag(θ) plus the arrow coupling — which regenerates
automatically because H is recomputed as VᵀAQ each expansion.

I/O discipline (the paper's contribution) is inherited from the substrate:
the subspace lives in the TieredStore, on the device up to its budget and
on the host tier beyond it, the newest block is pinned in the device tier,
MvTransMv/MvTimesMatAddMv stream in groups, and restart compression is the
only whole-subspace write.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.multivector import MultiVector
from repro.core.operator import CAP_FUSED_EXPAND, capabilities
from repro.core.ortho import cholqr, bcgs2
from repro.core.residuals import EigResult, ritz_residual_bounds, sort_ritz
from repro.core.tiered import TieredStore
from repro.kernels import ops as kops


def _expand(op, v: MultiVector, q: jnp.ndarray, h: np.ndarray,
            impl: kops.Impl, *, fused_passes: bool = True
            ) -> tuple[jnp.ndarray, np.ndarray, np.ndarray]:
    """One block expansion. Appends q to V; returns (q_next, new H, R_next).

    Every path produces the identical Krylov invariant A·q = V·h + q_next·r
    with h = h1 + h2 (the bcgs2 convention — the second-pass correction
    belongs in the H column, since W = V·(h1+h2) + Q·R is what actually
    holds; the solver used to hand-inline CGS2 here and drop h2):

      * local: semi-external SpMM then `ortho.bcgs2` over the out-of-core
        subspace — two streamed reads of V when fused_passes (each CGS
        pass is one `SubspacePass` read, §3.4.3), four when not;
      * operator-fused (declares the `fused_expand` capability, e.g. the
        sharded `dist.DistOperator`): one combined SpMM+CGS2/CholQR2 step
        over the operator's device-resident subspace shards — V's blocks
        are *not* re-read from the store at all; the MultiVector is the
        spill/restart copy (the paper's "subspace on SSD, recent matrix
        cached in fast memory" split).
    """
    b = q.shape[1]
    v.append_block(q)
    if CAP_FUSED_EXPAND in capabilities(op):
        q_next, h_col, r_next = op.fused_expand(v, q)
    else:
        w = op.matmat(q)                               # semi-external SpMM
        q_next, h_col, r_next = bcgs2(v, w, impl=impl, fused=fused_passes)

    m_old = h.shape[0]
    m_new = m_old + b
    h_new = np.zeros((m_new, m_new), dtype=np.float64)
    h_new[:m_old, :m_old] = h
    col = np.asarray(h_col, dtype=np.float64)
    h_new[:, m_old:] = col
    h_new[m_old:, :] = col.T                            # enforce symmetry
    return q_next, h_new, np.asarray(r_next, dtype=np.float64)


def eigsh(op, nev: int, *, block_size: int = 4, num_blocks: int | None = None,
          tol: float = 1e-6, max_restarts: int = 60, which: str = "LM",
          store: TieredStore | None = None, impl: kops.Impl = "auto",
          group_size: int = 8, seed: int = 0,
          compute_eigenvectors: bool = True, fused_passes: bool = True,
          callback: Callable | None = None,
          checkpointer=None) -> EigResult:
    """Compute `nev` eigenpairs of a symmetric LinearOperator.

    Defaults follow the paper's parameter study (§4.3): block size b,
    num_blocks NB with subspace m = b·NB; NB defaults to 2·ceil(nev/b)+2.

    The subspace stays on the device up to the store's device budget
    (default: half the device's memory) and spills past it to the
    store's slow tier. Pass `store=TieredStore(backend="safs",
    backend_opts={"root": dir})` to spill into SAFS page files on disk
    (§3.4.1) instead of the default in-RAM emulation, and
    `device_budget_bytes=` to spill earlier — the solver code is
    backend-agnostic.

    fused_passes=True (default) runs every whole-subspace operation
    through the fused streamed-pass engine (§3.4.3): CGS2 reorthogonali-
    zation in 2 subspace reads per expansion instead of 4, restart
    compression in exactly 1 read regardless of k_keep. fused_passes=
    False keeps the unfused reference path (parity tests, I/O benches).

    checkpointer: a `ckpt.solver.SolveCheckpointer` (normally built by
    `core.solver.solve(..., checkpoint=/resume=)`). Snapshots land at
    restart boundaries — right after thick-restart compression, when the
    live state is exactly the compressed subspace plus H = diag(θ), q and
    r_next (the paper's §3.4 observation: restart compression IS the
    checkpoint compression). Resume restores that state bit-identically
    and continues at the next restart index.
    """
    b = block_size
    if num_blocks is None:
        num_blocks = 2 * (-(-nev // b)) + 2
    num_blocks = max(num_blocks, -(-nev // b) + 2)
    m_max = b * num_blocks
    keep_blocks = max(-(-nev // b) + 1, num_blocks // 2)
    k_keep = min(keep_blocks * b, m_max - b)

    store = store or TieredStore()
    n = op.n

    resume = checkpointer.load(store) if checkpointer is not None else None
    if resume is not None:
        # bit-identical continuation from the last committed restart
        # boundary: same subspace blocks, same H/q/r_next, same counters
        v = resume.mvs["v"]
        h = np.asarray(resume.arrays["h"], np.float64)
        q = jnp.asarray(resume.arrays["q"], jnp.float32)
        r_next = np.asarray(resume.arrays["r_next"], np.float64)
        theta_out = np.asarray(resume.arrays["theta_out"], np.float64)
        res_out = np.asarray(resume.arrays["res_out"], np.float64)
        n_ops = int(resume.extra["n_ops"])
        start_restart = resume.step
    else:
        key = jax.random.PRNGKey(seed)
        q, _ = cholqr(jax.random.normal(key, (n, b), jnp.float32),
                      impl=impl)
        v = MultiVector(store, n, group_size=group_size, impl=impl)
        h = np.zeros((0, 0), dtype=np.float64)
        r_next = np.zeros((b, b), dtype=np.float64)
        n_ops = 0
        theta_out = np.zeros(nev)
        res_out = np.full(nev, np.inf)
        start_restart = 0
    converged = False
    restarts = start_restart

    for restarts in range(start_restart, max_restarts):
        while v.ncols + b <= m_max:
            q, h, r_next = _expand(op, v, q, h, impl,
                                   fused_passes=fused_passes)
            n_ops += 1

        # --- restart: Rayleigh-Ritz on H ---------------------------------
        theta, y = np.linalg.eigh(h)
        order = sort_ritz(theta, which)
        theta, y = theta[order], y[:, order]

        # residual bounds via the coupling S = R_next · y[last block rows]
        s = r_next @ y[-b:, :]
        res = np.linalg.norm(s, axis=0)
        scale = np.maximum(1.0, np.abs(theta))
        ok = res <= tol * scale
        theta_out = theta[:nev].copy()
        res_out = res[:nev].copy()
        if callback is not None:
            # fresh copies: theta_out/res_out are returned in EigResult,
            # so a mutating callback must not be able to corrupt them
            callback(restarts, theta_out.copy(), res_out.copy())
        if bool(ok[:nev].all()):
            converged = True
            break

        # --- thick restart: compress V onto k best Ritz vectors ----------
        # fused: all k_keep/b output blocks from ONE streamed read of V
        yk = jnp.asarray(y[:, :k_keep], jnp.float32)
        v_new = v.compress(yk, [b] * (k_keep // b), fused=fused_passes)
        v.delete()
        v = v_new
        h = np.diag(theta[:k_keep])
        # A V_new = V_new Θ + Q S  with S = r_next @ y_keep[last rows]
        # regenerated automatically on next expansion via VᵀAQ.

        if checkpointer is not None:
            # restart boundary = snapshot point (module docstring); may
            # raise SolveSuspended after committing on preemption
            checkpointer.maybe_checkpoint(store, restarts + 1, lambda: {
                "mvs": {"v": v},
                "arrays": {"h": h, "q": np.asarray(q), "r_next": r_next,
                           "theta_out": theta_out, "res_out": res_out},
                "extra": {"n_ops": n_ops}})

    # --- materialize Ritz vectors: one more streamed pass (the same
    # multi-accumulator engine as restart compression — one read of V) ----
    vec = None
    if compute_eigenvectors:
        theta_full, y_full = np.linalg.eigh(h)
        order = sort_ritz(theta_full, which)
        yk = jnp.asarray(y_full[:, order[:nev]], jnp.float32)
        vec = np.asarray(v.mv_times_mat(yk))

    return EigResult(
        eigenvalues=theta_out, eigenvectors=vec, residuals=res_out,
        n_restarts=restarts, n_ops=n_ops, m_subspace=m_max,
        converged=converged,
        io_stats=store.stats.as_dict() if store else None,
        resumed_step=(checkpointer.resumed_step
                      if checkpointer is not None else None),
    )
