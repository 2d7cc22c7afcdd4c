"""The pluggable solver family — one protocol over the streamed substrate.

The paper frames FlashEigen as an Anasazi-framework extension (§2):
Krylov–Schur, Block Davidson and LOBPCG are interchangeable *solver
managers* over the same MultiVector/SpMM traits. This module is that seam
for the repo: every eigensolver registers as a `Solver` implementation and
drivers call

    solve(op, nev, method="krylov_schur" | "lanczos" | "lobpcg" | "svd")

instead of hard-coding one algorithm. All implementations share the same
substrate contract through `SolverContext`:

  operator    any `LinearOperator` (GraphOperator, DistOperator, HvpOperator,
              a spectral transform, ...) — consulted for declared
              capabilities (`core.operator.capabilities`), never sniffed;
  store       the `TieredStore` holding every out-of-core block the method
              allocates, so `EigResult.io_stats` is comparable across
              methods (bytes-per-converged-pair is the paper's real
              question — `benchmarks/bench_eigen.py` measures it);
  ortho       the orthogonalization policy ("fused" streams each CGS /
              gram / update step as one multi-consumer `SubspacePass`;
              "unfused" keeps the single-consumer reference passes);
  which/tol/max_iters and the convergence state they imply;
  callback    per-restart (or per-iteration) telemetry
              `callback(step, theta[:nev], res[:nev])` for convergence
              traces without re-running.

Spectral transforms compose at this layer: when the operator declares
`CAP_SPECTRAL_TRANSFORM` (ShiftInvertOperator, ChebyshevFilterOperator),
`solve` runs the chosen method on the transform — `which` then selects in
the *transformed* spectrum, "LM" being the natural choice since both
transforms map wanted eigenvalues to dominant ones — and afterwards maps
the Ritz values back through `op.untransform` and replaces the cheap
residual bounds with true residuals measured against the *inner* operator,
so the returned `EigResult` always describes eigenpairs of A itself.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Protocol, Union

import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.progress import ConvergenceTracker
from repro.core.krylov_schur import eigsh
from repro.core.lanczos import lanczos_eigsh
from repro.core.lobpcg import lobpcg
from repro.core.operator import CAP_SPECTRAL_TRANSFORM, capabilities
from repro.core.residuals import EigResult
from repro.core.svd import svds
from repro.core.tiered import TieredStore
from repro.kernels import ops as kops


@dataclasses.dataclass
class SolverContext:
    """Everything a solver implementation receives: the operator, the
    shared block substrate, the ortho policy, the convergence targets and
    the telemetry hook. One context = one solve."""
    op: object
    nev: int
    which: str
    tol: float
    max_iters: int
    store: TieredStore
    block_size: Optional[int] = None
    ortho: str = "fused"                  # "fused" | "unfused" pass policy
    impl: kops.Impl = "auto"
    seed: int = 0
    compute_eigenvectors: bool = True
    callback: Optional[Callable] = None
    checkpoint: Optional[object] = None   # ckpt.solver.CheckpointPolicy
    resume: Optional[str] = None          # checkpoint root to resume from
    options: Dict = dataclasses.field(default_factory=dict)
    # method-specific extras (num_blocks, precond, at_op, ...)

    @property
    def fused_passes(self) -> bool:
        return self.ortho == "fused"


class Solver(Protocol):
    """A solver implementation: a name for the registry plus a solve
    entrypoint. Implementations are thin adapters over the algorithm
    modules — the algorithms stay importable and testable on their own."""
    name: str

    def solve(self, ctx: SolverContext) -> EigResult:
        ...


def _make_checkpointer(ctx: SolverContext, method: str, *, block_size):
    """Build the checkpoint/resume bridge for the methods that support it
    (None when the context asks for neither). The solve-shape params are
    recorded in every snapshot and verified on resume, so a checkpoint
    can never silently continue a *different* solve."""
    if ctx.checkpoint is None and ctx.resume is None:
        return None
    from repro.ckpt.solver import SolveCheckpointer
    return SolveCheckpointer(
        ctx.checkpoint, method=method,
        resume_from=(os.fspath(ctx.resume) if ctx.resume else None),
        params={"nev": ctx.nev, "which": ctx.which,
                "block_size": block_size})


class _KrylovSchur:
    name = "krylov_schur"
    default_which = "LM"

    def solve(self, ctx: SolverContext) -> EigResult:
        b = ctx.block_size or 4
        return eigsh(
            ctx.op, ctx.nev, block_size=b,
            num_blocks=ctx.options.get("num_blocks"),
            tol=ctx.tol, max_restarts=ctx.max_iters, which=ctx.which,
            store=ctx.store, impl=ctx.impl, seed=ctx.seed,
            group_size=ctx.options.get("group_size", 8),
            compute_eigenvectors=ctx.compute_eigenvectors,
            fused_passes=ctx.fused_passes, callback=ctx.callback,
            checkpointer=_make_checkpointer(ctx, self.name, block_size=b))


class _Lanczos:
    name = "lanczos"
    default_which = "LM"

    def solve(self, ctx: SolverContext) -> EigResult:
        return lanczos_eigsh(
            ctx.op, ctx.nev, block_size=ctx.block_size or 4,
            num_blocks=ctx.options.get("num_blocks"), which=ctx.which,
            store=ctx.store, impl=ctx.impl, seed=ctx.seed,
            group_size=ctx.options.get("group_size", 8),
            compute_eigenvectors=ctx.compute_eigenvectors,
            fused_passes=ctx.fused_passes, callback=ctx.callback)


class _Lobpcg:
    name = "lobpcg"
    default_which = "LA"

    def solve(self, ctx: SolverContext) -> EigResult:
        return lobpcg(
            ctx.op, ctx.nev, block_size=ctx.block_size,
            tol=ctx.tol, max_iters=ctx.max_iters, which=ctx.which,
            precond=ctx.options.get("precond"), store=ctx.store,
            seed=ctx.seed, impl=ctx.impl, fused_passes=ctx.fused_passes,
            group_size=ctx.options.get("group_size", 8),
            callback=ctx.callback,
            checkpointer=_make_checkpointer(
                ctx, self.name, block_size=ctx.block_size or ctx.nev))


class _Svd:
    """`svd.svds` behind the family dispatch: eigensolve of AᵀA via the
    Krylov–Schur manager, σ = √λ. Requires `at_op` (the Aᵀ operator) in
    ctx.options; the returned EigResult carries σ as `eigenvalues` and U
    as `eigenvectors` (use `svd.svds` directly for the full triplet)."""
    name = "svd"
    default_which = "LA"

    def solve(self, ctx: SolverContext) -> EigResult:
        at_op = ctx.options.get("at_op")
        if at_op is None:
            raise ValueError("method='svd' needs options={'at_op': <Aᵀ op>}")
        r = svds(ctx.op, at_op, ctx.nev, block_size=ctx.block_size or 2,
                 num_blocks=ctx.options.get("num_blocks"), tol=ctx.tol,
                 max_restarts=ctx.max_iters, store=ctx.store, impl=ctx.impl,
                 seed=ctx.seed, compute_vectors=ctx.compute_eigenvectors,
                 callback=ctx.callback)
        return EigResult(
            eigenvalues=r.s, eigenvectors=r.u,
            residuals=np.zeros_like(r.s), n_restarts=r.n_restarts,
            n_ops=r.n_ops, m_subspace=0, converged=r.converged,
            io_stats=r.io_stats)


_REGISTRY: Dict[str, Solver] = {}


def register_solver(solver: Solver) -> None:
    """Add (or replace) a family member. Exposed so experiments can
    register e.g. a Block-Davidson prototype without touching core."""
    _REGISTRY[solver.name] = solver


def solver_names() -> list:
    return sorted(_REGISTRY)


for _s in (_KrylovSchur(), _Lanczos(), _Lobpcg(), _Svd()):
    register_solver(_s)


def _untransform(op, res: EigResult) -> EigResult:
    """Map an EigResult computed on a spectral transform back to the inner
    operator: eigenvalues via `op.untransform` (Rayleigh quotients on the
    inner operator when vectors were materialized), residuals re-measured
    against the inner operator (the solver's cheap bounds were residuals
    of f(A), which say nothing quantitative about A)."""
    vecs = res.eigenvectors
    lam = op.untransform(res.eigenvalues,
                         None if vecs is None else jnp.asarray(vecs))
    if vecs is None:
        return dataclasses.replace(res, eigenvalues=lam)
    x = jnp.asarray(vecs, jnp.float32)
    ax = op.inner.matmat(x)
    th = jnp.asarray(lam, jnp.float32)
    resid = np.asarray(jnp.linalg.norm(ax - x * th[None, :], axis=0),
                       np.float64)
    return dataclasses.replace(res, eigenvalues=lam, residuals=resid)


def solve(op, nev: int, *, method: str = "krylov_schur",
          which: str | None = None, tol: float = 1e-6,
          max_iters: int = 60, block_size: int | None = None,
          store: TieredStore | None = None, ortho: str = "fused",
          impl: kops.Impl = "auto", seed: int = 0,
          compute_eigenvectors: bool = True,
          callback: Callable | None = None,
          trace: Union[obs_trace.Tracer, str, os.PathLike, None] = None,
          checkpoint=None, resume: Union[str, os.PathLike, None] = None,
          **options) -> EigResult:
    """Solve for `nev` eigenpairs of `op` with the chosen family member.

    method: one of `solver_names()` — "krylov_schur" (the paper's driver),
    "lanczos" (HEIGEN-style no-restart baseline), "lobpcg" (3·b working
    set, out-of-core [X, W, P]), "svd" (AᵀA Gram path; needs
    options={'at_op': ...}).

    which defaults per method ("LM" for the Krylov solvers, "LA" for
    LOBPCG/svd). When `op` declares CAP_SPECTRAL_TRANSFORM, `which`
    selects in the transformed spectrum (default "LM": both transforms
    map the wanted part of the spectrum to dominant eigenvalues) and the
    result is mapped back to eigenpairs of the inner operator — so e.g.

        solve(ShiftInvertOperator(a_op, sigma), nev, method="lobpcg")

    returns the `nev` eigenvalues of A nearest sigma, ordered by
    proximity, with true A-residuals.

    store: the `TieredStore` for the method's out-of-core blocks. Default:
    a fresh in-RAM store whose device budget is half the device's memory,
    so the subspace stays on the device while it fits and spills to the
    store's slow tier past that; give `TieredStore(device_budget_bytes=
    ..., backend="safs", ...)` to spill earlier, into SAFS page files.

    trace: pass an `obs.Tracer` (or a path — a fresh Tracer is created and
    its JSONL timeline written there on completion) to record the whole
    solve: a root "solve" span, every instrumented substrate span
    (operator applies, streamed passes, SAFS fill/evict/retire/
    prefetch-wait), per-step "convergence.step" events with an ETA
    estimate, and a "solve.io" metrics record with before/after/delta
    I/O-counter snapshots. The solver implementations are untouched —
    everything rides the module-level tracer + the `callback` seam. The
    Tracer is attached to the result as `EigResult.trace`; feed its JSONL
    to `python -m repro.obs.report` for the human/CI report. The "solve"
    span opens with or without a Tracer, so a `jax.profiler` trace of the
    solve holds it and every substrate span beneath it, on the device
    trace's clock (`obs/README.md`).

    checkpoint: a `ckpt.solver.CheckpointPolicy(root, every_restarts=N,
    guard=...)` — the solve snapshots its full state at restart (eigsh) /
    iteration (lobpcg) boundaries into `root` and, when the policy's
    `ft.PreemptionGuard` fires mid-solve, finishes the in-flight restart,
    checkpoints, and raises `ckpt.solver.SolveSuspended` (exit-resumable
    SIGTERM handling). resume: a checkpoint root to continue from — the
    solve restores the newest committed snapshot bit-identically and
    walks on; pass both to keep checkpointing after a resume. Supported
    by the out-of-core iterative methods ("krylov_schur", "lobpcg").

    All remaining keyword arguments land in `SolverContext.options`
    (num_blocks, group_size, precond, at_op, ...).
    """
    if method not in _REGISTRY:
        raise ValueError(f"unknown method {method!r}; "
                         f"registered: {solver_names()}")
    if (checkpoint is not None or resume is not None) and method not in (
            "krylov_schur", "lobpcg"):
        raise ValueError(
            f"checkpoint/resume is supported for methods "
            f"'krylov_schur' and 'lobpcg', not {method!r}")
    solver = _REGISTRY[method]
    is_transform = CAP_SPECTRAL_TRANSFORM in capabilities(op)
    if which is None:
        which = "LM" if is_transform else getattr(solver, "default_which",
                                                  "LM")
    if is_transform and method == "lobpcg" and which == "LM":
        # LOBPCG optimizes an algebraic extreme; for the transforms LM ≈ LA
        # (shift-invert near a dominant σ-neighborhood, Chebyshev filters
        # are ≥ 1 on the wanted set) — take the algebraic top.
        which = "LA"

    trace_path = None
    tracer = None
    if trace is not None:
        if isinstance(trace, obs_trace.Tracer):
            tracer = trace
        else:
            trace_path = os.fspath(trace)
            tracer = obs_trace.Tracer()

    ctx = SolverContext(
        op=op, nev=nev, which=which, tol=tol, max_iters=max_iters,
        store=store or TieredStore(), block_size=block_size, ortho=ortho,
        impl=impl, seed=seed, compute_eigenvectors=compute_eigenvectors,
        callback=callback, checkpoint=checkpoint,
        resume=os.fspath(resume) if resume is not None else None,
        options=options)

    def run() -> EigResult:
        with obs_trace.span("solve", method=method, nev=nev, which=which,
                            tol=tol) as sp:
            res = solver.solve(ctx)
            if is_transform:
                res = _untransform(op, res)
            sp.set(converged=res.converged, restarts=res.n_restarts,
                   n_ops=res.n_ops)
        return res

    if tracer is None:
        return run()
    conv = ConvergenceTracker(tracer, tol=tol, nev=nev, method=method)
    ctx.callback = conv.chain(callback)
    with obs_trace.tracing(tracer):
        s0 = obs_metrics.snapshot_store(ctx.store)
        res = run()
        s1 = obs_metrics.snapshot_store(ctx.store)
        tracer.metric("solve.io", {"start": s0, "end": s1,
                                   "delta": obs_metrics.delta(s0, s1)})
    if trace_path is not None:
        tracer.write_jsonl(trace_path)
    return dataclasses.replace(res, trace=tracer)
