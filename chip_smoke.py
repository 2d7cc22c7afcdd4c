"""Chip smoke test: drive the eigensolver's main path once on a TPU.

    python chip_smoke.py               # one chip: solve phase, served phase
    python chip_smoke.py --four-chips  # DistOperator on a 4-chip mesh vs
                                       # the one-chip solve of that graph

Solve phase: the paper's semi-external regime. A seeded symmetric R-MAT
graph of the friendster shape (configs/flasheigen.py: ~26 nonzeros per
vertex, b=4, NB=8, nev=8) cut to 2^20 vertices is packed into 64x64
blocks plus a COO side path and solved by `core.solve(method=
"krylov_schur")` through `GraphOperator` with every kernel on the chip
(`impl="pallas"`). The subspace lives in a `TieredStore` over a SAFS
backend whose device budget is half the subspace's bytes, so it spills.
The result is checked against a float64 host reference: residuals
||Av - lv|| from a scipy CSR matvec, and eigenvalues against ARPACK.

Served phase: `EigenService` (`build_service(backend="safs")`) drains a
queue of eigsh / lobpcg / cluster jobs of 2^18 vertices plus one of 1500
vertices (a row count that reaches the kernels only through zero-row
padding). Every job must end DONE, `validate_report` must be empty, and
each spectrum must match the same host reference.

Diagnostics go to stdout first; every time printed is a smoke timing, not
a benchmark figure. The last line is one JSON object naming the device.
Any failure raises, which exits non-zero without that line; so does a
machine whose first JAX device is not a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.hostdev import enable_compile_cache  # noqa: E402  (before jax)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402
import scipy.sparse.linalg as sla  # noqa: E402
from scipy.sparse.csgraph import connected_components  # noqa: E402

from repro.configs.flasheigen import GRAPHS  # noqa: E402
from repro.core import GraphOperator, TieredStore, solve  # noqa: E402
from repro.graphs import (normalized_adjacency, pack_tiles,  # noqa: E402
                          rmat_graph)
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import JobSpec, build_service, validate_report  # noqa: E402
from repro.serve.session import planted_partition  # noqa: E402

SEED = 0
FRIENDSTER = GRAPHS["friendster"]
# 2^20, not 2^22: the COO side path's scatter-add takes ~2.3 s per SpMM at
# 2^20 on a v5e and grows with the edges, so a 2^22 solve would not finish
# within the smoke's 20 minutes
SOLVE_LOG2N = 20
SERVE_LOG2N = 18
FOUR_CHIP_LOG2N = 18
BLOCK_SHAPE = (64, 64)
# dense 64x64 blocks only where they hold >= 64 entries (1.6% fill); the
# rest of a power-law graph rides the COO side path
MIN_BLOCK_NNZ = 64
SOLVE_TOL = 1e-5
MAX_RESTARTS = 60
RESID_TOL = 1e-4        # float64 ||Av - lv|| / ||v|| of the f32 solve
EIG_RTOL = 1e-5         # eigenvalues against the ARPACK reference
DENSE_MAX = 512         # components up to this size are solved densely


class SmokeError(RuntimeError):
    """A phase's result failed its check."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- reference
def reference_top(a: sp.csr_matrix, nev: int) -> np.ndarray:
    """The `nev` largest eigenvalues of the symmetric matrix `a` in float64,
    descending. The spectrum of a graph matrix is the union of its
    connected components' spectra, so each component is solved on its
    own: ARPACK for large ones, dense for small ones. ARPACK on the whole
    matrix returns an arbitrary number of copies of an eigenvalue that
    several components share (1 for a normalized adjacency)."""
    ncomp, lab = connected_components(a, directed=False)
    sizes = np.bincount(lab, minlength=ncomp)
    if np.any(np.diff(lab) < 0):     # make each component contiguous
        order = np.argsort(lab, kind="stable")
        a = a[order][:, order].tocsr()
    starts = np.concatenate([[0], np.cumsum(sizes)])
    diag = a.diagonal()
    vals = [diag[starts[:-1][sizes == 1]]]           # 1x1 components
    for c in np.flatnonzero(sizes > 1):
        blk = a[starts[c]:starts[c + 1], starts[c]:starts[c + 1]]
        if sizes[c] <= DENSE_MAX:
            vals.append(np.linalg.eigvalsh(blk.toarray()))
        else:
            vals.append(sla.eigsh(blk, k=nev, which="LA", tol=1e-10,
                                  return_eigenvectors=False))
    return np.sort(np.concatenate(vals))[::-1][:nev]


def check_spectrum(name: str, got, want: np.ndarray) -> float:
    got = np.sort(np.asarray(got, np.float64))[::-1]
    check(got.shape == want.shape and np.all(np.isfinite(got)),
          f"{name}: eigenvalues {got} do not match the shape of {want}")
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    check(bool(np.all(rel <= EIG_RTOL)),
          f"{name}: eigenvalues {got} differ from the reference {want} "
          f"(max rel {rel.max():.3e} > {EIG_RTOL})")
    return float(rel.max())


def csr(n: int, r, c, v) -> sp.csr_matrix:
    return sp.csr_matrix((np.asarray(v, np.float64), (r, c)), shape=(n, n))


# ---------------------------------------------------------------- graphs
def friendster_graph(log2n: int, seed: int = SEED):
    """Seeded symmetric R-MAT graph of the friendster shape at 2^log2n
    vertices, reduced to its largest connected component and normalized.
    Returns (n, rows, cols, vals). R-MAT leaves many vertex ids without an
    edge, and the normalized adjacency has eigenvalue 1 once per
    component, so the top of the whole graph's spectrum is one repeated
    value; the largest component keeps nearly every edge and has a top
    spectrum worth checking."""
    n = 1 << log2n
    per_vertex = FRIENDSTER.n_edges / FRIENDSTER.n_vertices
    r, c, v = rmat_graph(n, int(per_vertex * n / 2), seed=seed,
                         symmetric=True)
    _, lab = connected_components(csr(n, r, c, v), directed=False)
    keep = lab == np.argmax(np.bincount(lab))
    new_id = (np.cumsum(keep) - 1).astype(np.int32)
    live = keep[r]
    n_lcc = int(keep.sum())
    r2, c2, v2 = normalized_adjacency(n_lcc, new_id[r[live]],
                                      new_id[c[live]], v[live])
    return n_lcc, r2, c2, v2


# ----------------------------------------------------------- solve phase
def solve_phase(log2n: int, impl: str) -> None:
    cfg = FRIENDSTER
    b, nb, nev = cfg.block_size, cfg.num_blocks, cfg.nev
    t0 = time.perf_counter()
    n, r, c, v = friendster_graph(log2n)
    tm = pack_tiles(n, n, r, c, v, block_shape=BLOCK_SHAPE,
                    min_block_nnz=MIN_BLOCK_NNZ)
    ingest_s = time.perf_counter() - t0
    n_pad = tm.shape[0]
    coo = int(tm.coo_vals.size)
    say(f"solve: R-MAT 2^{log2n} vertices, largest component n={n} "
        f"(padded {n_pad}), {r.size} nonzeros: {r.size - coo} in "
        f"{tm.nblocks} dense {BLOCK_SHAPE[0]}x{BLOCK_SHAPE[1]} blocks, "
        f"{coo} on the COO side path; image {tm.nbytes_image()} bytes")

    subspace_bytes = n_pad * b * nb * 4
    store = TieredStore(device_budget_bytes=subspace_bytes // 2,
                        backend="safs")
    op = GraphOperator(tm, store=store, impl=impl)
    kw = dict(method="krylov_schur", which="LA", tol=SOLVE_TOL,
              block_size=b, num_blocks=nb, impl=impl, seed=SEED)

    t0 = time.perf_counter()         # one restart compiles every shape
    warm = TieredStore(device_budget_bytes=subspace_bytes // 2,
                       backend="safs")
    try:
        solve(op, nev, max_iters=1, store=warm, **kw)
    finally:
        warm.close()
    compile_s = time.perf_counter() - t0
    store.reset_stats()

    t0 = time.perf_counter()
    res = solve(op, nev, max_iters=MAX_RESTARTS, store=store, **kw)
    solve_s = time.perf_counter() - t0
    io = store.stats
    say(f"solve: smoke timing (not a benchmark): ingest {ingest_s:.1f} s, "
        f"compile (one warm-up restart) {compile_s:.1f} s, "
        f"solve {solve_s:.1f} s; {res.n_restarts} restarts, "
        f"{res.n_ops} SpMMs, converged={res.converged}")
    disk = store.backend.stats
    say(f"solve: store device budget {subspace_bytes // 2} of "
        f"{subspace_bytes} subspace bytes; host->device "
        f"{io.pass_bytes_read} bytes in {io.passes} subspace passes, "
        f"device->host {io.host_bytes_written} bytes; SAFS disk read "
        f"{disk.host_bytes_read} written {disk.host_bytes_written} bytes")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"solve: device peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    check(res.converged, f"solve: not converged in {MAX_RESTARTS} "
          f"restarts (residual bounds {res.residuals})")

    a = csr(n_pad, r, c, v)
    x = np.asarray(res.eigenvectors, np.float64)
    lam = np.asarray(res.eigenvalues, np.float64)
    check(x.shape == (n_pad, nev) and bool(np.all(np.isfinite(x))),
          f"solve: eigenvectors of shape {x.shape} are not finite "
          f"({n_pad}, {nev})")
    resid = (np.linalg.norm(a @ x - x * lam[None, :], axis=0)
             / np.linalg.norm(x, axis=0))
    say(f"solve: float64 residuals ||Av-lv||/||v|| max {resid.max():.3e}")
    check(bool(np.all(resid <= RESID_TOL)),
          f"solve: residuals {resid} above {RESID_TOL}")
    t0 = time.perf_counter()
    want = reference_top(a, nev)
    rel = check_spectrum("solve", lam, want)
    say(f"solve: eigenvalues {np.sort(lam)[::-1].tolist()} match ARPACK "
        f"on the same matrix to max rel {rel:.3e} "
        f"(reference {time.perf_counter() - t0:.1f} s)")
    store.close()


# ---------------------------------------------------------- served phase
def served_jobs(log2n: int) -> list:
    n = 1 << log2n
    return [
        JobSpec("embed", kind="eigsh", n=n, nnz=13 * n, nev=4, tol=1e-6,
                max_iters=200),
        # LOBPCG's f32 Ritz values err by about its residual, so its
        # tolerance sits well inside EIG_RTOL
        JobSpec("lobpcg", kind="lobpcg", n=n, nnz=13 * n, nev=4, tol=3e-6,
                max_iters=200),
        JobSpec("cluster", kind="cluster", n=n, k_classes=4, nev=4,
                tol=1e-6, max_iters=200, priority=1),
        JobSpec("embed-1500", kind="eigsh", n=1500, nnz=15000, nev=6,
                tol=1e-6, max_iters=150),
    ]


def job_matrix(spec: JobSpec) -> sp.csr_matrix:
    """The job's normalized adjacency, rebuilt on the host from its seed."""
    if spec.graph == "planted":
        _, r, c, v = planted_partition(spec.n, spec.k_classes,
                                       seed=spec.seed)
    else:
        r, c, v = rmat_graph(spec.n, spec.nnz, seed=spec.seed,
                             symmetric=True)
    return csr(spec.n, *normalized_adjacency(spec.n, r, c, v))


def served_phase(log2n: int) -> None:
    specs = served_jobs(log2n)
    # one job at a time: a 2^18 R-MAT job keeps a 2.7 GB block image on
    # the chip and its SpMM relayouts it into 4.4 GB of temporaries
    # (compiled memory analysis), so two at once would not fit 16 GB
    service = build_service(backend="safs", max_concurrent=1)
    try:
        t0 = time.perf_counter()
        for spec in specs:
            service.submit(spec)
        service.drain()
        drain_s = time.perf_counter() - t0
        report = service.report()
    finally:
        service.close()
    errors = validate_report(report)
    check(not errors, f"served: validate_report: {errors}")
    say(f"served: smoke timing (not a benchmark): {len(specs)} jobs "
        f"drained in {drain_s:.1f} s")
    jobs = {j["job_id"]: j for j in report["jobs"]}
    for spec in specs:
        j = jobs[spec.job_id]
        check(j["state"] == "done",
              f"served: job {spec.job_id} ended {j['state']}: {j['error']}")
        result = j["result"]
        check(result["converged"],
              f"served: job {spec.job_id} not converged "
              f"(residual bounds {result['residuals']})")
        rel = check_spectrum(f"served {spec.job_id}", result["eigenvalues"],
                             reference_top(job_matrix(spec), spec.nev))
        purity = "" if j["purity"] is None else f", purity {j['purity']:.3f}"
        say(f"served: job {spec.job_id} ({spec.kind}, n={spec.n}) done in "
            f"{j['wall_s']:.1f} s, eigenvalues match the reference to max "
            f"rel {rel:.3e}{purity}")


# ------------------------------------------------------------ four chips
def four_chip_phase(log2n: int, impl: str) -> None:
    from repro.dist.dist_operator import DistOperator, default_mesh
    cfg = FRIENDSTER
    devices = jax.devices()
    check(len(devices) == 4, f"four chips: {len(devices)} devices")
    mesh = default_mesh(devices)
    check(len({d.id for d in mesh.devices.flat}) == 4,
          f"four chips: mesh {mesh} does not span 4 distinct devices")
    n, r, c, v = friendster_graph(log2n)
    kw = dict(method="krylov_schur", which="LA", tol=SOLVE_TOL,
              max_iters=MAX_RESTARTS, block_size=cfg.block_size,
              num_blocks=cfg.num_blocks, impl=impl, seed=SEED)

    t0 = time.perf_counter()
    dop = DistOperator(n, r, c, v, mesh=mesh)
    held = {s.device.id for a in (dop._pc, dop._pr, dop._pv)
            for s in a.addressable_shards}
    check(held == {d.id for d in devices},
          f"four chips: edge panels sit on devices {sorted(held)}")
    res4 = solve(dop, cfg.nev, **kw)
    t4 = time.perf_counter() - t0
    check(res4.converged, "four chips: DistOperator solve not converged")

    t0 = time.perf_counter()
    tm = pack_tiles(n, n, r, c, v, block_shape=BLOCK_SHAPE,
                    min_block_nnz=MIN_BLOCK_NNZ)
    res1 = solve(GraphOperator(tm, impl=impl), cfg.nev, **kw)
    t1 = time.perf_counter() - t0
    check(res1.converged, "four chips: one-chip solve not converged")
    one = np.sort(np.asarray(res1.eigenvalues, np.float64))[::-1]
    rel = check_spectrum("four chips", res4.eigenvalues, one)
    say(f"four chips: mesh {dict(mesh.shape)} over devices "
        f"{sorted(held)}; R-MAT 2^{log2n}, largest component n={n}")
    say(f"four chips: DistOperator eigenvalues "
        f"{np.sort(res4.eigenvalues)[::-1].tolist()} match the one-chip "
        f"GraphOperator solve to max rel {rel:.3e}")
    say(f"four chips: smoke timing (not a benchmark): DistOperator "
        f"{t4:.1f} s ({res4.n_restarts} restarts), one chip {t1:.1f} s "
        f"({res1.n_restarts} restarts)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip DistOperator solve and the "
                         "one-chip solve it is compared with")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform})",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count} jax={jax.__version__}")
    check(kops.use_pallas(), "kernels would not resolve to Pallas")
    if args.four_chips:
        four_chip_phase(FOUR_CHIP_LOG2N, "pallas")
    else:
        solve_phase(SOLVE_LOG2N, "pallas")
        served_phase(SERVE_LOG2N)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
