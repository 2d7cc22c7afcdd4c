"""The comparison that decides `correct`.

Each traffic mode compares the eigenpairs that the timed path returned
with the float64 reference (`reference.py`). The numbers, per mode:

  both    resid_gap   max | ||Ax - theta x|| / ||x|| - the solver's own
                      residual bound |: the Krylov decomposition the bound
                      is read from must hold for A itself;
          rq_gap      max |theta - x'Ax / x'x|, the Ritz value against the
                      Rayleigh quotient of the returned vector;
  solves  eig_rel     max of |theta - lambda| / |lambda|, lambda the top
                      eigenvalues by ARPACK in float64 on the same matrix;
          unconverged solves that stopped at max_restarts (limit 0).

Each number has its limit in the cell's file (`workloads/<cell>.json`).
"""
from __future__ import annotations

import numpy as np

from bench import reference


def capped_numbers(a, results) -> dict:
    resid_gap, rq_gap = 0.0, 0.0
    for r in results:
        res, rq = reference.pair_checks(a, r.eigenvalues, r.eigenvectors)
        theta = np.asarray(r.eigenvalues, np.float64)
        bound = np.asarray(r.residuals, np.float64)
        resid_gap = max(resid_gap, float(np.max(np.abs(res - bound))))
        rq_gap = max(rq_gap, float(np.max(np.abs(theta - rq))))
    return {"resid_gap": resid_gap, "rq_gap": rq_gap}


def solves_numbers(a, ref_eigs, results) -> dict:
    eig_rel = 0.0
    for r in results:
        got = np.sort(np.asarray(r.eigenvalues, np.float64))[::-1]
        err = np.abs(got - ref_eigs) / np.abs(ref_eigs)
        eig_rel = max(eig_rel, float(np.max(err)))
    return {"eig_rel": eig_rel, **capped_numbers(a, results),
            "unconverged": float(sum(not r.converged for r in results))}


def numbers(mode: str, a, results, ref_eigs=None) -> dict:
    if mode == "solves":
        return solves_numbers(a, ref_eigs, results)
    return capped_numbers(a, results)


def aggregate(per: list) -> dict:
    """One run's numbers from its solves' numbers: the worst of each, and
    the count of unconverged solves."""
    return {k: (sum(p[k] for p in per) if k == "unconverged"
                else max(p[k] for p in per)) for k in per[0]}


def verdict(nums: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [[name, number, limit], ...]). A number that is not
    finite, or a limit the cell does not state, fails."""
    rows, ok = [], True
    for name, value in nums.items():
        limit = limits.get(name)
        good = (limit is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and good
        rows.append([name, value, limit])
    return ok, rows
