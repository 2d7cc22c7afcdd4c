"""The control of `correct`: the reference solver in the program's place.

    python bench/control.py --workload <cell> --seeds 1 2 3 \
        [--precision high|highest] [--solves 2]

It builds the cell's graph, then for each seed runs
`reference.krylov_schur` (the plain solver, in device memory, with the
traffic's restart cap and the configuration's b, NB, nev and tol)
`--solves` times from the start seeds a run with that --seed draws, and
passes its eigenpairs to the same comparison a run makes. At `high`,
the three-pass bfloat16 products just below the configuration's full
float32, the cell's limits have to refuse it; at `highest` they have to
pass it. The benchmark's runs never run
this; it is how the upper readings in `PERF.md` were taken, on the chip
at the cell's own size. Prints one JSON line per seed and exits 0.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control(cell, seeds, precision: str, n_solves: int,
            graph_overrides: dict | None = None) -> list:
    """The control's compared numbers and verdict for each seed."""
    from bench import check, graphs, reference
    from bench.run import start_seeds

    cfg = dict(cell.config)
    if graph_overrides:
        cfg["graph"] = {**cfg["graph"], **graph_overrides}
    mode = cell.traffic["mode"]
    n, rows, cols, vals = graphs.generate(cfg)
    bm = cfg["packing"]["block_shape"][0]
    n_pad = -(-n // bm) * bm
    spmm = reference.EllSpmm(n, rows, cols, vals, precision=precision,
                             n_pad=n_pad)
    max_iters = (cfg["max_restarts"] if mode == "solves"
                 else cell.traffic["restarts"])
    a = reference.csr(n, rows, cols, vals)
    ref = (reference.top_eigenvalues(a, cfg["nev"]) if mode == "solves"
           else None)
    out = []
    for seed in seeds:
        seeds_ = start_seeds(seed, cell)
        t0 = time.perf_counter()
        results = [reference.krylov_schur(
            spmm, n_pad, nev=cfg["nev"], block_size=cfg["block_size"],
            num_blocks=cfg["num_blocks"], tol=cfg["tol"],
            max_restarts=max_iters, which=cfg["which"],
            seed=seeds_[i], precision=precision)
            for i in range(n_solves)]
        solve_s = time.perf_counter() - t0
        per = [check.numbers(mode, a, [r], ref) for r in results]
        nums = check.aggregate(per)
        ok, _ = check.verdict(nums, cell.limits)
        out.append({"seed": seed, "precision": precision, "correct": ok,
                    "numbers": nums, "n_ops": [r.n_ops for r in results],
                    "restarts": [r.n_restarts for r in results],
                    "solve_s": solve_s})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="high",
                    choices=("high", "highest"))
    ap.add_argument("--solves", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.run import load_cell
    cell = load_cell(args.workload, False)
    for row in control(cell, args.seeds, args.precision, args.solves):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
