"""The cells at the size these tests run on the CPU.

At this size a knn solve converges at its first restart, and its
Ritz pairs then read about 3e-7 in `resid_gap` and 6e-8 in `rq_gap`,
where the chip's unconverged pairs at 2^19 read under 7e-9 and 4e-8
(PERF.md). The knn cell's limits are therefore set here for this size,
from its own readings in the same way: sound runs read at most 2.9e-7
and 6e-8, the control at `high` at least 5.0e-6 and 1.0e-6. The
friendster cell keeps its own limits, which hold at this size too.
"""
import dataclasses

from bench import run

KNN_SMALL = {"log2n": 12, "band_halfwidth": 200, "nnz_per_row": 97}
SMALL = {"friendster-spill": {"log2n": 11}, "knn-ram": KNN_SMALL}
KNN_LIMITS = {"resid_gap": 2e-06, "rq_gap": 4e-07}


def small_cell(name: str, trace: bool = False) -> run.Cell:
    cell = run.load_cell(name, trace)
    if cell.config["name"] == "knn":
        cell = dataclasses.replace(cell, limits=KNN_LIMITS)
    return cell
