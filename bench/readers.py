"""What the metric files under `metrics/` share: the record of one run
and the reductions that several metrics apply to it."""
from __future__ import annotations

import dataclasses
import sys
from typing import List, Optional

from bench import costs, devtrace


@dataclasses.dataclass
class RunRecord:
    """One run of a cell, as the metric readers see it."""
    mode: str                  # "solves" | "capped"
    results: list              # the window's EigResults, in order
    io: List[dict]             # each solve's store IOStats.as_dict()
    window_s: float            # window start to the end of the last solve
    setup_s: float             # process start to window start
    peak_bytes: Optional[int]  # device peak_bytes_in_use after the window
    peak: dict                 # the chip's row of peaks.json
    trace: Optional[devtrace.Trace] = None

    @property
    def n_ops(self) -> int:
        return sum(int(r.n_ops) for r in self.results)


def roofline_share(run: RunRecord, kernels) -> Optional[float]:
    """Least time of the kernels' calls in the traced window over their
    summed device time, in percent; None where no call was traced."""
    if run.trace is None:
        return None
    least = spent = 0.0
    for k in kernels:
        for e in devtrace.kernel_events(run.trace, k):
            res, ops = devtrace.shapes(e.name)
            if res is None or not ops:
                print(f"readers: no shapes in the trace for {k}: "
                      f"{e.name[:200]}", file=sys.stderr)
                return None
            flops, nbytes = costs.KERNELS[k](res, ops)
            least += costs.least_seconds(flops, nbytes, run.peak)
            spent += (e.end - e.start) * 1e-9
    return 100.0 * least / spent if spent > 0 else None


def idle_pct(run: RunRecord, mode: str) -> Optional[float]:
    if run.trace is None or run.mode != mode:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - devtrace.busy_ns(run.trace) / (hi - lo))


def per_expansion(run: RunRecord, counter: str) -> Optional[float]:
    if run.mode != "capped" or not run.n_ops:
        return None
    return sum(io[counter] for io in run.io) / run.n_ops
