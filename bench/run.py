"""Run one cell of the on-chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the cell's chips.
Everything that belongs to a cell is found by name from `BENCHMARK.json`:
the configuration file (`configs/<config>.json`: graph, solver parameters),
the traffic file (`traffic/<traffic>.json`: whole or capped solves, store),
the cell file (`workloads/<cell>.json`: the limits of `correct`) and one
reader per metric (`metrics/<metric>.py`).

A run generates the configuration's graph (fixed by its `graph_seed`, as
a deployment solves one data set) with the benchmark's own generator
(`graphs.py`, kept in `bench/.cache/` for the checkout's later runs),
packs it with the program's `pack_tiles`, builds
`GraphOperator(impl="pallas")`, and compiles every program the window
runs (`Solves.warm_up`). The window then runs solves through
`repro.core.solve` back to back for --seconds (a solve that starts in it
runs to its end), each from its own start block drawn from --seed
(`start_seeds`) and in a fresh `TieredStore`. After the window every
returned eigenpair is compared in float64 with the reference built from
the benchmark's edge arrays (`reference.py`, `check.py`). With --trace 1
the window runs under the JAX profiler and the per-layer metrics are
read from the trace; with --trace 0 the end-to-end metrics are printed.

The last stderr lines give each compared number beside its limit; the
last stdout line is one JSON object with correct, attempted, failed,
metrics, device, breakdown (traced runs) and checks. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SEEDS = 4096          # start blocks drawn per run: more than any window
CACHE_DIR = os.path.join(ROOT, "bench", ".cache")    # generated graphs


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: list       # BENCHMARK.json entries this cell reports


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, trace: bool, root: str = ROOT) -> Cell:
    """The cell named `name` and the files BENCHMARK.json leads to."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    group = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = [m for m in group if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(root, "bench", "traffic",
                                   f"{w['traffic']}.json")),
        limits=_json(os.path.join(root, "bench", "workloads",
                                  f"{name}.json"))["limits"],
        metrics=metrics)


def load_reader(metric: str, root: str = ROOT):
    """`metrics/<metric>.py`'s `read(run)`."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    mod_name = "bench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class RestartSpans:
    """The solver callback that marks each restart as a profiler span:
    one `bench.restart` from the solve's start (or the previous
    Rayleigh-Ritz) to the next Rayleigh-Ritz."""

    def __init__(self):
        self._open = None

    def start(self):
        from jax.profiler import TraceAnnotation
        self._open = TraceAnnotation("bench.restart")
        self._open.__enter__()

    def stop(self):
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __call__(self, step, theta, res):
        self.stop()
        self.start()


class Solves:
    """Runs the traffic's solves of one configuration on one operator,
    each in a fresh TieredStore under `tmp`."""

    def __init__(self, op, cell: Cell, impl: str, tmp: str):
        cfg, tr = cell.config, cell.traffic
        self.op, self.cfg, self.traffic, self.impl = op, cfg, tr, impl
        self.tmp = tmp
        self.mode = tr["mode"]
        self.max_iters = (cfg["max_restarts"] if self.mode == "solves"
                          else tr["restarts"])
        self.subspace_bytes = op.n * cfg["block_size"] * cfg["num_blocks"] * 4
        self.count = 0

    def _store(self, root: str, backend: str):
        from repro.core import TieredStore
        share = self.traffic["store"]["device_budget_share"]
        return TieredStore(
            device_budget_bytes=int(self.subspace_bytes * share),
            backend=backend,
            backend_opts={"root": root} if backend == "safs" else {})

    def one(self, seed: int, *, max_iters: int | None = None,
            tol: float | None = None, backend: str | None = None):
        """One solve from start block `seed`: (EigResult, store IOStats).
        The keywords override the traffic's restart cap, the
        configuration's tol and the store's backend (for the warm-up)."""
        from jax.profiler import TraceAnnotation
        from repro.core import solve
        cfg = self.cfg
        self.count += 1
        root = os.path.join(self.tmp, f"store{self.count}")
        store = self._store(root, backend or self.traffic["store"]["backend"])
        spans = RestartSpans()
        try:
            with TraceAnnotation("bench.solve"):
                spans.start()
                try:
                    res = solve(self.op, cfg["nev"], method="krylov_schur",
                                which=cfg["which"],
                                tol=cfg["tol"] if tol is None else tol,
                                max_iters=max_iters or self.max_iters,
                                block_size=cfg["block_size"],
                                num_blocks=cfg["num_blocks"],
                                store=store, impl=self.impl, seed=seed,
                                callback=spans)
                finally:
                    spans.stop()
            io = store.stats.as_dict()
        finally:
            store.close()
            shutil.rmtree(root, ignore_errors=True)
        return res, io

    def warm_up(self, seed: int) -> None:
        """Compile every program the window runs. The programs do not
        depend on the store's backend, so this goes through a store on
        host RAM: a solve capped at one restart (expansions at every
        subspace width, a thick restart, Ritz vectors from the compressed
        basis) and one that converges at its first Rayleigh-Ritz (Ritz
        vectors from the full basis), which a whole solve ends with."""
        self.one(seed, max_iters=1, backend="ram")
        if self.mode == "solves":
            self.one(seed, max_iters=1, tol=float("inf"), backend="ram")


def start_seeds(seed: int, cell: Cell) -> list:
    """The start-block seeds a run with --seed `seed` draws, in order;
    the last one is the warm-up's.

    Where the traffic names `start_blocks`, the solves cycle through that
    many start blocks, fixed by the configuration's `graph_seed`, in an
    order drawn from --seed: a whole solve's restarts depend on its start
    block, so every run does the same set of solves, in another order.
    """
    import numpy as np
    state = np.random.SeedSequence(seed).generate_state(N_SEEDS + 1)
    seeds = [int(s) % (1 << 31) for s in state]
    pool_size = cell.traffic.get("start_blocks")
    if pool_size:
        pool = np.random.SeedSequence(
            [cell.config["graph_seed"], pool_size]).generate_state(pool_size)
        order = np.random.default_rng(seed).permutation(pool_size)
        seeds[:N_SEEDS] = [int(pool[order[i % pool_size]]) % (1 << 31)
                           for i in range(N_SEEDS)]
    return seeds


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            impl: str = "pallas", device=None, peak: dict | None = None,
            graph_overrides: dict | None = None, compiles=lambda: 0,
            t_start: float = T_START, cache_dir: str | None = None) -> dict:
    """Set up, run the window, check. Returns the result object (without
    printing). `graph_overrides` shrinks the graph for tests on the CPU;
    `cache_dir` keeps the generated graph (`graphs.generate`)."""
    import jax
    from repro.core import GraphOperator
    from repro.graphs import pack_tiles

    from bench import check, devtrace, graphs, readers, reference

    cfg = dict(cell.config)
    if graph_overrides:
        cfg["graph"] = {**cfg["graph"], **graph_overrides}
        cell = dataclasses.replace(cell, config=cfg)
    solve_seeds = start_seeds(seed, cell)

    t0 = time.perf_counter()
    n, rows, cols, vals = graphs.generate(cfg, cache_dir)
    t1 = time.perf_counter()
    pk = cfg["packing"]
    tm = pack_tiles(n, n, rows, cols, vals,
                    block_shape=tuple(pk["block_shape"]),
                    min_block_nnz=pk["min_block_nnz"])
    t2 = time.perf_counter()
    op = GraphOperator(tm, impl=impl)
    print(f"run: n={n} nnz={rows.size} blocks={tm.nblocks} "
          f"coo={tm.coo_vals.size}; generate {t1 - t0:.2f} s, "
          f"pack {t2 - t1:.2f} s", file=sys.stderr)
    del tm
    tmp = tempfile.mkdtemp(prefix="bench_")
    try:
        solves = Solves(op, cell, impl, tmp)
        t0 = time.perf_counter()
        solves.warm_up(solve_seeds[-1])
        setup_s = time.perf_counter() - t_start
        print(f"run: warm-up solve {time.perf_counter() - t0:.2f} s, "
              f"set-up {setup_s:.2f} s", file=sys.stderr)

        trace_dir = os.path.join(tmp, "trace")
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiled = compiles()
        results, io = [], []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            while time.perf_counter() - t0 < seconds:
                res, st = solves.one(solve_seeds[len(results)])
                results.append(res)
                io.append(st)
        window_s = time.perf_counter() - t0
        compiled = compiles() - compiled
        tr = None
        if trace:
            jax.profiler.stop_trace()
            tr = devtrace.load(trace_dir)
        stats = device.memory_stats() if device is not None else None
        peak_bytes = (stats or {}).get("peak_bytes_in_use")
        del op, solves
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"run: {len(results)} solves, {sum(r.n_ops for r in results)} "
          f"applies in {window_s:.2f} s; {compiled} compiles in the window; "
          f"device peak {peak_bytes} bytes", file=sys.stderr)

    # ---- the comparison, once the window is closed and the device freed
    a = reference.csr(n, rows, cols, vals)
    mode = cell.traffic["mode"]
    ref_eigs = (reference.top_eigenvalues(a, cfg["nev"])
                if mode == "solves" else None)
    per = [check.numbers(mode, a, [r], ref_eigs)
           for r in results]
    failed = sum(not check.verdict(p, cell.limits)[0] for p in per)
    correct, rows_ = check.verdict(check.aggregate(per), cell.limits)

    record = readers.RunRecord(
        mode=mode, results=results, io=io,
        window_s=window_s, setup_s=setup_s, peak_bytes=peak_bytes,
        peak=peak or {}, trace=tr)
    metrics = {}
    for m in cell.metrics:
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {}
    if device is not None:
        dev = {"platform": device.platform, "kind": device.device_kind,
               "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct) and failed == 0,
           "attempted": len(results), "failed": failed,
           "metrics": metrics, "device": dev}
    if tr is not None:
        lo, hi = tr.window()
        dev["busy_s"] = devtrace.busy_ns(tr) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {"device_ops": devtrace.device_op_totals(tr),
                            "idle_gaps": devtrace.idle_gaps(tr)}
    out["checks"] = {name: {"number": v, "limit": lim}
                     for name, v, lim in rows_}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    cell = load_cell(args.workload, bool(args.trace))

    from repro.hostdev import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from bench import costs
    peak = costs.peaks(devices[0].device_kind)
    n_compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: n_compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    out = execute(cell, args.seed, args.seconds, bool(args.trace),
                  device=devices[0], peak=peak,
                  compiles=lambda: len(n_compiles), cache_dir=CACHE_DIR)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['number']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
