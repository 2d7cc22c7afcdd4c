"""repro.obs — span tracer, metrics registry, solve timelines, report.

Covers the observability contract end-to-end:

  * tracer mechanics: nesting, thread attribution, the disabled-is-free
    no-op guard, record cap accounting, the JSONL exporter, and the
    profiler sink (spans as `TraceMe` events, with or without a Tracer);
  * metrics snapshots: the duck-typed `snapshot_counters` over every
    counter spelling in the repo, recursive `delta` with derived-field
    recomputation, `gauges`, the registry's error isolation;
  * the `callback` seam across all four solvers through `solve()`
    dispatch (monotone steps, nev-length arrays, mutation safety) on ram
    and safs backends;
  * `solve(..., trace=...)`: the complete timeline (operator applies,
    subspace passes, SAFS fill/prefetch-wait/write-behind-retire,
    convergence events) and the byte-exact reconciliation of pass.subspace
    span bytes against the store's own IOStats;
  * `repro.obs.report` validation, for the CI gate in run_tier1.sh.
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.core import GraphOperator, IOStats, TieredStore, solve
from repro.core.tiered import HOST
from repro.graphs import pack_tiles
from repro.obs import (MetricsRegistry, NULL_SPAN, SCHEMA, Tracer,
                       delta, derive, gauges, snapshot_counters,
                       snapshot_store, trace, tracing)
from repro.obs import report
from repro.obs.progress import ConvergenceTracker


def _op(small_graph, store=None):
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    return GraphOperator(tm, store=store, impl="ref")


# ---------------------------------------------------------------- tracer
def test_span_nesting_and_attrs():
    t = Tracer()
    with t.span("outer", a=1):
        with t.span("inner") as sp:
            sp.set(bytes=42)
    recs = t.records()
    assert [r["name"] for r in recs] == ["inner", "outer"]  # close order
    inner, outer = recs
    assert inner["args"]["bytes"] == 42
    assert outer["args"]["a"] == 1
    assert inner["dur"] >= 0 and outer["dur"] >= inner["dur"]
    # inner nests inside outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_span_records_error_on_exception():
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (rec,) = t.records()
    assert rec["args"]["error"] == "RuntimeError"


def test_disabled_tracing_is_noop():
    assert trace.active() is None
    # module-level span() with no tracer installed returns the shared
    # singleton — the whole cost of a disabled build is one None check
    sp = trace.span("anything", bytes=1)
    assert sp is NULL_SPAN
    with sp as s:
        s.set(more=2)             # swallowed
    trace.event("anything")       # no-op, no error


def test_tracing_contextmanager_installs_and_restores():
    t1, t2 = Tracer(), Tracer()
    assert trace.active() is None
    with tracing(t1):
        assert trace.active() is t1
        with trace.span("a"):
            pass
        with tracing(t2):          # nested solves stack
            assert trace.active() is t2
            with trace.span("b"):
                pass
        assert trace.active() is t1
    assert trace.active() is None
    assert [r["name"] for r in t1.records()] == ["a"]
    assert [r["name"] for r in t2.records()] == ["b"]


def test_thread_attribution():
    t = Tracer()

    def worker():
        with t.span("off-thread"):
            pass

    with t.span("main"):
        th = threading.Thread(target=worker, name="bg")
        th.start()
        th.join()
    tids = {r["name"]: r["tid"] for r in t.records()}
    assert tids["off-thread"] != tids["main"]
    meta = t.export_records()[0]
    assert "bg" in meta["threads"].values()


def test_record_cap_counts_dropped():
    t = Tracer(max_records=2)
    for i in range(5):
        t.event("e", i=i)
    assert len(t.records()) == 2 and t.dropped == 3
    summ = t.export_records()[-1]
    assert summ["type"] == "summary" and summ["dropped"] == 3


def test_jsonl_export_layout(tmp_path):
    t = Tracer()
    with t.span("s", x=np.int64(7)):      # numpy attrs must serialize
        pass
    t.event("ev", arr=np.arange(3))
    t.metric("m", {"a": {"b": 1}})
    path = str(tmp_path / "t.jsonl")
    t.write_jsonl(path)
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["type"] == "meta" and recs[0]["schema"] == SCHEMA
    assert recs[-1]["type"] == "summary"
    assert recs[-1] == {"type": "summary", "spans": 1, "events": 1,
                        "metrics": 1, "dropped": 0}
    by = {r["type"]: r for r in recs[1:-1]}
    assert by["span"]["args"]["x"] == 7
    assert by["event"]["args"]["arr"] == [0, 1, 2]
    assert by["metrics"]["data"] == {"a": {"b": 1}}


def test_profiler_span_without_a_tracer(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        sp = trace.span("store.get", bytes=1)
        assert sp is not NULL_SPAN
        with sp as s:
            assert s.set(more=2) is s     # attrs are the Tracer's alone
    assert trace.span("store.get") is NULL_SPAN   # off again


def test_tracer_span_under_the_profiler_records_as_before(tmp_path):
    import jax
    t = Tracer()
    with jax.profiler.trace(str(tmp_path)), tracing(t):
        with trace.span("outer", a=1):
            with trace.span("inner") as sp:
                sp.set(bytes=42)
    inner, outer = t.records()
    assert (inner["name"], inner["args"]) == ("inner", {"bytes": 42})
    assert (outer["name"], outer["args"]) == ("outer", {"a": 1})


# --------------------------------------------------------------- metrics
def test_snapshot_counters_duck_typing():
    assert snapshot_counters(None) is None
    assert snapshot_counters({"a": 1}) == {"a": 1}
    st = IOStats()
    st.cache_hits = 3
    snap = snapshot_counters(st)                  # via as_dict()
    assert snap["cache_hits"] == 3 and "hit_rate" in snap

    class HasStatsAttr:
        stats = st
    assert snapshot_counters(HasStatsAttr())["cache_hits"] == 3

    class HasStatsMethod:
        def stats(self):
            return {"x": 1}
    assert snapshot_counters(HasStatsMethod()) == {"x": 1}

    with pytest.raises(TypeError, match="counter surface"):
        snapshot_counters(object())


def test_iostats_as_dict_types_and_hit_rate():
    """Satellite: the declared Dict[str, float] return is now honest, and
    hit_rate is a uniform derived field."""
    st = IOStats()
    st.cache_hits, st.cache_misses = 3, 1
    st.pass_bytes_read, st.passes = 100, 4
    d = st.as_dict()
    assert d["hit_rate"] == pytest.approx(0.75)
    assert d["bytes_per_pass"] == pytest.approx(25.0)
    assert all(isinstance(v, (int, float)) for v in d.values())
    assert st.hit_rate() == pytest.approx(0.75)
    empty = IOStats()
    assert empty.hit_rate() == 0.0                # no div-by-zero


def test_delta_recurses_and_recomputes_derived():
    before = {"logical": {"cache_hits": 10, "cache_misses": 10,
                          "hit_rate": 0.5, "passes": 2,
                          "pass_bytes_read": 200, "bytes_per_pass": 100.0},
              "tag": "x"}
    after = {"logical": {"cache_hits": 40, "cache_misses": 20,
                         "hit_rate": 2 / 3, "passes": 4,
                         "pass_bytes_read": 600, "bytes_per_pass": 150.0},
             "tag": "x"}
    d = delta(before, after)
    assert d["logical"]["cache_hits"] == 30
    # derived fields recomputed from the subtracted counters, NOT subtracted
    assert d["logical"]["hit_rate"] == pytest.approx(30 / 40)
    assert d["logical"]["bytes_per_pass"] == pytest.approx(400 / 2)
    assert d["tag"] == "x"                        # non-numeric passthrough
    assert derive({"cache_hits": 1, "cache_misses": 3})["hit_rate"] == 0.25


def test_gauges_from_store_snapshot():
    store = TieredStore()
    store.put("a", np.ones((16, 4), np.float32))
    store.demote("a")
    store.get("a")
    snap = snapshot_store(store)
    g = gauges(snap)
    assert 0.0 <= g["logical_hit_rate"] <= 1.0
    assert g["overlap_fraction"] == 0.0           # ram backend: no prefetch
    assert g["write_read_ratio"] >= 0.0


def test_metrics_registry_isolation():
    reg = MetricsRegistry()
    reg.register("good", lambda: {"v": 1})
    reg.register("bad", lambda: 1 / 0)
    reg.register("stats_obj", IOStats())
    snap = reg.snapshot()
    assert snap["good"] == {"v": 1}
    assert "ZeroDivisionError" in snap["bad"]["error"]
    assert "host_bytes_read" in snap["stats_obj"]
    reg.unregister("bad")
    assert reg.names() == ["good", "stats_obj"]


def test_ram_backend_stats_dict_shape():
    store = TieredStore()
    snap = store.backend.stats_dict()
    assert set(snap) == {"io", "cache", "prefetch", "write_behind",
                         "namespaces", "integrity"}
    assert snap["cache"] is None and snap["prefetch"] is None
    assert snap["integrity"] is None       # checksums are a safs feature


# ------------------------------------------------------- convergence/ETA
def test_convergence_tracker_eta_decay():
    t = Tracer()
    c = ConvergenceTracker(t, tol=1e-8, nev=2, method="test")
    r = 1.0
    etas = []
    for k in range(6):
        c.update(k, np.array([1.0, 1.0]), np.array([r, r / 2]))
        etas.append(c.eta_steps())
        r *= 0.1
    assert etas[0] is None                        # single point: no rate yet
    assert etas[-1] is not None and etas[-1] < etas[1]
    evs = [r for r in t.records() if r["name"] == "convergence.step"]
    assert len(evs) == 6
    assert evs[-1]["args"]["eta_steps"] == etas[-1]


def test_convergence_tracker_converged_and_stagnant():
    c = ConvergenceTracker(None, tol=1e-6, nev=1)
    c.update(0, np.array([1.0]), np.array([1e-9]))
    assert c.eta_steps() == 0                     # already below tol
    c2 = ConvergenceTracker(None, tol=1e-12, nev=1)
    for k in range(5):
        c2.update(k, np.array([1.0]), np.array([1e-3]))  # flat: no decay
    assert c2.eta_steps() is None


def test_convergence_tracker_chain_calls_user_callback():
    seen = []
    c = ConvergenceTracker(None, tol=1e-6, nev=1)
    cb = c.chain(lambda k, th, r: seen.append(k))
    cb(0, np.array([1.0]), np.array([0.5]))
    assert seen == [0] and len(c.history) == 1


# -------------------------------------------------- callback seam (4 solvers)
def _callback_recorder(nev):
    steps, arrays = [], []

    def cb(step, theta, res):
        steps.append(step)
        arrays.append((theta.copy(), res.copy()))
        assert theta.shape == (nev,) and res.shape == (nev,)
        theta[:] = -1e9            # mutation must not corrupt the solver
        res[:] = -1e9
    return cb, steps, arrays


@pytest.mark.parametrize("method,kw", [
    ("krylov_schur", dict(block_size=4, max_iters=100)),
    ("lanczos", dict(block_size=4, num_blocks=40)),
    ("lobpcg", dict(block_size=8, max_iters=300)),
])
def test_callback_all_eig_methods(small_graph, method, kw):
    nev = 4
    cb, steps, _ = _callback_recorder(nev)
    res = solve(_op(small_graph), nev, method=method, which="LA",
                tol=1e-5, callback=cb, **kw)
    assert len(steps) > 0
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    # callbacks received copies: the poisoned arrays must not leak back
    assert np.all(np.abs(res.eigenvalues) < 1e8)
    assert np.all(res.residuals > -1e8)


def test_callback_svd_method(small_graph):
    nev = 3
    cb, steps, arrays = _callback_recorder(nev)
    n, r, c, v, a = small_graph
    tm = pack_tiles(n, n, r, c, v, block_shape=(64, 64), min_block_nnz=4)
    op = GraphOperator(tm, impl="ref")
    at = GraphOperator(tm, impl="ref")
    res = solve(op, nev, method="svd", at_op=at, tol=1e-6, max_iters=60)
    res_cb = solve(op, nev, method="svd", at_op=at, tol=1e-6, max_iters=60,
                   callback=cb)
    assert len(steps) > 0 and steps == sorted(steps)
    # svd callback reports σ-space values: non-negative, and the final
    # callback σ's match the returned singular values
    sig_last = arrays[-1][0]
    np.testing.assert_allclose(np.sort(sig_last)[::-1][:nev],
                               res_cb.eigenvalues, rtol=1e-4)
    np.testing.assert_allclose(res_cb.eigenvalues, res.eigenvalues,
                               rtol=1e-6)                 # cb didn't perturb


@pytest.mark.disk
def test_callback_on_safs_backend(small_graph, disk_tmp):
    nev = 4
    cb, steps, _ = _callback_recorder(nev)
    store = TieredStore(backend="safs",
                        backend_opts={"root": os.path.join(disk_tmp, "p"),
                                      "cache_bytes": 1 << 20})
    res = solve(_op(small_graph, store=store), nev, method="krylov_schur",
                which="LA", tol=1e-5, max_iters=100, block_size=4,
                store=store, callback=cb)
    store.close()
    assert len(steps) > 0 and steps == sorted(steps)
    assert np.all(np.abs(res.eigenvalues) < 1e8)


# ------------------------------------------------------- traced solves
def test_traced_solve_ram_reconciles(small_graph, tmp_path):
    path = str(tmp_path / "solve.jsonl")
    one_block = small_graph[0] * 4 * 4   # the older blocks spill
    res = solve(_op(small_graph), 4, method="krylov_schur", which="LA",
                tol=1e-5, max_iters=100, block_size=4, trace=path,
                store=TieredStore(device_budget_bytes=one_block))
    assert isinstance(res.trace, Tracer)
    assert trace.active() is None          # uninstalled after the solve
    records = report.load(path)
    assert report.validate(records) == []
    names = {r["name"] for r in records if r.get("type") == "span"}
    assert {"solve", "pass.subspace", "operator.matmat"} <= names
    assert len(report.events(records, "convergence.step")) == res.n_restarts + 1
    rec = report.reconcile(records)
    assert rec["exact"] and rec["lossless"]
    assert rec["span_pass_count"] == rec["iostats_passes"] > 0
    assert rec["span_pass_bytes"] == rec["iostats_pass_bytes_read"] > 0
    # the root span carries the solve outcome
    root = next(r for r in records
                if r.get("type") == "span" and r["name"] == "solve")
    assert root["args"]["converged"] == res.converged
    assert root["args"]["nev"] == 4


def test_traced_solve_accepts_tracer_instance(small_graph):
    t = Tracer()
    res = solve(_op(small_graph), 2, method="lobpcg", tol=1e-4,
                max_iters=300, block_size=8, trace=t)
    assert res.trace is t
    assert t.counts()["spans"] > 0
    assert any(r["name"] == "convergence.step" for r in t.records())


def test_untraced_solve_has_no_trace(small_graph):
    res = solve(_op(small_graph), 2, method="krylov_schur", which="LA",
                tol=1e-4, max_iters=60)
    assert res.trace is None


@pytest.mark.disk
def test_traced_solve_safs_full_timeline(small_graph, disk_tmp, tmp_path):
    """The acceptance timeline: one traced safs solve contains operator
    applies, subspace passes, prefetch waits and write-behind retires,
    plus convergence events — and reconciles byte-exactly."""
    n = small_graph[0]
    store = TieredStore(
        device_budget_bytes=2 * n * 4 * 4, backend="safs",
        backend_opts={"root": os.path.join(disk_tmp, "pages"),
                      "cache_bytes": 3 * n * 4 * 4})
    path = str(tmp_path / "safs_solve.jsonl")
    res = solve(_op(small_graph, store=store), 4, method="krylov_schur",
                which="LA", tol=1e-6, max_iters=100, block_size=4,
                group_size=2, store=store, trace=path)
    snap = store.backend.stats_dict()
    store.close()
    assert set(snap) == {"io", "cache", "prefetch", "write_behind",
                         "namespaces", "integrity"}
    assert snap["integrity"]["pages_verified"] > 0
    assert snap["integrity"]["crc_failures"] == 0
    assert snap["prefetch"]["files_prefetched"] > 0
    assert snap["write_behind"]["pages_retired"] > 0

    records = report.load(path)
    assert report.validate(records) == []
    names = {r["name"] for r in records if r.get("type") == "span"}
    assert {"solve", "operator.matmat", "pass.subspace", "safs.fill",
            "safs.prefetch_wait", "safs.wb.retire"} <= names
    assert len(report.events(records, "convergence.step")) > 0
    rec = report.reconcile(records)
    assert rec["exact"], rec
    # off-thread SAFS work attributed to non-main tids
    wb = [r for r in records if r.get("type") == "span"
          and r["name"] == "safs.wb.retire"]
    solve_span = next(r for r in records if r.get("type") == "span"
                      and r["name"] == "solve")
    assert any(r["tid"] != solve_span["tid"] for r in wb)
    assert res.converged


def test_every_store_call_that_reaches_the_backend_is_a_span():
    t = Tracer()
    store = TieredStore()
    block = np.ones((16, 4), np.float32)
    with tracing(t):
        store.put("a", block, tier=HOST)
        store.put("b", block)             # device tier: no backend call
        store.get("b")                    # device hit: no span
        store.demote("b")
        store.host_pin("b")
        store.prefetch(["a", "b"])
        store.get("a")
        store.delete("a")
        store.close()
    recs = t.records()
    assert [r["name"] for r in recs] == [
        "store.put", "store.demote", "store.host_pin", "store.prefetch",
        "store.get", "store.delete", "store.close"]
    assert {r["type"] for r in recs} == {"span"}


# ------------------------------------------------- the profiler's clock
def _profiled(tmp_path, fn):
    """fn() under the JAX profiler, inside the benchmark's window span;
    returns its result and the trace as `bench.devtrace` reads it."""
    import jax
    from bench import devtrace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = str(tmp_path / "profile")
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            out = fn()
    return out, devtrace.load(log_dir)


def _inside(a, b) -> bool:
    return b.start <= a.start and a.end <= b.end


@pytest.mark.disk
def test_profiled_safs_solve_nests_its_spans_without_a_tracer(
        small_graph, disk_tmp, tmp_path):
    n = small_graph[0]
    store = TieredStore(
        device_budget_bytes=2 * n * 4 * 4, backend="safs",
        backend_opts={"root": os.path.join(disk_tmp, "pages"),
                      "cache_bytes": 3 * n * 4 * 4})

    def run():
        try:
            return solve(_op(small_graph, store=store), 4,
                         method="krylov_schur", which="LA", tol=1e-6,
                         max_iters=2, block_size=4, group_size=2,
                         store=store)
        finally:
            store.close()
    res, tr = _profiled(tmp_path, run)
    assert res.trace is None and trace.active() is None
    (line,) = [h for h in tr.host if any(e.name == "solve" for e in h)]
    (root,) = [e for e in line if e.name == "solve"]
    passes = [e for e in line if e.name == "pass.subspace"]
    gets = [e for e in line if e.name == "store.get"]
    bcgs2 = [e for e in line if e.name == "ortho.bcgs2"]
    assert passes and gets and bcgs2
    assert all(_inside(p, root) for p in passes + bcgs2)
    assert any(_inside(g, p) for g in gets for p in passes)
    assert any(_inside(p, b) for p in passes for b in bcgs2)
    assert {"operator.matmat", "store.demote", "store.prefetch",
            "store.host_pin", "store.close"} <= {e.name for e in line}


def test_traced_solve_under_the_profiler_keeps_its_records(small_graph,
                                                           tmp_path):
    from collections import Counter
    kw = dict(method="krylov_schur", which="LA", tol=1e-5, max_iters=100,
              block_size=4)
    plain = solve(_op(small_graph), 4, trace=Tracer(), **kw).trace
    res, tr = _profiled(
        tmp_path, lambda: solve(_op(small_graph), 4, trace=Tracer(), **kw))

    def kinds(t):
        return [(r["type"], r["name"]) for r in t.records()]
    assert kinds(res.trace) == kinds(plain)
    records = res.trace.export_records()
    assert report.validate(records) == []
    # the same spans reach both sinks
    spans = Counter(r["name"] for r in records if r.get("type") == "span")
    (line,) = [h for h in tr.host if any(e.name == "solve" for e in h)]
    assert Counter(e.name for e in line if e.name in spans) == spans


# ---------------------------------------------------------------- report
def test_report_validate_catches_problems(tmp_path):
    assert report.validate([]) == ["empty trace"]
    bad = [{"type": "meta", "schema": "other/v9"},
           {"type": "span", "name": "s", "ts": 0.0, "dur": -5.0, "args": {}}]
    problems = report.validate(bad)
    assert any("schema" in p for p in problems)
    assert any("negative duration" in p for p in problems)
    # lossless trace with a metrics record that disagrees with its spans
    lying = [
        {"type": "meta", "schema": SCHEMA},
        {"type": "span", "name": report.PASS_SPAN, "ts": 0.0, "dur": 1.0,
         "args": {"bytes": 100}},
        {"type": "metrics", "name": "solve.io", "ts": 2.0,
         "data": {"delta": {"logical": {"passes": 2,
                                        "pass_bytes_read": 999}}}},
        {"type": "summary", "spans": 1, "events": 0, "metrics": 1,
         "dropped": 0},
    ]
    assert any("mismatch" in p for p in report.validate(lying))
    # the same disagreement on a lossy trace is skipped, not failed
    lying[-1]["dropped"] = 7
    assert report.validate(lying) == []


def test_report_cli_roundtrip(small_graph, tmp_path, capsys):
    path = str(tmp_path / "cli.jsonl")
    solve(_op(small_graph), 2, method="krylov_schur", which="LA",
          tol=1e-4, max_iters=60, trace=path)
    assert report.main([path, "--validate"]) == 0
    out = capsys.readouterr().out
    assert "validation OK" in out and "phase breakdown" in out
    with pytest.raises(SystemExit):
        report.main([path, "--chrome", str(tmp_path / "c.json")])
