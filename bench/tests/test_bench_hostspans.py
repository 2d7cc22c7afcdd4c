"""The split of device idle time over the program's spans, on synthetic
traces and on one the JAX profiler recorded of a small solve on the CPU."""
import types

import pytest

from bench import devtrace, hostspans, readers, run
from bench.devtrace import Event, Trace

LAYERS = ["store", "passes", "operator", "restart"]


def ev(name, s, e):
    return Event(name, float(s), float(e))


def _trace(host):
    """One chip, busy over [10, 20) and [60, 70) of a [0, 100) window, so
    idle over [0, 10), [20, 60) and [70, 100); `host` is the main
    thread's line: program spans and JAX's own events."""
    ops = [ev("%f.1 = f32[4]{0} fusion()", s, e) for s, e in
           ((10, 20), (60, 70))]
    return Trace({"/device:TPU:0": ops}, {},
                 [ev("bench.window", 0, 100), ev("bench.solve", 0, 100)],
                 [host])


def test_a_gap_is_split_at_its_inner_span_not_named_by_its_middle():
    tr = _trace([ev("solve", 5, 95), ev("store.get", 22, 45)])
    got = hostspans.idle_ns_by_layer(tr)
    # [20, 60) idles 23 ns in store.get and 17 ns in the loop around it
    assert got["store"] == pytest.approx(23.0)
    assert got["restart"] == pytest.approx(5.0 + 17.0 + 25.0)
    assert got["other"] == pytest.approx(5.0 + 5.0)
    # the midpoint rule books all 40 ns of that gap to store.get
    mid = dict(devtrace.idle_gaps(tr))
    assert mid["bench.solve:store.get"] == pytest.approx(40e-9)


def test_layers_and_other_sum_to_the_window_idle_time():
    tr = _trace([ev("solve", 0, 90), ev("operator.matmat", 8, 25),
                 ev("ortho.bcgs2", 30, 58), ev("pass.subspace", 31, 50),
                 ev("store.get", 33, 40), ev("safs.fill", 34, 36),
                 ev("store.close", 92, 99)])
    got = hostspans.idle_ns_by_layer(tr)
    assert got == pytest.approx({"store": 7.0 + 7.0, "passes": 3.0 + 18.0,
                                 "operator": 2.0 + 5.0,
                                 "restart": 8.0 + 5.0 + 2.0 + 20.0,
                                 "other": 2.0 + 1.0})
    lo, hi = tr.window()
    assert sum(got.values()) == pytest.approx(
        (hi - lo) - devtrace.busy_ns(tr))


def test_jax_host_events_fall_to_their_enclosing_program_span():
    tr = _trace([ev("solve", 0, 100), ev("pass.subspace", 20, 60),
                 ev("PjitFunction(_pad)", 25, 50), ev("DevicePut", 52, 58),
                 ev("DevicePut", 75, 80)])
    got = hostspans.idle_ns_by_layer(tr)
    assert got["passes"] == pytest.approx(40.0)
    assert got["restart"] == pytest.approx(10.0 + 30.0)
    assert got["other"] == 0.0


def test_a_child_past_its_parent_is_counted_once():
    pieces = hostspans.segments([ev("solve", 0, 50),
                                 ev("store.get", 40, 55)])
    assert pieces == [(0.0, 40.0, "restart"), (40.0, 55.0, "store")]


@pytest.mark.parametrize("host", [
    [ev("PjitFunction(f)", 20, 60), ev("DevicePut", 70, 80)],
    [ev("store.get", 22, 45)],
    [ev("solve", 100, 120)]],
    ids=["jax-events-only", "no-solve", "solve-outside-the-window"])
def test_no_solve_span_in_the_window_reads_none(host):
    tr = _trace(host)
    assert hostspans.idle_ns_by_layer(tr) is None
    record = readers.RunRecord(
        mode="solves", results=[types.SimpleNamespace(n_ops=5)], io=[],
        window_s=1.0, setup_s=1.0, peak_bytes=None, peak={}, trace=tr)
    for layer in LAYERS:
        assert run.load_reader(f"idle_ms_per_apply.{layer}")(record) is None


@pytest.mark.parametrize("layer", LAYERS)
def test_reader_is_idle_ms_over_the_window_applies(layer):
    tr = _trace([ev("solve", 0, 90), ev("operator.matmat", 8, 25),
                 ev("pass.subspace", 30, 50), ev("store.get", 33, 40)])
    record = readers.RunRecord(
        mode="solves", results=[types.SimpleNamespace(n_ops=2),
                                types.SimpleNamespace(n_ops=3)],
        io=[], window_s=1.0, setup_s=1.0, peak_bytes=None, peak={},
        trace=tr)
    got = run.load_reader(f"idle_ms_per_apply.{layer}")(record)
    assert got == pytest.approx(
        hostspans.idle_ns_by_layer(tr)[layer] * 1e-6 / 5)
    assert got > 0
    assert run.load_reader(f"idle_ms_per_apply.{layer}")(
        readers.RunRecord("solves", [], [], 1.0, 1.0, None, {})) is None


def test_a_recorded_solve_is_split_over_every_layer(tmp_path):
    """A SAFS-backed solve recorded by the JAX profiler on the CPU, which
    has no device plane: the whole window counts as idle."""
    import jax
    from repro.core import GraphOperator, TieredStore, solve
    from repro.graphs import normalized_adjacency, pack_tiles, rmat_graph

    n = 600
    r, c, v = normalized_adjacency(n, *rmat_graph(n, 5000, seed=3,
                                                  symmetric=True))
    op = GraphOperator(pack_tiles(n, n, r, c, v, block_shape=(64, 64),
                                  min_block_nnz=4), impl="ref")
    store = TieredStore(device_budget_bytes=2 * n * 4 * 4, backend="safs",
                        backend_opts={"root": str(tmp_path / "pages")})
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = str(tmp_path / "profile")
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            solve(op, 4, which="LA", tol=1e-5, max_iters=2, block_size=4,
                  store=store, impl="ref", group_size=2)
            store.close()
    tr = devtrace.load(log_dir)
    assert not tr.device_ops
    got = hostspans.idle_ns_by_layer(tr)
    lo, hi = tr.window()
    assert sum(got.values()) == pytest.approx(hi - lo)
    assert all(got[layer] > 0 for layer in LAYERS)
    assert got["other"] < got["restart"]
