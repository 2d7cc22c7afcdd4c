"""The trace reduction, on synthetic intervals and on a small trace that
the JAX profiler recorded on a TPU v5e (three kernels, twice each)."""
import os

import pytest

from bench import devtrace
from bench.devtrace import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, s, e):
    return Event(name, float(s), float(e))


def test_union_merges_overlaps_and_clips():
    got = devtrace.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]


def test_gaps_are_the_complement():
    busy = [(1, 3), (5, 12), (20, 25)]
    assert devtrace.gaps(busy, 0, 30) == [(0, 1), (3, 5), (12, 20),
                                          (25, 30)]


def _synthetic():
    ops0 = [ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 10, 30),
            ev("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %b)", 20, 40),
            ev("%gram.3 = f32[32,4]{1,0} custom-call(f32[512,32]{1,0} %c, "
               "f32[512,4]{1,0} %d, f32[1]{0} %e)", 60, 70)]
    ops1 = [ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 10, 90)]
    modules = {"/device:TPU:0": [ev("jit_f(123)", 5, 45),
                                 ev("jit_gram(456)", 55, 75)]}
    spans = [ev("bench.window", 0, 100), ev("bench.solve", 0, 100),
             ev("bench.restart", 40, 80)]
    host = [[ev("PjitFunction(f)", 45, 55), ev("eigh", 80, 95)]]
    return Trace({"/device:TPU:0": ops0, "/device:TPU:1": ops1}, modules,
                 spans, host)


def test_busy_is_the_union_averaged_over_chips():
    tr = _synthetic()
    # chip 0: [10, 40) + [60, 70) = 40 ns; chip 1: [10, 90) = 80 ns
    assert devtrace.busy_ns(tr) == pytest.approx(60.0)


def test_idle_gaps_are_named_by_the_host_and_summed():
    gaps = dict(devtrace.idle_gaps(_synthetic()))
    # chip 0 idles [0,10) [40,60) [70,100)
    assert gaps == pytest.approx({
        "bench.solve": 10e-9,
        "bench.restart:PjitFunction(f)": 20e-9,
        "bench.solve:eigh": 30e-9})


def test_device_op_totals_group_instances():
    tot = dict(devtrace.device_op_totals(_synthetic()))
    assert tot == pytest.approx({"jit_f/fusion": 40e-9,
                                 "jit_gram/gram": 10e-9})


def test_kernel_calls_are_custom_calls_of_that_name():
    (g,) = devtrace.kernel_events(_synthetic(), "gram")
    assert devtrace.shapes(g.name) == (
        ("f32", (32, 4)),
        [("f32", (512, 32)), ("f32", (512, 4)), ("f32", (1,))])
    assert devtrace.kernel_events(_synthetic(), "fusion") == []


def test_innermost_follows_nesting():
    evs = [ev("outer", 0, 100), ev("a", 10, 20), ev("b", 30, 60),
           ev("b.inner", 40, 50)]
    got = devtrace.innermost(evs, [5, 15, 25, 45, 55, 99, 150])
    assert got == ["outer", "a", "outer", "b.inner", "b", "outer", None]


def test_trace_without_a_window_span_is_an_error():
    tr = _synthetic()
    tr.spans = tr.spans[1:]
    with pytest.raises(ValueError):
        tr.window()


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "small.xplane.pb")
    return devtrace.from_profile(ProfileData.from_file(path))


def test_recorded_trace_has_a_window_and_a_busy_chip(recorded):
    lo, hi = recorded.window()
    busy = devtrace.busy_ns(recorded)
    assert len(recorded.device_ops) == 1
    assert 0 < busy < hi - lo


@pytest.mark.parametrize("kernel,result,operands", [
    ("spmm_blocksparse", (8192, 4), [(884,), (884,), (884, 64, 64),
                                     (8192, 4)]),
    ("gram", (32, 4), [(8192, 32), (8192, 4), (1,)]),
    ("tsgemm", (8192, 4), [(8192, 32), (32, 4), (8192, 4), (1,), (1,)])])
def test_recorded_kernels_are_found_with_their_shapes(recorded, kernel,
                                                      result, operands):
    evs = devtrace.kernel_events(recorded, kernel)
    assert evs
    for e in evs:
        res, ops = devtrace.shapes(e.name)
        assert res == ("f32", result)
        assert [d for _, d in ops] == operands


def test_recorded_breakdown_lists_ops_and_gaps(recorded):
    ops = devtrace.device_op_totals(recorded)
    gaps = devtrace.idle_gaps(recorded)
    assert ops and gaps and len(ops) <= 10 and len(gaps) <= 10
    assert all(s > 0 for _, s in ops + gaps)
