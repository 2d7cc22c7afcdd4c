"""friendster-hbm at a CPU size: whole solves with the whole subspace and
its restart in HBM are correct, report the cell's end-to-end metrics, and
read every block from HBM with nothing evicted. Its configuration is
friendster's graph and solver under another source, so the pair differs
in the traffic's device budget alone."""
import dataclasses
import json
import os

from bench import run

COUNTERS = ("hbm_hit_pct", "evicted_mb_per_solve")


def test_small_hbm_run_is_correct_and_never_leaves_hbm():
    cell = run.load_cell("friendster-hbm", False)
    traced = run.load_cell("friendster-hbm", True)
    cell = dataclasses.replace(cell, metrics=cell.metrics + [
        m for m in traced.metrics if m["name"] in COUNTERS])
    out = run.execute(cell, 2 ** 31 + 16, 0.5, False, impl="ref",
                      graph_overrides={"log2n": 11})
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["checks"]) == set(cell.limits)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"solve_s", "setup_s", *COUNTERS}
    assert m["solve_s"] > 0 and m["setup_s"] > 0
    assert m["hbm_hit_pct"] == 100.0
    assert m["evicted_mb_per_solve"] == 0.0


def test_configuration_is_friendsters_but_for_its_source():
    def load(name):
        with open(os.path.join(run.ROOT, "bench", "configs",
                               f"{name}.json")) as f:
            return json.load(f)
    own = ("name", "source", "deployment")
    hbm, base = load("friendster-hbm"), load("friendster")
    assert set(hbm) == set(base)
    assert {k: v for k, v in hbm.items() if k not in own} == \
        {k: v for k, v in base.items() if k not in own}
