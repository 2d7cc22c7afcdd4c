"""The harness end to end on the CPU: no result without a TPU, and a
whole run (set-up, window, check) at a tiny size with the chip check
skipped."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run

from bench.tests.small import SMALL, small_cell


def _run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "knn-ram",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run_cli(run.ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    out = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and "{" not in out.stdout


@pytest.mark.parametrize("name", sorted(SMALL))
def test_a_small_run_is_correct_and_reports_its_metrics(name):
    cell = small_cell(name)
    out = run.execute(cell, 2 ** 31 + 99, 0.5, False, impl="ref",
                      graph_overrides=SMALL[name])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.limits)
    want = {m["name"] for m in cell.metrics} - {"peak_hbm_gb"}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_whole_solves_cycle_through_one_set_of_start_blocks():
    cell = run.load_cell("friendster-spill", False)
    pool = cell.traffic["start_blocks"]
    a, b = (run.start_seeds(s, cell) for s in (2 ** 31 + 1, 2 ** 31 + 2))
    assert sorted(a[:pool]) == sorted(b[:pool]) and a[:pool] != b[:pool]
    assert len(set(a[:pool])) == pool
    assert a[pool:2 * pool] == a[:pool]
    assert a == run.start_seeds(2 ** 31 + 1, cell)


def test_capped_solves_draw_every_start_block_from_the_seed():
    cell = run.load_cell("knn-ram", False)
    a, b = (run.start_seeds(s, cell) for s in (2 ** 31 + 1, 2 ** 31 + 2))
    assert not set(a[:8]) & set(b[:8])
    assert len(set(a)) == len(a)
