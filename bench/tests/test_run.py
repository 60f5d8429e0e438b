"""The command line refuses what it cannot measure, and a cell's runner
prints a well-formed result at a size the CPU holds."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax

from conftest import ROOT, smoke_cell
from harness import sim_train

ARGS = ["--workload", "gpt2xl-1chip.aqsgd", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_cli_refuses_a_cpu_and_prints_no_result():
    p = _cli(ROOT)
    assert p.returncode == 3, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""


def _check_result(res, metric_names):
    line = json.dumps(res)
    back = json.loads(line)
    assert list(back)[:3] == ["correct", "attempted", "failed"]
    assert list(back)[-1] == "checks"
    assert set(back["metrics"]) <= set(metric_names)
    for m in back["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in back["checks"].values():
        assert set(c) == {"value", "limit"}
    assert back["attempted"] > 0 and back["failed"] == 0
    assert set(back["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    return back


def test_runner_prints_end_to_end_metrics_at_smoke_size(cpu_peaks):
    cell = smoke_cell("gpt2xl-1chip.aqsgd")
    res = sim_train.run(cell, seed=2 ** 31 + 7, seconds=1.0, trace=False,
                        t_start=time.perf_counter(),
                        devices=jax.devices()[:1], log=lambda *a: None)
    back = _check_result(res, [m["name"] for m in cell.end_to_end])
    assert set(back["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert back["correct"] is True


def test_runner_traced_run_at_smoke_size(cpu_peaks):
    cell = smoke_cell("gpt2xl-1chip.aqsgd")
    res = sim_train.run(cell, seed=11, seconds=1.0, trace=True,
                        t_start=time.perf_counter(),
                        devices=jax.devices()[:1], log=lambda *a: None)
    back = _check_result(res, [m["name"] for m in cell.per_layer])
    # a CPU has no device trace: only the host and counter metrics read
    assert set(back["metrics"]) == {"step_ms_p90", "wire_bytes_per_token"}
    assert back["metrics"]["wire_bytes_per_token"]["value"] == 3 * (
        (128 // 2 + 4) + (128 + 4))
    assert {"busy_s", "window_s"} <= set(back["device"])
    assert set(back["breakdown"]) == {"device_ops", "idle_gaps"}
