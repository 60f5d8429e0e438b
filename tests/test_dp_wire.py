"""The DP gradient wire against the simulation, the compressed psum,
and the wires through the real `launch.train --distributed` CLI."""
import os
import subprocess
import sys

import pytest

from workerproc import ROOT, run_worker

pytestmark = pytest.mark.slow          # multi-process workers, minutes


def test_launch_train_fp16_wire():
    """The registry-only fp16 DP wire trains end-to-end through the
    real `launch.train` CLI (the acceptance path: a wire that exists
    ONLY as a registry entry reaches the distributed trainer)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--distributed", "--data-par", "2", "--stages", "2",
         "--steps", "3", "--batch", "4", "--samples", "8",
         "--seq", "32", "--microbatches", "2",
         "--dp-grad-bits", "4", "--dp-wire", "fp16"],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "final loss" in r.stdout


def test_launch_train_chunked_ring_identical_losses():
    """`--dp-chunks 2` (the double-buffered chunked ring) through the
    real `launch.train` CLI produces the IDENTICAL printed loss stream
    as the monolithic `--dp-chunks 1` run — chunking is scheduling
    only, so with deterministic rounding every step loss matches to
    the printed digit."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    outs = {}
    for chunks in ("1", "2"):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--smoke",
             "--distributed", "--data-par", "2", "--stages", "2",
             "--steps", "3", "--batch", "4", "--samples", "8",
             "--seq", "32", "--microbatches", "2", "--no-stochastic",
             "--dp-grad-bits", "4", "--dp-wire", "ring",
             "--dp-chunks", chunks],
            capture_output=True, text=True, timeout=900, env=env)
        assert r.returncode == 0, \
            f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
        outs[chunks] = [ln for ln in r.stdout.splitlines()
                        if "loss" in ln]
    assert outs["1"], outs
    assert outs["1"] == outs["2"], (outs["1"], outs["2"])


def test_quantized_psum_mean():
    """b-bit compressed allreduce: replica-consistent and unbiased."""
    out = run_worker("collectives_worker.py", "run")
    assert "OK collectives" in out


def test_dp_grad_wire_matches_simulation():
    """Both error-feedback compressed DP gradient wires — the i32-lane
    code psum and the bandwidth-optimal compressed ring (packed b-bit
    segments on rotation ppermutes + fused local unpack-accumulate) —
    match `grad_compress.compress_allreduce` bit-for-bit, on both
    backends, across ring sizes {2, 3, 5, 8} and compound pod x data
    axes (2x2, 2x3) including non-power-of-two ragged segments."""
    out = run_worker("dp_grad_worker.py", "run")
    assert "OK dp_grad" in out
