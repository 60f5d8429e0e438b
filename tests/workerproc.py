"""Shared by the test files that run the program in a subprocess: a
script of ``tests/workers`` (the host device count must be set before
JAX initializes), and the loss lines of a `launch.train` run."""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_worker(script, arg, timeout=1500):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "workers", script),
         arg],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


def loss_lines(stdout):
    """The ``step N loss`` and ``final loss`` lines of a run's output."""
    return [ln for ln in stdout.splitlines()
            if re.match(r"(step\s+\d+ loss|final loss)", ln)]
