"""Distributed runtime tests — run in subprocesses because the host
device count must be set before JAX initializes.  The pipeline's
modes, DP wires and CLI wire runs live in `test_pipeline_modes.py`,
`test_pipeline_dp.py` and `test_dp_wire.py`."""
import pytest

from workerproc import run_worker

pytestmark = pytest.mark.slow          # multi-process workers, minutes


def test_checkpoint_state_structs_roundtrip():
    """Every struct `make_state_structs` emits — dense and ZeRO
    segment-sharded opt moments, eval_shape-derived dp_error, raw and
    z-bit buffer dtypes, quantized opt state — survives
    save -> restore bit-identically on a 1-D and a 2x2 mesh, both
    codec backends."""
    out = run_worker("ckpt_worker.py", "run")
    assert "OK ckpt_roundtrip" in out


def test_dryrun_smoke_mesh():
    """A reduced-config dry-run on a small in-container mesh proves the
    launch path end-to-end (the full 256/512-chip dry-runs are run via
    `python -m repro.launch.dryrun`, recorded in EXPERIMENTS.md)."""
    out = run_worker("dryrun_worker.py", "smoke")
    assert "DRYRUN OK" in out
