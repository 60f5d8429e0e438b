"""The shard_map pipeline's activation modes and kernels on host
devices: fp32 equivalence, AQ-SGD and z-bit message buffers, every
architecture family, and the codec kernels under `layers.rows_over`."""
import pytest

from workerproc import run_worker

pytestmark = pytest.mark.slow          # multi-process workers, minutes


@pytest.mark.parametrize("check", [
    "fp32_equivalence", "aqsgd_buffers", "zbit_buffers",
    "modes_all_archs", "kernels_under_rows_over"])
def test_pipeline(check):
    out = run_worker("pipeline_worker.py", check)
    assert f"OK {check}" in out or "OK" in out
