"""The shard_map pipeline's data-parallel gradient wires and MoE expert
parallelism on host devices."""
import pytest

from workerproc import run_worker

pytestmark = pytest.mark.slow          # multi-process workers, minutes


@pytest.mark.parametrize("check", [
    "expert_parallel", "dp_grad_pipeline", "dp_wire_parity",
    "dp_wire_fp16"])
def test_pipeline(check):
    out = run_worker("pipeline_worker.py", check)
    assert f"OK {check}" in out or "OK" in out


def test_moe_expert_parallel_numerics():
    """EP dispatch/weight all_to_all == single-device MoE, E<D and E>=D."""
    out = run_worker("moe_ep_worker.py", "run")
    assert "OK moe_ep" in out
