"""``attn_kernel_ms``: the attention core's flash kernels by name, read
on the synthetic trace of `test_trace`."""
import pytest

from harness import trace as tr
from kernels import flash_attention as F
from kernels import quant_pack as Q

from test_trace import MS, OPS, TABLE, _read, _run

FLASH_OPS = [("flash_fwd.3", 9 * MS, 10 * MS),          # 1 ms
             ("flash_bwd_dkv.1", 20.5 * MS, 22 * MS),   # 1.5 ms
             ("flash_bwd_dq.2", 22 * MS, 22.5 * MS)]    # 0.5 ms
KERNEL = {"kind": "kernel", "flops": 0.0, "count": 1, "hbm_bytes": 1e6}


def _flash_run():
    run = _run()
    run.trace.ops[0] = OPS + FLASH_OPS
    run.ops = dict(TABLE, **{n: KERNEL for n, _, _ in FLASH_OPS})
    return run


def test_kernel_names():
    assert [F.kernel_of(n) for n, _, _ in FLASH_OPS] == list(F.FLASH)
    assert F.kernel_of("jvp_jit_delta_quantize_pack__.3") is None
    assert F.kernel_of("fusion.1") is None
    # no flash kernel leaks into the codec's time
    assert all(Q.kernel_of(n) not in Q.CODEC for n, _, _ in FLASH_OPS)


def test_attn_kernel_ms_sums_the_three_kernels_per_step():
    run = _flash_run()
    assert _read("attn_kernel_ms", run) == pytest.approx(1.5)
    assert _read("codec_ms", run) == pytest.approx(1.0)


def test_attn_kernel_ms_reads_nothing_without_the_kernels():
    assert _read("attn_kernel_ms", _run()) is None
    run = _flash_run()
    run.trace = tr.Trace(spans=run.trace.spans)
    assert _read("attn_kernel_ms", run) is None
