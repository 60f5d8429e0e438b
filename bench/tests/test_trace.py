"""The trace reduction and the readers of the per-layer metrics, on a
synthetic trace whose answers are known."""
import types

import pytest

from harness import metrics, trace as tr

# one device: two step programs of 10 ms, 2 ms apart, inside a window
# of [0, 30 ms]; ops inside them (names as the HLO names instructions)
MS = 1e-3
OPS = [("fusion.1", 1 * MS, 4 * MS),            # matmul fusion, 3 ms
       ("jvp_jit_delta_quantize_pack__.3", 4 * MS, 5 * MS),   # codec, 1 ms
       ("copy.2", 5 * MS, 6 * MS),
       ("fusion.1", 6 * MS, 9 * MS),
       ("fusion.1", 13 * MS, 16 * MS),
       ("jvp_jit_delta_quantize_pack__.3", 16 * MS, 17 * MS),
       ("fusion.1", 17.5 * MS, 20.5 * MS)]
MODULES = [("jit_train_step(7)", 1 * MS, 11 * MS),
           ("jit_train_step(7)", 13 * MS, 23 * MS)]
SPANS = [("bench.window", 0.0, 30 * MS),
         ("bench.feed", 9.5 * MS, 12.5 * MS),
         ("bench.train_step", 0.5 * MS, 9.5 * MS)]
TABLE = {"fusion.1": {"kind": "matmul", "flops": 3e9, "count": 1},
         "jvp_jit_delta_quantize_pack__.3": {"kind": "kernel", "flops": 0.0,
                                             "count": 1,
                                             "hbm_bytes": 55_721_984},
         "copy.2": {"kind": "other", "flops": 0.0, "count": 1}}


def test_union_busy_gaps():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ev = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0)]
    assert tr.busy(ev, 0.0, 10.0) == 4.0
    assert tr.busy(ev, 1.5, 5.5) == 2.0
    assert tr.gaps(ev, 0.0, 10.0) == [(3.0, 5.0), (6.0, 10.0)]


def test_event_names_are_instruction_names():
    assert tr.op_name("%fusion.12 = f32[4,1024]{1,0} fusion(%p), "
                      "kind=kLoop, calls=%fc") == "fusion.12"
    assert tr.op_name("%while.496 = (s32[], f32[4]) while(%t)") == \
        "while.496"
    assert tr.op_name("copy.2") == "copy.2"


def test_loops_are_left_out_of_the_top_operations():
    ev = OPS + [("while.4", 0.0, 25 * MS)]
    top = tr.top_ops(ev, 0.0, 30 * MS, skip={"while.4"})
    assert [n for n, _ in top][0] == "fusion.1"


def test_tops_and_attribution():
    top = tr.top_ops(OPS, 0.0, 30 * MS, k=2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(12 * MS)
    gaps = tr.gaps(OPS, 0.0, 30 * MS)
    att = tr.attribute(gaps, SPANS)
    assert att[0] == ["bench.window", pytest.approx(9.5 * MS)]
    assert ["bench.feed", pytest.approx(4 * MS)] in att


def _run():
    return types.SimpleNamespace(
        trace=tr.Trace(ops={0: OPS}, modules={0: MODULES}, spans=SPANS),
        ops=TABLE, steps=2, batch=4, seq=1024, tokens_per_step=4096,
        flops_per_token=1e6, step_times=[0.010, 0.012, 0.011],
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        cell=types.SimpleNamespace(
            config={"d_model": 1600},
            traffic={"fw_bits": 4, "bw_bits": 8, "stochastic": True,
                     "stages": 4}))


def _read(name, run):
    return metrics.read_all([{"name": name}], run)[name]


def test_metric_readers_on_synthetic_trace():
    run = _run()
    assert _read("step_gap_ms", run) == pytest.approx(2.0)
    # step 1 busy: ops within [1, 11] -> [1,6] + [6,9] = 8 ms;
    # step 2: [13,17] + [17.5,20.5] = 7 ms
    assert _read("step_device_ms", run) == pytest.approx(7.5)
    busy = 8 + 7
    assert _read("idle_pct", run) == pytest.approx(100 * (1 - busy / 30))
    assert _read("codec_ms", run) == pytest.approx(1.0)
    assert _read("matmul_roofline", run) == pytest.approx(
        100 * 4 * 3e9 / 1e12 / (12 * MS))
    need = 2 * 55_721_984
    assert _read("codec_roofline", run) == pytest.approx(
        100 * need / 1e11 / (2 * MS))
    assert _read("step_mfu", run) == pytest.approx(
        100 * 1e6 * 4096 * 2 / ((8 + 7) * MS * 1e12))
    assert 11.0 < _read("step_ms_p90", run) <= 12.0
    assert metrics.busy_window(run) == (pytest.approx(busy * MS),
                                        pytest.approx(30 * MS))
    bd = metrics.breakdown(run)
    assert bd["device_ops"][0][0] == "fusion.1"
    assert len(bd["idle_gaps"]) <= 10


def test_readers_return_nothing_without_a_device_trace():
    run = _run()
    run.trace = tr.Trace(spans=SPANS)
    for name in ("step_gap_ms", "step_device_ms", "idle_pct", "codec_ms",
                 "codec_roofline", "matmul_roofline", "step_mfu"):
        assert _read(name, run) is None, name


def test_other_kernels_are_left_out_of_the_codec_roofline():
    run = _run()
    want = _read("codec_roofline", run)
    run.trace.ops[0] = OPS + [("mystery_kernel.3", 25 * MS, 26 * MS)]
    run.ops = dict(TABLE, **{"mystery_kernel.3": {"kind": "kernel",
                                                  "flops": 0.0,
                                                  "count": 1,
                                                  "hbm_bytes": 1e9}})
    assert _read("codec_roofline", run) == pytest.approx(want)
