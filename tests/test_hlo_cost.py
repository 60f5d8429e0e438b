"""Validate the loop-aware HLO cost parser against ground truth:
fully-unrolled compiles (where XLA's own cost_analysis is exact) —
and pin the compressed ring collectives' wire bytes against the
analytic models and the i32-psum baseline (the perf claims)."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as Q
from repro.launch.hlo_cost import hlo_cost
from repro.launch.mesh import make_mesh_auto, shard_map
from workerproc import run_worker


def _compiled(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_flops_match_unrolled_and_analytic():
    def make(unroll):
        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            c, _ = jax.lax.scan(body, x, ws, unroll=unroll)
            return c
        return f

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    expected = 10 * 2 * 128 * 256 * 256
    c_scan = _compiled(make(1), x, ws)
    c_unrl = _compiled(make(True), x, ws)
    f_scan = hlo_cost(c_scan.as_text()).flops
    f_unrl = hlo_cost(c_unrl.as_text()).flops
    assert f_scan == pytest.approx(expected, rel=1e-6)
    assert f_unrl == pytest.approx(expected, rel=1e-6)
    # and XLA's raw number undercounts the scan by the trip count
    ca = c_scan.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert ca["flops"] * 9 < f_scan


def test_nested_scan_trip_products():
    def f(x, ws):
        def outer(c, _):
            def inner(ci, w):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, ws)
            return c, None
        c, _ = jax.lax.scan(outer, x, None, length=5)
        return c

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    expected = 5 * 3 * 2 * 64 ** 3
    got = hlo_cost(_compiled(f, x, ws).as_text()).flops
    assert got == pytest.approx(expected, rel=1e-6)


def test_grad_flops_roughly_triple():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    fwd = hlo_cost(_compiled(loss, w, x).as_text()).flops
    bwd = hlo_cost(_compiled(jax.grad(loss), w, x).as_text()).flops
    assert 2.0 <= bwd / fwd <= 3.6     # fwd + two matmul transposes


def test_collective_bytes_counted_with_trips():
    mesh = make_mesh_auto((1,), ("x",))

    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "x"), None
        c, _ = jax.lax.scan(body, x, None, length=4)
        return c

    smapped = shard_map(f, mesh, jax.sharding.PartitionSpec("x"),
                        jax.sharding.PartitionSpec("x"))
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    cost = hlo_cost(jax.jit(smapped).lower(x).compile().as_text())
    # 4 iterations x (8*128*4) bytes; single-device all-reduce may be
    # elided -> accept either exact or zero-with-note
    expected = 4 * 8 * 128 * 4
    assert cost.coll["all-reduce"] in (0.0, pytest.approx(expected))


@functools.lru_cache(maxsize=1)
def _wire_measurements():
    """One hlo_wire_worker run shared by both wire regressions (the
    subprocess compiles all three collectives at three widths — the
    slowest part of this module)."""
    stdout = run_worker("hlo_wire_worker.py", "run", timeout=900)
    line = [ln for ln in stdout.splitlines()
            if ln.startswith("HLOWIRE ")][0]
    return json.loads(line[len("HLOWIRE "):])


def test_registry_wire_bytes_models_are_exact():
    """EVERY wire registered on the dp-grad plane must have a
    `WireSpec.wire_bytes` model that matches the collective bytes of
    its compiled HLO EXACTLY, at every tested width.  The worker
    derives its wire list from the registry, and this test pins that
    list against the registry too — so registering a new DP wire
    auto-enrolls it here, and a wire cannot land without an exact byte
    model (the fp16 passthrough's 2-byte lanes included)."""
    from repro.comm import wires as W
    out = _wire_measurements()
    assert set(out["wires"]) == set(W.wire_names("dp-grad"))
    for bits in (2, 4, 8):
        row = out["bits"][str(bits)]
        for name in out["wires"]:
            assert row[name] == row["model_" + name], (bits, name, row)


def test_chunked_wire_bytes_equal_monolithic_model():
    """The chunked double-buffered schedule must not change what goes
    on the wire: for every chunkable DP wire, at every tested K
    (including the ragged K=3 at seg=32) and every width, the
    HLO-measured collective bytes of the ``chunks=K`` compile equal
    the MONOLITHIC ``wire_bytes`` model EXACTLY — K slices of the same
    payload, not K payloads, and no hidden padding bytes."""
    from repro.comm import wires as W
    out = _wire_measurements()
    chunkable = [n for n in out["wires"]
                 if W.get_wire(n, plane="dp-grad").chunkable]
    assert chunkable == ["ring", "ring-sharded"]
    for bits in (2, 4, 8):
        row = out["bits"][str(bits)]
        assert set(row["chunked"]) == set(chunkable), row["chunked"]
        for name in chunkable:
            for k in ("2", "3", "4"):
                assert row["chunked"][name][k] == \
                    row["model_" + name], (bits, name, k, row)


def test_fp16_wire_bytes_between_sharded_and_psum():
    """The fp16 passthrough ships exactly rows*d*2 bytes — half the
    psum baseline, independent of the bits knob — and the b-bit codec
    wires stay below it at low widths (the whole point of the codec)."""
    out = _wire_measurements()
    rows, d = out["rows"], out["d"]
    for bits in (2, 4):
        row = out["bits"][str(bits)]
        assert row["fp16"] == rows * d * 2, row
        assert row["ring"] < row["fp16"] < row["psum"], (bits, row)


def test_sharded_wire_collective_bytes_regression():
    """The ZeRO-sharded wire (`ring_ef_reduce_scatter_bucket`) stops at
    the reduce-scatter midpoint, so its HLO collective bytes must
    (a) match `collectives.ring_wire_bytes(..., sharded=True)` EXACTLY
    — only the n-1 packed b-bit segment hops plus the f32 scale pmax —
    and (b) be STRICTLY fewer than the full ring's at every tested b
    (the all-gather of packed code sums vanishes entirely)."""
    out = _wire_measurements()
    n, rows, d = out["n"], out["rows"], out["d"]
    seg = -(-rows // n)
    for bits in (2, 4, 8):
        row = out["bits"][str(bits)]
        assert row["sharded"] == row["model_sharded"], (bits, row)
        # exactly the reduce-scatter half: packed payload + scale pmax
        assert row["model_sharded"] == \
            (n - 1) * seg * Q.packed_width(d, bits) + rows * 4, \
            (bits, row)
        assert row["sharded"] < row["ring"], (bits, row)
        assert row["sharded"] < row["psum"], (bits, row)


def test_ring_wire_collective_bytes_regression():
    """The compressed ring collective must genuinely ship the b-bit
    payload: its HLO collective bytes must (a) match the analytic model
    `collectives.ring_wire_bytes` EXACTLY, and (b) stay at the b-bit
    payload level relative to the i32-psum baseline — <= b/32 of the
    baseline plus the exactness overhead (the packed code-sum
    all-gather at b + ceil(log2 n) bits, and the f32 scale pmax both
    wires pay).  Compiled on a real 4-host-device mesh in a subprocess
    (device count must precede JAX init)."""
    out = _wire_measurements()
    n, rows, d = out["n"], out["rows"], out["d"]
    seg = -(-rows // n)
    scale_bytes = rows * 4
    for bits in (2, 4, 8):
        row = out["bits"][str(bits)]
        # the model is exact — wire accounting in the benchmarks reports
        # the same bytes the compiled program ships
        assert row["ring"] == row["model"], (bits, row)
        # the reduce-scatter half is exactly the b-bit packed payload
        sum_overhead = (n - 1) * seg * Q.sum_packed_width(d, bits, n)
        assert row["ring"] <= row["psum"] * bits / 32.0 \
            + sum_overhead + scale_bytes, (bits, row)
        # and the ring is a strict win over the i32 psum at every width
        assert row["ring"] < row["psum"], (bits, row)


def test_serving_hop_wire_bytes_pinned():
    """The delta decode hop — compiled as a real collective-permute
    crossing (encode_delta -> ppermute codes+scales ->
    decode_accumulate) — must ship EXACTLY the fw-activation ppermute
    wire's modeled bytes over the (B, 1, d) decode shape, and stay
    STRICTLY below the fp16 (and fp32) passthrough hop at every width:
    the serving-plane acceptance gate."""
    out = _wire_measurements()
    hop = out["hop"]
    for bits in (2, 4, 8):
        row = hop[str(bits)]
        assert row["measured"] == row["model"], (bits, row)
        assert row["model"] < hop["fp16"] < hop["fp32"], (bits, hop)


def test_serving_kv_bytes_pinned():
    """The quantized KV append's compiled output buffers (codes + group
    scales — the kv plane's HBM payload) must match the registered
    ``paged`` wire's byte model EXACTLY, and undercut the raw-f32 cache
    at every width.  Enrolment mirrors the DP wires: the worker derives
    the model from the registry, so the kv plane cannot drift from its
    pinned claim."""
    import numpy as np
    out = _wire_measurements()
    kv = out["kv"]
    raw = int(np.prod(kv["shape"])) * 4
    for bits in (2, 4, 8):
        row = kv[str(bits)]
        assert row["measured"] == row["model"], (bits, row)
        assert row["model"] < raw, (bits, row, raw)


def test_every_plane_enrolled_in_byte_regression():
    """Registry completeness: every plane in `repro.comm.wires.PLANES`
    — kv-cache included — has at least one registered wire, and every
    wire of every plane is covered by a byte measurement in THIS
    module's worker output: dp-grad wires by name, the fw/bw ppermute
    pair by the hop crossing, the z-buffer/kv-cache HBM wires by the
    result-bytes compile.  A new plane cannot land unmeasured."""
    from repro.comm import wires as W
    out = _wire_measurements()
    covered = {
        "dp-grad": set(out["wires"]),
        # the hop crossing compiles the ppermute codec both directions
        "fw-activation": {"ppermute"} if "hop" in out else set(),
        "bw-gradient": {"ppermute"} if "hop" in out else set(),
        # HBM planes: z-buffer shares the codec model the hop pins; the
        # kv append is measured directly
        "z-buffer": {"hbm"} if "hop" in out else set(),
        "kv-cache": {"paged"} if "kv" in out else set(),
    }
    for plane in W.PLANES:
        names = set(W.wire_names(plane))
        assert names, plane
        assert names <= covered.get(plane, set()), (plane, names)
