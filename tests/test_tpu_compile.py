"""Compile-only checks of every boundary-codec kernel for a TPU v5e.

Interpret-mode parity (tests/test_kernels.py) cannot show that Mosaic
lowers a kernel: casts, unsigned reductions, lane reshapes and scoped
VMEM are only checked by the TPU compiler.  These tests compile each
`repro.kernels.quant_pack` kernel with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` topology at gpt2-xl width
(d = 1600, packed widths 400 / 800 / 1600 — not multiples of 128) and
at the DP bucket width (group_d = 512), bits 2 / 4 / 8, plus the ragged
small-row grid `ops._padded_rows` produces.  Nothing runs; a compile
that passes is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, so describing it while
collecting would make the test workers disagree on what exists.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quantization as Q
from repro.kernels import ops
from repro.kernels import quant_pack as qp

ROWS = 4096          # batch x seq of one microbatch (4 x 1024)
N_WORKERS = 4        # DP ring size: code sums packed at 4 / 8 / 16 bits


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _args(sharding, rows: int, d: int, bits: int):
    """name -> (kernel, positional shapes, static kwargs)."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pw = Q.packed_width(d, bits)
    spw = Q.sum_packed_width(d, bits, N_WORKERS)
    x, scale = s((rows, d), jnp.float32), s((rows, 1), jnp.float32)
    packed, codes = s((rows, pw), jnp.uint8), s((rows, d), jnp.int32)
    n = {"n": N_WORKERS}
    return {
        "delta_quantize_pack": (qp.delta_quantize_pack, (x, x, x), {}),
        "dequant_unpack_accumulate": (qp.dequant_unpack_accumulate,
                                      (packed, scale, x), {}),
        "quantize_pack": (qp.quantize_pack, (x, x), {}),
        "unpack_dequant": (qp.unpack_dequant, (packed, scale), {}),
        "quantize_pack_scaled": (qp.quantize_pack_scaled,
                                 (x, scale, x), {}),
        "unpack_codes": (qp.unpack_codes, (packed,), {}),
        "dequant_sum_mean": (qp.dequant_sum_mean, (codes, scale), n),
        "quantize_codes_scaled": (qp.quantize_codes_scaled,
                                  (x, scale, x), {"pack": True}),
        "unpack_accumulate": (qp.unpack_accumulate, (packed, codes), {}),
        "pack_sums": (qp.pack_sums, (codes,), n),
        "unpack_sums": (qp.unpack_sums,
                        (s((rows, spw), jnp.uint8),), n),
    }


KERNELS = sorted(_args(None, 8, 512, 4))


def _compile(sharding, name: str, rows: int, d: int, bits: int):
    fn, args, kw = _args(sharding, rows, d, bits)[name]
    compiled = fn.lower(*args, bits=bits, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_every_kernel_is_covered():
    jitted = {f for f in dir(qp) if not f.startswith("_")
              and callable(getattr(getattr(qp, f), "lower", None))}
    assert jitted == set(KERNELS)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("d", [1600, 512])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name, d, bits):
    _compile(one_chip, name, ROWS, d, bits)


@pytest.mark.parametrize("name", KERNELS)
def test_ragged_rows_compile_for_v5e(one_chip, name):
    rows = ops._padded_rows(20, qp.DEFAULT_BLOCK_R)
    assert rows < qp.DEFAULT_BLOCK_R
    _compile(one_chip, name, rows, 1600, 4)


@pytest.mark.parametrize("name", ["delta_quantize_pack", "quantize_pack",
                                  "quantize_codes_scaled"])
def test_oncore_prng_encode_compiles_for_v5e(one_chip, name):
    """The opt-in on-core PRNG path (seed instead of a noise tensor)."""
    fn, args, kw = _args(one_chip, ROWS, 1600, 4)[name]
    seed = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    args = args[:-1]                     # drop the noise tensor
    compiled = fn.lower(*args, bits=4, seed=seed, interpret=False,
                        **kw).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
