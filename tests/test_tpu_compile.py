"""Compile-only checks of every boundary-codec kernel for a TPU v5e.

Interpret-mode parity (tests/test_kernels.py) cannot show that Mosaic
lowers a kernel: casts, unsigned reductions, lane reshapes and scoped
VMEM are only checked by the TPU compiler.  These tests compile each
`repro.kernels.quant_pack` kernel with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` topology at gpt2-xl width
(d = 1600, packed widths 400 / 800 / 1600 — not multiples of 128) and
at the DP bucket width (group_d = 512), bits 2 / 4 / 8, plus the ragged
small-row grid `ops._padded_rows` produces; and the three flash
attention kernels (`repro.kernels.flash_attention`) at the gpt2-xl
cell's shapes and at a GQA shape, and pipeline train steps over all
four chips whose dense prefix layer or encoder takes those kernels
outside the trunk's shard_map.  Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, so describing it while
collecting would make the test workers disagree on what exists.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import env
from repro.comm.config import CommConfig
from repro.configs.base import get_config
from repro.core import quantization as Q
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import quant_pack as qp
from repro.optim.adamw import AdamWConfig
from repro.training import pipeline as PL

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


# (B, H, Hk, S, hd, dtype, softcap): the gpt2-xl cell (batch 4 x 1024,
# 25 heads of 64, f32) and gemma2-9b's attention (GQA 16/8, hd 256,
# bf16, softcap 50)
FLASH_SHAPES = {
    "gpt2xl": (4, 25, 25, 1024, 64, jnp.float32, 0.0),
    "gqa": (1, 16, 8, 1024, 256, jnp.bfloat16, 50.0),
}
FLASH_BLOCK_K = 512


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkv",
                                    "flash_bwd_dq"])
@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_kernel_compiles_for_v5e(one_chip, shape, kernel):
    b, h, hk, s, hd, dtype, cap = FLASH_SHAPES[shape]

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, kv = arg((b, h, hd, s), dtype), arg((b, hk, hd, s), dtype)
    pos, window = arg((b, s), jnp.int32), arg((), jnp.int32)
    block_q = fa.block_q_for(s, FLASH_BLOCK_K, hd)
    assert block_q is not None
    st = dict(causal=True, softcap=cap, block_q=block_q,
              block_k=FLASH_BLOCK_K, interpret=False)
    if kernel == "flash_fwd":
        fn = jax.jit(lambda *a: fa.forward(*a, **st))
        args = (q, kv, kv, pos, pos, window)
    else:
        fn = jax.jit(lambda *a: fa.backward(*a, **st))
        args = (q, kv, kv, pos, pos, window, q,
                arg((b, h, 1, s), jnp.float32), q)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and kernel in text, kernel


# arch -> what of it the pipeline runs in its GSPMD section, at a
# sequence of 128 the kernels tile
GSPMD_ATTENTION = {
    "deepseek-moe-16b": dict(),             # its dense first layer
    "whisper-small": dict(encoder_seq=128),  # the audio encoder
}


@pytest.mark.parametrize("arch", sorted(GSPMD_ATTENTION))
def test_pipeline_gspmd_attention_compiles_for_v5e_2x2(topo, monkeypatch,
                                                       arch):
    """deepseek-moe's dense first layer and whisper's encoder run in the
    pipeline step's GSPMD section, outside the trunk's shard_map, over
    all four chips of a (data 2, model 2) mesh.  At a sequence the
    kernels tile, their attention must run them per data shard
    (`layers.rows_over`): GSPMD refuses to partition a Mosaic call."""
    monkeypatch.setattr(env, "pallas_interpret", lambda: False)
    cfg = get_config(arch, smoke=True).with_(**GSPMD_ATTENTION[arch])
    pcfg = PL.PipelineConfig(microbatches=2, comm=CommConfig())
    gb, seq = 4, 128
    assert cfg.first_dense_layers or cfg.family == "audio"
    assert fa.block_q_for(seq, pcfg.block_k, cfg.head_dim) is not None
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    step, meta = PL.make_train_step(cfg, pcfg, mesh, AdamWConfig(),
                                    global_batch=gb, seq_len=seq,
                                    buffer_samples=gb // 2)
    state, batch, key = PL.make_state_structs(cfg, pcfg, meta, mesh,
                                              global_batch=gb,
                                              seq_len=seq)
    key = jax.ShapeDtypeStruct(key.shape, key.dtype,
                               sharding=NamedSharding(mesh, P()))
    text = step.lower(state, batch, key).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert kernel in text, kernel
