"""`repro.comm` contract tests: CommConfig serialization round-trips,
registry rejection/did-you-mean, registry completeness (every DP wire
carries a byte model the HLO regression exercises), and the removed
legacy kwargs on PipelineConfig / SimTrainConfig (they must raise a
loud migration error, never silently accept or warn)."""
import argparse
import dataclasses
import os

import pytest

from repro.comm import (CommConfig, Codec, PlaneConfig, get_wire,
                        list_wires, wire_names)
from repro.comm import config as comm_cli
from repro.comm import wires as W
from repro.core import collectives as C
from repro.core.aqsgd import CompressionConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parser():
    ap = argparse.ArgumentParser()
    comm_cli.add_cli_args(ap)
    return ap


SAMPLE_CONFIGS = [
    CommConfig(),
    CommConfig(mode="fp32"),
    CommConfig(dp=PlaneConfig(bits=4)),
    CommConfig(dp=PlaneConfig(bits=4, wire="fp16")),
    CommConfig(mode="directq", fw=PlaneConfig(bits=2),
               bw=PlaneConfig(bits=4), zbuf=PlaneConfig(bits=2),
               dp=PlaneConfig(bits=8, wire="ring-sharded", group_d=256)),
    CommConfig(dp=PlaneConfig(bits=4, chunks=2)),
    CommConfig(dp=PlaneConfig(bits=4, wire="ring-sharded", chunks=4)),
    CommConfig(fw=PlaneConfig(bits=4, stochastic=False),
               bw=PlaneConfig(bits=8, stochastic=False),
               dp=PlaneConfig(bits=4, stochastic=False,
                              error_feedback=False, wire="psum")),
]


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", SAMPLE_CONFIGS)
def test_json_round_trip(cfg):
    assert CommConfig.from_json(cfg.to_json()) == cfg


@pytest.mark.parametrize("cfg", SAMPLE_CONFIGS)
def test_cli_round_trip(cfg):
    """to_flags -> argparse -> from_args reproduces the config exactly
    (the flat-flag surface and the JSON surface agree)."""
    args = _parser().parse_args(cfg.to_flags())
    assert comm_cli.from_args(args) == cfg


def test_comm_config_file_input(tmp_path):
    """--comm-config accepts a path to a JSON file as well as a
    literal string, and wins over the flat flags."""
    cfg = CommConfig(dp=PlaneConfig(bits=4, wire="fp16"))
    p = tmp_path / "comm.json"
    p.write_text(cfg.to_json())
    args = _parser().parse_args(
        ["--dp-wire", "psum", "--comm-config", str(p)])
    assert comm_cli.from_args(args) == cfg
    args = _parser().parse_args(["--comm-config", cfg.to_json()])
    assert comm_cli.from_args(args) == cfg


def test_to_flags_raises_on_flat_inexpressible():
    """The documented contract: to_flags raises (rather than silently
    dropping) settings the flat surface cannot express."""
    with pytest.raises(ValueError, match="buffer_dtype"):
        CommConfig(buffer_dtype="bfloat16").to_flags()
    with pytest.raises(ValueError, match="group_d"):
        CommConfig(fw=PlaneConfig(bits=4, group_d=64)).to_flags()
    with pytest.raises(ValueError, match="backends differ"):
        CommConfig(fw=PlaneConfig(bits=4,
                                  backend="reference")).to_flags()


def test_fw_bits_zero_requires_fp32():
    """bits=0 means uncompressed; a compressed mode must not silently
    substitute a default width."""
    with pytest.raises(ValueError, match="fw.bits=0"):
        CommConfig(mode="aqsgd", fw=PlaneConfig(bits=0))
    assert CommConfig(mode="fp32", fw=PlaneConfig(bits=0)).fw.bits == 0


def test_json_subset_and_unknown_keys():
    c = CommConfig.from_json('{"dp": {"bits": 4, "wire": "fp16"}}')
    assert c.dp.bits == 4 and c.dp.wire == "fp16"
    assert c.fw.bits == 4 and c.mode == "aqsgd"      # defaults kept
    with pytest.raises(ValueError, match="unknown CommConfig key"):
        CommConfig.from_json('{"pd": {"bits": 4}}')
    with pytest.raises(ValueError, match="unknown dp plane key"):
        CommConfig.from_json('{"dp": {"bitz": 4}}')


# ---------------------------------------------------------------------------
# chunked-schedule knob: validation, registry gating, CLI surface
# ---------------------------------------------------------------------------

def test_chunks_invalid_counts_raise_loudly():
    """chunks must be a positive int: zero, negatives, bools, and
    non-ints all raise with the did-you-mean-style hint, at both the
    config layer and the collective's own geometry check."""
    for bad in (0, -1, True, 1.5, "2"):
        with pytest.raises(ValueError,
                           match="did you mean chunks=1"):
            CommConfig(dp=PlaneConfig(bits=4, chunks=bad))
        with pytest.raises(ValueError,
                           match="did you mean chunks=1"):
            C.ring_chunk_bounds(8, bad)


def test_chunks_exceeding_segment_rows_raise():
    """A chunk ships at least one row per hop: K > seg raises with the
    valid range and the nearest legal count."""
    with pytest.raises(ValueError, match=r"exceeds the segment's 8 "
                                         r"rows.*did you mean "
                                         r"chunks=8"):
        C.ring_chunk_bounds(8, 9)
    # ...and through the byte-model entry point, which validates the
    # same geometry even though chunking never changes its answer
    with pytest.raises(ValueError, match="exceeds the segment"):
        C.ring_wire_bytes((6, 8), 4, n=2, chunks=7)
    assert C.ring_wire_bytes((6, 8), 4, n=2, chunks=3) == \
        C.ring_wire_bytes((6, 8), 4, n=2)


def test_chunks_on_non_chunkable_wires_rejected():
    """dp.chunks != 1 on a wire whose collective has no chunked
    schedule (psum, fp16) must raise loudly, naming the chunkable
    wires — never silently ignore the knob."""
    for wire in ("psum", "fp16"):
        with pytest.raises(ValueError,
                           match=r"not supported by wire.*chunkable "
                                 r"wires: ring, ring-sharded.*did "
                                 r"you mean wire='ring'"):
            CommConfig(dp=PlaneConfig(bits=4, wire=wire, chunks=2))
    # chunkable wires accept it
    assert CommConfig(dp=PlaneConfig(bits=4, chunks=2)).dp.chunks == 2
    assert CommConfig(dp=PlaneConfig(
        bits=4, wire="ring-sharded", chunks=3)).dp.chunks == 3


def test_chunkable_flags_match_registry():
    """`chunkable` is a registry property: exactly the ring-family DP
    wires declare it, and the --dp-chunks help text is generated from
    the registry (naming every chunkable wire)."""
    assert [s.name for s in list_wires("dp-grad") if s.chunkable] == \
        ["ring", "ring-sharded"]
    help_text = _parser().format_help()
    assert "--dp-chunks" in help_text
    assert "ring, ring-sharded" in help_text


def test_dp_chunks_cli_and_json_round_trip():
    """--dp-chunks reaches CommConfig.dp.chunks and survives both the
    flag and JSON surfaces (the parametrized round-trip tests cover
    the full-config equality; this pins the knob's plumbing)."""
    args = _parser().parse_args(["--dp-grad-bits", "4",
                                 "--dp-chunks", "4"])
    cfg = comm_cli.from_args(args)
    assert cfg.dp.chunks == 4
    assert "--dp-chunks" in cfg.to_flags()
    assert CommConfig.from_json(cfg.to_json()) == cfg
    rt = CommConfig.from_json('{"dp": {"bits": 4, "chunks": 2}}')
    assert rt.dp.chunks == 2


# ---------------------------------------------------------------------------
# registry: rejection, did-you-mean, completeness
# ---------------------------------------------------------------------------

def test_unknown_wire_did_you_mean():
    with pytest.raises(ValueError, match="did you mean 'ring-sharded'"):
        CommConfig(dp=PlaneConfig(bits=4, wire="ring-shraded"))
    with pytest.raises(ValueError, match="did you mean 'ring'"):
        get_wire("rng")
    # hopeless names still list the registered set
    with pytest.raises(ValueError, match="registered wires: ring"):
        get_wire("qsgd-topk-v2")


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        W.register_wire("ring", summary="dup",
                        wire_bytes=lambda s, b, n: 0)


def test_registry_completeness_dp_byte_models():
    """Every wire registered on the dp-grad plane must carry a
    collective, a simulator, and a positive-int `wire_bytes` model —
    and the HLO worker that pins the models against compiled programs
    (tests/test_hlo_cost.py) must derive its wire list from the
    registry, so a new wire cannot dodge the byte regression."""
    dp = list_wires("dp-grad")
    assert {s.name for s in dp} >= {"ring", "psum", "ring-sharded",
                                    "fp16"}
    for spec in dp:
        assert spec.collective is not None, spec.name
        assert spec.sim_allreduce is not None, spec.name
        for bits in (2, 4, 8):
            b = spec.wire_bytes((128, 256), bits, 4)
            assert isinstance(b, int) and b > 0, (spec.name, bits, b)
    # the measurement worker enrolls wires from the registry itself
    src = open(os.path.join(ROOT, "tests", "workers",
                            "hlo_wire_worker.py")).read()
    assert "wire_names(\"dp-grad\")" in src
    # and the ring/sharded models are the collectives' own
    assert get_wire("ring").wire_bytes((128, 256), 4, 4) == \
        C.ring_wire_bytes((128, 256), 4, n=4)
    assert get_wire("ring-sharded").wire_bytes((128, 256), 4, 4) == \
        C.ring_wire_bytes((128, 256), 4, n=4, sharded=True)


def test_activation_planes_registered():
    """The registry covers all five planes (the unified accounting the
    e2e CSV's plane column and `--list-wires` source)."""
    assert wire_names("fw-activation") == ["ppermute"]
    assert wire_names("bw-gradient") == ["ppermute"]
    assert wire_names("z-buffer") == ["hbm"]
    assert wire_names("kv-cache") == ["paged"]
    assert get_wire("hbm", plane="z-buffer").network is False
    assert get_wire("paged", plane="kv-cache").network is False
    fw = get_wire("ppermute", plane="fw-activation")
    # boundary payload: packed codes + f32 row scales
    assert fw.wire_bytes((8, 64, 512), 4, 1) == \
        8 * 64 * (512 // 2) + 8 * 64 * 4
    kv = get_wire("paged", plane="kv-cache")
    # one grouped append: packed codes + f32 scale per group row;
    # bits=0 falls back to the raw-f32 cache footprint
    assert kv.wire_bytes((8, 1, 4, 64), 8, 1) == 8 * 4 * 64 + 8 * 4 * 4
    assert kv.wire_bytes((8, 1, 4, 64), 0, 1) == 8 * 4 * 64 * 4


# ---------------------------------------------------------------------------
# codec + activation view
# ---------------------------------------------------------------------------

def test_codec_wraps_boundary_ops():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import boundary as B
    codec = Codec(bits=4, stochastic=False, backend="reference")
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
    packed, scale = codec.encode(x)
    pb, sb = B.encode(x, bits=4, stochastic=False, backend="reference")
    np.testing.assert_array_equal(packed, pb)
    np.testing.assert_array_equal(scale, sb)
    np.testing.assert_array_equal(
        codec.decode(packed, scale, d=64),
        B.decode(pb, sb, bits=4, d=64, backend="reference"))
    assert codec.wire_bytes((8, 64)) == 8 * (64 // 2) + 8 * 4
    err = codec.init_state({"w": jnp.zeros((100, 3))}, group_d=32)
    assert err.shape == (-(-300 // 32), 32)


def test_activation_view_matches_legacy_defaults():
    assert CommConfig().activation == CompressionConfig()
    cc = CompressionConfig(mode="directq", fw_bits=2, bw_bits=4,
                           buffer_bits=2, stochastic=False,
                           backend="reference")
    assert CommConfig.from_legacy(cc).activation == cc
    # bw_bits >= 32 (uncompressed backward) round-trips through bits=0
    cc32 = CompressionConfig(bw_bits=32)
    assert CommConfig.from_legacy(cc32).activation == cc32


# ---------------------------------------------------------------------------
# removed legacy kwargs
# ---------------------------------------------------------------------------

def test_pipeline_config_legacy_kwargs_removed():
    """The one-release deprecation shims are gone: passing any
    pre-registry kwarg raises a loud error that names the kwarg and
    points at comm= / from_legacy, and the mirror reader properties no
    longer exist (reads go through comm)."""
    from repro.training import pipeline as PL
    with pytest.raises(TypeError, match=r"dp_wire=.*removed.*"
                                        r"comm=CommConfig"):
        # repro-lint: disable=no-legacy-comm-kwargs (pins the error)
        PL.PipelineConfig(dp_grad_bits=4, dp_wire="ring-sharded",
                          buffer_bits=2)
    with pytest.raises(TypeError, match="compression=.*from_legacy"):
        # repro-lint: disable=no-legacy-comm-kwargs (pins the error)
        PL.PipelineConfig(compression=CompressionConfig(mode="fp32"))
    new = PL.PipelineConfig(comm=CommConfig(
        zbuf=PlaneConfig(bits=2), dp=PlaneConfig(bits=4,
                                                 wire="ring-sharded")))
    assert new.comm.dp.wire == "ring-sharded" and new.comm.zbuf.bits == 2
    # no mirror properties survive — old readers must migrate to comm
    # (the InitVar class attributes remain, but only as inert None
    # defaults for the rejection gate, never comm-derived values)
    for name in ("compression", "buffer_bits", "dp_grad_bits",
                 "dp_grad_group", "dp_wire"):
        assert not isinstance(getattr(type(new), name, None), property)
        assert getattr(new, name, None) is None
    # replace() swaps comm cleanly now that the InitVar defaults are
    # all None (nothing re-raises)
    rep = dataclasses.replace(new, warmup=True)
    assert rep.comm == new.comm and rep.warmup
    swapped = dataclasses.replace(
        new, comm=CommConfig(dp=PlaneConfig(bits=4, wire="psum")))
    assert swapped.comm.dp.wire == "psum" and swapped.comm.zbuf.bits == 0
    assert not hasattr(new, "with_comm")
    assert dataclasses.replace(
        new, comm=swapped.comm).comm.dp.wire == "psum"
    # the sanctioned migration path reproduces the old kwarg semantics
    via_legacy = PL.PipelineConfig(comm=CommConfig.from_legacy(
        None, dp_grad_bits=4, dp_wire="ring-sharded", buffer_bits=2))
    assert via_legacy.comm == new.comm


def test_sim_config_legacy_kwargs_removed():
    from repro.training import simulated as sim
    with pytest.raises(TypeError, match="dp_sharded=.*removed"):
        # deliberate violation: this test pins the rejection error
        sim.SimTrainConfig(  # repro-lint: disable=no-legacy-comm-kwargs
            compression=CompressionConfig(mode="directq", fw_bits=2,
                                          bw_bits=4),
            dp_grad_bits=4, dp_workers=2, dp_sharded=True)
    new = sim.SimTrainConfig(
        comm=CommConfig(mode="directq", fw=PlaneConfig(bits=2),
                        bw=PlaneConfig(bits=4),
                        dp=PlaneConfig(bits=4, wire="ring-sharded")),
        dp_workers=2)
    assert new.comm.dp_wire_spec.sharded is True
    for name in ("compression", "dp_grad_bits", "dp_grad_group",
                 "dp_sharded"):
        assert not isinstance(getattr(type(new), name, None), property)
        assert getattr(new, name, None) is None
    # from_legacy covers the dp_sharded flag via the wire name
    via_legacy = sim.SimTrainConfig(
        comm=CommConfig.from_legacy(
            CompressionConfig(mode="directq", fw_bits=2, bw_bits=4),
            dp_grad_bits=4, dp_wire="ring-sharded"),
        dp_workers=2)
    assert via_legacy.comm == new.comm
    swapped = dataclasses.replace(new,
                                  comm=CommConfig(dp=PlaneConfig(bits=4)))
    assert swapped.comm.dp_wire_spec.sharded is False
    assert swapped.comm.dp.bits == 4 and swapped.dp_workers == 2
    assert not hasattr(new, "with_comm")


def test_fp16_wire_sim_trains():
    """The fp16 passthrough trains in the simulated trainer (finite,
    decreasing) — the registry's sim_allreduce hook end-to-end."""
    import jax
    import math
    from repro.configs.base import get_config
    from repro.data.pipeline import Dataset, DatasetConfig
    from repro.optim.adamw import AdamWConfig
    from repro.training import simulated as sim
    cfg = get_config("gpt2-xl-paper", smoke=True).with_(num_layers=2)
    dc = DatasetConfig(num_samples=16, seq_len=16,
                       vocab_size=cfg.vocab_size)
    tcfg = sim.SimTrainConfig(
        num_stages=2,
        comm=CommConfig(dp=PlaneConfig(bits=4, wire="fp16")),
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=6),
        dp_workers=2)
    _, losses = sim.train(cfg, tcfg, Dataset(dc), num_steps=6,
                          batch_size=4, key=jax.random.PRNGKey(0))
    assert all(map(math.isfinite, losses)), losses
    assert losses[-1] < losses[0], losses
