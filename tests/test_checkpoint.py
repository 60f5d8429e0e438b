"""Versioned checkpoint subsystem gates (ISSUE 8 tentpole plane 1).

Covers the manifest protocol end-to-end on small synthetic trees plus
the real simulated-trainer state: bit-exact round-trips (incl. bf16 /
bool / uint32 PRNG key data), fail-closed corruption detection (a
single flipped byte in ``arrays.npz`` OR ``manifest.json`` refuses to
load), loud structure/comm-config diffs instead of bare KeyErrors,
keep-last-k rotation, and crash-residue cleanup.  The distributed
`make_state_structs` round-trip (1-D and 2x2 meshes, both codec
backends) lives in tests/workers/ckpt_worker.py (slow tier).
"""
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.comm import CommConfig


def make_tree():
    """A small tree exercising every dtype class the trainer stores:
    bf16 (ml_dtypes, stored as f32), f32, bool, int32, uint32 key."""
    rng = np.random.default_rng(0)
    return {
        "params": {"w": jnp.asarray(rng.standard_normal((3, 4)),
                                    jnp.bfloat16),
                   "b": jnp.asarray(rng.standard_normal(4),
                                    jnp.float32)},
        "opt": {"mu": jnp.asarray(rng.standard_normal((3, 4)),
                                  jnp.float32),
                "step": jnp.asarray(7, jnp.int32)},
        "seen": jnp.asarray([True, False, True]),
        "k_run": jnp.asarray([123, 456], jnp.uint32),
    }


def assert_trees_bit_equal(a, b):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = {ckpt.checkpoint._leaf_key(p): v
          for p, v in jax.tree_util.tree_flatten_with_path(b)[0]}
    assert len(la) == len(lb)
    for p, va in la:
        vb = lb[ckpt.checkpoint._leaf_key(p)]
        assert np.dtype(va.dtype) == np.dtype(vb.dtype), p
        na, nb = np.asarray(va), np.asarray(vb)
        assert na.tobytes() == nb.tobytes(), p


# ---------------------------------------------------------------------------
# legacy single-file API (hardened)
# ---------------------------------------------------------------------------

def test_legacy_roundtrip(tmp_path):
    tree = make_tree()
    path = str(tmp_path / "params.npz")
    ckpt.save(path, tree)
    out = ckpt.restore(path, jax.eval_shape(lambda: tree))
    assert_trees_bit_equal(tree, out)
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]


def test_legacy_restore_loud_diff(tmp_path):
    tree = make_tree()
    path = str(tmp_path / "params.npz")
    ckpt.save(path, tree)
    like = jax.eval_shape(lambda: tree)
    del like["opt"]["mu"]                        # -> unexpected
    like["extra"] = jax.ShapeDtypeStruct((2,), jnp.float32)  # missing
    like["params"]["b"] = jax.ShapeDtypeStruct((5,), jnp.float32)
    with pytest.raises(ckpt.CheckpointError) as e:
        ckpt.restore(path, like)
    msg = str(e.value)
    assert "missing from checkpoint: extra" in msg
    assert "unexpected in checkpoint: opt/mu" in msg
    assert "shape mismatch: params/b" in msg


# ---------------------------------------------------------------------------
# manifest protocol
# ---------------------------------------------------------------------------

def test_save_state_roundtrip_bit_exact(tmp_path):
    tree = make_tree()
    comm = CommConfig.from_dict({"mode": "aqsgd", "fw": {"bits": 4},
                                 "dp": {"bits": 4, "wire": "ring"}})
    path = ckpt.save_state(str(tmp_path), tree, step=3, comm=comm,
                           extra={"data_position": 3})
    assert os.path.basename(path) == "step_00000003"
    out, body = ckpt.restore_state(str(tmp_path),
                                   jax.eval_shape(lambda: tree),
                                   comm=comm)
    assert_trees_bit_equal(tree, out)
    assert body["step"] == 3
    assert body["extra"]["data_position"] == 3
    assert body["comm"] == comm.to_dict()
    assert body["fingerprint"] == ckpt.tree_fingerprint(tree)


def test_rotation_and_latest(tmp_path):
    tree = make_tree()
    for s in (2, 4, 6, 8):
        ckpt.save_state(str(tmp_path), tree, step=s, keep=2)
    assert ckpt.checkpoint_steps(str(tmp_path)) == [6, 8]
    assert ckpt.latest_step(str(tmp_path)) == 8
    out, body = ckpt.restore_state(str(tmp_path),
                                   jax.eval_shape(lambda: tree), step=6)
    assert body["step"] == 6
    with pytest.raises(ckpt.CheckpointError, match="available"):
        ckpt.resolve_checkpoint(str(tmp_path), step=2)


def test_recommit_same_step(tmp_path):
    """Replay after recovery re-commits an existing step: the new
    content wins and no tmp residue survives."""
    tree = make_tree()
    ckpt.save_state(str(tmp_path), tree, step=5)
    tree2 = jax.tree_util.tree_map(lambda x: x, tree)
    tree2["opt"]["step"] = jnp.asarray(99, jnp.int32)
    ckpt.save_state(str(tmp_path), tree2, step=5)
    out, _ = ckpt.restore_state(str(tmp_path),
                                jax.eval_shape(lambda: tree))
    assert int(out["opt"]["step"]) == 99
    assert not [n for n in os.listdir(tmp_path) if n.startswith(".tmp")]


def test_orphan_cleanup(tmp_path):
    tree = make_tree()
    ckpt.save_state(str(tmp_path), tree, step=1)
    orphan = tmp_path / ".tmp-999-deadbeef"
    orphan.mkdir()
    (orphan / "arrays.npz").write_bytes(b"partial")
    (tmp_path / "old.tmp123.npz").write_bytes(b"legacy partial")
    removed = ckpt.clean_orphans(str(tmp_path))
    assert sorted(removed) == [".tmp-999-deadbeef", "old.tmp123.npz"]
    assert ckpt.checkpoint_steps(str(tmp_path)) == [1]   # untouched
    assert ckpt.clean_orphans(str(tmp_path)) == []


def test_empty_dir_fails_loudly(tmp_path):
    with pytest.raises(ckpt.CheckpointError, match="no committed"):
        ckpt.resolve_checkpoint(str(tmp_path))


# ---------------------------------------------------------------------------
# fail-closed corruption detection (satellite d)
# ---------------------------------------------------------------------------

def _flip_byte(path, offset=None):
    data = bytearray(open(path, "rb").read())
    offset = len(data) // 2 if offset is None else offset
    data[offset] ^= 0xFF
    open(path, "wb").write(bytes(data))


def test_array_byteflip_fails_closed(tmp_path):
    tree = make_tree()
    path = ckpt.save_state(str(tmp_path), tree, step=1)
    _flip_byte(os.path.join(path, ckpt.ARRAYS_NAME))
    with pytest.raises(ckpt.CheckpointError, match="SHA-256 mismatch"):
        ckpt.restore_state(str(tmp_path), jax.eval_shape(lambda: tree))


def test_array_crc_catches_sha_preserving_swap(tmp_path):
    """Per-array CRCs are verified even when someone rewrites the npz
    (and the manifest's npz_sha256) around a corrupted array."""
    tree = make_tree()
    path = ckpt.save_state(str(tmp_path), tree, step=1)
    npz_path = os.path.join(path, ckpt.ARRAYS_NAME)
    with np.load(npz_path) as data:
        flat = dict(data)
    flat["opt/mu"] = flat["opt/mu"] + 1.0
    with open(npz_path, "wb") as f:
        np.savez(f, **flat)
    mpath = os.path.join(path, ckpt.MANIFEST_NAME)
    manifest = json.load(open(mpath))
    import hashlib
    manifest["body"]["npz_sha256"] = hashlib.sha256(
        open(npz_path, "rb").read()).hexdigest()
    manifest["crc32"] = zlib.crc32(
        ckpt.checkpoint._canonical(manifest["body"]))
    json.dump(manifest, open(mpath, "w"), sort_keys=True,
              separators=(",", ":"))
    with pytest.raises(ckpt.CheckpointError,
                       match="CRC32 mismatch on array 'opt/mu'"):
        ckpt.restore_state(str(tmp_path), jax.eval_shape(lambda: tree))


def test_manifest_byteflip_fails_closed(tmp_path):
    tree = make_tree()
    path = ckpt.save_state(str(tmp_path), tree, step=1)
    mpath = os.path.join(path, ckpt.MANIFEST_NAME)
    # flip inside the fingerprint hex string: still valid JSON, so
    # only the manifest's own CRC can catch it
    raw = open(mpath).read()
    fp = json.loads(raw)["body"]["fingerprint"]
    open(mpath, "w").write(raw.replace(fp, "f" * len(fp), 1))
    with pytest.raises(ckpt.CheckpointError, match="manifest CRC"):
        ckpt.restore_state(str(tmp_path), jax.eval_shape(lambda: tree))
    open(mpath, "w").write(raw[: len(raw) // 2])   # truncated JSON
    with pytest.raises(ckpt.CheckpointError, match="corrupt"):
        ckpt.restore_state(str(tmp_path), jax.eval_shape(lambda: tree))


def test_format_version_1_refused(tmp_path):
    """Version 1 stored packed buffers in the interleaved byte layout:
    same shapes and dtypes, different codes — a valid v1 manifest must
    be refused, not unpacked as planar bytes."""
    tree = make_tree()
    path = ckpt.save_state(str(tmp_path), tree, step=1)
    mpath = os.path.join(path, ckpt.MANIFEST_NAME)
    manifest = json.load(open(mpath))
    manifest["body"]["format_version"] = 1
    manifest["crc32"] = zlib.crc32(
        ckpt.checkpoint._canonical(manifest["body"]))
    json.dump(manifest, open(mpath, "w"), sort_keys=True,
              separators=(",", ":"))
    with pytest.raises(ckpt.CheckpointError,
                       match="format_version 1 != supported 2"):
        ckpt.restore_state(str(tmp_path), jax.eval_shape(lambda: tree))


# ---------------------------------------------------------------------------
# loud mismatch diffs (satellite b)
# ---------------------------------------------------------------------------

def test_structure_mismatch_diff_and_fingerprint(tmp_path):
    tree = make_tree()
    ckpt.save_state(str(tmp_path), tree, step=1)
    like = jax.eval_shape(lambda: tree)
    del like["seen"]
    like["dp_error"] = jax.ShapeDtypeStruct((2, 8), jnp.float32)
    with pytest.raises(ckpt.CheckpointError) as e:
        ckpt.restore_state(str(tmp_path), like)
    msg = str(e.value)
    assert "missing from checkpoint: dp_error" in msg
    assert "unexpected in checkpoint: seen" in msg
    assert "fingerprint" in msg
    assert "different model/comm/optimizer configuration" in msg


def test_comm_mismatch_diff(tmp_path):
    tree = make_tree()
    saved = CommConfig.from_dict({"mode": "aqsgd", "fw": {"bits": 4},
                                  "dp": {"bits": 4, "wire": "ring"}})
    live = CommConfig.from_dict({"mode": "aqsgd", "fw": {"bits": 4},
                                 "dp": {"bits": 8, "wire": "psum"}})
    ckpt.save_state(str(tmp_path), tree, step=1, comm=saved)
    with pytest.raises(ckpt.CheckpointError) as e:
        ckpt.restore_state(str(tmp_path), jax.eval_shape(lambda: tree),
                           comm=live)
    msg = str(e.value)
    assert "dp.bits: checkpoint=4 run=8" in msg
    assert "dp.wire: checkpoint='ring' run='psum'" in msg
    # matching comm loads fine
    out, _ = ckpt.restore_state(str(tmp_path),
                                jax.eval_shape(lambda: tree),
                                comm=saved)
    assert_trees_bit_equal(tree, out)


# ---------------------------------------------------------------------------
# real simulated-trainer state (fast-tier slice of satellite c)
# ---------------------------------------------------------------------------

def test_sim_train_state_roundtrip(tmp_path):
    """The FULL single-host state — params, opt, AQ-SGD message
    buffers (raw + seen), dp_error EF stack — survives bit-exactly."""
    from repro.configs.base import get_config
    from repro.training import simulated as sim
    from repro.optim.adamw import AdamWConfig

    comm = CommConfig.from_dict({"mode": "aqsgd", "fw": {"bits": 4},
                                 "bw": {"bits": 8},
                                 "dp": {"bits": 4, "wire": "ring"}})
    cfg = get_config("gpt2-xl-paper", smoke=True)
    tcfg = sim.SimTrainConfig(num_stages=2, comm=comm,
                              optimizer=AdamWConfig(), dp_workers=2)
    state = sim.init_train_state(cfg, tcfg, 16, 32, jax.random.PRNGKey(3))
    ckpt.save_state(str(tmp_path), state, step=11, comm=comm)
    out, body = ckpt.restore_state(
        str(tmp_path), jax.eval_shape(lambda: state), comm=comm)
    assert body["step"] == 11
    assert_trees_bit_equal(state, out)
