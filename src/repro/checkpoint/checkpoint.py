"""Versioned, manifest-based full-state checkpointing.

A checkpoint is a directory ``<dir>/step_00000123/`` holding exactly
two files:

* ``arrays.npz``    — every leaf of the state pytree, path-encoded
  keys (``params/layers/wq`` …), ml_dtypes leaves (bf16/f8) stored as
  f32 and re-cast on restore (exact: f32 is a superset of bf16);
* ``manifest.json`` — a CRC-protected JSON record of the format
  version, the step, the run's ``CommConfig.to_json()`` payload, a
  fingerprint of the state STRUCTURE (sorted (path, shape, dtype)
  triples), per-array CRC32 checksums, the whole-file SHA-256 of
  ``arrays.npz``, and free-form ``extra`` metadata (PRNG key, data
  position, last loss).

Write protocol (crash-safe, satellite of ISSUE 8): stage into a
UNIQUE ``.tmp-<pid>-<uuid>/`` directory inside ``<dir>``, fsync both
files, then ``os.rename`` the staged directory into place and fsync
the parent.  A kill at any point leaves either the previous
checkpoint set intact or an orphaned ``.tmp-*`` directory that
`clean_orphans` removes on startup — a stale tmp can never be renamed
over a good checkpoint (the old single-name ``path + ".tmp"`` scheme
could).  Rotation (``keep`` last k) renames the victim to a tmp name
before deleting, so a crash mid-rotation also degrades to an orphan.

Read protocol (fail closed): the manifest's own CRC, the npz SHA-256,
and every per-array CRC32 are verified BEFORE any value is returned;
a single flipped byte in either file raises :class:`CheckpointError`
naming the corrupt artifact.  Structure mismatches (a checkpoint from
a different config) raise a loud diff of missing / unexpected /
mismatched paths plus both fingerprints — never a bare ``KeyError``
or shape assert.  When the caller passes its live ``CommConfig``, a
differing stored comm config is reported key-by-key.

The legacy single-file API (`save`/`restore` on one ``.npz``) is kept
for params-only export (``launch.train --checkpoint``, benchmarks)
with the same hardened tmp protocol and loud restore errors.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import uuid
import zlib
from typing import Any, Optional

import jax
import numpy as np

# 2: packed z-bit buffers use the planar byte layout of
# docs/WIRE_FORMATS.md; a version-1 checkpoint holds interleaved bytes
# of the same shape that would unpack as wrong codes, so it is refused.
FORMAT_VERSION = 2
ARRAYS_NAME = "arrays.npz"
MANIFEST_NAME = "manifest.json"
STEP_PREFIX = "step_"
TMP_PREFIX = ".tmp-"


class CheckpointError(RuntimeError):
    """A checkpoint could not be written, found, verified, or mapped
    onto the requested state structure.  Always actionable: the
    message names the offending file/paths instead of surfacing a
    bare ``KeyError`` / shape assert from the guts of the loader."""


# ---------------------------------------------------------------------------
# pytree <-> flat dict of numpy arrays
# ---------------------------------------------------------------------------

def _leaf_key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def flatten_tree(tree: Any) -> dict:
    """Flatten a pytree into ``{path-key: np.ndarray}`` (the npz
    payload).  ml_dtypes leaves (bf16/f8 — numpy kind outside
    ``biufc``) are stored as f32; `restore` re-casts them exactly."""
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if arr.dtype.kind not in "biufc":
            arr = arr.astype(np.float32)
        flat[_leaf_key(path)] = arr
    return flat


def _struct_items(tree: Any) -> list:
    """Sorted (key, shape, logical-dtype) triples of a pytree whose
    leaves are arrays OR ShapeDtypeStructs (eval_shape output)."""
    items = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        items.append((_leaf_key(path), tuple(int(s) for s in leaf.shape),
                      str(np.dtype(leaf.dtype))))
    return sorted(items)


def tree_fingerprint(tree: Any) -> str:
    """SHA-256 over the sorted (path, shape, dtype) triples of a
    pytree — the state-STRUCTURE identity the manifest records.  Two
    trees fingerprint equal iff `restore_state` can map one's arrays
    onto the other bit-exactly."""
    blob = json.dumps(_struct_items(tree)).encode()
    return hashlib.sha256(blob).hexdigest()


def _restore_flat(flat: dict, like: Any, *, where: str,
                  stored_fp: Optional[str] = None) -> Any:
    """Map a flat ``{key: array}`` dict onto the structure of `like`.

    Any missing / unexpected / shape-mismatched path fails LOUDLY
    with the full diff and (when known) both structure fingerprints —
    the satellite replacing the old bare KeyError/AssertionError."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    want = {_leaf_key(p): leaf for p, leaf in leaves}
    missing = sorted(set(want) - set(flat))
    unexpected = sorted(set(flat) - set(want))
    mismatched = sorted(
        (k, flat[k].shape, want[k].shape) for k in set(want) & set(flat)
        if tuple(flat[k].shape) != tuple(want[k].shape))
    if missing or unexpected or mismatched:
        lines = [f"checkpoint {where} does not match the requested "
                 f"state structure:"]
        lines += [f"  missing from checkpoint: {k} "
                  f"(want {want[k].shape} {np.dtype(want[k].dtype)})"
                  for k in missing]
        lines += [f"  unexpected in checkpoint: {k} {flat[k].shape}"
                  for k in unexpected]
        lines += [f"  shape mismatch: {k} stored {s} != wanted {w}"
                  for k, s, w in mismatched]
        if stored_fp is not None:
            lines.append(f"  manifest fingerprint {stored_fp} != "
                         f"state-struct fingerprint "
                         f"{tree_fingerprint(like)} — the checkpoint "
                         f"was written by a different model/comm/"
                         f"optimizer configuration")
        raise CheckpointError("\n".join(lines))
    out = [flat[_leaf_key(p)].astype(np.dtype(leaf.dtype))
           for p, leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# durable file primitives
# ---------------------------------------------------------------------------

def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _tmp_name() -> str:
    return f"{TMP_PREFIX}{os.getpid()}-{uuid.uuid4().hex[:12]}"


def clean_orphans(directory: str) -> list:
    """Remove crash residue: ``.tmp-*`` staging entries (and legacy
    ``*.tmp*.npz`` single-file temps) left in ``directory`` by a
    killed writer.  Called on trainer startup; returns the removed
    names.  Committed checkpoints are never touched."""
    removed = []
    if not os.path.isdir(directory):
        return removed
    for name in sorted(os.listdir(directory)):
        p = os.path.join(directory, name)
        if name.startswith(TMP_PREFIX) or (".tmp" in name
                                           and name.endswith(".npz")):
            shutil.rmtree(p) if os.path.isdir(p) else os.remove(p)
            removed.append(name)
    return removed


# ---------------------------------------------------------------------------
# legacy single-file API (params-only export) — hardened
# ---------------------------------------------------------------------------

def save(path: str, tree: Any) -> None:
    """Write one pytree to a single ``.npz`` — atomically: a UNIQUE
    tmp name in the target directory, fsync, then rename.  A kill
    mid-write leaves only an orphan (`clean_orphans` pattern), never
    a partially-written file under the final name, and a later save
    can never rename a STALE tmp over a good checkpoint (the failure
    mode of the old fixed ``path + ".tmp"`` name)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, _tmp_name() + ".npz")
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **flatten_tree(tree))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise
    _fsync_path(d)


def restore(path: str, like: Any) -> Any:
    """Restore a `save` file into the structure of `like`.  Missing /
    unexpected / mis-shaped keys raise a :class:`CheckpointError`
    listing every offending path (never a bare KeyError)."""
    with np.load(path) as data:
        flat = dict(data)
    return _restore_flat(flat, like, where=path)


# ---------------------------------------------------------------------------
# manifest-based versioned checkpoints
# ---------------------------------------------------------------------------

def _ckpt_name(step: int) -> str:
    return f"{STEP_PREFIX}{step:08d}"


def checkpoint_steps(directory: str) -> list:
    """Steps of every COMMITTED checkpoint in ``directory`` (a
    ``step_*`` dir whose manifest file exists), ascending."""
    if not os.path.isdir(directory):
        return []
    steps = []
    for name in os.listdir(directory):
        if not name.startswith(STEP_PREFIX):
            continue
        if os.path.exists(os.path.join(directory, name, MANIFEST_NAME)):
            try:
                steps.append(int(name[len(STEP_PREFIX):]))
            except ValueError:
                continue
    return sorted(steps)


def latest_step(directory: str) -> Optional[int]:
    """The newest committed checkpoint step, or None."""
    steps = checkpoint_steps(directory)
    return steps[-1] if steps else None


def _canonical(body: dict) -> bytes:
    return json.dumps(body, sort_keys=True,
                      separators=(",", ":")).encode()


def _comm_dict(comm) -> Optional[dict]:
    if comm is None:
        return None
    return comm.to_dict() if hasattr(comm, "to_dict") else dict(comm)


def save_state(directory: str, state: Any, *, step: int, comm=None,
               extra: Optional[dict] = None, keep: int = 0) -> str:
    """Commit the FULL train state as checkpoint ``step`` under
    ``directory``; returns the committed path.

    ``comm`` (a `repro.comm.CommConfig`, or its dict) is recorded so
    `restore_state` can refuse a config-mismatched resume with a
    field diff.  ``extra`` is free-form JSON metadata (PRNG key, data
    position, loss).  ``keep > 0`` rotates: after the commit only the
    newest ``keep`` checkpoints survive.  See the module docstring
    for the crash-safety protocol."""
    os.makedirs(directory, exist_ok=True)
    flat = flatten_tree(state)
    tmp = os.path.join(directory, _tmp_name())
    os.makedirs(tmp)
    try:
        npz_path = os.path.join(tmp, ARRAYS_NAME)
        with open(npz_path, "wb") as f:
            np.savez(f, **flat)
            f.flush()
            os.fsync(f.fileno())
        with open(npz_path, "rb") as f:
            npz_sha = hashlib.sha256(f.read()).hexdigest()
        arrays = {}
        for key, shape, dtype in _struct_items(state):
            arr = flat[key]
            arrays[key] = {"shape": list(shape), "dtype": dtype,
                           "stored_dtype": str(arr.dtype),
                           "crc32": zlib.crc32(arr.tobytes())}
        body = {"format_version": FORMAT_VERSION, "step": int(step),
                "comm": _comm_dict(comm),
                "fingerprint": tree_fingerprint(state),
                "arrays": arrays, "npz_sha256": npz_sha,
                "extra": extra or {}}
        manifest = {"crc32": zlib.crc32(_canonical(body)), "body": body}
        mpath = os.path.join(tmp, MANIFEST_NAME)
        with open(mpath, "w") as f:
            json.dump(manifest, f, sort_keys=True,
                      separators=(",", ":"))
            f.flush()
            os.fsync(f.fileno())
        _fsync_path(tmp)
        final = os.path.join(directory, _ckpt_name(step))
        if os.path.exists(final):
            # replay after recovery re-commits an existing step: move
            # the old one aside first (a crash here leaves an orphan,
            # not a loss — the staged replacement is already durable)
            old = os.path.join(directory, _tmp_name())
            os.rename(final, old)
            os.rename(tmp, final)
            shutil.rmtree(old)
        else:
            os.rename(tmp, final)
    except BaseException:
        if os.path.isdir(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
        raise
    _fsync_path(directory)
    if keep > 0:
        for s in checkpoint_steps(directory)[:-keep]:
            victim = os.path.join(directory, _ckpt_name(s))
            doomed = os.path.join(directory, _tmp_name())
            os.rename(victim, doomed)     # crash here -> orphan
            shutil.rmtree(doomed)
    return os.path.join(directory, _ckpt_name(step))


def _load_manifest(ckpt_path: str) -> dict:
    mpath = os.path.join(ckpt_path, MANIFEST_NAME)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        raise CheckpointError(f"{ckpt_path}: no {MANIFEST_NAME} — not "
                              f"a committed checkpoint")
    except json.JSONDecodeError as e:
        raise CheckpointError(f"{mpath}: manifest is corrupt (JSON "
                              f"parse failed: {e}); refusing to load")
    body, crc = manifest.get("body"), manifest.get("crc32")
    if body is None or crc != zlib.crc32(_canonical(body)):
        raise CheckpointError(f"{mpath}: manifest CRC mismatch — the "
                              f"file was corrupted after commit; "
                              f"refusing to load")
    if body.get("format_version") != FORMAT_VERSION:
        raise CheckpointError(
            f"{mpath}: format_version {body.get('format_version')!r} "
            f"!= supported {FORMAT_VERSION}")
    return body


def resolve_checkpoint(directory: str,
                       step: Optional[int] = None) -> str:
    """Path of the checkpoint to restore: ``directory`` itself if it
    IS a committed checkpoint, else its newest (or ``step``-selected)
    ``step_*`` child.  No committed checkpoint raises loudly."""
    if os.path.exists(os.path.join(directory, MANIFEST_NAME)):
        return directory
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise CheckpointError(
                f"{directory}: no committed checkpoint found "
                f"(nothing matching {STEP_PREFIX}*/{MANIFEST_NAME})")
    path = os.path.join(directory, _ckpt_name(step))
    if not os.path.exists(os.path.join(path, MANIFEST_NAME)):
        raise CheckpointError(f"{path}: no committed checkpoint at "
                              f"step {step}; available: "
                              f"{checkpoint_steps(directory)}")
    return path


def _diff_comm(stored: dict, live: dict) -> list:
    diffs = []

    def walk(a, b, prefix):
        for k in sorted(set(a) | set(b)):
            va, vb = a.get(k), b.get(k)
            if isinstance(va, dict) and isinstance(vb, dict):
                walk(va, vb, f"{prefix}{k}.")
            elif va != vb:
                diffs.append(f"  {prefix}{k}: checkpoint={va!r} "
                             f"run={vb!r}")
    walk(stored, live, "")
    return diffs


def restore_state(directory: str, like: Any, *,
                  step: Optional[int] = None, comm=None):
    """Load and VERIFY a committed checkpoint into the structure of
    ``like``; returns ``(state, manifest_body)``.

    Verification is fail-closed, in order: manifest CRC, whole-file
    npz SHA-256, per-array CRC32, structure fingerprint (mismatch
    raises the missing/unexpected/mismatched diff of `_restore_flat`),
    and — when ``comm`` is given — the stored `CommConfig` (mismatch
    raises a field-by-field diff).  A checkpoint that fails ANY check
    raises :class:`CheckpointError`; garbage is never returned."""
    path = resolve_checkpoint(directory, step)
    body = _load_manifest(path)
    npz_path = os.path.join(path, ARRAYS_NAME)
    try:
        with open(npz_path, "rb") as f:
            raw = f.read()
    except FileNotFoundError:
        raise CheckpointError(f"{path}: {ARRAYS_NAME} is missing")
    if hashlib.sha256(raw).hexdigest() != body["npz_sha256"]:
        raise CheckpointError(
            f"{npz_path}: SHA-256 mismatch vs manifest — the array "
            f"payload was corrupted after commit; refusing to load")
    with np.load(npz_path) as data:
        flat = dict(data)
    for key, meta in body["arrays"].items():
        if key not in flat:
            continue                       # structure diff handles it
        if zlib.crc32(flat[key].tobytes()) != meta["crc32"]:
            raise CheckpointError(
                f"{npz_path}: CRC32 mismatch on array {key!r} — "
                f"corrupt payload; refusing to load")
    if comm is not None and body.get("comm") is not None:
        live = _comm_dict(comm)
        if live != body["comm"]:
            raise CheckpointError(
                "checkpoint comm config != this run's comm config:\n"
                + "\n".join(_diff_comm(body["comm"], live))
                + "\n  pass the checkpoint's config (or a fresh "
                  "--ckpt-dir) to proceed")
    state = _restore_flat(flat, like, where=path,
                          stored_fp=body["fingerprint"])
    return state, body
