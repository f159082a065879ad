"""Fault-tolerant checkpointing: atomic, checksummed, keep-k, elastic re-shard.

Layout: <dir>/step_<k>/  — one .npy per pytree leaf (path-flattened names) plus a
manifest.json holding the treedef, shapes, dtypes, per-leaf CRC32 checksums and
the data-pipeline state. Writes go to <dir>/.tmp_step_<k> and are os.replace'd
into place, so a killed writer never leaves a half-checkpoint that restore would
pick up (restart safety). `keep` prunes old steps after a successful commit.

Restore is defensive: a missing/corrupt manifest, a leaf file that is absent,
truncated or bit-flipped (checksum mismatch), or a shape/dtype drift against the
manifest all raise `CheckpointError` with the offending step and leaf named —
never a deep pytree-mismatch traceback from inside `jax.tree` — so a crashed
restore says WHAT is broken and the caller can fall back to an earlier step
(`all_steps` lists only directories with a committed manifest).

Elastic restore: leaves are loaded host-side and re-placed with `jax.device_put`
against the *current* mesh's NamedShardings (computed from the same logical-axes
tree by the rules engine) — a checkpoint written on any mesh restores onto any
other mesh, including a different device count.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import zlib
from typing import Any

import jax
import numpy as np


class CheckpointError(RuntimeError):
    """A checkpoint is unreadable: missing, truncated, corrupt or mismatched.

    Carries a human-actionable message naming the step and leaf; callers that
    keep multiple steps catch this and fall back to `latest_step` minus one.
    """


def _flatten(tree: Any):
    leaves, treedef = jax.tree.flatten(tree)
    paths = [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for path, _ in jax.tree.flatten_with_path(tree)[0]
    ]
    return leaves, paths, treedef


def save_checkpoint(directory: str, step: int, tree: Any, extra: dict | None = None, keep: int = 3) -> str:
    leaves, paths, _ = _flatten(tree)
    tmp = os.path.join(directory, f".tmp_step_{step}")
    final = os.path.join(directory, f"step_{step}")
    if os.path.exists(tmp):  # leftover from a killed writer — never committed
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "extra": extra or {}, "leaves": []}
    for leaf, path in zip(leaves, paths):
        arr = np.asarray(jax.device_get(leaf))
        fname = path.replace("/", "__") + ".npy"
        fpath = os.path.join(tmp, fname)
        np.save(fpath, arr)
        with open(fpath, "rb") as f:
            crc = zlib.crc32(f.read())
        manifest["leaves"].append({"path": path, "file": fname,
                                   "shape": list(arr.shape),
                                   "dtype": str(arr.dtype), "crc32": crc})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)  # atomic commit
    _prune(directory, keep)
    return final


def _prune(directory: str, keep: int) -> None:
    steps = sorted(all_steps(directory))
    for s in steps[:-keep] if keep else []:
        shutil.rmtree(os.path.join(directory, f"step_{s}"), ignore_errors=True)


def all_steps(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and os.path.exists(os.path.join(directory, name, "manifest.json")):
            out.append(int(m.group(1)))
    return out


def latest_step(directory: str) -> int | None:
    steps = all_steps(directory)
    return max(steps) if steps else None


def _load_manifest(path: str, step: int) -> dict:
    mpath = os.path.join(path, "manifest.json")
    if not os.path.isdir(path) or not os.path.exists(mpath):
        raise CheckpointError(f"no committed checkpoint at step {step}: {path}")
    try:
        with open(mpath) as f:
            return json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        raise CheckpointError(
            f"checkpoint step {step}: manifest.json unreadable ({e})"
        ) from e


def _load_leaf(path: str, step: int, entry: dict) -> np.ndarray:
    """One leaf, verified against its manifest record before it is trusted."""
    fpath = os.path.join(path, entry["file"])
    if not os.path.exists(fpath):
        raise CheckpointError(
            f"checkpoint step {step}: leaf {entry['path']!r} file missing "
            f"({entry['file']})"
        )
    with open(fpath, "rb") as f:
        data = f.read()
    crc = entry.get("crc32")  # pre-checksum checkpoints: skip the CRC gate
    if crc is not None and zlib.crc32(data) != crc:
        raise CheckpointError(
            f"checkpoint step {step}: leaf {entry['path']!r} is corrupt "
            f"(CRC mismatch — truncated or bit-flipped {entry['file']})"
        )
    try:
        arr = np.load(os.path.join(path, entry["file"]))
    except Exception as e:
        raise CheckpointError(
            f"checkpoint step {step}: leaf {entry['path']!r} failed to "
            f"parse ({e})"
        ) from e
    if list(arr.shape) != entry["shape"] or str(arr.dtype) != entry["dtype"]:
        raise CheckpointError(
            f"checkpoint step {step}: leaf {entry['path']!r} is "
            f"{arr.shape} {arr.dtype}, manifest says "
            f"{tuple(entry['shape'])} {entry['dtype']}"
        )
    return arr


def restore_checkpoint(directory: str, step: int, like: Any, shardings: Any | None = None):
    """Restore into the structure of `like` (a pytree of arrays/ShapeDtypeStructs).

    `shardings`: optional matching pytree of NamedShardings for elastic placement
    on the current mesh; None -> plain host arrays. Raises `CheckpointError`
    (never a raw pytree/IO traceback) when the checkpoint is missing, truncated,
    corrupt, or does not cover `like`'s leaves.
    """
    path = os.path.join(directory, f"step_{step}")
    manifest = _load_manifest(path, step)
    by_path = {e["path"]: e for e in manifest["leaves"]}
    _, paths, treedef = _flatten(like)
    missing = [p for p in paths if p not in by_path]
    if missing:
        raise CheckpointError(
            f"checkpoint step {step} does not cover the requested structure; "
            f"missing leaves: {missing}"
        )
    shard_leaves = (
        jax.tree.leaves(shardings) if shardings is not None else [None] * len(paths)
    )
    loaded = []
    for p, sh in zip(paths, shard_leaves):
        arr = _load_leaf(path, step, by_path[p])
        loaded.append(jax.device_put(arr, sh) if sh is not None else arr)
    return jax.tree.unflatten(treedef, loaded), manifest["extra"]
