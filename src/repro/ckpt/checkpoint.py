"""Checkpointing: atomic save/restore with manifest + async writer +
elastic resharding on restore.

Layout (one directory per step):

    ckpt_dir/step_000123/
        manifest.json      # step, flat keys, shapes/dtypes, mesh shape
        arrays.npz         # full (unsharded) arrays, keyed by flat path

For this container the host gathers full arrays (addressable shards); on a
real multi-host pod each process would write its addressable shards and the
manifest records the global shape — the restore path already reshards from
full arrays to whatever mesh the new jit uses, which is what elastic
restart needs (profiles are re-keyed per the paper: a profile is only valid
for its axis size).

Writes are atomic (tmp dir + rename); ``AsyncCheckpointer`` overlaps the
serialization with training (device->host copy happens synchronously, disk
write on a worker thread) and keeps the newest K checkpoints.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import threading

import jax
import numpy as np


def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        a = np.asarray(leaf)
        if a.dtype.kind not in "biufc":
            # ml_dtypes (bfloat16, ...) do not survive npz: store raw bits;
            # the manifest keeps the logical dtype for restore
            a = a.view(f"u{a.dtype.itemsize}")
        out[key] = a
    return out, treedef


def save(ckpt_dir, step: int, tree, *, extra: dict | None = None,
         keep: int = 3) -> pathlib.Path:
    d = pathlib.Path(ckpt_dir)
    d.mkdir(parents=True, exist_ok=True)
    tmp = d / f".tmp_step_{step:09d}"
    final = d / f"step_{step:09d}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    arrays, _ = _flatten(tree)
    np.savez(tmp / "arrays.npz", **arrays)
    manifest = {
        "step": step,
        "keys": sorted(arrays.keys()),
        "shapes": {k: list(v.shape) for k, v in arrays.items()},
        "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
        "extra": extra or {},
    }
    (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)
    _gc(d, keep)
    return final


def _gc(d: pathlib.Path, keep: int):
    steps = sorted(p for p in d.glob("step_*") if p.is_dir())
    for p in steps[:-keep]:
        shutil.rmtree(p, ignore_errors=True)


def latest_step(ckpt_dir) -> int | None:
    d = pathlib.Path(ckpt_dir)
    if not d.exists():
        return None
    steps = sorted(int(p.name.split("_")[1]) for p in d.glob("step_*")
                   if p.is_dir())
    return steps[-1] if steps else None


def restore(ckpt_dir, step: int, like_tree):
    """Restore into the structure (and shardings) of ``like_tree`` —
    leaves may be arrays or ShapeDtypeStructs; full arrays are resharded by
    ``jax.device_put`` against the target sharding when present."""
    d = pathlib.Path(ckpt_dir) / f"step_{step:09d}"
    data = np.load(d / "arrays.npz")
    flat, treedef = jax.tree.flatten_with_path(like_tree)
    leaves = []
    for path, like in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        arr = data[key]
        tgt = np.dtype(like.dtype)
        if arr.dtype != tgt and tgt.kind not in "biufc" \
                and arr.dtype.itemsize == tgt.itemsize:
            arr = arr.view(tgt)           # raw-bits round trip (bfloat16)
        if tuple(arr.shape) != tuple(like.shape):
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} vs "
                f"target {like.shape}")
        sharding = getattr(like, "sharding", None)
        if sharding is not None and hasattr(sharding, "mesh"):
            leaves.append(jax.device_put(arr, sharding))
        else:
            leaves.append(jax.numpy.asarray(arr, dtype=like.dtype))
    return jax.tree.unflatten(treedef, leaves)


def manifest(ckpt_dir, step: int) -> dict:
    d = pathlib.Path(ckpt_dir) / f"step_{step:09d}"
    return json.loads((d / "manifest.json").read_text())


class AsyncCheckpointer:
    """Overlap checkpoint writes with training."""

    def __init__(self, ckpt_dir, *, keep: int = 3):
        self.ckpt_dir = pathlib.Path(ckpt_dir)
        self.keep = keep
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def save(self, step: int, tree, *, extra=None):
        self.wait()
        host_tree = jax.tree.map(np.asarray, tree)   # sync device->host

        def work():
            try:
                save(self.ckpt_dir, step, host_tree, extra=extra,
                     keep=self.keep)
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err
