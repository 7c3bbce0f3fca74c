"""Sharded checkpointing: atomic, async-capable, reshard-on-restore.

Layout:  <dir>/step_<N>/arrays.npz + manifest.json (tree structure,
dtypes, shapes).  Writes go to a ``.tmp`` directory renamed atomically, so
a crash mid-write can never corrupt the latest checkpoint — the
fault-tolerance contract the runtime driver relies on.

``restore`` accepts a target sharding pytree: arrays are ``device_put``
straight into the (possibly different) mesh — this is the elastic-rescale
path (train on (16,16), restore onto (8,16), keep going).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Optional, Tuple

import jax
import numpy as np


_SEP = "::"


def _keystr(path) -> str:
    return jax.tree_util.keystr(path, simple=True, separator=_SEP)


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[_keystr(path)] = leaf
    return flat


def _to_storable(arr: np.ndarray):
    """npz cannot store ml_dtypes (bf16 etc.) — view them as same-width
    uints and record the logical dtype in the manifest."""
    if arr.dtype.kind == "V" or arr.dtype.name in ("bfloat16", "float8_e4m3fn",
                                                   "float8_e5m2"):
        width = arr.dtype.itemsize
        return arr.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[width]), \
            arr.dtype.name
    return arr, arr.dtype.name


def _from_storable(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    if arr.dtype.name != dtype_name:
        import ml_dtypes
        return arr.view(np.dtype(getattr(ml_dtypes, dtype_name)))
    return arr


def save(ckpt_dir: str, step: int, tree, *, blocking: bool = True
         ) -> Optional[threading.Thread]:
    """Write checkpoint for ``step``.  With blocking=False the serialization
    happens on a background thread (async checkpointing); the caller must
    not mutate ``tree`` buffers (jax arrays are immutable — safe)."""
    flat = _flatten(tree)
    host = {}
    logical_dtypes = {}
    for k, v in flat.items():
        arr, dtype_name = _to_storable(np.asarray(v))
        host[k] = arr
        logical_dtypes[k] = dtype_name
    treedef = jax.tree_util.tree_structure(tree)

    def _write():
        final = os.path.join(ckpt_dir, f"step_{step:010d}")
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        np.savez(os.path.join(tmp, "arrays.npz"), **host)
        manifest = {
            "step": step,
            "treedef": str(treedef),
            "keys": {k: {"shape": list(v.shape),
                         "dtype": logical_dtypes[k]}
                     for k, v in host.items()},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)

    if blocking:
        _write()
        return None
    t = threading.Thread(target=_write, daemon=True)
    t.start()
    return t


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(m.group(1)) for d in os.listdir(ckpt_dir)
             if (m := re.fullmatch(r"step_(\d+)", d))]
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, target_tree, *, shardings=None):
    """Restore into the structure of ``target_tree`` (shapes validated).
    ``shardings``: optional pytree of Sharding — arrays are placed onto it
    (the elastic / different-mesh path)."""
    base = os.path.join(ckpt_dir, f"step_{step:010d}")
    with open(os.path.join(base, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(base, "arrays.npz")) as z:
        data = {k: _from_storable(z[k], manifest["keys"][k]["dtype"])
                for k in z.files}
    flat_target = _flatten(target_tree)
    missing = set(flat_target) - set(data)
    extra = set(data) - set(flat_target)
    if missing or extra:
        raise ValueError(f"checkpoint/target mismatch: missing={sorted(missing)[:3]} "
                         f"extra={sorted(extra)[:3]}")
    flat_shard = _flatten(shardings) if shardings is not None else {}

    def rebuild(path_keys, leaf):
        key = _keystr(path_keys)
        arr = data[key]
        want = tuple(leaf.shape)
        if tuple(arr.shape) != want:
            raise ValueError(f"{key}: shape {arr.shape} != {want}")
        if key in flat_shard:
            return jax.device_put(arr, flat_shard[key])
        return jax.device_put(arr)

    return jax.tree_util.tree_map_with_path(rebuild, target_tree)


class CheckpointManager:
    """save-every / keep-last-k / async — the driver-facing API."""

    def __init__(self, ckpt_dir: str, *, keep: int = 3, async_save: bool = True):
        self.dir = ckpt_dir
        self.keep = keep
        self.async_save = async_save
        self._pending: Optional[threading.Thread] = None
        os.makedirs(ckpt_dir, exist_ok=True)

    def save(self, step: int, tree):
        self.wait()
        self._pending = save(self.dir, step, tree,
                             blocking=not self.async_save)
        self._gc(pending_step=step)

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def latest(self) -> Optional[int]:
        return latest_step(self.dir)

    def restore(self, target_tree, *, step: Optional[int] = None,
                shardings=None):
        step = step if step is not None else self.latest()
        if step is None:
            return None, None
        return step, restore(self.dir, step, target_tree, shardings=shardings)

    def _gc(self, pending_step: Optional[int] = None):
        steps = sorted({int(m.group(1)) for d in os.listdir(self.dir)
                        if (m := re.fullmatch(r"step_(\d+)", d))}
                       | ({pending_step} if pending_step is not None else set()))
        doomed = steps[:-self.keep] if self.keep else []
        for s in doomed:
            if s == pending_step:
                continue
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                          ignore_errors=True)
