"""Step-atomic, async, elastic checkpointing.

Layout:  <dir>/step_<N>/
           manifest.json   — tree structure, shapes, dtypes, crc32 per leaf
           <leaf_key>.npy  — one file per pytree leaf
           COMMIT          — written last; restore only sees committed steps

* **async**: ``save`` snapshots arrays to host then writes on a background
  thread — the train loop never blocks on the filesystem (the paper's bulk
  overlap idea applied to checkpoints).
* **atomic**: a step directory without COMMIT is ignored and garbage-
  collected; a crash mid-write can never corrupt restore.
* **elastic**: leaves are stored unsharded; ``restore`` re-device_puts onto
  any mesh/sharding — restart on a different pod count re-shards for free.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import zlib

import numpy as np
import jax



def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    items = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        items.append((key, leaf))
    return items, jax.tree.structure(tree)


class Checkpointer:
    def __init__(self, directory: str, *, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None
        self.save_log: list[tuple[int, float]] = []

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree, *, blocking: bool = False) -> None:
        host = jax.tree.map(lambda x: np.asarray(x), tree)   # device->host now
        self.wait()                                          # one in flight

        def write():
            tmp = os.path.join(self.dir, f".tmp_step_{step}")
            final = os.path.join(self.dir, f"step_{step}")
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
            items, _ = _flatten(host)
            manifest = {"step": step, "leaves": {}}
            for key, leaf in items:
                fn = key.replace("/", "__") + ".npy"
                np.save(os.path.join(tmp, fn), leaf)
                manifest["leaves"][key] = {
                    "file": fn, "shape": list(np.shape(leaf)),
                    "dtype": str(np.asarray(leaf).dtype),
                    "crc": zlib.crc32(np.ascontiguousarray(leaf).tobytes()),
                }
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            with open(os.path.join(tmp, "COMMIT"), "w") as f:
                f.write("ok")
            shutil.rmtree(final, ignore_errors=True)
            os.rename(tmp, final)
            self._gc()

        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write,
                                            name="checkpoint-writer")
            self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = sorted(self.committed_steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"),
                          ignore_errors=True)
        for d in os.listdir(self.dir):                 # orphaned tmp dirs
            if d.startswith(".tmp_step_"):
                shutil.rmtree(os.path.join(self.dir, d), ignore_errors=True)

    # --------------------------------------------------------------- restore
    def committed_steps(self) -> list[int]:
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and os.path.exists(
                    os.path.join(self.dir, d, "COMMIT")):
                out.append(int(d.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.committed_steps()
        return steps[-1] if steps else None

    def restore(self, tree_like, step: int | None = None, *,
                shardings=None, verify: bool = True):
        """Restore into the structure of ``tree_like``; optional shardings
        pytree re-shards every leaf onto the current mesh (elastic)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError("no committed checkpoint")
        d = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        items, treedef = _flatten(tree_like)
        leaves = []
        shard_items = None
        if shardings is not None:
            shard_items, _ = _flatten(shardings)
            shard_map_ = dict(shard_items)
        for key, proto in items:
            meta = manifest["leaves"][key]
            arr = np.load(os.path.join(d, meta["file"]))
            if verify:
                crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
                if crc != meta["crc"]:
                    raise IOError(f"checkpoint leaf {key} corrupt")
            if shardings is not None:
                arr = jax.device_put(arr, shard_map_[key])
            leaves.append(arr)
        return jax.tree.unflatten(treedef, leaves), step
