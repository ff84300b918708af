"""Atomic, async-capable, resharding checkpointer.

Format: one directory per step —
    ckpt_dir/step_000123/
        meta.json                 (step, flat key list, dtypes, shapes)
        <flat-key>.npy            (one file per leaf)
    ckpt_dir/step_000123.done     (commit marker)

Writes go to ``step_X.tmp`` and are renamed after the commit marker is
fsynced — a crash mid-write never corrupts the latest checkpoint (restore
scans for the newest ``.done`` whose directory actually holds a
``meta.json``, falling back past stale markers left by an interrupted
re-save; orphaned ``step_X.tmp`` buffers are GC'd on construction).
``save_async`` runs the serialization on a worker thread so the train
loop only pays for the host transfer; ``wait`` re-raises whatever that
thread raised, so a failed save fails the run.

Elastic restore: leaves are stored unsharded; ``restore`` device_puts them
under whatever shardings the *current* mesh dictates, so restarting on a
different DP/TP degree re-shards transparently. (A production deployment
would write per-shard files + a global index; the commit protocol and the
re-shard path are the load-bearing parts and are identical.)
"""

from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import ml_dtypes
import numpy as np

_SEP = "::"

# numpy can't serialize ml_dtypes (bf16, fp8) via np.save — store the raw
# bit pattern in a same-width integer view and record the logical dtype.
_EXOTIC_TO_STORAGE = {
    np.dtype(ml_dtypes.bfloat16): np.uint16,
    np.dtype(ml_dtypes.float8_e4m3fn): np.uint8,
    np.dtype(ml_dtypes.float8_e5m2): np.uint8,
}
_NAME_TO_EXOTIC = {str(d): d for d in _EXOTIC_TO_STORAGE}


def _flatten(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = _SEP.join(
            str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)
        out.append((key, leaf))
    return out


class Checkpointer:
    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None
        # GC orphaned write buffers from a previous crashed save: a
        # step_X.tmp dir is by construction uncommitted and unreadable.
        for name in os.listdir(directory):
            if name.startswith("step_") and name.endswith(".tmp"):
                path = os.path.join(directory, name)
                if os.path.isdir(path):
                    shutil.rmtree(path)

    # ------------------------------------------------------------------ #
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    def save(self, step: int, tree, extra: Optional[Dict] = None) -> str:
        """Blocking atomic save."""
        final = self._step_dir(step)
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(tree)
        meta = {"step": step, "keys": [], "extra": extra or {}}
        for key, leaf in flat:
            arr = np.asarray(jax.device_get(leaf))
            fname = key.replace("/", "_") + ".npy"
            logical = str(arr.dtype)
            storage = _EXOTIC_TO_STORAGE.get(arr.dtype)
            np.save(os.path.join(tmp, fname),
                    arr.view(storage) if storage else arr)
            meta["keys"].append(
                {"key": key, "file": fname, "dtype": logical,
                 "shape": list(arr.shape)})
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
        done = final + ".done"
        if os.path.exists(final):
            # Re-save of an existing step: drop the commit marker before
            # touching the directory, so a crash inside the swap window
            # leaves no marker pointing at a missing/partial checkpoint.
            if os.path.exists(done):
                os.remove(done)
            shutil.rmtree(final)
        os.rename(tmp, final)
        with open(done, "w") as f:
            f.write(str(step))
            f.flush()
            os.fsync(f.fileno())
        return final

    def save_async(self, step: int, tree, extra: Optional[Dict] = None):
        """Non-blocking save: transfers to host now, writes on a thread."""
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                 tree)
        self.wait()

        def write():
            try:
                self.save(step, host_tree, extra)
            except Exception as e:  # noqa: BLE001 — re-raised by wait()
                self._error = e

        self._thread = threading.Thread(target=write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        """Block until the in-flight async save ends; re-raise its error."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        error, self._error = self._error, None
        if error is not None:
            raise error

    # ------------------------------------------------------------------ #
    def _committed_steps(self) -> List[int]:
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and name.endswith(".done"):
                try:
                    steps.append(int(name[len("step_"):-len(".done")]))
                except ValueError:
                    continue
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        """Newest step that is both committed (``.done``) and readable
        (``meta.json`` present).  A stale marker left by an interrupted
        re-save is skipped, falling back to the next-newest step."""
        for s in reversed(self._committed_steps()):
            if os.path.isfile(os.path.join(self._step_dir(s), "meta.json")):
                return s
        return None

    def restore(self, step: Optional[int] = None, target=None,
                shardings=None) -> Tuple[Any, Dict]:
        """Returns (tree, extra). ``target`` provides the tree structure;
        ``shardings`` (same structure) re-shards onto the current mesh."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        d = self._step_dir(step)
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        by_key = {e["key"]: e for e in meta["keys"]}

        def _load(e):
            arr = np.load(os.path.join(d, e["file"]))
            exotic = _NAME_TO_EXOTIC.get(e["dtype"])
            return arr.view(exotic) if exotic is not None else arr

        if target is None:
            # reconstruct flat dict
            out = {e["key"]: _load(e) for e in meta["keys"]}
            return out, meta.get("extra", {})

        flat = _flatten(target)
        missing = sorted(k for k, _ in flat if k not in by_key)
        unexpected = sorted(set(by_key) - {k for k, _ in flat})
        if missing or unexpected:
            raise KeyError(
                f"checkpoint step {step} does not match the target tree: "
                f"missing from checkpoint: {missing or 'none'}; "
                f"unexpected in checkpoint: {unexpected or 'none'}")
        sh_flat = (_flatten(shardings) if shardings is not None
                   else [(k, None) for k, _ in flat])
        leaves = []
        for (key, _leaf), (_, sh) in zip(flat, sh_flat):
            e = by_key[key]
            arr = _load(e)
            if sh is not None:
                leaves.append(jax.device_put(arr, sh))
            else:
                leaves.append(jax.numpy.asarray(arr))
        treedef = jax.tree_util.tree_structure(target)
        return (jax.tree_util.tree_unflatten(treedef, leaves),
                meta.get("extra", {}))


class CheckpointManager:
    """Retention + cadence policy around a Checkpointer."""

    def __init__(self, directory: str, interval: int = 100,
                 keep: int = 3, async_save: bool = True):
        self.ckpt = Checkpointer(directory)
        self.interval = interval
        self.keep = keep
        self.async_save = async_save

    def maybe_save(self, step: int, tree, extra=None, force=False) -> bool:
        if not force and (self.interval <= 0 or step % self.interval != 0):
            return False
        if force:
            # Drain any in-flight async save; skip if this step is already
            # committed (final flush after a cadence save of the same step).
            self.ckpt.wait()
            if self.latest_step() == step:
                return False
        if self.async_save and not force:
            self.ckpt.save_async(step, tree, extra)
        else:
            self.ckpt.save(step, tree, extra)
        self._gc()
        return True

    def _gc(self) -> None:
        steps = sorted(
            int(n[len("step_"):-len(".done")])
            for n in os.listdir(self.ckpt.directory) if n.endswith(".done"))
        for s in steps[:-self.keep] if self.keep > 0 else []:
            d = self.ckpt._step_dir(s)
            for path in (d, d + ".done"):
                if os.path.isdir(path):
                    shutil.rmtree(path)
                elif os.path.exists(path):
                    os.remove(path)

    def restore_latest(self, target=None, shardings=None):
        return self.ckpt.restore(None, target, shardings)

    def latest_step(self):
        return self.ckpt.latest_step()

    def wait(self):
        self.ckpt.wait()
