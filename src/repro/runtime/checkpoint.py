"""Fault-tolerant checkpointing: atomic, resumable, async, re-shardable.

Layout (one directory per step):
    ckpt_dir/step_000123/
        manifest.json      -- tree structure, shapes, dtypes, step, mesh
        arrays.npz         -- flattened leaves keyed by path
    ckpt_dir/LATEST        -- text file naming the newest complete step

Writes go to ``step_N.tmp`` then ``os.rename`` -> crash-safe: a partially
written checkpoint is never visible.  ``AsyncCheckpointer`` runs the save
on a writer thread (double-buffered, matching production async ckpt).
Restore targets *any* mesh: arrays are loaded on host then device_put
against the new sharding -- this is the elastic re-shard path.

Restore is also corruption-tolerant when no explicit step is pinned: a
checkpoint that turns out unreadable on disk (truncated npz, mangled
manifest) is skipped with a warning and the next older complete step is
tried — crash recovery should degrade to an older snapshot, not refuse
to start.
Asking for a *specific* ``step=`` stays strict: the caller named the
state they need, so silently serving older state would be a lie.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import zipfile
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..obs.log import LOG

__all__ = ["AsyncCheckpointer", "checkpoint_meta", "latest_step",
           "prune_old", "restore", "save"]

Pytree = Any

#: Failure modes of an on-disk checkpoint (vs. a caller bug): missing
#: or truncated files, a zip container np.load cannot open, mangled
#: manifest JSON, a leaf key the arrays archive no longer holds.
_CORRUPT = (OSError, ValueError, KeyError, json.JSONDecodeError,
            zipfile.BadZipFile)


def _flatten(tree: Pytree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def save(ckpt_dir: str | Path, step: int, tree: Pytree,
         extra: Optional[Dict] = None) -> Path:
    """Atomic synchronous save."""
    ckpt_dir = Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    final = ckpt_dir / f"step_{step:08d}"
    tmp = ckpt_dir / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    flat = _flatten(tree)
    np.savez(tmp / "arrays.npz", **flat)
    treedef = jax.tree_util.tree_structure(tree)
    manifest = {
        "step": step,
        "treedef": str(treedef),
        "keys": sorted(flat),
        "extra": extra or {},
    }
    (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    # LATEST last: readers never see a name before its data is complete
    latest_tmp = ckpt_dir / "LATEST.tmp"
    latest_tmp.write_text(final.name)
    os.rename(latest_tmp, ckpt_dir / "LATEST")
    return final


def latest_step(ckpt_dir: str | Path) -> Optional[int]:
    """The step named by ``LATEST``, or None when nothing is saved.

    ``LATEST`` is written (atomically, last) by :func:`save`, so the
    returned step is always a *complete* checkpoint directory."""
    f = Path(ckpt_dir) / "LATEST"
    if not f.exists():
        return None
    return int(f.read_text().strip().split("_")[-1])


def _complete_steps(ckpt_dir: Path) -> List[int]:
    """All complete (renamed, non-``.tmp``) step numbers, newest first."""
    return sorted((int(p.name.split("_")[-1])
                   for p in ckpt_dir.glob("step_*")
                   if p.is_dir() and not p.name.endswith(".tmp")),
                  reverse=True)


def restore(ckpt_dir: str | Path, template: Pytree, step: Optional[int] = None,
            shardings: Optional[Pytree] = None) -> Pytree:
    """Restore into the structure of `template`.

    shardings: optional tree of NamedSharding for the *current* mesh --
    pass a different mesh's shardings to elastically re-shard.

    With ``step=None`` (resume-from-newest), a corrupt step on disk is
    skipped with a ``repro.obs.log`` warning record and the next older
    complete step is tried.  An explicit ``step`` is strict and raises on
    corruption.
    """
    ckpt_dir = Path(ckpt_dir)
    if step is not None:
        return _restore_step(ckpt_dir, template, step, shardings)
    steps = _complete_steps(ckpt_dir)
    if not steps:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    last_err: Optional[BaseException] = None
    for s in steps:
        try:
            return _restore_step(ckpt_dir, template, s, shardings)
        except _CORRUPT as err:
            LOG.warning(
                "checkpoint unreadable; falling back to the previous "
                "complete step", step=f"step_{s:08d}", dir=str(ckpt_dir),
                error=f"{type(err).__name__}: {err}")
            last_err = err
    raise FileNotFoundError(
        f"no readable checkpoint under {ckpt_dir} "
        f"({len(steps)} corrupt step(s) skipped)") from last_err


def _restore_step(ckpt_dir: Path, template: Pytree, step: int,
                  shardings: Optional[Pytree]) -> Pytree:
    """Load one specific step directory into `template`'s structure."""
    folder = ckpt_dir / f"step_{step:08d}"
    data = np.load(folder / "arrays.npz")

    paths_leaves = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    shard_leaves = (jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
        if shardings is not None else None)
    for i, (path, leaf) in enumerate(paths_leaves[0]):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        arr = data[key]
        if hasattr(leaf, "dtype"):
            arr = arr.astype(leaf.dtype)
        if shard_leaves is not None:
            arr = jax.device_put(arr, shard_leaves[i])
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(paths_leaves[1], leaves)


def checkpoint_meta(ckpt_dir: str | Path, step: int) -> Dict:
    """One step's manifest: tree structure, leaf keys, and the saver's
    ``extra`` sidecar (the elastic session stashes its scheduler/tuner
    state there — see ``repro.serving.elastic.checkpoint_session``)."""
    folder = Path(ckpt_dir) / f"step_{step:08d}"
    return json.loads((folder / "manifest.json").read_text())


class AsyncCheckpointer:
    """Double-buffered writer thread; ``wait()`` joins the in-flight save."""

    def __init__(self, ckpt_dir: str | Path):
        self.ckpt_dir = Path(ckpt_dir)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save(self, step: int, tree: Pytree, extra: Optional[Dict] = None):
        """Snapshot ``tree`` to host memory and write it on the writer
        thread.  Joins any in-flight save first (double-buffering depth
        one), so the caller blocks only on host transfer, never on
        disk."""
        self.wait()
        host_tree = jax.tree.map(np.asarray, tree)  # snapshot before async

        def _run():
            try:
                save(self.ckpt_dir, step, host_tree, extra)
            except BaseException as e:  # surfaced on next wait()
                self._error = e
        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()

    def wait(self):
        """Join the in-flight save, re-raising any writer-thread error
        here on the caller's thread.  Idempotent; a no-op when nothing
        is in flight."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err


def prune_old(ckpt_dir: str | Path, keep: int = 3):
    """Retain the newest `keep` complete checkpoints."""
    ckpt_dir = Path(ckpt_dir)
    steps = sorted(int(p.name.split("_")[-1])
                   for p in ckpt_dir.glob("step_*") if p.is_dir()
                   and not p.name.endswith(".tmp"))
    for s in steps[:-keep]:
        shutil.rmtree(ckpt_dir / f"step_{s:08d}", ignore_errors=True)
