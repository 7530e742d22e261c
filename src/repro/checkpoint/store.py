"""Sharded checkpointing with elastic restore.

Layout:  <dir>/step_<N>/
           manifest.json      — step, user meta, tree structure, shapes,
                                dtypes, content hashes
           arrays.npz         — one entry per leaf (host-gathered)

Design points for 1000+-node deployments (scaled-down here, same contract):
  * each leaf records a content hash — restore verifies integrity and
    refuses silently-truncated files (a real failure mode at scale);
  * restore is **elastic**: arrays are re-device_put with the *target* mesh's
    shardings, so a 512-chip checkpoint restores onto 256 chips (or a
    different DP/TP split) without conversion tooling;
  * writes go to a temp dir + atomic rename, so a node failure mid-write
    never corrupts the latest-complete checkpoint; stale ``step_*.tmp``
    leftovers from a mid-write kill are swept on the next `latest_step`;
  * a free-form ``meta`` dict rides in the manifest — the SpGEMM loops use
    it to snapshot the **plan signature** (pow2/floor caps, hash caps,
    local path, batch-count floor) next to the iterate
    so a restored run rebuilds the identical fused-step executable with zero
    extra retraces (see `runtime/resilient.py`);
  * `AsyncCheckpointer` runs the host-gather + write on a worker thread,
    overlapping the next multiply (checkpoint stalls are a top straggler
    source); it records stall time and bytes written for the `RunReport`.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

try:
    import msgpack
except ImportError:  # pragma: no cover
    msgpack = None

_STEP_RE = re.compile(r"^step_(\d+)$")


def _step_of(name: str) -> Optional[int]:
    """Step number of a checkpoint dir entry, or None for foreign entries.

    Defensive by design: a checkpoint dir accumulates junk over long runs
    (``step_00000003.bak`` from operators, editor droppings, ``.tmp`` from a
    mid-write kill) and a naive ``int(d.split("_")[1])`` turns any of it
    into a crash at restore time.
    """
    m = _STEP_RE.match(name)
    return int(m.group(1)) if m else None


def sweep_stale_tmp(path: str) -> int:
    """Remove ``step_*.tmp`` leftovers from a mid-write kill.

    Safe against a concurrent in-flight writer only in the sense the store
    already requires: one writer per directory (the AsyncCheckpointer
    enforces a single outstanding save). Returns the number swept.
    """
    if not os.path.isdir(path):
        return 0
    swept = 0
    for d in os.listdir(path):
        if d.endswith(".tmp") and _step_of(d[: -len(".tmp")]) is not None:
            try:
                shutil.rmtree(os.path.join(path, d))
                swept += 1
            except FileNotFoundError:
                pass  # vanished between list and rmtree — already gone
    return swept


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}, treedef


def save(
    path: str, step: int, state: Dict[str, Any],
    meta: Optional[Dict[str, Any]] = None,
) -> str:
    """Synchronous checkpoint write. Returns the final directory.

    ``meta`` is any JSON-serializable dict, stored in the manifest and read
    back via `load_meta` — the plan-signature side channel for the SpGEMM
    loops (it never touches the array payload, so the content-hash contract
    is unchanged).
    """
    final = os.path.join(path, f"step_{step:08d}")
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    flat, _ = _flatten(state)
    arrays = {k: np.asarray(v) for k, v in flat.items()}
    manifest = {
        "step": step,
        "meta": meta or {},
        "leaves": {
            k: {
                "shape": list(a.shape),
                "dtype": str(a.dtype),
                "hash": hashlib.sha256(a.tobytes()).hexdigest()[:16],
            }
            for k, a in arrays.items()
        },
    }
    np.savez(os.path.join(tmp, "arrays.npz"), **{
        k.replace("/", "\x00"): a for k, a in arrays.items()
    })
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def dir_nbytes(d: str) -> int:
    """Total bytes of one checkpoint directory (manifest + arrays)."""
    try:
        return sum(
            os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)
        )
    except OSError:
        return 0


def steps_available(path: str) -> List[int]:
    """Sorted complete checkpoint steps (foreign entries and .tmp ignored)."""
    if not os.path.isdir(path):
        return []
    steps = [s for d in os.listdir(path) if (s := _step_of(d)) is not None]
    return sorted(steps)


def latest_step(path: str) -> Optional[int]:
    sweep_stale_tmp(path)
    steps = steps_available(path)
    return steps[-1] if steps else None


def _read_verified(d: str) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Load one checkpoint dir, verifying every leaf hash.

    Any corruption — unreadable/truncated npz, missing leaves, or a content
    hash mismatch — surfaces as IOError so callers have one refusal channel.
    """
    try:
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        with np.load(os.path.join(d, "arrays.npz")) as z:
            arrays = {k.replace("\x00", "/"): z[k] for k in z.files}
    except IOError:
        raise
    except Exception as e:  # truncated zip, bad JSON, missing member ...
        raise IOError(f"checkpoint unreadable: {d}: {e}") from e
    for k, meta in manifest["leaves"].items():
        if k not in arrays:
            raise IOError(f"checkpoint corruption: {k} missing from arrays")
        a = arrays[k]
        h = hashlib.sha256(a.tobytes()).hexdigest()[:16]
        if h != meta["hash"]:
            raise IOError(f"checkpoint corruption: {k} hash mismatch")
    return arrays, manifest


def load_meta(path: str, step: int) -> Dict[str, Any]:
    """The ``meta`` dict stored with `save` (plan signature et al.)."""
    d = os.path.join(path, f"step_{step:08d}")
    try:
        with open(os.path.join(d, "manifest.json")) as f:
            return json.load(f).get("meta", {})
    except IOError:
        raise
    except Exception as e:
        raise IOError(f"checkpoint manifest unreadable: {d}: {e}") from e


def restore_arrays(path: str, step: int) -> Dict[str, np.ndarray]:
    """Hash-verified flat leaf dict, no template tree needed.

    The template-free twin of `restore`: callers that rebuild typed state
    themselves (the resilient SpGEMM loops) get the raw host arrays keyed by
    `jax.tree_util.keystr` paths and decide placement/sharding on their own.
    """
    d = os.path.join(path, f"step_{step:08d}")
    arrays, _ = _read_verified(d)
    return arrays


def restore(
    path: str, step: int, like: Dict[str, Any], shardings=None
) -> Dict[str, Any]:
    """Restore into the structure of `like`, resharding onto `shardings`
    (elastic: the saved mesh layout is irrelevant — only shapes must match)."""
    d = os.path.join(path, f"step_{step:08d}")
    arrays, _ = _read_verified(d)
    flat_like, treedef = _flatten(like)
    if set(flat_like) != set(arrays):
        missing = set(flat_like) ^ set(arrays)
        raise KeyError(f"checkpoint tree mismatch: {sorted(missing)[:5]} ...")
    sh_flat = None
    if shardings is not None:
        sh_flat, _ = _flatten(shardings)
    out = {}
    for k, template in flat_like.items():
        a = arrays[k]
        assert tuple(a.shape) == tuple(template.shape), (k, a.shape, template.shape)
        if sh_flat is not None and k in sh_flat:
            out[k] = jax.device_put(a, sh_flat[k])
        else:
            out[k] = jax.device_put(a)
    leaves = [out[jax.tree_util.keystr(p)] for p, _ in
              jax.tree_util.tree_flatten_with_path(like)[0]]
    return jax.tree_util.tree_unflatten(treedef, leaves)


class AsyncCheckpointer:
    """Threaded save: snapshot to host, write off-thread, never block > one
    outstanding checkpoint (back-pressure instead of unbounded queue).

    Accounting for the durability `RunReport`: `stalls`/`stall_s` measure
    time spent blocked on a previous in-flight write (a save issued while
    the prior one is still writing), `bytes_written` totals finished
    checkpoint sizes. A failed background write surfaces on the next
    `save`/`wait` instead of dying silently on the worker thread.
    """

    def __init__(self, path: str, keep: int = 3):
        self.path = path
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.last_saved: Optional[int] = None
        self.stalls = 0
        self.stall_s = 0.0
        self.bytes_written = 0
        sweep_stale_tmp(path)

    def save(self, step: int, state, meta: Optional[Dict[str, Any]] = None):
        # back-pressure: at most one in flight
        if self._thread is not None and self._thread.is_alive():
            self.stalls += 1
        t0 = time.perf_counter()
        self.wait()
        self.stall_s += time.perf_counter() - t0
        host_state = jax.tree.map(np.asarray, state)  # snapshot now

        def work():
            try:
                final = save(self.path, step, host_state, meta=meta)
                self.bytes_written += dir_nbytes(final)
                self.last_saved = step
                self._gc()
            except BaseException as e:  # surface on next save/wait
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def save_sync(self, step: int, state, meta: Optional[Dict[str, Any]] = None):
        """Blocking save through the same accounting/GC as the async path."""
        self.wait()
        final = save(self.path, step, state, meta=meta)
        self.bytes_written += dir_nbytes(final)
        self.last_saved = step
        self._gc()
        return final

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            e, self._error = self._error, None
            raise e

    def _gc(self):
        try:
            entries = os.listdir(self.path)
        except FileNotFoundError:
            return  # whole dir vanished (external cleanup) — nothing to gc
        steps = sorted(s for d in entries if (s := _step_of(d)) is not None)
        for s in steps[: -self.keep] if self.keep > 0 else steps:
            try:
                shutil.rmtree(os.path.join(self.path, f"step_{s:08d}"))
            except FileNotFoundError:
                pass  # vanished between list and rmtree — already gone
