"""Checkpointing: atomic, versioned, elastic-restorable.

Layout:  <dir>/step_<N>/arrays.npz + manifest.json, written to a tmp dir
and atomically renamed, so a preempted writer never leaves a torn
checkpoint.  Restore targets ANY mesh: arrays are saved unsharded (single
host here; a multi-host deployment writes per-host shards keyed by the same
manifest) and `restore(..., shardings=...)` re-device_puts onto the target
sharding — this is the elastic-rescale path (tested 1 -> 8 -> 4 devices).

Retention keeps the most recent `keep` checkpoints; `latest_step` powers
``--resume auto``.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fsutil import fsync_dir, fsync_file
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# seconds spent in fsync by each save (data, manifest and directories)
FSYNC_HIST = "checkpoint_fsync_seconds"


def _flatten(tree: Any) -> Dict[str, np.ndarray]:
    flat = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        arr = np.asarray(leaf)
        # npz cannot store bf16 -> view as uint16 with dtype tag
        flat[name] = arr
    return flat


def leaf_names(tree: Any) -> List[str]:
    """Flat leaf names in tree order — the keys `save` writes arrays under.
    Lets host-side callers pair `restore_flat` arrays with a template."""
    return list(_flatten(tree).keys())


def _json_safe(obj: Any) -> Any:
    """Recursively coerce numpy scalars/arrays so `extra` always serializes.
    Non-finite floats become strings ("inf"/"nan") so the manifest stays
    strict JSON (json.dump would emit the non-standard Infinity token);
    ``float()`` parses them back on restore."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return _json_safe(obj.tolist())
    if isinstance(obj, np.generic):
        return _json_safe(obj.item())
    if isinstance(obj, float) and not np.isfinite(obj):
        return str(obj)
    return obj


def save(tree: Any, ckpt_dir: str, step: int, *, keep: int = 3,
         extra: Optional[Dict] = None) -> str:
    """Write ``tree`` (and the JSON-able ``extra``) as ``step``.  The
    phases (``checkpoint.gather``, ``.serialize``, ``.fsync``,
    ``.publish``) show on a profile being taken; the fsync time also goes
    to the ``checkpoint_fsync_seconds`` histogram."""
    fsync_hist = obs_metrics.global_registry().histogram(FSYNC_HIST)
    os.makedirs(ckpt_dir, exist_ok=True)
    with obs_trace.phase("checkpoint.gather"):
        flat = _flatten(tree)
        manifest = dict(step=int(step),
                        names=list(flat.keys()),
                        dtypes={k: str(v.dtype) for k, v in flat.items()},
                        shapes={k: list(v.shape) for k, v in flat.items()},
                        extra=_json_safe(extra or {}))
        arrays = {}
        for k, v in flat.items():
            if v.dtype == jnp.bfloat16:
                arrays[k] = v.view(np.uint16)
            else:
                arrays[k] = v
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_")
    try:
        with obs_trace.phase("checkpoint.serialize"):
            np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
        # durable BEFORE the rename publishes the step dir: a power loss
        # must never leave a visible step_N with truncated contents
        with obs_trace.phase("checkpoint.fsync", fsync_hist):
            fsync_file(os.path.join(tmp, "manifest.json"))
            fsync_file(os.path.join(tmp, "arrays.npz"))
            fsync_dir(tmp)
        with obs_trace.phase("checkpoint.publish"):
            final = os.path.join(ckpt_dir, f"step_{int(step):08d}")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
        with obs_trace.phase("checkpoint.fsync", fsync_hist):
            fsync_dir(ckpt_dir)
    except Exception:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    with obs_trace.phase("checkpoint.publish"):
        _retain(ckpt_dir, keep)
    return final


def _retain(ckpt_dir: str, keep: int) -> None:
    steps = all_steps(ckpt_dir)
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)


def all_steps(ckpt_dir: str) -> List[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_"):
            try:
                out.append(int(name.split("_")[1]))
            except ValueError:
                pass
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(tree_template: Any, ckpt_dir: str, step: Optional[int] = None,
            *, shardings: Any = None) -> Any:
    """Restore into the template's structure.  `shardings` (optional pytree
    of NamedSharding, same structure) re-targets any mesh — elastic."""
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{int(step):08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))

    leaves_kp, treedef = jax.tree_util.tree_flatten_with_path(tree_template)
    shard_leaves = (jax.tree.leaves(shardings)
                    if shardings is not None else [None] * len(leaves_kp))
    out = []
    for (kp, leaf), sh in zip(leaves_kp, shard_leaves):
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        arr = data[name]
        want_dtype = manifest["dtypes"][name]
        if want_dtype == "bfloat16":
            arr = arr.view(jnp.bfloat16)
        arr = jnp.asarray(arr)
        if sh is not None:
            arr = jax.device_put(arr, sh)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def restore_flat(ckpt_dir: str, step: Optional[int] = None
                 ) -> tuple[Dict[str, np.ndarray], Dict]:
    """Raw host-side restore: (flat name->np.ndarray, manifest).

    Unlike :func:`restore` this never routes arrays through ``jnp.asarray``,
    so float64 host state (e.g. PER sum-tree priorities) survives without the
    x64-disabled downcast.  Callers rebuild pytrees via :func:`leaf_names`.
    """
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{int(step):08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    out = {}
    for name in manifest["names"]:
        arr = data[name]
        if manifest["dtypes"][name] == "bfloat16":
            arr = arr.view(jnp.bfloat16)
        out[name] = arr
    return out, manifest


def manifest_of(ckpt_dir: str, step: Optional[int] = None) -> Dict:
    step = step if step is not None else latest_step(ckpt_dir)
    with open(os.path.join(ckpt_dir, f"step_{int(step):08d}",
                           "manifest.json")) as f:
        return json.load(f)
