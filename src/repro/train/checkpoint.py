"""Fault-tolerant checkpointing: atomic write (tmp + rename), latest-valid
resume, corrupted-checkpoint quarantine. Nested-dict pytrees of arrays are
stored as a single .npz with path-encoded keys — no pickle.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_SEP = "\x1f"          # unit separator: never appears in our dict keys
_STEP_RE = re.compile(r"^step_(\d+)$")


def _flatten(tree, prefix=()) -> Dict[str, np.ndarray]:
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, prefix + (str(k),)))
    else:
        out[_SEP.join(prefix)] = np.asarray(tree)
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> Dict:
    root: Dict = {}
    for key, val in flat.items():
        parts = key.split(_SEP)
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return root


def save_checkpoint(ckpt_dir: str, step: int, params, opt_state,
                    extra: Optional[Dict] = None) -> str:
    """Atomic: writes into step_<n>.tmp then renames to step_<n>."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "params.npz"), **_flatten(params))
    np.savez(os.path.join(tmp, "opt_state.npz"), **_flatten(opt_state))
    meta = {"step": step, "time": time.time(), **(extra or {})}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    return final


def list_checkpoints(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return []
    steps = []
    for name in os.listdir(ckpt_dir):
        m = _STEP_RE.match(name)
        if m and os.path.isdir(os.path.join(ckpt_dir, name)):
            steps.append(int(m.group(1)))
    return sorted(steps)


def _load_dir(path: str) -> Tuple[Dict, Dict, Dict]:
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    with np.load(os.path.join(path, "params.npz")) as z:
        params = _unflatten({k: z[k] for k in z.files})
    with np.load(os.path.join(path, "opt_state.npz")) as z:
        opt = _unflatten({k: z[k] for k in z.files})
    return params, opt, meta


def restore_latest(ckpt_dir: str, quarantine: bool = True
                   ) -> Optional[Tuple[Dict, Dict, Dict]]:
    """Restore the newest valid checkpoint; corrupted ones are renamed to
    *.corrupt and skipped (node-failure recovery path)."""
    for step in reversed(list_checkpoints(ckpt_dir)):
        path = os.path.join(ckpt_dir, f"step_{step}")
        try:
            return _load_dir(path)
        except Exception:
            if quarantine:
                dst = path + ".corrupt"
                if os.path.exists(dst):
                    shutil.rmtree(dst)
                os.replace(path, dst)
    return None


def to_device(tree, like=None, sharding_tree=None):
    """numpy tree -> jnp tree (optionally matching dtypes of `like` and
    shardings of `sharding_tree` for resharded/elastic restore)."""
    def put(path_val, like_val=None, shard=None):
        dtype = None if like_val is None else like_val.dtype
        if shard is not None:
            # straight from host memory into the shards, never whole on the
            # default device first
            return jax.device_put(np.asarray(path_val, dtype=dtype), shard)
        return jnp.asarray(path_val, dtype=dtype)
    if like is None and sharding_tree is None:
        return jax.tree.map(put, tree)
    if sharding_tree is None:
        return jax.tree.map(put, tree, like)
    if like is None:
        return jax.tree.map(lambda t, s: put(t, None, s), tree, sharding_tree)
    return jax.tree.map(put, tree, like, sharding_tree)
