"""Sharded, atomic checkpoint I/O (offline container: numpy files).

Layout:  <dir>/step_<N>/
            manifest.json      {step, paths, shapes, dtypes, tree}
            <flat-path>.npy    one file per leaf (host-gathered)
            COMMIT             written last — presence marks integrity

Atomicity: leaves + manifest land in ``step_<N>.tmp`` which is renamed
after COMMIT is written, so a crash mid-save never corrupts the latest
checkpoint.  Restore reads full arrays and ``device_put``s them under the
*target* sharding — which is how elastic rescale works: the new mesh's
shardings are applied at load time regardless of the saving topology.
"""
from __future__ import annotations

import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np


def _names(tree) -> list[str]:
    """One file name per leaf, from its path in the tree."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for k in path:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append("__".join(parts))
    return out


def _flat(tree) -> list[tuple[str, np.ndarray]]:
    return list(zip(_names(tree),
                    (np.asarray(x) for x in jax.tree_util.tree_leaves(tree))))


_BIT_DTYPES = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8,
               "float8_e5m2": np.uint8}


def _to_savable(arr: np.ndarray) -> tuple[np.ndarray, str]:
    """numpy cannot serialise ml_dtypes (bfloat16, fp8) natively — store
    the raw bits as uintN; the logical dtype lives in the manifest."""
    name = str(arr.dtype)
    if name in _BIT_DTYPES:
        return arr.view(_BIT_DTYPES[name]), name
    return arr, name


def _from_saved(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name in _BIT_DTYPES:
        import ml_dtypes
        return arr.view(np.dtype(getattr(ml_dtypes, dtype_name)))
    return arr


def save(directory: str, step: int, tree) -> str:
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    leaves = _flat(tree)
    manifest = {"step": step, "leaves": []}
    for name, arr in leaves:
        bits, dtype_name = _to_savable(arr)
        np.save(os.path.join(tmp, name + ".npy"), bits)
        manifest["leaves"].append(
            {"name": name, "shape": list(arr.shape), "dtype": dtype_name})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(tmp, "COMMIT"), "w") as f:
        f.write("ok")
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    best = None
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and os.path.exists(os.path.join(directory, name, "COMMIT")):
            s = int(m.group(1))
            best = s if best is None else max(best, s)
    return best


def restore(directory: str, step: int, like, shardings=None):
    """Load into the structure of ``like`` (arrays or
    ``jax.ShapeDtypeStruct``s); apply ``shardings`` if given (pytree of
    NamedSharding matching ``like``) — elastic resharding."""
    path = os.path.join(directory, f"step_{step:08d}")
    if not os.path.exists(os.path.join(path, "COMMIT")):
        raise FileNotFoundError(f"no committed checkpoint at {path}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    dtype_of = {e["name"]: e["dtype"] for e in manifest["leaves"]}
    names = _names(like)
    arrays = [_from_saved(np.load(os.path.join(path, n + ".npy")),
                          dtype_of.get(n, ""))
              for n in names]
    flat_like, treedef = jax.tree_util.tree_flatten(like)
    assert len(arrays) == len(flat_like), "checkpoint/model structure differ"
    if shardings is not None:
        flat_sh = treedef.flatten_up_to(shardings)
        arrays = [jax.device_put(a, s) for a, s in zip(arrays, flat_sh)]
    else:
        arrays = [jnp.asarray(a) for a in arrays]
    return treedef.unflatten(arrays)


def remove(directory: str, step: int) -> None:
    path = os.path.join(directory, f"step_{step:08d}")
    if os.path.exists(path):
        shutil.rmtree(path)
