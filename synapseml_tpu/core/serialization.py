"""Stage persistence: metadata.json + out-of-band complex params.

Reference: ``org/apache/spark/ml/{Serializer,ComplexParamsSerializer}.scala`` —
JSON for simple params, object serialization for complex ones (models,
DataFrames, UDFs). Here: JSON metadata + npz for numpy/pytree leaves + pickle
fallback for callables/objects, per complex param.
"""

from __future__ import annotations

import importlib
import json
import os
import pickle
import shutil
from typing import Any

import numpy as np

__all__ = ["save_stage", "load_stage", "prepare_dir", "save_pytree",
           "load_pytree", "flatten_pytree", "tree_structure", "rebuild_pytree"]


def prepare_dir(path: str, overwrite: bool = True) -> None:
    if os.path.exists(path):
        if not overwrite:
            raise FileExistsError(path)
        shutil.rmtree(path)
    os.makedirs(path, exist_ok=True)


def _flatten_pytree(tree: Any, prefix: str = "",
                    leaf_fn=np.asarray) -> dict[str, np.ndarray]:
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten_pytree(v, f"{prefix}{k}/", leaf_fn))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten_pytree(v, f"{prefix}{i}/", leaf_fn))
    else:
        out[prefix.rstrip("/")] = leaf_fn(tree)
    return out


# public aliases: the sharded checkpointer flattens each host's shard with
# the SAME naming/structure scheme as the single-file format, so an N-shard
# assembly and a plain save_pytree round-trip are byte-interchangeable.
# ``leaf_fn`` lets that caller keep RAW leaves (cross-process jax arrays
# cannot survive np.asarray) while sharing this one traversal/naming codec.
def flatten_pytree(tree: Any, prefix: str = "",
                   leaf_fn=np.asarray) -> dict[str, np.ndarray]:
    return _flatten_pytree(tree, prefix, leaf_fn)


def tree_structure(tree: Any) -> Any:
    return _tree_structure(tree)


# No file save_pytree writes grows past this unless one leaf alone is larger:
# a process file-size limit (RLIMIT_FSIZE) or a filesystem's own maximum
# refuses a multi-GB npz with EFBIG — BERT-base's params + Adam moments are
# 1.3 GB — where several smaller files are accepted.
MAX_FILE_BYTES = 512 << 20


def save_pytree(tree: Any, path: str,
                max_file_bytes: int | None = None) -> list[str]:
    """Save a (possibly nested dict) pytree of arrays as ``path.npz`` +
    structure JSON. Leaves beyond ``max_file_bytes`` (default
    ``MAX_FILE_BYTES``) spill, in flatten order,
    into ``path.part00001.npz``, ...; the structure's root records the file
    count. Returns every path written (a checkpoint digests each one)."""
    if max_file_bytes is None:
        max_file_bytes = MAX_FILE_BYTES
    groups, room = [{}], max_file_bytes
    for name, leaf in _flatten_pytree(tree).items():
        if groups[-1] and leaf.nbytes > room:
            groups.append({})
            room = max_file_bytes
        groups[-1][name] = leaf
        room -= leaf.nbytes
    written = _part_paths(path, len(groups))
    for target, group in zip(written, groups):
        np.savez(target, **group)
    structure = _tree_structure(tree)
    if len(groups) > 1:
        structure["__parts__"] = len(groups)
    with open(path + ".tree.json", "w") as f:
        json.dump(structure, f)
    return written + [path + ".tree.json"]


def _part_paths(path: str, parts: int) -> list[str]:
    return [path + ".npz"] + [f"{path}.part{i:05d}.npz"
                              for i in range(1, parts)]


def _tree_structure(tree: Any) -> Any:
    if isinstance(tree, dict):
        return {"__kind__": "dict", "items": {k: _tree_structure(v) for k, v in tree.items()}}
    if isinstance(tree, (list, tuple)):
        kind = "list" if isinstance(tree, list) else "tuple"
        return {"__kind__": kind, "items": [_tree_structure(v) for v in tree]}
    return {"__kind__": "leaf"}


def rebuild_pytree(structure: Any, flat: Any) -> Any:
    """Inverse of :func:`flatten_pytree` + :func:`tree_structure`:
    ``flat`` is any mapping of slash-joined leaf path -> array (an open
    npz works). The sharded-checkpoint assembler reuses this so its
    multi-shard reconstruction cannot drift from the single-file format."""

    def rebuild(node, prefix=""):
        kind = node["__kind__"]
        if kind == "dict":
            return {k: rebuild(v, f"{prefix}{k}/") for k, v in node["items"].items()}
        if kind in ("list", "tuple"):
            seq = [rebuild(v, f"{prefix}{i}/") for i, v in enumerate(node["items"])]
            return seq if kind == "list" else tuple(seq)
        return flat[prefix.rstrip("/")]

    return rebuild(structure)


def load_pytree(path: str) -> Any:
    with open(path + ".tree.json") as f:
        structure = json.load(f)
    flat = {}
    for part in _part_paths(path, structure.get("__parts__", 1)):
        with np.load(part, allow_pickle=False) as data:
            flat.update({name: data[name] for name in data.files})
    return rebuild_pytree(structure, flat)


def _is_array_pytree(v: Any) -> bool:
    if isinstance(v, (bytes, bytearray, str)):
        return False  # np.isscalar says True, but npz round-trips these as
        # 0-d S/U arrays that break len()/indexing consumers — pickle instead
    if isinstance(v, np.ndarray) or np.isscalar(v):
        return True
    if hasattr(v, "__array__") and hasattr(v, "dtype"):  # jax arrays
        return True
    if isinstance(v, dict):
        # non-str keys would be stringified by the npz flatten and not restored
        return (bool(v) and all(isinstance(k, str) for k in v)
                and all(_is_array_pytree(x) for x in v.values()))
    if isinstance(v, (list, tuple)):
        return bool(v) and all(_is_array_pytree(x) for x in v)
    return False


def save_stage(stage, path: str, overwrite: bool = True) -> None:
    from .pipeline import PipelineStage  # local import to avoid cycle

    prepare_dir(path, overwrite)
    complex_vals = stage.complex_param_values()
    meta = {
        "class": f"{type(stage).__module__}.{type(stage).__qualname__}",
        "uid": stage.uid,
        "params": _jsonify(stage.simple_param_values()),
        "complexParams": {},
    }
    for name, value in complex_vals.items():
        entry: dict[str, Any] = {}
        target = os.path.join(path, f"complex_{name}")
        if isinstance(value, PipelineStage):
            entry["kind"] = "stage"
            save_stage(value, target, overwrite=overwrite)
        elif isinstance(value, list) and value and all(isinstance(v, PipelineStage) for v in value):
            entry["kind"] = "stage_list"
            entry["n"] = len(value)
            for i, v in enumerate(value):
                save_stage(v, f"{target}_{i:03d}", overwrite=overwrite)
        elif _is_array_pytree(value):
            entry["kind"] = "pytree"
            save_pytree(_to_numpy_tree(value), target)
        else:
            entry["kind"] = "pickle"
            with open(target + ".pkl", "wb") as f:
                pickle.dump(value, f)
        meta["complexParams"][name] = entry
    with open(os.path.join(path, "metadata.json"), "w") as f:
        json.dump(meta, f, indent=2, default=str)


def _to_numpy_tree(v: Any) -> Any:
    if isinstance(v, dict):
        return {k: _to_numpy_tree(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        t = [_to_numpy_tree(x) for x in v]
        return t if isinstance(v, list) else tuple(t)
    return np.asarray(v)


def _jsonify(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, np.ndarray):
            out[k] = {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
        elif isinstance(v, (np.integer,)):
            out[k] = int(v)
        elif isinstance(v, (np.floating,)):
            out[k] = float(v)
        else:
            out[k] = v
    return out


def _unjsonify(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, dict) and "__ndarray__" in v:
            out[k] = np.asarray(v["__ndarray__"], dtype=v["dtype"])
        else:
            out[k] = v
    return out


def load_stage(path: str):
    with open(os.path.join(path, "metadata.json")) as f:
        meta = json.load(f)
    mod_name, _, cls_name = meta["class"].rpartition(".")
    cls = getattr(importlib.import_module(mod_name), cls_name)
    stage = cls.__new__(cls)
    # re-run Params.__init__ machinery without subclass ctor side effects
    from .params import Params

    Params.__init__(stage, uid=meta["uid"])
    stage.set(**_unjsonify(meta["params"]))
    for name, entry in meta.get("complexParams", {}).items():
        target = os.path.join(path, f"complex_{name}")
        if entry["kind"] == "stage":
            value = load_stage(target)
        elif entry["kind"] == "stage_list":
            value = [load_stage(f"{target}_{i:03d}") for i in range(entry["n"])]
        elif entry["kind"] == "pytree":
            value = load_pytree(target)
        else:
            with open(target + ".pkl", "rb") as f:
                value = pickle.load(f)
        stage.set(**{name: value})
    # where this stage was loaded FROM: stages whose artifact carries
    # sidecar trees next to metadata.json (retrieval index shards,
    # published via ``ModelRegistry.publish(extra_tree=...)``) resolve
    # them lazily through this attribute
    stage._artifact_dir = os.path.abspath(path)
    if hasattr(stage, "_post_load"):
        stage._post_load()
    return stage
