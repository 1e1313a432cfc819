"""Random weights from the seed, made by the benchmark in one jitted call,
and the same arrays renamed into the reference's layout.

The program's state tree gives only the names, shapes and types of its
leaves. The values follow the rules of the configuration's reference
module (``weight_std``, ``weight_const``; see ``bench/reference/spt.py``),
and ``reference_tree`` renames by its ``reference_path``. Each leaf's key
is the seed's key folded with the CRC-32 of the leaf's name in the model,
so a leaf's values depend on its name and the seed alone.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

Path = Tuple[str, ...]


def model_path(path: Path) -> Path:
    """A leaf's name in the model: its path below "train" or "frozen"."""
    return path[1:] if path[0] in ("train", "frozen") else path


def _path(keys) -> Path:
    return tuple(str(getattr(k, "key", k)) for k in keys)


def make_state(abstract: Any, model: dict, key, ref):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs; None leaves stay
    None), filled from ``key`` by the reference module ``ref``'s rules.
    Moments, counters and "step" are zero. A leaf's values depend on its
    name in the model and the key, not on the tree around it."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [_path(p) for p, _ in flat]
    # a leaf's key depends on its own name only (not on the tree around it)
    keys = [jax.random.fold_in(key,
                               zlib.crc32("/".join(model_path(p)).encode()))
            for p in paths]

    def leaf(path, sds, k):
        if path[0] in ("opt", "step"):
            return jnp.zeros(sds.shape, sds.dtype)
        std = ref.weight_std(path, sds.shape, model)
        if std == 0.0:
            return jnp.full(sds.shape, ref.weight_const(path), sds.dtype)
        return (std * jax.random.normal(k, sds.shape, sds.dtype)
                ).astype(sds.dtype)

    leaves = [leaf(p, s, k) for p, (_, s), k in zip(paths, flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def reference_tree(state: dict, ref) -> Tuple[Dict, Dict]:
    """(trainable, frozen) nested dicts in the layout of the reference
    module ``ref`` (its ``reference_path``), holding the arrays of
    ``state`` (no copy)."""
    import jax

    out = {"train": {}, "frozen": {}}
    for part in ("train", "frozen"):
        flat, _ = jax.tree_util.tree_flatten_with_path(state[part])
        for p, arr in flat:
            node = out[part]
            rp = ref.reference_path((part,) + _path(p))
            for s in rp[:-1]:
                node = node.setdefault(s, {})
            node[rp[-1]] = arr
    return out["train"], out["frozen"]


def layer_leaves(tree: dict, layers: int) -> Dict[str, Any]:
    """Per-layer leaves of a reference-layout tree: each stacked leaf under
    "layers" splits into one leaf per layer ("layers/3/attn/wq/b")."""
    import numpy as np

    out = {}

    def walk(node, name):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], f"{name}/{k}" if name else k)
            return
        a = np.asarray(node, np.float32)
        if name.startswith("layers/") and a.shape[:1] == (layers,):
            for i in range(layers):
                out[f"layers/{i}/{name[7:]}"] = a[i]
        else:
            out[name] = a

    walk(tree, "")
    return out
