"""Random weights from the seed, made by the benchmark in one jitted call.

The program's state tree gives only the names, shapes and types of its
leaves. Values: projections by 1/sqrt(fan-in), embeddings at 0.02, norms
at one and zero, codebooks at unit scale (the size of a normalised head's
sub-vector), and the frozen weights that write the residual stream (the
attention and FFN outputs) further by the configuration's
``residual_gain``. SPT's discrete choices (PQ codes, top-L keys, top-G'
groups) flip under any rounding; with GPT-2 scales, 28 random layers
amplify the flips until bfloat16 and float32 runs of one model share no
more than their statistics. A small residual gain (as SkipInit and ReZero
start) keeps the stack well-conditioned. The residual stream then stays
near the token's embedding, so the final norm's scale is drawn around zero
(unit normal): with a tied head, a scale of one would make every token
predict itself.

Adapters: the two that write the residual stream start as LoRA starts,
their up factor at zero; the others are taken mid-run, both factors
non-zero, so that their every leaf has a gradient at step one.

``reference_tree`` renames the same arrays into the reference's layout.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Tuple

Path = Tuple[str, ...]


def _std(path: Path, shape, model: dict) -> float:
    """Standard deviation of a leaf; 0 for a constant (see ``_const``)."""
    name, parent = path[-1], path[-2] if len(path) > 1 else ""
    residual = model["residual_gain"]
    if name in ("embedding", "pos_embedding"):
        return 0.02
    if name == "codebooks":
        return 1.0
    if parent == "final_norm" and name == "scale":
        return 1.0
    if name in ("scale", "bias") and "norm" in parent:
        return 0.0
    if name == "c" and len(path) > 2:                  # LoRA up factor
        if "wo" in path or "lora_outer" in path:       # writes the residual
            return 0.0
        return 0.1 / math.sqrt(shape[-2])
    if name == "w_outer":
        return residual / math.sqrt(shape[-2] * model["active_groups"])
    if parent == "wo" and name == "w":
        return residual / math.sqrt(shape[-2])
    return 1.0 / math.sqrt(shape[-2])


def _const(path: Path) -> float:
    return 1.0 if path[-1] == "scale" else 0.0


def _name(path: Path) -> Path:
    """A leaf's name in the model: its path below "train" or "frozen"."""
    return path[1:] if path[0] in ("train", "frozen") else path


def make_state(abstract: Any, model: dict, key):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs; None leaves stay
    None), filled from ``key``. Moments, counters and "step" are zero. A
    leaf's values depend on its name in the model and the key, not on the
    tree around it."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [tuple(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    # a leaf's key depends on its own name only (not on the tree around it)
    keys = [jax.random.fold_in(key, zlib.crc32("/".join(_name(p)).encode()))
            for p in paths]

    def leaf(path, sds, k):
        if path[0] in ("opt", "step"):
            return jnp.zeros(sds.shape, sds.dtype)
        std = _std(path, sds.shape, model)
        if std == 0.0:
            return jnp.full(sds.shape, _const(path), sds.dtype)
        return (std * jax.random.normal(k, sds.shape, sds.dtype)
                ).astype(sds.dtype)

    leaves = [leaf(p, s, k) for p, (_, s), k in zip(paths, flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# program layout -> reference layout
_RENAME = {("embed", "embedding"): ("embed",),
           ("pos", "pos_embedding"): ("pos",),
           ("head", "w"): ("head",)}


def reference_path(path: Path) -> Path:
    """('frozen','units','b0_attn','mixer','wq','lora','b') ->
    ('layers','attn','wq','b'), and so on."""
    p = _name(path)
    if p[:1] == ("units",):
        p = ("layers",) + p[2:]
        p = tuple("attn" if s == "mixer" else s for s in p)
        p = tuple(s for s in p if s not in ("lora", "pq"))
    return _RENAME.get(p, p)


def reference_tree(state: dict) -> Tuple[Dict, Dict]:
    """(trainable, frozen) nested dicts in the reference's layout, holding
    the arrays of ``state`` (no copy)."""
    import jax

    out = {"train": {}, "frozen": {}}
    for part in ("train", "frozen"):
        flat, _ = jax.tree_util.tree_flatten_with_path(state[part])
        for p, arr in flat:
            path = (part,) + tuple(str(getattr(k, "key", k)) for k in p)
            node = out[part]
            rp = reference_path(path)
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
