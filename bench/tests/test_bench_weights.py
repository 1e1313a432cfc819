"""The seeded weights of the two tiny configurations, pinned: every leaf's
bytes (by SHA-256), shape and dtype, each leaf's name in the reference's
layout, and the per-layer names the comparison reads. The fixture was made
by bench/weights.py when it still held the SPT weight rules itself; the
rules now come from the configuration's reference module, and neither a
weight nor a name may have moved."""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import common, program, weights
from bench.jobs import train
from bench.tests import tiny

PINNED = json.loads((pathlib.Path(__file__).resolve().parent / "fixtures"
                     / "pinned_weights.json").read_text())


def _path(keys):
    return tuple(str(getattr(k, "key", k)) for k in keys)


@pytest.mark.parametrize("config", ["tiny-qwen", "tiny-opt"])
def test_seeded_weights_and_names_are_pinned(config):
    from repro.train import state as S
    want = PINNED["configs"][config]
    cell = tiny.cell(config)
    ref = common.reference_of(cell)
    cfg = program.model_config(cell.config)
    state = train.make_weights(S.abstract_state(cfg), cell.config["model"],
                               PINNED["seed"], ref)
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    leaves, names = {}, {}
    for p, a in flat:
        path = _path(p)
        a = np.asarray(a)
        leaves["/".join(path)] = [str(a.dtype), list(a.shape),
                                  hashlib.sha256(a.tobytes()).hexdigest()]
        if path[0] in ("train", "frozen"):
            names["/".join(path)] = "/".join(ref.reference_path(path))
    assert leaves == want["leaves"]
    assert names == want["reference_path"]
    t, f = weights.reference_tree(state, ref)
    m = ref.model_from(cell.config["model"])
    assert sorted(ref.per_layer(t, m)) == want["per_layer"]["train"]
    assert sorted(ref.per_layer(f, m)) == want["per_layer"]["frozen"]
