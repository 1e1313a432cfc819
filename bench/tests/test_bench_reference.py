"""The float32 reference computes what the program computes: on the tiny
configurations, with every weight in float32 and matmuls at "highest"
precision, the program's loss and first gradient (jnp path and Pallas
kernels in interpret mode) agree with the reference's. A reference that a
configuration names is found by that name alone."""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, program, weights
from bench.jobs import train
from bench.reference.common import Dots
from bench.tests import tiny


@pytest.mark.parametrize("config", ["tiny-qwen", "tiny-opt"])
@pytest.mark.parametrize("impl", [("sparse_jnp", "grouped"),
                                  ("pallas", "pallas")])
def test_reference_matches_the_program_in_float32(config, impl):
    from repro.launch import steps
    from repro.train import state as S
    cell = tiny.cell(config)
    ref = common.reference_of(cell)
    conf = dict(cell.config, program=dict(
        cell.config["program"], dtype="float32",
        spt={"attn_impl": impl[0], "ffn_impl": impl[1]}))
    cfg = program.model_config(conf)
    m = ref.model_from(conf["model"])
    state = train.make_weights(S.abstract_state(cfg), conf["model"], 11, ref)
    state = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        state)
    b = train.batches(cell.traffic, 256, 11)[0]
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    with jax.default_matmul_precision("highest"):
        step = jax.jit(steps.build_train_step(cfg, train.optimizer(
            cell.traffic)))
        new, metrics = step(state, batch)
        t, f = weights.reference_tree(state, ref)
        loss, g = jax.value_and_grad(ref.train_loss)(
            t, f, batch["tokens"], batch["labels"], m, Dots("f32"))
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)
    b1 = cell.traffic["optimizer"]["b1"]
    got = train._per_layer(jax.device_get(new["opt"]["m"]), ref, m)
    want = ref.per_layer(g, m)
    # the program's first moment holds the clipped gradient times (1 - b1)
    clip = min(1.0, cell.traffic["optimizer"]["grad_clip"] / np.sqrt(
        sum(float(np.sum(v * v)) for v in want.values())))
    for k, v in want.items():
        np.testing.assert_allclose(got[k] / (1 - b1), v * clip,
                                   rtol=1e-4, atol=1e-7, err_msg=k)


def test_a_reference_added_as_a_file_alone_drives_the_same_steps(tmp_path):
    ref_dir = tmp_path / "bench" / "reference"
    ref_dir.mkdir(parents=True)
    shutil.copy(common.BENCH / "reference" / "spt.py", ref_dir / "spt2.py")
    cell = tiny.cell()
    copy = tiny.cell()
    copy.config = dict(copy.config, reference="spt2")
    copy.bench_dir = tmp_path / "bench"
    assert common.reference_of(copy).__file__ == str(ref_dir / "spt2.py")
    checked = train.batches(cell.traffic, 256, 2 ** 31 + 5)[
        :cell.traffic["checked_steps"]]
    with jax.default_matmul_precision("highest"):
        want = train.reference_steps(cell, 2 ** 31 + 5, checked, Dots("f32"))
        got = train.reference_steps(copy, 2 ** 31 + 5, checked, Dots("f32"))
    assert got[0] == want[0]
    for g, w in zip(got[1:], want[1:]):
        assert sorted(g) == sorted(w)
        assert all(np.array_equal(g[k], w[k]) for k in w)
