"""What decides ``correct``, driven through the rest of a run on the CPU
(the look for a chip skipped) at a small size, against the committed
limits: each fault that a fine-tuning cell can have, and the float8
control, come out not correct."""
import json

import pytest

from bench import common
from bench.tests import tiny

SEED = 2 ** 31 + 101


def _run(capsys, cell, **kw):
    import jax
    run = common.load_module(common.BENCH / "run.py", "bench_run_checks")
    run.run_cell(cell, tiny.args(SEED, **kw), jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return line


def _broken_step(mode):
    from repro.launch import steps
    real = steps.build_train_step

    def build(cfg, ocfg, loss_chunk=512):
        step = real(cfg, ocfg, loss_chunk)

        def broken(state, batch):
            if mode == "unchanged":              # the state comes back as is
                return state, step(state, batch)[1]
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)             # half the batch left out
        return broken
    return build


def test_a_sound_run_reads_every_number_under_its_limit_but_the_median(
        capsys):
    # The median leaf's gap reads some 1e-3 at this size, noisier than at
    # the cells' (where it is held); the other numbers have room here too.
    line = _run(capsys, tiny.cell(batch=4, seq_len=128))
    compared = line["compared"]
    assert set(compared) == {"grad", "grad_median", "update"}
    for name in ("grad", "update"):
        assert compared[name]["value"] <= compared[name]["limit"], compared
    assert 0 < compared["grad_median"]["value"] < 0.01


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(capsys, monkeypatch, fault):
    from repro.launch import steps
    monkeypatch.setattr(steps, "build_train_step", _broken_step(fault))
    line = _run(capsys, tiny.cell(batch=4, seq_len=128))
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("config", ["tiny-qwen", "tiny-opt"])
def test_the_float8_control_is_not_correct(capsys, config):
    limits = {"tiny-qwen": "qwen3-0.6b", "tiny-opt": "opt-2.7b"}[config]
    line = _run(capsys, tiny.cell(config, limits, **CONTROL_SIZE),
                control="fp8")
    assert line["correct"] is False, line["compared"]


# the control's size: large enough that float8 shows, small enough for a
# test run
CONTROL_SIZE = {"batch": 4, "seq_len": 128}


def test_a_trainer_on_weights_of_its_own_is_refused_by_name():
    import numpy as np
    from bench.jobs import train
    want = {"layer0.a": np.ones((2, 3), np.float32)}
    train._check_weights({"layer0.a": np.ones((2, 3), np.float32)}, want)
    with pytest.raises(train.SeamError):
        train._check_weights({"layer0.a": np.zeros((2, 3), np.float32)},
                             want)
    with pytest.raises(train.SeamError):
        train._check_seams(object())


def test_a_trainer_on_the_benchmarks_weights_in_bfloat16_is_taken():
    import ml_dtypes
    import numpy as np
    from bench.jobs import train
    want = {"layer0.a": np.linspace(-1.0, 1.0, 64, dtype=np.float32)}
    got = {"layer0.a": want["layer0.a"].astype(ml_dtypes.bfloat16)}
    train._check_weights(got, want)
