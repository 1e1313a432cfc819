"""Small cells for the benchmark's CPU tests: the fixtures' two-layer
configurations under the fine-tuning traffic, shortened."""
from __future__ import annotations

import json
import pathlib
import types

from bench import common

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"

BENCH = {
    "configs": [{"name": "tiny", "file": "unused"}],
    "workloads": [{"name": "tiny.finetune", "config": "tiny",
                   "traffic": "finetune", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s", "unit": "tokens/s"},
                   {"name": "train_peak_gib", "unit": "GiB"}],
    "per_layer": [],
}


def cell(config: str = "tiny-qwen", limits_of: str = "qwen3-0.6b",
         **traffic) -> common.Cell:
    """A cell of a fixture configuration under the committed fine-tuning
    traffic, cut to batch 2 x 64 unless ``traffic`` says otherwise, held to
    the committed limits of ``limits_of``'s cell."""
    t = common.load_json(common.BENCH / "traffic" / "finetune-1k.json")
    t.update({"batch": 2, "seq_len": 64, "pool": 4, "traced_steps": 2,
              **traffic})
    limits = common.load_json(common.BENCH / "limits"
                              / f"{limits_of}.finetune-1k.json")
    conf = json.loads((FIXTURES / f"{config}.json").read_text())
    return common.Cell.from_parts("tiny.finetune", BENCH, conf, t, limits)


def args(seed: int, seconds: float = 1.0, control=None):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 control=control)
