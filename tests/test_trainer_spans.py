"""Trainer.run's profiler spans and its per-step counter event, read back
from a profiler trace of three tiny fine-tuning steps on the CPU."""
import dataclasses
import glob

import jax
import pytest

from repro import configs
from repro.data.pipeline import DataConfig, synthetic_dataset
from repro.models import ffn
from repro.optim.adamw import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig

STEPS = 3
CHILDREN = ("spt.train.next_batch", "spt.train.place", "spt.train.dispatch",
            "spt.train.fetch", "spt.train.counters", "spt.train.hook")


def _tiny_cfg():
    return dataclasses.replace(
        configs.get_smoke("qwen3-0.6b"), num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256)


def _train():
    """Three steps of a fresh trainer (the data runs on past them); the
    losses the step hook saw."""
    cfg = _tiny_cfg()
    data = synthetic_dataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2,
                   seed=5), steps=STEPS + 2)
    t = Trainer(cfg, OptimizerConfig(total_steps=STEPS),
                TrainerConfig(total_steps=STEPS, log_interval=1), seed=1)
    losses = []
    rep = t.run(data, step_hook=lambda step, m: losses.append(m["loss"]))
    assert rep["final_step"] == STEPS
    return losses


def _host_events(trace_dir):
    """(name, start ns, end ns, args) of every ``spt.train`` host event."""
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spt.train"):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        losses = _train()
    finally:
        jax.profiler.stop_trace()
    return losses, _host_events(d)


def _steps(events):
    return [e for e in events if e[0] == "spt.train"]


def test_one_step_span_per_step_with_its_number(traced):
    _, events = traced
    steps = _steps(events)
    assert [e[3]["step_num"] for e in steps] == list(range(STEPS))


def test_each_child_nests_in_its_step_in_order(traced):
    _, events = traced
    for name, a, b, _ in _steps(events):
        inside = [e for e in events
                  if e[0] != "spt.train" and a <= e[1] and e[2] <= b]
        assert tuple(e[0] for e in inside) == CHILDREN, name
    # the step's children account for every child event of the run
    children = [e for e in events if e[0] != "spt.train"]
    assert len(children) == STEPS * len(CHILDREN)


def test_counters_count_kept_pairs_under_routed_pairs_and_slots(traced):
    _, events = traced
    counters = [e[3] for e in events if e[0] == "spt.train.counters"]
    assert len(counters) == STEPS
    # batch 2 x 32 tokens, G' = 4 of G = 8 groups, 2 routed layers;
    # capacity(32, 8, 4, 1.25, 8) = 24 slots per (sequence, group)
    for c in counters:
        assert c["routed_pairs"] == 2 * 32 * 4 * 2
        assert c["routed_slots"] == 2 * 2 * 8 * 24
        assert 0 < c["routed_kept"] <= c["routed_pairs"]
        assert c["routed_kept"] <= c["routed_slots"]


def test_the_profiler_does_not_change_the_losses(traced):
    losses, _ = traced
    assert _train() == losses


def test_slot_counts_only_where_the_ffn_routes_through_capacity():
    cfg = _tiny_cfg()
    # 2 routed layers, each dropping half its pairs: dropped sums to 1.0
    assert ffn.slot_counts(cfg, 2, 32, 1.0) == {
        "routed_pairs": 512.0, "routed_kept": 256.0, "routed_slots": 768.0}
    assert ffn.slot_counts(cfg.with_spt(routed_ffn=False), 2, 32, 0.0) is None
    assert ffn.slot_counts(cfg.with_spt(ffn_impl="dense"), 2, 32, 0.0) is None
