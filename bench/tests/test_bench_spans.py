"""bench/spans.py and the metrics that read the program's spans, on a
small recorded trace: two steps in the window, the feed's last ask after
them, and a checked step before it.

Device 0 runs [1000,5000] and [9000,15000] of the window [0,20000], so
it idles over [0,1000], [5000,9000] and [15000,20000]: 10000 ns."""
import json
import pathlib

import pytest

from bench import common, spans

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures" / \
    "program_spans.json"


@pytest.fixture()
def events():
    return json.loads(FIXTURE.read_text())


def _metric(name, events, monkeypatch):
    monkeypatch.setattr(spans, "of_cell", lambda cell: events)
    reader = common.load_module(common.BENCH / "metrics" / f"{name}.py")
    return reader.read({"cell": None})


def test_idle_is_what_device_zero_leaves_of_the_window(events):
    assert spans.idle(events) == [(0.0, 1000.0), (5000.0, 9000.0),
                                  (15000.0, 20000.0)]


def test_a_gap_that_straddles_two_spans_is_split_by_overlap(events):
    by = spans.idle_by_span(events)
    # [5000,9000] runs from step 3's fetch through its hook into step 4's
    # next batch, placement, dispatch and fetch: each gets its overlap
    assert by["spt.train.hook"] == 1200 + 1100
    assert by["spt.train.next_batch"] == 200 + 500 + 200
    assert by["spt.train.place"] == 200 + 500
    assert by["spt.train.dispatch"] == 400 + 600
    assert by["spt.train.fetch"] == 100 + 600 + 400 + 200
    # between a step's children, the step span itself is innermost
    assert by["spt.train"] == 4 * 100
    # before the first step and after the feed ran dry: no program span
    assert by[None] == 100 + 3300
    assert sum(by.values()) == 10000


def test_only_step_spans_that_dispatched_in_the_window_count(events):
    assert spans.steps(events) == [(100.0, 7000.0), (7000.0, 16500.0)]
    # step 3: [100,1000] and [5000,7000]; step 4: [7000,9000], [15000,16500]
    assert spans.idle_in(events, spans.steps(events)) == 2900 + 3500


def test_trainer_idle_is_the_idle_time_per_step(events, monkeypatch):
    value = _metric("trainer_idle_ms.train", events, monkeypatch)
    assert value == pytest.approx((2900 + 3500) / 2 * 1e-6)


def test_slot_fill_sums_the_window_counters(events, monkeypatch):
    value = _metric("routed_ffn_slot_fill.train", events, monkeypatch)
    assert value == pytest.approx(100.0 * (1000 + 1010) / (2 * 1296))


@pytest.mark.parametrize("name", ["trainer_idle_ms.train",
                                  "routed_ffn_slot_fill.train"])
def test_nothing_is_read_without_program_spans(events, monkeypatch, name):
    events["program"] = []
    assert _metric(name, events, monkeypatch) is None
    assert _metric(name, None, monkeypatch) is None


def test_a_directory_without_a_trace_reads_nothing(tmp_path):
    assert spans.read(tmp_path) is None


def test_read_takes_program_spans_and_their_arguments_from_a_trace(
        tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.StepTraceAnnotation("spt.train", step_num=7):
            with jax.profiler.TraceAnnotation("spt.train.counters",
                                              routed_kept=3.0):
                pass
    jax.profiler.stop_trace()
    events = spans.read(tmp_path)
    assert spans.read(tmp_path) is events           # parsed once
    names = [n for n, _, _, _ in events["program"]]
    assert sorted(names) == ["spt.train", "spt.train.counters"]
    assert [n for n, _, _ in events["host"]] == ["bench.window"]
    assert spans.counters(events) == [{"routed_kept": 3.0}]
    step = next(e for e in events["program"] if e[0] == "spt.train")
    assert step[3]["step_num"] == 7
