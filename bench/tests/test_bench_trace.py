"""bench/trace.py's reduction on a small recorded trace."""
import json
import pathlib

import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures" / \
    "trace_events.json"


@pytest.fixture()
def reduced():
    return trace.reduce(json.loads(FIXTURE.read_text()))


def test_busy_is_the_union_of_leaf_operations_inside_the_window(reduced):
    # window [0, 13000] ns; the loop [900,5900] holds the ops of its body;
    # leaves [1000,4000] [4000,5000] [5000,5500] [8000,9000]
    # [12000,13000 clipped from 14000]: 4500 + 1000 + 1000
    assert reduced["window_s"] == pytest.approx(13000e-9)
    assert reduced["busy_s"] == pytest.approx(6500e-9)
    assert "while.3" not in dict(reduced["device_ops"])


def test_device_ops_are_summed_by_name_and_ranked(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.1"] == pytest.approx(3500e-9)
    assert reduced["device_ops"][0][0] == "fusion.1"
    assert ops["fusion.9"] == pytest.approx(1000e-9)      # clipped


def test_idle_gaps_are_labelled_by_the_innermost_host_span(reduced):
    gaps = reduced["idle_gaps"]
    # gaps: [0,1000] [5500,8000] [9000,12000]; the longest first
    assert [g for _, g in gaps] == pytest.approx([3000e-9, 2500e-9, 1000e-9])
    # [9000,12000]: midpoint 10500 lies in the step hook only
    # [5500,8000]: midpoint 6750 in the step dispatch
    assert [n for n, _ in gaps] == ["bench.step_hook", "bench.step_dispatch",
                                    "bench.window"]


def test_kernel_time_matches_the_operation_description(reduced):
    pallas = r"= bf16\[64,1024,128\]\S* custom-call tpu_custom_call"
    assert trace.kernel(reduced, [pallas]) == pytest.approx((1000e-9, 1))
    assert trace.kernel(reduced, [r"^fusion\.1 "]) == \
        pytest.approx((3500e-9, 2))
    assert trace.kernel(reduced, [r"no_such_kernel"]) == (0, 0)


def test_an_instruction_is_described_by_name_type_op_and_target():
    text = ('%pq_assign.42 = s32[64,1024,16]{2,1,0:T(8,128)} custom-call('
            'bf16[64,1024,128]{2,1,0} %x), custom_call_target='
            '"tpu_custom_call", frontend_attributes={}')
    assert trace.describe(text) == (
        "pq_assign.42", "pq_assign.42 = s32[64,1024,16]{2,1,0:T(8,128)} "
        "custom-call tpu_custom_call")
    name, _ = trace.describe("%while.1 = (s32[], f32[2]) while(%t)")
    assert name == "while.1"


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})


def test_peaks_of_an_unknown_device_are_an_error():
    assert trace.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        trace.peaks("TPU v99")
