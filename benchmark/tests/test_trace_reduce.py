"""The reduction from a trace to numbers: its arithmetic on hand-made
intervals, and its numbers on the small trace recorded on a v5e
(``tools/record_small_trace.py``, PR 25)."""
import os

import pytest

import trace_reduce as tr
from conftest import HERE

SMALL = os.path.join(HERE, "data", "small_v5e.xplane.pb")


def test_union_merges_nested_and_overlapping_intervals():
    assert tr._union([(0, 10), (2, 3), (5, 12), (20, 21)]) == [
        [0, 12], [20, 21]]


def test_self_time_takes_the_body_out_of_the_loop():
    # a while op of 100 ns encloses two ops of 30 and 50; one op stands apart
    got = tr._self_times([(0, 100, "while"), (10, 30, "dot"),
                          (45, 50, "fusion"), (200, 5, "dot")])
    assert got == {"while": 20, "dot": 35, "fusion": 50}


def test_short_name_groups_the_layers_calls_of_one_kernel():
    a = tr.short_name(
        '%pure.47 = f32[8,16,8,128]{3,2,1,0:T(8,128)S(1)} custom-call('
        's32[8,128]{1,0} %copy-done.5), custom_call_target="tpu_custom_call"')
    b = tr.short_name(
        '%pure.24 = f32[8,16,8,128]{3,2,1,0:T(8,128)} custom-call('
        's32[8,128]{1,0} %copy-done.4), custom_call_target="tpu_custom_call"')
    assert a == b == "pure custom-call:tpu_custom_call f32[8,16,8,128]"
    assert tr.short_name(
        "%copy.219 = bf16[24,1025,16,16,128]{4,2,3,1,0:T(8,128)(2,1)} "
        "copy(bf16[24,1025,16,16,128]{4,3,2,1,0} %kc.1)"
    ) == "copy copy bf16[24,1025,16,16,128]"


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_the_recorded_trace_reduces_to_its_pinned_numbers():
    import harness

    red = tr.reduce_trace(SMALL, host_spans=harness.Spans.NAMES)
    assert red.n_devices == 1
    pinned = PINNED
    assert red.window_s == pytest.approx(pinned["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(pinned["busy_s"], rel=1e-9)
    assert 0 < red.busy_s < red.window_s
    top = dict(red.top_ops(3))
    for name, seconds in pinned["top_ops"].items():
        assert top[name] == pytest.approx(seconds, rel=1e-9)
    idle = dict(red.top_idle())
    for name, seconds in pinned["idle"].items():
        assert idle[name] == pytest.approx(seconds, rel=1e-9)
    assert sum(idle.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert red.kernel_seconds(pinned["pattern"]) == pytest.approx(
        pinned["pattern_s"], rel=1e-9)


#: read off the recorded trace once (PR 25); a change to the reduction that
#: moves any of them changes every later PR's per-layer numbers
PINNED = {
    "window_s": 0.034738298, "busy_s": 0.000164315,
    "top_ops": {"convolution_tanh_fusion fusion bf16[1024,1024]": 0.000138826,
                "copy-done copy-done bf16[1024,1024]": 1.8898e-05,
                "copy copy bf16[1024,1024]": 6.324e-06},
    # three steps of a four-trip loop: the loop's own time is what its body
    # leaves, and the 10 ms sleeps between the steps are the idle time
    "idle": {"make_batch": 0.034573983},
    "pattern": "convolution", "pattern_s": 0.000138826}
