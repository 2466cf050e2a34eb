"""The trace -> metrics reduction, on a hand-built trace whose lines
overlap (``fixtures/device_trace.textproto``)."""
import os

import pytest

from benchmark import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "device_trace.textproto")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_profile(xplane.read_profile(None, FIXTURE),
                                 xplane.load_names())


def test_union_merges_nested_and_touching_intervals():
    assert xplane.union([(0, 2), (1, 3), (3, 4), (6, 7), (6.5, 6.6)]) == [
        (0, 4), (6, 7)]
    assert xplane.union([(5, 5), (2, 1)]) == []


def test_clip_and_gaps():
    assert xplane.clip([(-1, 1), (2, 3), (9, 12), (13, 14)], 0, 10) == [
        (0, 1), (2, 3), (9, 10)]
    assert xplane.gaps([(1, 3), (4, 5)], 0, 10) == [(0, 1), (3, 4), (5, 10)]


def test_busy_is_the_union_of_the_op_line_clipped_to_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.010)
    # while.1 [1,3] holds custom-call.2 [1.2,2.8]; the last op is cut at 10.
    assert reduced["busy_s"] == pytest.approx(0.004)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    # What PR 22 printed: durations summed over lines that cover the same
    # time pass the window.
    summed = sum(seconds for _, seconds, _ in reduced["device_ops"])
    assert summed > reduced["busy_s"]


def test_the_traced_window_is_the_part_of_the_session_the_caller_names():
    # The session also spans arming and collecting: [2 ms, 8 ms) of it.
    part = xplane.reduce_profile(
        xplane.read_profile(None, FIXTURE), xplane.load_names(),
        (1_002_000_000, 1_008_000_000))
    assert part["window_s"] == pytest.approx(0.006)
    assert part["busy_s"] == pytest.approx(0.0025)  # [2,3] [4,5] [5.5,6]
    assert part["kernels"]["verify_keyed"]["launches"] == 1
    assert "verify_indexed" not in part["kernels"]  # cut by the edge
    assert sum(s for _, s in part["idle_gaps"]) == pytest.approx(0.0035)


def test_launches_are_grouped_by_kernel_label(reduced):
    kernels = reduced["kernels"]
    # The launch that runs past the session's end is not a whole launch.
    assert kernels["verify_indexed"] == {
        "seconds": pytest.approx(0.002), "launches": 1}
    assert kernels["verify_keyed"] == {
        "seconds": pytest.approx(0.001), "launches": 1}
    assert kernels["other"]["launches"] == 1


def test_launch_gaps(reduced):
    assert reduced["launch_gaps_us"] == {"count": 2,
                                         "p50": pytest.approx(750.0)}


def test_idle_gaps_are_named_by_the_host_event_over_them(reduced):
    idle = dict(reduced["idle_gaps"])
    assert idle["TransferToDevice"] == pytest.approx(0.0035)
    assert idle["PjitFunction(_verify_fused_indexed_pallas_jit)"] == (
        pytest.approx(0.001))
    assert idle["unattributed"] == pytest.approx(0.0015)
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_a_trace_without_a_device_event_is_an_error():
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        text = f.read()
    no_ops = ProfileData.from_text_proto(
        text.replace('name: "XLA Ops"', 'name: "Other"'))
    with pytest.raises(ValueError, match="no operation"):
        xplane.reduce_profile(no_ops, xplane.load_names())
    host_only = ProfileData.from_text_proto(
        text.replace('name: "/device:TPU:0"', 'name: "/device:CPU:0"'))
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce_profile(host_only, xplane.load_names())
