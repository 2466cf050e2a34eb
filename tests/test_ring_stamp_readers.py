"""The two benchmark readers of the ring's stamps that say how often a
client's shared connection engages (``service_requests_per_read``,
``service_loop_cpu_ms_per_request``), fed a service report built by hand:
the ratios worked out over the whole seconds inside the window, and None
where the ring lacks the stamps (a program from before them leaves the
metric out, it does not raise)."""
import json
import os
import types

import pytest

from benchmark import harness

LAYER_METRICS = os.path.join(harness.HERE, "layer_metrics")

# The window [100.5, 104.5) holds the whole seconds 101, 102 and 103.
WINDOW = (100.5, 104.5)


def _second(requests, reads, loop_cpu_s):
    return {
        "service_decode": [requests // 32, 0.01, 0.008, 0.001],
        "requests": requests, "signatures": 8 * requests, "launches": 40,
        "reads": reads, "writes": reads,
        "process_cpu_s": 0.5, "threads_cpu_s": 0.4, "loop_cpu_s": loop_cpu_s,
    }


def _report(seconds):
    return {"stages": {
        "clock": "time.monotonic",
        "columns": ["count", "wall_s", "cpu_s", "max_wall_s"],
        "sample_one_in": 32,
        "seconds": seconds,
    }}


STAMPED = _report({
    "100": _second(9000, 9000, 0.9),  # before the window
    "101": _second(4000, 1000, 0.30),
    # 102: a second in which the service booked nothing
    "103": _second(2000, 1000, 0.18),
    "104": _second(9000, 9000, 0.9),  # cut by the window's end
})
# 6,000 requests in 2,000 reads, on 0.48 s of the loop's CPU.
EXPECTED = {
    "service_requests_per_read": 3.0,
    "service_loop_cpu_ms_per_request": 0.08,
}


def _without(*stamps):
    """The ring of a program that counts requests and not ``stamps``."""
    return _report({
        second: {k: v for k, v in entry.items() if k not in stamps}
        for second, entry in STAMPED["stages"]["seconds"].items()})


def _read(name, report=None, window=WINDOW):
    reader = harness.load_module(
        os.path.join(LAYER_METRICS, name + ".py"),
        "ring_stamp_reader_" + name)
    return reader.read(
        types.SimpleNamespace(window=window, service_report=report))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_stamp_reader_sums_the_whole_seconds_inside_the_window(name):
    assert _read(name, STAMPED) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_stamp_reader_finds_nothing_where_the_ring_lacks_its_stamps(name):
    assert _read(name) is None
    assert _read(name, {"platform": "tpu"}) is None
    assert _read(name, STAMPED, window=None) is None
    assert _read(name, _without("reads", "writes", "loop_cpu_s")) is None
    assert _read(name, STAMPED, window=(102.1, 102.9)) is None  # no second


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_stamp_reader_is_a_per_layer_metric_of_the_catch_up_cell(name):
    """The entry that names the reader: the verifier service's layer, moves
    ``verified_sig_s``, read in ``service10-catchup`` alone."""
    with open(os.path.join(os.path.dirname(harness.HERE),
                           "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry["layer"] == "verifier service"
    assert entry["moves"] == "verified_sig_s"
    assert entry["workloads"] == ["service10-catchup"]
    assert entry["better"] == (
        "higher" if name == "service_requests_per_read" else "lower")
