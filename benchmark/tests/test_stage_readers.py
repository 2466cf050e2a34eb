"""The readers of the program's stage clock, fed a hand-built run: a report
with a ring and two validators' scrapes at the window's edges.  Each new
per-layer metric gives the number worked out by hand here, and None where
its source is missing (the parent commit has no ring and no series)."""
import os
import types

import pytest

from benchmark import harness

LAYER_METRICS = os.path.join(harness.HERE, "layer_metrics")

# The window [100.5, 104.5) holds the whole seconds 101, 102 and 103.
WINDOW = (100.5, 104.5)


def _second(requests, scale=1.0, gc=None, lag=None):
    """One second's ring entry: [count, wall_s, cpu_s, max_wall_s].  The
    service clocked a quarter of the requests: a request stage's count,
    wall and CPU are a quarter of what all would give."""
    def cell(wall, cpu, longest):
        return [requests // 4, wall * scale / 4, cpu * scale / 4, longest]

    entry = {
        "service_decode": cell(0.010, 0.008, 0.001),
        "service_pool_wait": cell(0.300, 0.0, 0.02),
        "service_unpack": cell(0.050, 0.040, 0.001),
        "service_pack": cell(0.200, 0.160, 0.002),
        "service_launch": cell(0.400, 0.300, 0.004),
        "service_fetch": cell(2.000, 0.0, 0.05),
        "service_reply_build": cell(0.020, 0.012, 0.001),
        "service_reply_wait": cell(0.500, 0.0, 0.03),
        # The second's stamp: what was answered and the CPU used in it.
        "requests": requests,
        "signatures": 8 * requests,
        "process_cpu_s": 0.02 * requests,
        "threads_cpu_s": 0.007 * requests,
        "loop_cpu_s": 0.002 * requests,
    }
    if gc is not None:
        entry["service_gc"] = [1, gc, gc, gc]  # every collection is booked
    if lag is not None:
        entry["service_loop_lag"] = [20, lag, 0.0, lag / 4]
    return entry


def _report():
    return {"stages": {
        "clock": "time.monotonic",
        "columns": ["count", "wall_s", "cpu_s", "max_wall_s"],
        "sample_one_in": 4,
        "seconds": {
            "100": _second(999, 9.0, gc=0.9, lag=0.9),  # before the window
            "101": _second(100, gc=0.2, lag=0.2),
            "102": _second(100, lag=0.1),
            "103": _second(200, 2.0, gc=0.1, lag=0.3),
            "104": _second(999, 9.0, gc=0.9, lag=0.9),  # cut by its end
        },
    }}


def _scrape(verify_sum, verify_count, dag_sum, dag_count):
    return harness.parse_metrics("\n".join([
        f'block_stage_seconds_sum{{stage="verify"}} {verify_sum}',
        f'block_stage_seconds_count{{stage="verify"}} {verify_count}',
        f'block_stage_seconds_sum{{stage="dag_add"}} {dag_sum}',
        f'block_stage_seconds_count{{stage="dag_add"}} {dag_count}',
        f'block_stage_seconds_sum{{stage="receive"}} 5.0',
        f'block_stage_seconds_count{{stage="receive"}} 50',
    ]))


def _run(report=None, nodes=None):
    return types.SimpleNamespace(
        window=WINDOW, seconds=WINDOW[1] - WINDOW[0],
        service_report=report, observed={"nodes": nodes} if nodes else {})


def _read(name, run):
    reader = harness.load_module(
        os.path.join(LAYER_METRICS, name + ".py"),
        "test_reader_" + name.replace(".", "_"))
    return reader.read(run)


# 400 requests in 3 whole seconds; the threads that carry the stages used
# 7 ms of CPU a request.  A clocked request's CPU in a stage: the stage's
# CPU over its count, e.g. service_launch 4 * 0.300 / 400 s.
SERVICE = {
    "service_cpu_cores.service": 0.007 * 400 / 3,
    "service_cpu_cores.fleet": 0.007 * 400 / 3,
    "service_cpu_ms_per_request": 7.0,
    "service_pack_cpu_ms": 1e3 * 4 * (0.040 + 0.160) / 400,
    "service_launch_cpu_ms": 1e3 * 4 * 0.300 / 400,
    "service_pool_wait_ms": 1e3 * 4 * 0.300 / 400,
    "service_fetch_wait_ms": 1e3 * 4 * 2.000 / 400,
    "service_reply_wait_ms": 1e3 * 4 * 0.500 / 400,
    "service_loop_lag_ms": 1e3 * (0.2 + 0.1 + 0.3) / 60,
    "service_gc_pause_share": 100.0 * (0.2 + 0.1) / 3,
}
# Node 0: verify 1.2 s over 100 batches = 12 ms; node 1: 0.4 s over 50 =
# 8 ms; the median of two is their mean.  dag_add: 3 ms and 5 ms.
NODES = {
    "block_verify_ms": 10.0,
    "block_dag_add_ms": 4.0,
}


@pytest.mark.parametrize("name, expected", sorted(SERVICE.items()))
def test_a_service_reader_sums_the_whole_seconds_inside_the_window(
        name, expected):
    assert _read(name, _run(report=_report())) == pytest.approx(expected)


@pytest.mark.parametrize("name, expected", sorted(NODES.items()))
def test_a_validator_reader_takes_the_window_mean_median_over_nodes(
        name, expected):
    nodes = {
        "start": [_scrape(1.0, 100, 0.10, 100), _scrape(2.0, 10, 1.00, 10)],
        "end": [_scrape(2.2, 200, 0.40, 200), _scrape(2.4, 60, 1.25, 60)],
    }
    assert _read(name, _run(nodes=nodes)) == pytest.approx(expected)


@pytest.mark.parametrize("name", sorted(SERVICE) + sorted(NODES))
def test_a_reader_finds_nothing_in_a_program_without_the_clock(name):
    """The parent's report has no ``stages`` and its validators no
    ``block_stage_seconds``: None, and the metric is left out."""
    bare = {"platform": "tpu", "dispatches": []}
    no_series = {"start": [harness.parse_metrics("committed_leaders_total 1")],
                 "end": [harness.parse_metrics("committed_leaders_total 9")]}
    assert _read(name, _run()) is None
    assert _read(name, _run(report=bare, nodes=no_series)) is None


def test_every_new_reader_is_an_entry_of_the_benchmark_and_a_program_span():
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in list(SERVICE) + list(NODES):
        assert entries[name]["source"] == "program_span", name
        assert os.path.exists(os.path.join(LAYER_METRICS, name + ".py"))
    cells = {name: entries[name]["workloads"] for name in entries}
    assert cells["service_cpu_cores.service"] == ["service10-catchup"]
    assert cells["service_cpu_cores.fleet"] == ["paper10-steady"]
    assert cells["block_verify_ms"] == ["paper10-steady"]


def test_a_window_that_holds_no_whole_second_reads_nothing():
    run = _run(report=_report())
    run.window = (101.2, 101.9)
    assert _read("service_cpu_cores.service", run) is None


def test_a_host_whose_thread_clocks_cannot_be_read_gives_no_cpu_cores():
    """Without ``threads_cpu_s`` in the stamps (no ``pthread_getcpuclockid``)
    the CPU of the threads is not guessed from the stages; the stage
    readers still read."""
    report = _report()
    for entry in report["stages"]["seconds"].values():
        del entry["threads_cpu_s"]
    run = _run(report=report)
    assert _read("service_cpu_cores.service", run) is None
    assert _read("service_cpu_ms_per_request", run) is None
    assert _read("service_launch_cpu_ms", run) == pytest.approx(3.0)
    assert _read("service_pool_wait_ms", run) == pytest.approx(3.0)

