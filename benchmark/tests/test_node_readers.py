"""The readers of a validator's by-second ring (``benchmark/node_readers``),
fed two hand-made flight-recorder documents - validator 1's holds a stalled
second - and a service report: every per-layer metric of PR 39 gives the
number worked out by hand here, and None where no validator left a document
(the parent commit leaves none, or one without ``"stages"``)."""
import json
import os
import types

import pytest

from benchmark import harness, node_readers

LAYER_METRICS = os.path.join(harness.HERE, "layer_metrics")

# The window [100.5, 105.5) holds the whole seconds 101, 102, 103 and 104.
WINDOW = (100.5, 105.5)

NEW = ("slow_seconds", "core_hold_max_ms", "core_busy_share",
       "node_loop_lag_max_ms", "host_stall_seconds", "executor_wait_ms",
       "wal_sync_max_ms", "checkpoint_max_ms", "exec_fold_ms",
       "scrape_max_ms")


def _second(rounds, core=(100, 0.2, 0.15, 0.004), lag=0.002, shed=0,
            commit=(4, 0.8), **stages):
    """One second of a validator's ring: [count, wall_s, cpu_s,
    max_wall_s] a stage, and the stamp's counts."""
    entry = {
        "core_command": list(core),
        "loop_lag": [4, 2 * lag, 0.0, lag],
        "executor_wait": [50, 0.05, 0.0, 0.003],
        "exec_fold": [rounds, 0.002 * rounds, 0.001 * rounds, 0.004],
        "phase_commit": [commit[0], commit[1], 0.0, commit[1] / 2],
        "rounds": rounds, "leaders": rounds, "proposals": rounds,
        "blocks_received": 9 * rounds, "tx_admitted": 1280, "shed": shed,
        "shed_lane_cap": shed, "leader_timeouts": 0, "verify_requests": 30,
        "process_cpu_s": 0.9, "threads_cpu_s": 0.8, "loop_cpu_s": 0.7,
    }
    entry.update({name: list(cell) for name, cell in stages.items()})
    return entry


def _documents():
    calm = {
        "100": _second(26, core=(100, 0.9, 0.9, 0.5)),  # before the window
        "101": _second(26, wal_sync=(1, 0.004, 0.0, 0.004)),
        "102": _second(26, scrape=(1, 0.03, 0.0, 0.03)),
        "103": _second(25, checkpoint=(1, 0.06, 0.0, 0.06)),
        "104": _second(27, wal_sync=(1, 0.007, 0.0, 0.007)),
        # Cut by the window's end: only the scrape at that edge counts.
        "105": _second(26, lag=0.9, scrape=(1, 0.045, 0.0, 0.045)),
    }
    stalled = {
        "101": _second(26),
        # The episode: the core owner held the loop 213 ms, the probe's
        # tick came 150 ms late, the rounds stopped, operations were shed.
        "102": _second(4, core=(30, 0.6, 0.5, 0.213), lag=0.15, shed=12,
                       commit=(2, 4.0)),
        # A second that was never stamped: stages, no counts.
        "103": {"core_command": [10, 0.1, 0.1, 0.05],
                "executor_wait": [10, 0.07, 0.0, 0.02]},
        "104": _second(30, core=(100, 0.3, 0.2, 0.006),
                       wal_sync=(1, 0.011, 0.0, 0.011)),
    }
    ring = {"clock": "time.monotonic",
            "columns": ["count", "wall_s", "cpu_s", "max_wall_s"],
            "sample_one_in": 1}
    return [{"authority": 0, "events": [], "stages": dict(ring, seconds=calm)},
            {"authority": 1, "events": [],
             "stages": dict(ring, seconds=stalled)}]


def _service(lags):
    return {"stages": {"seconds": {
        str(second): {"service_loop_lag": [10, lag, 0.0, lag], "requests": 9}
        for second, lag in lags.items()}}}


def _run(tmp_path, documents=(), report=None):
    workdir = tmp_path / "work"
    for index, document in enumerate(documents):
        directory = workdir / "fleet" / f"validator-{index}"
        directory.mkdir(parents=True)
        (directory / "flight-recorder.json").write_text(json.dumps(document))
    (workdir / "fleet" / "validator-9").mkdir(parents=True)  # SIGKILLed
    return types.SimpleNamespace(
        workdir=str(workdir), window=WINDOW, service_report=report,
        traced_interval=(104.2, 104.6),
        trace_reduced={"busy_s": 0.1, "window_s": 0.4})


def _read(name, run):
    reader = harness.load_module(
        os.path.join(LAYER_METRICS, name + ".py"),
        "node_metric_" + name.replace(".", "_"))
    return reader.read(run)


# Worked out by hand over the seconds 101-104 of the two documents.
EXPECTED = {
    # Validator 0: 26, 26, 25, 27 -> median 26, none under 13: 0.
    # Validator 1: 26, 4, 0 (not stamped), 30 -> median 15, under 7.5: two.
    # Median of (0, 2).
    "slow_seconds": 1.0,
    # The 213 ms command of validator 1 (0.5 s before the window is out).
    "core_hold_max_ms": 213.0,
    # Validator 0: 4 x 0.2 / 4 s = 20%; validator 1: (0.2 + 0.6 + 0.1 +
    # 0.3) / 4 = 30%; median of the two.
    "core_busy_share": 25.0,
    # Validator 1's 150 ms tick (0.9 s lies in second 105, cut).
    "node_loop_lag_max_ms": 150.0,
    # The service lagged in 102 and 104; in 102 validator 1 alone of two
    # lagged over 100 ms: no second in which both did.
    "host_stall_seconds": 0.0,
    # Validator 0: 0.2 / 200 = 1 ms; validator 1: (0.05 x 3 + 0.07) / 160
    # = 1.375 ms; median of the two.
    "executor_wait_ms": 1.1875,
    "wal_sync_max_ms": 11.0,
    "checkpoint_max_ms": 60.0,
    # 2 ms a commit on both (0.002 x rounds over rounds).
    "exec_fold_ms": 2.0,
    # The scrape at the window's end edge (second 105, which it cuts).
    "scrape_max_ms": 45.0,
}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_of_the_validators_ring_gives_the_number_worked_by_hand(
        name, tmp_path):
    run = _run(tmp_path, _documents(), _service({102: 0.2, 104: 0.3}))
    assert _read(name, run) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_no_validator_left_a_document(
        name, tmp_path):
    """The parent's validators leave no document, or - where an operator
    asked for one - a document without ``"stages"``: None, and the metric
    is left out of the line."""
    assert _read(name, _run(tmp_path)) is None
    bare = [{"authority": 0, "events": []}]
    assert _read(name, _run(tmp_path / "bare", bare,
                            _service({102: 0.2}))) is None


def test_every_loop_of_the_machine_late_at_once_is_a_stalled_second(
        tmp_path):
    """``host_stall_seconds`` counts a second in which the service and the
    validators (eight of them, or all where fewer left a document) each
    hold a probe tick over 100 ms late."""
    documents = _documents()
    documents[0]["stages"]["seconds"]["102"]["loop_lag"] = [4, 0.5, 0.0, 0.4]
    run = _run(tmp_path, documents, _service({102: 0.2, 104: 0.3}))
    assert _read("host_stall_seconds", run) == 1.0
    quiet = _run(tmp_path / "q", documents, _service({102: 0.05}))
    assert _read("host_stall_seconds", quiet) == 0.0
    assert _read("host_stall_seconds",
                 _run(tmp_path / "n", documents, None)) is None


def test_the_window_is_logged_by_the_second_once_a_run(tmp_path, capsys):
    run = _run(tmp_path, _documents(), _service({102: 0.2}))
    assert node_readers.window(run) is node_readers.window(run)
    for name in NEW:
        _read(name, run)
    log = capsys.readouterr().err
    assert log.count("validators by the second (2 documents") == 1
    assert ("102: rounds 15/4 shed 6/12 phase_commit 1100.0/2000.0 "
            "longest core_command 213.0ms") in log
    assert "103: rounds 12.5/0" in log
    # The traced interval: what the validators sent the service, beside
    # the device's idle share.
    assert "requests the validators sent to the service by the second " \
           "{104: 60}" in log
    assert "device idle share 0.75" in log


def test_every_new_reader_is_an_entry_of_the_benchmark_with_its_cells():
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in spec["per_layer"]}
    fleet = ["paper10-steady", "transfers10-signed", "paper10wan-steady",
             "paper10f3-steady"]
    layers = {"slow_seconds": "core", "core_hold_max_ms": "core",
              "core_busy_share": "core", "node_loop_lag_max_ms": "core",
              "host_stall_seconds": "launch",
              "executor_wait_ms": "collector and pipeline",
              "wal_sync_max_ms": "storage", "checkpoint_max_ms": "storage",
              "exec_fold_ms": "execution", "scrape_max_ms": "launch"}
    assert [m["name"] for m in spec["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        entry = entries[name]
        assert os.path.exists(os.path.join(LAYER_METRICS, name + ".py"))
        assert entry["layer"] == layers[name], name
        assert entry["moves"] == "finality_p50_s"
        assert entry["better"] == "lower"
        assert entry["source"] in ("program_span", "program_counter")
        assert entry["workloads"] == (
            ["transfers10-signed"] if name == "exec_fold_ms" else fleet)
