"""The wide-area cell off the chip: the whole command on ``toy4-wan`` (four
validators in two regions, ``toy_wan/spec.json``), both ``--trace`` values,
and its control, ``control_zero_delay_node_main.py`` - a node whose links
let every frame but the RTT probe through at once, with the table, the
gauges and the counts as configured - which must come out ``correct:
false`` by the floor comparison alone.

Slow (a CPU service traces the ladder for over a minute, a CPU fleet commits
a leader every few seconds, and the toy's far links are seconds long so
that the floor stands clear of a CPU's processor time): ~4 minutes a case.
One at a time: the cells share their ports."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, lastline
from benchmark.reference import wan

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_wan", "spec.json")
CONTROL = os.path.join(HERE, "control_zero_delay_node_main.py")
CELL = "toy4-wan"
SECONDS = 10
DELAY_CHECKS = ("finality_floor_s", "not counted through the delay line",
                "mesh RTT", "dropped at a full send queue")


def rehearse(trace: int, seed: int, spec: str = TOY):
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--spec", spec, "--workload", CELL, "--seed", str(seed),
         "--seconds", str(SECONDS), "--trace", str(trace)],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    path = os.path.join(harness.ROOT, ".bench_work",
                        f"{CELL}-t{trace}.last_line.json")
    with open(path) as f:
        line = json.load(f)
    compared = [l for l in done.stdout.splitlines()
                if l.startswith("compared: ")]
    return done, line, compared


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_off_the_chip(trace):
    done, line, compared = rehearse(trace, 3_200_000_011 + trace)
    assert done.returncode == harness.EXIT_NO_TPU, done.stderr[-3000:]
    assert "no TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    cell = harness.find_cell(CELL, TOY)
    assert lastline.validate(line, cell, bool(trace)) == []
    assert line["correct"] is True, done.stdout[-3000:]
    for name in DELAY_CHECKS:
        assert any(name in l and l.endswith("ok") for l in compared), name
    if trace:
        metrics = line["metrics"]
        assert 0.0 < metrics["finality_floor_share"]["value"] < 100.0
        assert metrics["rounds_s"]["value"] > 0.0
        assert metrics["sigs_per_dispatch.wan"]["value"] > 0.0
        for name in ("mesh_hold_excess_ms", "leaders_indirect_share",
                     "leaders_skipped_share"):
            assert name in metrics, sorted(metrics)
        assert "setup_s" not in metrics
    else:
        assert 0 <= line["failed"] < line["attempted"]
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}


def test_a_node_that_lets_blocks_past_the_line_is_not_correct():
    control = harness.load_module(CONTROL, "control_zero_delay_node_main")
    spec = control.control_spec(TOY, CELL, os.path.join(
        harness.ROOT, ".bench_work", "control-toy4-wan"))
    done, line, compared = rehearse(0, 3_200_000_031, spec)
    assert done.returncode != 0
    assert (line.get("line") or line)["correct"] is False
    failed = [l for l in compared if l.endswith("FAILED")]
    # By the floor comparison alone: the program's own counters, its
    # gauges and its RTT probe all read as configured.
    assert len(failed) == 1 and "finality_floor_s" in failed[0], failed


def test_the_configuration_states_the_references_table():
    """``paper10wan.json`` by value against ``reference/wan.py``, and
    against ``paper10.json`` in everything but the delay."""
    configs = os.path.join(harness.HERE, "configs")
    config = harness.load_json(os.path.join(configs, "paper10wan.json"))
    assert config["parameters"]["link_delay_ms"] == wan.one_way_table_ms()
    assert config["assumed"]["rtt_ms"]["table"] == wan.RTT_MS
    assert config["regions"] == list(wan.REGIONS)
    paper10 = harness.load_json(os.path.join(configs, "paper10.json"))
    parameters = dict(config["parameters"])
    del parameters["link_delay_ms"]
    assert parameters == paper10["parameters"]
    for key in ("validators", "transaction_bytes", "verifier", "node_env",
                "service", "probe"):
        assert config[key] == paper10[key], key
    entry = {c["name"]: c for c in harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json"))["configs"]}["paper10wan"]
    assert entry["source"] == config["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    steady = harness.load_json(os.path.join(harness.HERE, "traffic",
                                            "steady.json"))
    steady_wan = harness.load_json(os.path.join(harness.HERE, "traffic",
                                                "steady-wan.json"))
    same = ("tick_s", "warmup_s", "drain_s", "grace_s", "trace")
    assert {k: steady[k] for k in same} == {k: steady_wan[k] for k in same}
    assert steady_wan["rate_tx_s"] <= steady["rate_tx_s"]


def _fake_run(latencies, floors, nodes=None):
    class Run:
        seconds = 10.0
        observed = {"client": {"latencies": latencies},
                    "wan": {"floors_s": floors}}
        snapshots = {}

    if nodes is not None:
        Run.observed["nodes"] = nodes
    return Run


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def test_the_new_readers_on_hand_built_scrapes():
    """Each reader on numbers whose answer is plain; and on a program that
    lacks the series (a parent commit) every one reads nothing."""
    def node(round_, frames, hold_sum, direct, indirect, skipped):
        return [
            ("threshold_clock_round", {}, float(round_)),
            ("mesh_link_delay_seconds", {"peer": "1"}, 0.010),
            ("mesh_link_delay_seconds", {"peer": "2"}, 0.050),
            ("mesh_delayed_frames_total", {"peer": "1"}, float(frames)),
            ("mesh_delayed_frames_total", {"peer": "2"}, float(frames)),
            ("block_stage_seconds_sum", {"stage": "mesh_hold"}, hold_sum),
            ("block_stage_seconds_count", {"stage": "mesh_hold"},
             2.0 * frames),
            ("mysticeti_commit_decision_total",
             {"rule": "direct", "outcome": "commit"}, float(direct)),
            ("mysticeti_commit_decision_total",
             {"rule": "indirect", "outcome": "commit"}, float(indirect)),
            ("mysticeti_commit_decision_total",
             {"rule": "indirect", "outcome": "skip"}, float(skipped)),
        ]

    nodes = {"start": [node(100, 0, 0.0, 0, 0, 0)],
             # 100 frames a link: 100 x 0.010 + 100 x 0.050 = 6 s of
             # configured delay, 6.2 s held: 1 ms a frame over.
             "end": [node(240, 100, 6.2, 70, 20, 10)]}
    run = _fake_run([0.4, 0.5, 0.6], [0.15, 0.16, 0.22, 0.23], nodes)
    assert _reader("finality_floor_share").read(run) == pytest.approx(32.0)
    assert _reader("rounds_s").read(run) == pytest.approx(14.0)
    assert _reader("mesh_hold_excess_ms").read(run) == pytest.approx(1.0)
    assert _reader("leaders_indirect_share").read(run) == pytest.approx(30.0)
    assert _reader("leaders_skipped_share").read(run) == pytest.approx(10.0)
    # A validator that has no delay line (a parent commit's, or an empty
    # table): scraped, but without the series.
    plain = _fake_run([0.2], [], {
        "start": [[("threshold_clock_round", {}, 100.0)]],
        "end": [[("threshold_clock_round", {}, 360.0)]]})
    plain.observed.pop("wan")
    assert _reader("rounds_s").read(plain) == pytest.approx(26.0)
    for name in ("finality_floor_share", "mesh_hold_excess_ms",
                 "leaders_indirect_share", "leaders_skipped_share",
                 "sigs_per_dispatch.wan"):
        assert _reader(name).read(plain) is None, name
    # An untraced run scrapes nothing at the window's edges.
    unscraped = _fake_run([0.2], [0.15])
    for name in ("rounds_s", "mesh_hold_excess_ms", "leaders_indirect_share",
                 "leaders_skipped_share", "sigs_per_dispatch.wan"):
        assert _reader(name).read(unscraped) is None, name
