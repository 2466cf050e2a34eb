"""The crash-recovery cell off the chip: the whole command on ``toy4-cr``
(four validators, the last one killed and started again on its WAL;
``toy_cr/spec.json``), both ``--trace`` values; its two controls -
``control_nowal_node_main.py``, a restart that ignores its WAL and boots
from genesis, which must come out ``correct: false`` by the recovery
comparisons and the two-digest check, and ``control_nogate_node_main.py``,
the proposal gate as it was before (connected is waited for), which must
come out ``correct: false`` by the leader timeouts in the window for the
returned validator's slots alone;
the configuration held to ``paper10.json``; the new readers on hand-built
runs.

Slow (a CPU service traces the ladder for over a minute): ~3 minutes a
case.  One at a time: the cells share the service's socket directory."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, lastline

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_cr", "spec.json")
CONTROL_NOWAL = os.path.join(HERE, "control_nowal_node_main.py")
CONTROL_NOGATE = os.path.join(HERE, "control_nogate_node_main.py")
CELL = "toy4-cr"
SECONDS = 20
SAFETY = ("differs from reference/commit_rule.py on that WAL's own DAG",
          "names otherwise", "pairs with two digests")
RECOVERY = ("reports of its recovery", "its own DAG does not support",
            "do not begin with the copy's sound bytes", "recovered boots")
LIVENESS = ("leader_timeout_total growth over the window",
            "connected_nodes is not 2",
            "still answered", "exit codes of the returned validator",
            "between its boot and the drain's end",
            "verified off the chip path, or rejected")
NEW = ("rejoin_lag_commits", "wal_replay_ms", "rejoin_blocks_s",
       "finality_p50_s.rejoin", "leader_timeouts_s.cr",
       "sigs_per_dispatch.cr")
# Read by the toy alone: its returned validator is in step inside the run,
# the cell's is not (PERF.md section 7), and a metric lists the cells in
# which its reader finds something to read.
TOY_ONLY = ("recover_s",)
# Twins of accepted readers whose lists of cells tests pin: what shows a
# stall of the nine, and whether the machine or the gate made it.
TWINS = ("slow_seconds.cr", "host_stall_seconds.cr",
         "node_loop_lag_max_ms.cr", "core_hold_max_ms.cr")


def rehearse(trace: int, seed: int, spec: str = TOY):
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--spec", spec, "--workload", CELL, "--seed", str(seed),
         "--seconds", str(SECONDS), "--trace", str(trace)],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=1500,
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
    done, line, compared = rehearse(trace, 4_500_000_011 + trace)
    assert done.returncode == harness.EXIT_NO_TPU, done.stderr[-3000:]
    assert "no TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    cell = harness.find_cell(CELL, TOY)
    assert lastline.validate(line, cell, bool(trace)) == []
    assert line["correct"] is True, done.stdout[-3000:]
    for name in SAFETY + RECOVERY + LIVENESS:
        assert any(name in l and l.endswith("ok") for l in compared), name
    assert "the rejoin by the half second" in done.stderr
    if trace:
        metrics = line["metrics"]
        assert metrics["leader_timeouts_s.cr"]["value"] == 0.0
        assert metrics["wal_replay_ms"]["value"] > 0.0
        # Absent where the returned validator was in step from its first
        # answer on (a toy fleet slowed to a round in two seconds by a
        # busy host stands still while it boots): nothing to divide.
        if "rejoin_blocks_s" in metrics:
            assert metrics["rejoin_blocks_s"]["value"] > 0.0
        assert metrics["rejoin_lag_commits"]["value"] < 1000.0
        assert metrics["finality_p50_s.rejoin"]["value"] > 0.0
        assert metrics["sigs_per_dispatch.cr"]["value"] > 0.0
        assert 0.0 <= metrics["leader_wait_ms"]["value"] < 2000.0
        assert metrics["rounds_s"]["value"] > 0.0
        if "recover_s" in metrics:
            assert 0.0 <= metrics["recover_s"]["value"] < SECONDS + 30
        assert "setup_s" not in metrics
    else:
        assert 0 <= line["failed"] < line["attempted"]
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}


def test_a_restart_that_ignores_its_wal_is_not_correct():
    control = harness.load_module(CONTROL_NOWAL, "control_nowal_node_main")
    spec = control.control_spec(TOY, CELL, os.path.join(
        harness.ROOT, ".bench_work", "control-toy4-cr"))
    done, line, compared = rehearse(0, 4_500_000_031, spec)
    assert done.returncode != 0
    assert (line.get("line") or line)["correct"] is False
    failed = [l for l in compared if l.endswith("FAILED")]
    # By what a recovery is held to, and by the second blocks it signed:
    # the fleet went on, nothing was lost, no timeout fired.
    assert any("reports of its recovery" in l for l in failed), failed
    assert any("pairs with two digests" in l for l in failed), failed
    assert all(any(name in l for name in RECOVERY + SAFETY[2:])
               for l in failed), failed


def test_a_gate_that_waits_for_whoever_is_connected_is_not_correct():
    control = harness.load_module(CONTROL_NOGATE, "control_nogate_node_main")
    spec = control.control_spec(TOY, CELL, os.path.join(
        harness.ROOT, ".bench_work", "control-toy4-cr"))
    # The toy's fleet makes a round and a half a second and the returned
    # validator is back in step some eight seconds after it connects: in a
    # run in which it leads no slot in those seconds nothing is waited for
    # and the control is sound (one run in a few; on the chip, 26 rounds a
    # second, every run of it fires 27 timeouts).  A second seed then.
    for seed in (4_500_000_032, 4_500_000_033):
        done, line, compared = rehearse(1, seed, spec)
        line = line.get("line") or line
        if line["correct"] is False:
            break
    assert done.returncode != 0
    assert line["correct"] is False
    failed = [l for l in compared if l.endswith("FAILED")]
    # The returned validator leads a slot in four and is behind: the
    # proposal held for its slot goes out at the toy's 5 s timeout.  Nothing
    # else is wrong: the WALs equal the reference and each other, the
    # recovery is what the copy holds.
    # (On a busy host the toy's fleet, halted 5 s by that timeout, may also
    # leave the returned validator short of its 20 commits: the same cause.)
    assert "leader_timeout_total growth over the window" in failed[0]
    assert all("between its boot and the drain's end" in l
               for l in failed[1:]), failed
    assert line["metrics"]["leader_timeouts_s.cr"]["value"] > 0.0


def test_the_configuration_is_paper10_plus_its_fault():
    """``paper10cr.json`` by value against ``paper10.json`` outside
    ``faults``, the ports, ``guarantees`` and the descriptive keys; the
    traffic mix against ``steady-f3.json``; the entries of
    ``BENCHMARK.json``."""
    configs = os.path.join(harness.HERE, "configs")
    config = harness.load_json(os.path.join(configs, "paper10cr.json"))
    paper10 = harness.load_json(os.path.join(configs, "paper10.json"))
    differing = {key for key in set(config) | set(paper10)
                 if config.get(key) != paper10.get(key)}
    assert differing == {
        "name", "source", "stands_for", "faults", "guarantees", "reduced",
        "assumed", "compared_heights_min", "in_step_commits", "parameters",
        "service"}
    ours, theirs = config["parameters"], paper10["parameters"]
    assert {k for k in theirs if ours[k] != theirs[k]} == {"ingress"}
    assert set(ours) == set(theirs)
    assert {k for k, v in theirs["ingress"].items()
            if ours["ingress"][k] != v} == {"gateway_port_base"}
    ports = ([ours["ingress"]["gateway_port_base"] + i for i in range(10)]
             + [config["service"]["metrics_port"]])
    assert max(ports) < 16000 and len(set(ports)) == 11
    others = [harness.load_json(os.path.join(configs, name))
              for name in os.listdir(configs) if name != "paper10cr.json"]
    taken = {c["service"]["metrics_port"] for c in others} | {
        c["parameters"]["ingress"]["gateway_port_base"] + i
        for c in others if "parameters" in c for i in range(10)}
    assert not taken & set(ports)
    assert ours["leader_timeout_s"] == 2.0
    assert ours["leader_liveness_horizon_rounds"] == 0
    assert ours["storage"]["snapshot_catchup"] is False
    assert config["faults"] == {
        "kind": "crash_recovery", "validators": [9],
        "kill_at_s_into_warmup": 1.0, "restart_at_s_into_window": 3.0,
        "signal": "SIGKILL"}
    assert set(config["guarantees"]) == {
        "safety", "recovery", "liveness", "acknowledgement", "finality",
        "verification", "durability"}
    traffic = harness.load_json(
        os.path.join(harness.HERE, "traffic", "rejoin.json"))
    steady = harness.load_json(
        os.path.join(harness.HERE, "traffic", "steady-f3.json"))
    assert {k for k in steady if traffic[k] != steady[k]} == {
        "driver", "why", "rate_tx_s"}
    assert traffic["rate_tx_s"] == 9 * 1280
    assert traffic["driver"] == "gateway_open_loop_rejoin"
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    assert spec["configs"][-1]["name"] == "paper10cr"
    assert spec["configs"][-1]["source"] == config["source"]
    assert len(config["source"]) <= 200
    assert spec["workloads"][-1] == {
        "name": "paper10cr-rejoin", "config": "paper10cr",
        "traffic": "rejoin", "chips": 1,
        "why": spec["workloads"][-1]["why"]}
    added = NEW + TWINS
    assert [m["name"] for m in spec["per_layer"]][-len(added):] == list(added)
    for metric in spec["per_layer"][-len(added):]:
        assert metric["workloads"] == ["paper10cr-rejoin"]
        assert metric["moves"] == "finality_p50_s"
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", metric["name"] + ".py"))
    cell = harness.find_cell("paper10cr-rejoin")
    assert {m["name"] for m in cell["end_to_end"]} == {
        "committed_tx_s", "finality_p50_s", "setup_s"}
    toy = harness.find_cell(CELL, TOY)
    assert ({m["name"] for m in toy["per_layer"]}
            == {m["name"] for m in cell["per_layer"]} | set(TOY_ONLY))


# -- the new readers on hand-built runs ---------------------------------------


class _Run:
    def __init__(self, observed, window=(100.0, 120.0), seconds=20.0):
        self.observed = observed
        self.window = window
        self.seconds = seconds
        self.snapshots = {}


def _read(name, run):
    reader = harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "rejoin_reader_" + name.replace(".", "_"))
    return reader.read(run)


def _point(height, blocks):
    return {"height": float(height), "round": float(height) + 3,
            "blocks": float(blocks)}


def _curve(heights):
    """Two validators that never died at 26 commits a second and the third
    (index 2) by ``heights`` (None: not answering), a point a half second."""
    rows = []
    for step, mine in enumerate(heights):
        at = 0.5 * step
        fleet = 1000 + 26 * at
        rows.append((at, [_point(fleet, 9 * fleet), _point(fleet + 2, 0),
                          None if mine is None else
                          _point(mine, 100 + 400 * at)]))
    return rows


def test_in_step_is_the_first_point_from_which_it_stays_in_step():
    from benchmark.drivers import gateway_open_loop_rejoin as driver

    class _Fleet:
        dead, live = [2], [0, 1]

    config = {"in_step_commits": 8}
    fleet = _Fleet()
    # Down, behind, in step once (then out again), in step for good.
    fleet.curve = _curve([None, 700, 900, 1039 - 8 + 1, 1000, 1066 - 8,
                          1079 - 8, 1092])
    assert driver.in_step_at(fleet, config) == 2.5
    fleet.curve = _curve([None, 700, 800, 900])
    assert driver.in_step_at(fleet, config) is None
    fleet.curve = []
    assert driver.in_step_at(fleet, config) is None


def test_the_new_readers_on_a_hand_built_rejoin():
    curve = _curve([None, 700, 900, 1000, 1058, 1071, 1092])
    samples = ([(101.0, 0.2)] * 5 + [(103.5, 0.9), (104.0, 0.5), (105.9, 0.7)]
               + [(106.5, 0.2)] * 5)
    series = [("block_stage_seconds_count", {"stage": "wal_replay"}, 1.0),
              ("block_stage_seconds_sum", {"stage": "wal_replay"}, 0.25),
              ("leader_timeout_total", {}, 0.0)]
    grown = [("leader_timeout_total", {}, 3.0)]
    observed = {
        "rejoin": {"back": 2, "restarted_at": 103.0, "curve": curve,
                   "recover_s": 2.0, "lag_commits": 4.0},
        "due_and_finality": samples,
        "nodes": {"start": [series, series, None],
                  "end": [grown, series, series]},
    }
    run = _Run(observed)
    assert _read("recover_s", run) == 2.0
    assert _read("rejoin_lag_commits", run) == 4.0
    assert _read("wal_replay_ms", run) == 250.0
    # Blocks from its first answer (0.5 s) to in step (2.0 s): 400 a second.
    assert _read("rejoin_blocks_s", run) == pytest.approx(400.0)
    # Due in [103.0, 105.0): 0.9 and 0.5.
    assert _read("finality_p50_s.rejoin", run) == pytest.approx(0.7)
    # The two that answered at both edges: 3 timeouts in 20 s.
    assert _read("leader_timeouts_s.cr", run) == pytest.approx(0.15)
    # Never in step: to the curve's end, and to the window's.
    observed["rejoin"]["recover_s"] = None
    assert _read("recover_s", run) is None
    assert _read("rejoin_blocks_s", run) == pytest.approx(400.0)
    assert _read("finality_p50_s.rejoin", run) == pytest.approx(0.2)


def test_the_new_readers_find_nothing_on_a_program_without_the_rejoin():
    """A parent commit's run (were it to get that far), or an untraced
    one: no ``rejoin`` record, no scrapes."""
    run = _Run({})
    for name in NEW + TOY_ONLY:
        assert _read(name, run) is None, name
    run = _Run({"rejoin": {"back": 2, "restarted_at": None, "curve": [],
                           "recover_s": None, "lag_commits": None}})
    for name in NEW + TOY_ONLY:
        assert _read(name, run) is None, name
