"""The crash-fault cell off the chip: the whole command on ``toy4-f1`` (four
validators, the last one killed; ``toy_f3/spec.json``), both ``--trace``
values; its two controls - ``control_commit_rule_node_main.py``, a node
whose committer skips every commit of a round divisible by ten, identically
on every validator, which must come out ``correct: false`` by the
comparison with ``reference/commit_rule.py`` alone, and
``control_norepair_node_main.py``, a node that never takes a peer out of the
proposal gate, which must come out ``correct: false`` by the leader
timeouts in the window and the heights its WALs hold; and a run in which a
live validator dies mid-way, after which nothing of the fleet may live or
hold a port.

Slow (a CPU service traces the ladder for over a minute): ~3 minutes a
case.  One at a time: the cells share their ports."""
import json
import os
import socket
import subprocess
import sys

import pytest

from benchmark import harness, lastline

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_f3", "spec.json")
CONTROL_RULE = os.path.join(HERE, "control_commit_rule_node_main.py")
CONTROL_NOREPAIR = os.path.join(HERE, "control_norepair_node_main.py")
CELL = "toy4-f1"
SECONDS = 10
FAULT_CHECKS = ("differs from reference/commit_rule.py",
                "every live WAL holds, compared with the reference",
                "blocks of a dead validator above the round held",
                "slots a dead validator leads",
                "leader_timeout_total growth",
                "connected_nodes is not 2 at the window's edges",
                "metrics port still answers",
                "exit codes of the killed validators")


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
    done, line, compared = rehearse(trace, 3_600_000_011 + trace)
    assert done.returncode == harness.EXIT_NO_TPU, done.stderr[-3000:]
    assert "no TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    cell = harness.find_cell(CELL, TOY)
    assert lastline.validate(line, cell, bool(trace)) == []
    assert line["correct"] is True, done.stdout[-3000:]
    for name in FAULT_CHECKS:
        assert any(name in l and l.endswith("ok") for l in compared), name
    if trace:
        metrics = line["metrics"]
        assert metrics["leader_timeouts_s"]["value"] == 0.0
        # One validator in four is dead: about a slot in four is skipped.
        assert 10.0 < metrics["leaders_skipped_share"]["value"] < 45.0
        assert 0.0 <= metrics["leader_wait_ms"]["value"] < 1000.0
        assert metrics["finality_rounds"]["value"] > 3.0
        assert metrics["rounds_s"]["value"] > 0.0
        assert metrics["sigs_per_dispatch.f3"]["value"] > 0.0
        assert "setup_s" not in metrics
    else:
        assert 0 <= line["failed"] < line["attempted"]
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}


def test_a_committer_that_skips_commits_is_not_correct():
    control = harness.load_module(CONTROL_RULE,
                                  "control_commit_rule_node_main")
    spec = control.control_spec(TOY, CELL, os.path.join(
        harness.ROOT, ".bench_work", "control-toy4-f1"))
    done, line, compared = rehearse(0, 3_600_000_031, spec)
    assert done.returncode != 0
    assert (line.get("line") or line)["correct"] is False
    failed = [l for l in compared if l.endswith("FAILED")]
    # By the reference comparison alone: the WALs agree with each other,
    # nothing is lost, no timeout fires, the dead stay dead.
    assert len(failed) == 1 and "reference/commit_rule.py" in failed[0], failed


def test_a_node_that_keeps_the_dead_in_the_gate_is_not_correct():
    control = harness.load_module(CONTROL_NOREPAIR,
                                  "control_norepair_node_main")
    spec = control.control_spec(TOY, CELL, os.path.join(
        harness.ROOT, ".bench_work", "control-toy4-f1"))
    done, line, compared = rehearse(0, 3_600_000_032, spec)
    assert done.returncode != 0
    assert (line.get("line") or line)["correct"] is False
    failed = [l for l in compared if l.endswith("FAILED")]
    # The dead validator leads a slot in four and each costs the toy's 10 s
    # timeout: timeouts fire in the window.  Nothing is unsafe: the WALs
    # equal the reference and each other.
    assert any("leader_timeout_total growth over the window" in l
               for l in failed), failed
    assert not any("reference/commit_rule.py" in l or "different leaders"
                   in l for l in failed), failed


DYING = '''"""A validator that dies of itself mid-run (authority 0, {after} s in)."""
import os, sys, threading
sys.path.insert(0, {root!r})
if sys.argv[sys.argv.index("--authority") + 1] == "0":
    threading.Timer({after}, os._exit, (7,)).start()
from mysticeti_tpu.cli import main
sys.exit(main())
'''


def test_nothing_of_the_fleet_lives_or_is_bound_after_a_run_that_raises():
    """A live validator exits under load: the client's connection to its
    gateway closes, ``drive`` raises out of the open loop, and the command
    ends with code 1 - with the killed validator reaped, the others and
    the service stopped, and every port of the configuration free."""
    control = harness.load_module(CONTROL_RULE,
                                  "control_commit_rule_node_main")
    work = os.path.join(harness.ROOT, ".bench_work")
    os.makedirs(work, exist_ok=True)
    wrapper = os.path.join(work, "dying_node_main.py")
    with open(wrapper, "w") as f:
        # Boot and the wait for the fleet's start-up to pass take 20-30 s
        # here (two 10 s leader timeouts and one calm), the load 52 s.
        f.write(DYING.format(root=harness.ROOT, after=45.0))
    spec = control.control_spec(TOY, CELL, os.path.join(
        work, "control-toy4-f1"), wrapper)
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--spec", spec, "--workload", CELL, "--seed", "3600000041",
         "--seconds", str(SECONDS), "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == harness.EXIT_FAILED, done.stderr[-3000:]
    assert "benchmark: gateway 0 closed" in done.stderr, done.stderr[-3000:]
    assert "killed validators [3]" in done.stderr  # it was mid-way
    config = harness.find_cell(CELL, spec)["config"]
    n = config["validators"]
    ports = ([1500 + i for i in range(n)] + [2500 + i for i in range(n)]
             + [config["parameters"]["ingress"]["gateway_port_base"] + i
                for i in range(n)] + [config["service"]["metrics_port"]])
    bound = []
    for port in ports:
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                bound.append(port)
    assert bound == []
    alive = []
    for pid in (p for p in os.listdir("/proc") if p.isdigit()):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().decode(errors="replace")
        except OSError:
            continue
        if f"{CELL}-t0" in cmdline:  # the run's work directory
            alive.append((pid, cmdline.replace("\0", " ")[:200]))
    assert alive == []


def test_the_configuration_is_paper10_plus_its_faults():
    """``paper10f3.json`` by value against ``paper10.json`` outside
    ``faults``, ``guarantees`` and the descriptive keys; the traffic mix
    against ``steady.json``; the entries of ``BENCHMARK.json``."""
    configs = os.path.join(harness.HERE, "configs")
    config = harness.load_json(os.path.join(configs, "paper10f3.json"))
    paper10 = harness.load_json(os.path.join(configs, "paper10.json"))
    for key in ("validators", "transaction_bytes", "verifier", "hosts",
                "regions", "injected_delay_ms", "node_env", "probe"):
        assert config[key] == paper10[key], key
    # The ports alone differ: under the chip host's ephemeral range.
    moved = json.loads(json.dumps(config["parameters"]))
    assert moved["ingress"].pop("gateway_port_base") + 10 <= 16000
    stated = json.loads(json.dumps(paper10["parameters"]))
    stated["ingress"].pop("gateway_port_base")
    assert moved == stated
    assert set(config["service"]) == set(paper10["service"]) == {
        "metrics_port"}
    assert config["service"]["metrics_port"] < 16000
    described = {"name", "source", "stands_for", "reduced", "assumed",
                 "faults", "guarantees", "compared_heights_min"}
    assert set(config) - described == set(paper10) - described
    assert config["parameters"]["leader_timeout_s"] == 2.0
    assert config["parameters"]["leader_liveness_horizon_rounds"] == 0
    assert config["faults"] == {
        "kind": "permanent", "validators": [7, 8, 9],
        "kill_at_s_into_warmup": 1.0, "signal": "SIGKILL"}
    for key in ("acknowledgement", "finality", "verification", "durability"):
        assert config["guarantees"][key] == paper10["guarantees"][key], key
    assert set(config["guarantees"]) == set(paper10["guarantees"]) | {
        "liveness", "faults"}
    assert sorted(config["reduced"]) == sorted(
        list(paper10["reduced"]) + ["duration"])
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in spec["configs"]}["paper10f3"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    cell = {w["name"]: w for w in spec["workloads"]}["paper10f3-steady"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "paper10f3", "steady-f3", 1)
    steady = harness.load_json(os.path.join(harness.HERE, "traffic",
                                            "steady.json"))
    steady_f3 = harness.load_json(os.path.join(harness.HERE, "traffic",
                                               "steady-f3.json"))
    same = ("tick_s", "warmup_s", "drain_s", "grace_s", "trace")
    assert {k: steady[k] for k in same} == {k: steady_f3[k] for k in same}
    live = config["validators"] - len(config["faults"]["validators"])
    assert steady_f3["rate_tx_s"] <= (
        steady["rate_tx_s"] / config["validators"] * live)
    assert steady_f3["driver"] == "gateway_open_loop_faults"
    # The kill lies inside the warm-up, the window in the steady state.
    assert (config["faults"]["kill_at_s_into_warmup"] + 2 * 2.0
            < steady_f3["warmup_s"])
    # The new metrics list the new cell and no other; the two that
    # test_stage_readers.py pins with == do not list it.
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == ["paper10f3-steady"], name
    for name in ("service_cpu_cores.fleet", "block_verify_ms"):
        assert "paper10f3-steady" not in per_layer[name]["workloads"], name


def _fake_run(latencies, nodes=None, snapshots=None):
    class Run:
        seconds = 10.0
        observed = {"client": {"latencies": latencies}}

    Run.snapshots = snapshots or {}
    if nodes is not None:
        Run.observed["nodes"] = nodes
    return Run


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


NEW = ("leader_wait_ms", "leader_timeouts_s", "finality_rounds",
       "sigs_per_dispatch.f3")


def test_the_new_readers_on_hand_built_scrapes_with_a_missing_node():
    """Each reader on numbers whose answer is plain, one validator of
    three without a scrape (a killed one); on a program that lacks the
    series (a parent commit) and in a run that scraped nothing (untraced)
    every one reads nothing."""
    def node(round_, timeouts, wait_sum, wait_count, verified):
        return [
            ("threshold_clock_round", {}, float(round_)),
            ("leader_timeout_total", {}, float(timeouts)),
            ("block_stage_seconds_sum", {"stage": "leader_wait"}, wait_sum),
            ("block_stage_seconds_count", {"stage": "leader_wait"},
             float(wait_count)),
            ("block_stage_seconds_sum", {"stage": "dag_add"}, 9.0),
            ("block_stage_seconds_count", {"stage": "dag_add"}, 9.0),
            ("verified_signatures_total",
             {"backend": "tpu-remote", "outcome": "accepted"},
             float(verified)),
        ]

    nodes = {"start": [node(100, 3, 1.0, 100, 1000),
                       node(100, 2, 2.0, 100, 1000), None],
             # 200 and 220 rounds in 10 s; 200 waits of 2 ms and 220 of
             # 4 ms; 1 + 2 timeouts; 3,000 signatures.
             "end": [node(300, 4, 1.4, 300, 2600),
                     node(320, 4, 2.88, 320, 2400), None]}
    snapshots = {
        "window_start": {"dispatches": [{"count": 10, "bucket": 256}]},
        "window_end": {"dispatches": [{"count": 110, "bucket": 256}]}}
    run = _fake_run([0.2, 0.25, 0.3], nodes, snapshots)
    assert _reader("leader_wait_ms").read(run) == pytest.approx(3.0)
    assert _reader("leader_timeouts_s").read(run) == pytest.approx(0.3)
    assert _reader("finality_rounds").read(run) == pytest.approx(0.25 * 21.0)
    assert _reader("sigs_per_dispatch.f3").read(run) == pytest.approx(30.0)
    # A parent commit's validators: scraped, without the clock's stage.
    plain = _fake_run([0.2], {
        "start": [[("threshold_clock_round", {}, 100.0)], None],
        "end": [[("threshold_clock_round", {}, 360.0)], None]})
    assert _reader("leader_wait_ms").read(plain) is None
    # The series is there and the window holds no sample of it: 0.0.
    still = _fake_run([0.2], {"start": [node(100, 0, 1.0, 100, 0)],
                              "end": [node(300, 0, 1.0, 100, 0)]})
    assert _reader("leader_wait_ms").read(still) == 0.0
    assert _reader("sigs_per_dispatch.f3").read(plain) is None
    assert _reader("finality_rounds").read(plain) == pytest.approx(0.2 * 26.0)
    # An untraced run scrapes nothing at the window's edges.
    unscraped = _fake_run([0.2])
    for name in NEW:
        assert _reader(name).read(unscraped) is None, name
