"""The whole command, off the chip, for both cells and both ``--trace``
values, at a size a CPU can hold (``toy/``): control flow, the two-process
hand-over of ``device``, the profiler's start and stop inside the service,
the line's shape - and that no CPU number can reach the driver.

Slow (the service traces the Ed25519 ladder for over a minute each time, and
a CPU fleet commits a leader every few seconds): ~3 minutes a case.  One at
a time: the cells share their ports.

The last four cases are the contract's: the reference's guarantee broken
underneath the timed path, the platform look aside, and ``correct`` comes
out false - with ``control_service_main.py`` (half of every batch accepted
unverified, above both kernels) and with ``control_keyed_service_main.py``
(only what the keyed-tile kernel serves on the chip, requests by one
signer, accepted unverified)."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, lastline

TOY = os.path.join(os.path.dirname(__file__), "toy", "spec.json")
CONTROLS = {
    name: os.path.join(os.path.dirname(__file__), name + ".py")
    for name in ("control_service_main", "control_keyed_service_main")
}
SECONDS = {"toy4-steady": 10, "toy8-catchup": 5}


def rehearse(workload: str, trace: int, seed: int, *extra: str):
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--spec", TOY, "--workload", workload, "--seed", str(seed),
         "--seconds", str(SECONDS[workload]), "--trace", str(trace), *extra],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    path = os.path.join(harness.ROOT, ".bench_work",
                        f"{workload}-t{trace}.last_line.json")
    with open(path) as f:
        line = json.load(f)
    return done, line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", sorted(SECONDS))
def test_the_whole_command_off_the_chip(workload, trace):
    done, line = rehearse(workload, trace, 2_200_000_011 + trace)
    # The platform gate has no flag: no result line, "no TPU", code 3.
    assert done.returncode == harness.EXIT_NO_TPU, done.stderr[-3000:]
    assert "no TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    # ... and the line it would have printed is whole.
    cell = harness.find_cell(workload, TOY)
    assert lastline.validate(line, cell, bool(trace)) == []
    assert line["correct"] is True, done.stdout[-3000:]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] > 0
    assert "compared: " in done.stdout
    if trace:
        device = line["device"]
        assert 0 < device["busy_s"] <= device["window_s"]
        assert line["breakdown"]["device_ops"]
        assert "setup_s" not in line["metrics"]
    else:
        # A CPU fleet commits so slowly that some of the window's
        # transactions outlast the drain: failed, not lost.
        assert 0 <= line["failed"] < line["attempted"]
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("workload", sorted(SECONDS))
def test_a_verifier_that_checks_less_is_not_correct(workload, control):
    done, line = rehearse(workload, 0, 2_200_000_031, "--service-main",
                          CONTROLS[control])
    assert done.returncode != 0
    assert (line.get("line") or line)["correct"] is False
    assert "differing from the oracle" in done.stdout
    failed = [l for l in done.stdout.splitlines()
              if l.startswith("compared: ") and l.endswith("FAILED")]
    assert any("differing from the oracle" in l for l in failed), failed
