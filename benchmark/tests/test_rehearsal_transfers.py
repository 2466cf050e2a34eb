"""The whole command off the chip for the signed-transfer cell, at a size a
CPU can hold (``toy_transfers/``): both ``--trace`` values, and the cell's
control - a service that accepts a client account's signature unverified
(``control_blob_service_main.py``) - which must come out ``correct: false``
by the probe AND by the client's own count of corrupted transfers that
were acknowledged.

Slow like ``test_rehearsal.py`` (~3 minutes a case), and one at a time: the
cells share their ports."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, lastline

HERE = os.path.dirname(__file__)
TOY = os.path.join(HERE, "toy_transfers", "spec.json")
CONTROL = os.path.join(HERE, "control_blob_service_main.py")
CELL, SECONDS = "toy4-transfers-signed", 10


def rehearse(trace: int, seed: int, *extra: str):
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--spec", TOY, "--workload", CELL, "--seed", str(seed),
         "--seconds", str(SECONDS), "--trace", str(trace), *extra],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    path = os.path.join(harness.ROOT, ".bench_work",
                        f"{CELL}-t{trace}.last_line.json")
    with open(path) as f:
        line = json.load(f)
    return done, line


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_off_the_chip(trace):
    done, line = rehearse(trace, 2_600_000_011 + trace)
    assert done.returncode == harness.EXIT_NO_TPU, done.stderr[-3000:]
    assert "no TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    cell = harness.find_cell(CELL, TOY)
    assert lastline.validate(line, cell, bool(trace)) == []
    assert line["correct"] is True, done.stdout[-4000:]
    assert line["device"]["platform"] == "cpu"
    compared = [l for l in done.stdout.splitlines()
                if l.startswith("compared: ")]
    for name in ("corrupted transfers acknowledged",
                 "corrupted transfers notified as committed",
                 "sound transfers refused as bad_signature",
                 "genesis allocation equal to the reference's",
                 "heights where the executed root differs from the "
                 "reference's fold of the WAL",
                 "kernels the window ran and the probe did not",
                 "compilations inside the window"):
        assert any(name in l and l.endswith("ok") for l in compared), name
    if trace:
        metrics = line["metrics"]
        assert metrics["tx_sig_share"]["value"] > 50.0
        assert metrics["sigs_per_dispatch.transfers"]["value"] > 1.0
        assert metrics["admit_verify_ms"]["value"] > 0.0
        assert "setup_s" not in metrics
    else:
        assert 0 <= line["failed"] < line["attempted"]
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}


def test_a_service_that_trusts_strangers_is_not_correct():
    done, line = rehearse(0, 2_600_000_031, "--service-main", CONTROL)
    assert done.returncode != 0
    assert (line.get("line") or line)["correct"] is False
    failed = [l for l in done.stdout.splitlines()
              if l.startswith("compared: ") and l.endswith("FAILED")]
    assert any("differing from the oracle" in l for l in failed), failed
    assert any("corrupted transfers acknowledged" in l
               for l in failed), failed


def test_the_blob_readers_divide_over_the_traced_window_alone():
    """Device seconds of the trace over lanes of the same interval: what
    the measured window launched, and what other kernels launched, move
    neither reader; a program that launches no such kernel reads nothing."""
    from benchmark import transfer_readers

    def snap(blob256, blob128, indexed):
        return {"dispatches": [
            {"kernel": "blob", "bucket": 256, "backend": "pallas",
             "count": blob256},
            {"kernel": "blob", "bucket": 128, "backend": "pallas",
             "count": blob128},
            {"kernel": "indexed", "bucket": 256, "backend": "pallas",
             "count": indexed},
        ]}

    class Run:
        snapshots = {"window_start": snap(0, 0, 0),
                     "window_end": snap(7000, 0, 900),
                     "trace_start": snap(7100, 0, 910),
                     "trace_end": snap(7190, 10, 990)}
        trace_reduced = {"kernels": {
            "verify_blob": {"launches": 100, "seconds": 0.075},
            "verify_indexed": {"launches": 80, "seconds": 0.05}}}
        trace_kind = "TPU v5 lite"

    lanes = (90 * 256 + 10 * 128) / 100
    assert transfer_readers.blob_lanes(Run) == pytest.approx(lanes)
    us = harness.load_module(os.path.join(
        harness.HERE, "layer_metrics", "blob_kernel_us_per_sig.py"), "us")
    share = harness.load_module(os.path.join(
        harness.HERE, "layer_metrics", "blob_kernel_hbm_share.py"), "share")
    assert us.read(Run) == pytest.approx(1e6 * 0.00075 / lanes)
    assert 0.0 < share.read(Run) == pytest.approx(
        100.0 * (lanes * 34 * 4 / 819e9) / 0.00075)

    class Parent(Run):
        snapshots = {"trace_start": snap(0, 0, 5), "trace_end": snap(0, 0, 9)}
        trace_reduced = {"kernels": {
            "verify_indexed": {"launches": 4, "seconds": 0.003}}}

    assert us.read(Parent) is None and share.read(Parent) is None
