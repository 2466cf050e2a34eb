"""Arithmetic of the readers that read a validator's own stage clock by the
second (``mysticeti_tpu/spans.StageClock`` over ``spans.NODE_STAGES``).

A validator that exits on SIGTERM leaves ``flight-recorder.json`` in its
storage directory (``Validator.stop``; ``<run.workdir>/fleet/validator-<i>/``
here).  Its ``"stages"`` block is the clock's ring as the verifier
service's report carries its own (``stage_readers.py``): per whole second of
``time.monotonic``, per stage ``[count, wall_s, cpu_s, max_wall_s]``, and
from that second's stamp what the validator counted in it
(``spans.NODE_STAMPS``: ``rounds``, ``leaders``, ``proposals``,
``blocks_received``, ``tx_admitted``, ``shed``, ``shed_lane_cap``,
``leader_timeouts``, ``verify_requests``) and the CPU seconds its process,
its clocked threads and its loop used.  The readers take the whole seconds
that lie inside ``run.window``, which is on the same clock, of every
validator that left a document: a SIGKILLed one leaves none.

A program without the ring (the parent of the PR that added it) leaves no
document, or one without ``"stages"``: every function then returns None and
the metric is left out of the line.

The first read of a run also logs the window by the second (``harness.log``,
stderr): the median and the worst validator's ``rounds``, ``shed`` and mean
``phase_commit``, and the host stage (HOST_STAGES) with the largest
``max_wall_s`` — and, of a
traced run, the seconds that overlap ``run.traced_interval`` with the
requests the validators sent to the service in each, beside the device's
idle share: a traced window in which the validators sent nothing says so.
"""
from __future__ import annotations

import glob
import json
import math
import os
import statistics
from typing import Dict, List, Optional

from benchmark import harness

# A loop that lagged this long in a second, in the service and in most
# validators at once, was not given the processor: the machine, not the
# program (``host_stall_seconds``).
STALL_S = 0.1
STALLED_VALIDATORS = 8
# The stages in which a validator's host time can hide: the log names the
# longest sample of these a second (a phase or a wait for a peer is long by
# nature and says nothing of the host).
HOST_STAGES = ("core_command", "loop_lag", "gc", "executor_wait",
               "wal_write", "wal_sync", "checkpoint", "exec_fold", "scrape")


def documents(run) -> Optional[Dict[int, dict]]:
    """{validator index: its ring} of every validator that left a
    document with ``"stages"``; None where none did."""
    found: Dict[int, dict] = {}
    pattern = os.path.join(run.workdir, "fleet", "validator-*",
                           "flight-recorder.json")
    for path in glob.glob(pattern):
        try:
            with open(path) as f:
                ring = json.load(f).get("stages")
        except (OSError, ValueError):
            continue
        if ring and ring.get("seconds"):
            index = os.path.basename(os.path.dirname(path)).rsplit("-", 1)[1]
            found[int(index)] = ring
    return found or None


def window(run) -> Optional[Dict[int, List[dict]]]:
    """{validator index: one entry a whole second inside the window, in
    order}; ``{}`` for a second in which that validator booked and stamped
    nothing.  Read once a run."""
    cached = getattr(run, "_node_window", None)
    if cached is not None:
        return cached or None
    rings = documents(run) if getattr(run, "workdir", None) else None
    out: Dict[int, List[dict]] = {}
    if rings and run.window:
        first, last = math.ceil(run.window[0]), math.floor(run.window[1])
        out = {
            index: [ring["seconds"].get(str(second), {})
                    for second in range(first, last)]
            for index, ring in sorted(rings.items())
        }
        if last <= first:
            out = {}
    run._node_window, run._node_rings = out, rings
    if out:
        _log_window(run, rings, first, last)
    return out or None


def _cell(entry: dict, stage: str) -> list:
    return entry.get(stage) or [0, 0.0, 0.0, 0.0]


def stage_max_ms(run, stage: str, edges: bool = False) -> Optional[float]:
    """Largest ``max_wall_s`` of ``stage`` in the window, over validators,
    in ms; 0.0 where the window holds no sample of it.  ``edges``: the two
    seconds the window's edges cut count too (what the harness does AT an
    edge - a scrape - ends in them)."""
    rows = window(run)
    if rows is None:
        return None
    longest = max(_cell(entry, stage)[3]
                  for seconds in rows.values() for entry in seconds)
    if edges:
        cut = {str(math.floor(edge)) for edge in run.window}
        longest = max([longest] + [
            _cell(ring["seconds"].get(second, {}), stage)[3]
            for ring in run._node_rings.values() for second in cut])
    return 1e3 * longest


def stage_mean_ms(run, stage: str) -> Optional[float]:
    """Mean wall of one sample of ``stage`` over the window, median over
    the validators that booked one; 0.0 where none did."""
    rows = window(run)
    if rows is None:
        return None
    means = []
    for seconds in rows.values():
        count = sum(_cell(entry, stage)[0] for entry in seconds)
        if count:
            means.append(sum(_cell(entry, stage)[1] for entry in seconds)
                         / count)
    return 1e3 * statistics.median(means) if means else 0.0


def stage_wall_share(run, stage: str) -> Optional[float]:
    """Wall seconds of ``stage`` over the window's seconds, in percent,
    median over validators."""
    rows = window(run)
    if rows is None:
        return None
    return 100.0 * statistics.median(
        sum(_cell(entry, stage)[1] for entry in seconds) / len(seconds)
        for seconds in rows.values())


def slow_seconds_of(seconds: List[dict]) -> int:
    """Seconds in which ``rounds`` grew by under half the median second's
    growth of this validator (a second it did not stamp grew by 0)."""
    grown = [entry.get("rounds", 0) for entry in seconds]
    half = statistics.median(grown) / 2.0
    return sum(1 for rounds in grown if rounds < half)


def slow_seconds(run) -> Optional[float]:
    """Median over validators of ``slow_seconds_of``: 0 in a calm run, a
    few in a run that held an episode of slow rounds."""
    rows = window(run)
    if rows is None:
        return None
    return float(statistics.median(
        slow_seconds_of(seconds) for seconds in rows.values()))


def host_stall_seconds(run) -> Optional[float]:
    """Seconds of the window in which the service's ``service_loop_lag``
    and at least STALLED_VALIDATORS validators' ``loop_lag`` (all of them
    where fewer left a document) each hold a sample over STALL_S."""
    rows = window(run)
    service = ((run.service_report or {}).get("stages") or {}).get("seconds")
    if rows is None or not service:
        return None
    first = math.ceil(run.window[0])
    need = min(STALLED_VALIDATORS, len(rows))
    stalled = 0
    for at in range(len(next(iter(rows.values())))):
        lagged = _cell(service.get(str(first + at), {}),
                       "service_loop_lag")[3] > STALL_S
        nodes = sum(1 for seconds in rows.values()
                    if _cell(seconds[at], "loop_lag")[3] > STALL_S)
        stalled += lagged and nodes >= need
    return float(stalled)


# -- the window by the second, for whoever reads the log ----------------------


def _worst_stage(entries: List[dict]) -> str:
    worst, name = 0.0, "-"
    for entry in entries:
        for stage in HOST_STAGES:
            cell = entry.get(stage)
            if cell and cell[3] > worst:
                worst, name = cell[3], stage
    return f"{name} {1e3 * worst:.1f}ms"


def _log_window(run, rings: Dict[int, dict], first: int, last: int) -> None:
    log = harness.log
    log(f"validators by the second ({len(rings)} documents; window "
        f"{first}-{last - 1} on time.monotonic): second, rounds median/min, "
        "shed median/max, phase_commit mean ms median/max, longest sample "
        "of a host stage")
    for second in range(first, last):
        entries = [ring["seconds"].get(str(second), {})
                   for ring in rings.values()]
        rounds = [e.get("rounds", 0) for e in entries]
        shed = [e.get("shed", 0) for e in entries]
        commit = [1e3 * c[1] / c[0] if c[0] else 0.0
                  for c in (_cell(e, "phase_commit") for e in entries)]
        log(f"  {second}: rounds {statistics.median(rounds):g}/{min(rounds)}"
            f" shed {statistics.median(shed):g}/{max(shed)}"
            f" phase_commit {statistics.median(commit):.1f}/{max(commit):.1f}"
            f" longest {_worst_stage(entries)}")
    traced = getattr(run, "traced_interval", None)
    if not traced:
        return
    reduced = getattr(run, "trace_reduced", None) or {}
    idle = (1.0 - reduced["busy_s"] / reduced["window_s"]
            if reduced.get("window_s") else None)
    sent = {
        second: sum(ring["seconds"].get(str(second), {})
                    .get("verify_requests", 0) for ring in rings.values())
        for second in range(math.floor(traced[0]), math.floor(traced[1]) + 1)
    }
    log(f"traced interval {traced[0]:.2f}-{traced[1]:.2f}: requests the "
        f"validators sent to the service by the second {sent}; device idle "
        f"share {idle if idle is None else round(idle, 4)}")
