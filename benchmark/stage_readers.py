"""Arithmetic of the readers that read the program's own stage clock
(``mysticeti_tpu/spans.StageClock``), through two doors:

* ``run.service_report["stages"]`` - the ring the verifier service writes
  into its report at stop (``VerifierServer._write_report``): per whole
  second of ``time.monotonic``, per stage ``[count, wall_s, cpu_s,
  max_wall_s]``, and from the clock's stamp of that second the requests and
  signatures answered in it and the CPU seconds the process
  (``process_cpu_s``) and the threads that carry the stages, the loop and
  the pool (``threads_cpu_s``), used in it.  The service clocks one request
  in ``sample_one_in`` through its eight request stages, so their ``count``,
  wall and CPU are of those requests alone.  The readers sum the whole
  seconds that lie inside ``run.window``, which is on the same clock;
* ``run.observed["nodes"]`` - every validator's ``/metrics`` at the window's
  edges, for ``block_stage_seconds{stage}``.

Two kinds of CPU number, and how far each can be trusted where the kernel
moves a thread's CPU clock in 10 ms ticks, as the chip's host does
(PERF.md, PR 24).  ``threads_cpu_s`` is every tick of seventeen threads
over the window, some 2,000 of them in 20 s: good to a few percent.  A
stage's CPU is the ticks that landed in that stage of the clocked requests
alone, a dozen or two in a window: right in the mean, and no finer than
1/sqrt(ticks) - a quarter of the value for ``service_launch``, more for
what is shorter.

A program without the clock (the parent of the PR that added it) has
neither: every function then returns None and the metric is left out.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional

from benchmark import readers

STAMPS = ("requests", "signatures", "process_cpu_s", "threads_cpu_s")


def service_window(run) -> Optional[dict]:
    """``{"seconds": n, "requests": n, "signatures": n, "process_cpu_s": s,
    "threads_cpu_s": s or None, "stages": {stage: [count, wall_s, cpu_s]}}``
    over the whole seconds inside the window."""
    ring = (run.service_report or {}).get("stages")
    if not ring or not run.window:
        return None
    first, last = math.ceil(run.window[0]), math.floor(run.window[1])
    out = {"seconds": 0, "stages": {}, **{name: 0 for name in STAMPS}}
    for second in range(first, last):
        out["seconds"] += 1
        entry = ring["seconds"].get(str(second))
        if entry is None:
            continue  # a second in which the service booked nothing
        for name in STAMPS:
            if name in entry and out[name] is not None:
                out[name] += entry[name]
            elif "requests" in entry:
                out[name] = None  # stamped, and this clock was not read
        for stage, cell in entry.items():
            if isinstance(cell, list):
                into = out["stages"].setdefault(stage, [0, 0.0, 0.0])
                for i in range(3):
                    into[i] += cell[i]
    return out if out["seconds"] and out["stages"] else None


def cpu_cores(run) -> Optional[float]:
    """CPU seconds of the threads that carry the service's stages (the
    loop and the pool: every working stage, the collections, and what lies
    between stages) per second of window."""
    window = service_window(run)
    if not window or window["threads_cpu_s"] is None:
        return None
    return window["threads_cpu_s"] / window["seconds"]


def cpu_ms_per_request(run) -> Optional[float]:
    """The same CPU over the requests answered in the window."""
    window = service_window(run)
    if not window or window["threads_cpu_s"] is None or not window["requests"]:
        return None
    return 1e3 * window["threads_cpu_s"] / window["requests"]


def stage_cpu_ms(run, stages) -> Optional[float]:
    """Mean CPU milliseconds of a clocked request in ``stages``."""
    window = service_window(run)
    cells = [(window or {"stages": {}})["stages"].get(stage)
             for stage in stages]
    if not all(cell and cell[0] for cell in cells):
        return None
    return 1e3 * sum(cell[2] / cell[0] for cell in cells)


def mean_wall_ms(run, stage: str) -> Optional[float]:
    """Mean wall milliseconds of one stage: a request, a probe tick."""
    window = service_window(run)
    cell = (window or {"stages": {}})["stages"].get(stage)
    return 1e3 * cell[1] / cell[0] if cell and cell[0] else None


def wall_share_percent(run, stage: str) -> Optional[float]:
    """Seconds in ``stage`` over the window's seconds, in percent."""
    window = service_window(run)
    if not window:
        return None
    cell = window["stages"].get(stage)
    return 100.0 * (cell[1] if cell else 0.0) / window["seconds"]


def block_stage_ms(run, stage: str) -> Optional[float]:
    """Mean of ``block_stage_seconds{stage}`` over the window (growth of
    sum over growth of count), median over nodes."""
    sums = readers.node_deltas(run, "block_stage_seconds_sum", stage=stage)
    counts = readers.node_deltas(run, "block_stage_seconds_count",
                                 stage=stage)
    means = [s / c for s, c in zip(sums, counts) if c > 0]
    return 1e3 * statistics.median(means) if means else None
