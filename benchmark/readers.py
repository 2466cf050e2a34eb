"""Arithmetic the per-layer readers share: window deltas of the program's
counters and histograms, the device's table of peaks, and the bytes a
verify launch must move.  Each reader in ``layer_metrics/`` is a few lines
over these and returns None where it finds nothing to read."""
from __future__ import annotations

import os
import statistics
from typing import List, Optional

from benchmark import harness


def node_deltas(run, name: str, **labels) -> List[float]:
    """Per node, the growth of one counter across the window."""
    nodes = run.observed.get("nodes")
    if not nodes:
        return []
    return [
        harness.series_sum(end, name, **labels)
        - harness.series_sum(start, name, **labels)
        for start, end in zip(nodes["start"], nodes["end"])
        if start is not None and end is not None
    ]


def phase_ms(run, phase: str) -> Optional[float]:
    """Mean seconds of one phase of ``mysticeti_e2e_finality_seconds`` over
    the window (growth of sum over growth of count), median over nodes."""
    sums = node_deltas(run, "mysticeti_e2e_finality_seconds_sum", phase=phase)
    counts = node_deltas(run, "mysticeti_e2e_finality_seconds_count",
                         phase=phase)
    means = [s / c for s, c in zip(sums, counts) if c > 0]
    return 1e3 * statistics.median(means) if means else None


def dispatches(run, edges=("window_start", "window_end")) -> Optional[int]:
    """Kernel launches the service counted between two snapshots: the
    window's edges, or ``("trace_start", "trace_end")``."""
    snaps = run.snapshots
    if edges[0] not in snaps or edges[1] not in snaps:
        return None
    return (sum(d["count"] for d in snaps[edges[1]]["dispatches"])
            - sum(d["count"] for d in snaps[edges[0]]["dispatches"]))


def lanes_per_dispatch(run) -> Optional[float]:
    snaps = run.snapshots
    n = dispatches(run)
    if not n:
        return None
    lanes = (sum(d["count"] * d["bucket"]
                 for d in snaps["window_end"]["dispatches"])
             - sum(d["count"] * d["bucket"]
                   for d in snaps["window_start"]["dispatches"]))
    return lanes / n


def peaks(run) -> dict:
    table = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    kind = run.trace_kind
    if kind not in table:
        raise harness.BenchError(
            f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def verify_kernel_time(run) -> Optional[tuple]:
    """(device seconds, launches) of the verify kernels in the trace."""
    reduced = run.trace_reduced
    if not reduced:
        return None
    names = harness.load_json(
        os.path.join(harness.HERE, "trace_names.json"))["verify_kernels"]
    rows = [reduced["kernels"][k] for k in names if k in reduced["kernels"]]
    launches = sum(r["launches"] for r in rows)
    if not launches:
        return None
    return sum(r["seconds"] for r in rows), launches


def launch_bytes(lanes: float, committee: int) -> float:
    """Bytes one verify launch must move at the least: the packed batch in
    ((lanes, 26) uint32: R, digest, s, key index, host flag -
    ops/ed25519.pack_blob_indexed), the key table (32 B a key) and one
    uint32 verdict a lane out."""
    return lanes * 26 * 4 + committee * 32 + lanes * 4
