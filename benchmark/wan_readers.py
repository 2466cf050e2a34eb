"""Arithmetic the readers of ``paper10wan-steady`` and its driver share:
what the validators' delay lines counted over the window, link by link, and
the commit rule's decisions by label.  Each returns nothing where the
program has no such series (a parent commit, an empty table)."""
from __future__ import annotations

import statistics
from typing import List, Optional

from benchmark import harness, readers


def node_link_frames(start: list, end: list) -> List[tuple]:
    """[(frames the line to a peer counted over the window, that link's
    configured one-way delay in seconds)] of one node's two scrapes."""
    out = []
    for name, labels, delay_s in end:
        if name == "mesh_link_delay_seconds":
            peer = labels["peer"]
            out.append((
                harness.series_sum(end, "mesh_delayed_frames_total",
                                   peer=peer)
                - harness.series_sum(start, "mesh_delayed_frames_total",
                                     peer=peer),
                delay_s))
    return out


def scraped(run) -> List[tuple]:
    nodes = run.observed.get("nodes") or {"start": [], "end": []}
    return [(start, end) for start, end in zip(nodes["start"], nodes["end"])
            if start is not None and end is not None]


def link_frames(run) -> List[tuple]:
    """``node_link_frames`` of every directed link of the fleet."""
    return [link for start, end in scraped(run)
            for link in node_link_frames(start, end)]


def mesh_hold_excess_ms(run) -> Optional[float]:
    """Per frame, what ``block_stage_seconds{stage="mesh_hold"}`` grew by
    over the window beyond frames x configured delay; median over nodes."""
    excess = []
    for start, end in scraped(run):
        links = node_link_frames(start, end)
        frames = sum(n for n, _ in links)
        if frames:
            held = (harness.series_sum(end, "block_stage_seconds_sum",
                                       stage="mesh_hold")
                    - harness.series_sum(start, "block_stage_seconds_sum",
                                         stage="mesh_hold"))
            configured = sum(n * delay_s for n, delay_s in links)
            excess.append(1e3 * (held - configured) / frames)
    return statistics.median(excess) if excess else None


def decision_share_percent(run, **labels) -> Optional[float]:
    """Leader slots decided with ``labels`` over all decided in the window,
    summed over nodes (``mysticeti_commit_decision_total{rule,outcome}``)."""
    name = "mysticeti_commit_decision_total"
    decided = sum(readers.node_deltas(run, name))
    if not decided:
        return None
    return 100.0 * sum(readers.node_deltas(run, name, **labels)) / decided
