"""Profiler trace -> the numbers the benchmark reports from it.

Reads one ``.xplane.pb`` (or, for the tests and the off-chip rehearsal, a
text-proto XSpace) with JAX's own reader and reduces it to:

* ``window_s`` - the profiler session's own length (the ``Task
  Environment`` plane's start and stop); every event is clipped to it;
* ``busy_s`` - the UNION of the device-op intervals on a device plane,
  averaged over the device planes.  A device plane carries several lines
  that cover the same time (modules, ops, steps) and an ops line nests
  (a ``while`` spans its body), so durations are never summed: intervals
  are merged first, and ``0 < busy_s <= window_s`` holds by construction;
* device time by operation and by kernel label (``trace_names.json``);
* the gaps between consecutive program launches;
* the longest idle gaps, each named by the host event that overlapped it
  most (``unattributed`` where none did).

Run as a process of its own (``harness.Run.reduce_trace``), held to the
CPU, so that the benchmark's parent never imports JAX.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering exactly what ``intervals`` do."""
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] that the disjoint ``busy`` leave."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def kernel_label(names: dict, program: str) -> Optional[str]:
    for kernel in names["kernels"]:
        if re.search(kernel["match"], program):
            return kernel["label"]
    return None


def _matches(patterns: List[str], name: str) -> bool:
    return any(re.search(p, name) for p in patterns)


def short_name(name: str) -> str:
    """``%while.27 = (s32[], ...) while(...)`` -> ``while.27``: the trace
    names an op by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _origin(line, session: dict) -> float:
    """Event times are absolute on some runtimes and relative to the
    session's start on others; both are brought to the session's start."""
    for event in line.events:
        if float(event.start_ns) > 1e17:
            return float(session["profile_start_time"])
        break
    return 0.0


def _spans(line, session: dict) -> List[tuple]:
    """[(start_ns, end_ns, name)] of a line, from the session's start.  An
    ops line holds millions of events: nothing else is read of them."""
    origin = _origin(line, session)
    return [(float(e.start_ns) - origin,
             float(e.start_ns) - origin + float(e.duration_ns), e.name)
            for e in line.events]


def reduce_profile(profile, names: dict,
                   window_wall_ns: Optional[Tuple[int, int]] = None) -> dict:
    planes = list(profile.planes)
    session = {}
    for plane in planes:
        if plane.name == "Task Environment":
            session = dict(plane.stats)
    if "profile_start_time" not in session:
        raise ValueError("the trace has no Task Environment plane: no window")
    session_ns = float(session["profile_stop_time"]
                       - session["profile_start_time"])
    # Event times count from the session's start.  The traced window is
    # the part of the session the caller names (by the wall clock the
    # session's own start is on): the session also spans the arming and
    # the collecting, during which no device event is recorded.
    lo, hi = 0.0, session_ns
    if window_wall_ns is not None:
        lo = max(lo, float(window_wall_ns[0] - session["profile_start_time"]))
        hi = min(hi, float(window_wall_ns[1] - session["profile_start_time"]))
    window_ns = hi - lo
    if window_ns <= 0:
        raise ValueError(f"the traced window is {window_ns} ns long "
                         f"(session {session_ns} ns)")
    device_planes = [p for p in planes
                     if re.search(names["device_plane"], p.name)]
    out: dict = {
        "planes": {p.name: [ln.name for ln in p.lines] for p in planes},
        "window_s": window_ns / 1e9,
        "device_planes": [p.name for p in device_planes],
    }
    if not device_planes:
        raise ValueError(
            f"no device plane matches {names['device_plane']!r} among "
            f"{[p.name for p in planes]}: the trace was taken in a process "
            "that does not hold the chip, or on another platform")

    busy_each, op_seconds, op_counts = [], {}, {}
    out["events_read"] = {}
    kernels: Dict[str, dict] = {}
    launch_gaps_ns: List[float] = []
    busy_first: List[Interval] = []
    for plane in device_planes:
        ops: List[tuple] = []
        launches: List[tuple] = []
        for line in plane.lines:
            if _matches(names["op_lines"], line.name):
                ops += _spans(line, session)
            elif _matches(names["launch_lines"], line.name):
                launches += _spans(line, session)
        out["events_read"][plane.name] = {"ops": len(ops),
                                          "launches": len(launches)}
        busy = union(clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_each.append(total(busy))
        if not busy_first:
            busy_first = busy
        for start, end, name in ops:
            inside = min(end, hi) - max(start, lo)
            if inside <= 0:
                continue
            op_seconds[name] = op_seconds.get(name, 0.0) + inside / 1e9
            op_counts[name] = op_counts.get(name, 0) + 1
        # A launch counts where it lies whole inside the window.
        launches = sorted(l for l in launches if l[0] >= lo and l[1] <= hi)
        for start, end, name in launches:
            label = kernel_label(names, name) or "other"
            row = kernels.setdefault(label, {"seconds": 0.0, "launches": 0})
            row["seconds"] += (end - start) / 1e9
            row["launches"] += 1
        launch_gaps_ns += [
            max(0.0, nxt[0] - prev[1])
            for prev, nxt in zip(launches, launches[1:])
        ]
    if not any(busy_each):
        raise ValueError(
            "the device planes hold no operation inside the traced window "
            f"(planes and lines: {out['planes']}): traced before the first dispatch, "
            "or the op lines are named otherwise than trace_names.json says")
    out["busy_s"] = sum(busy_each) / len(busy_each) / 1e9
    out["busy_s_each"] = [b / 1e9 for b in busy_each]
    # Nested ops each count their whole span here: a ranking, not a sum.
    out["device_ops"] = sorted(
        ([short_name(n), s, op_counts[n]] for n, s in op_seconds.items()),
        key=lambda r: -r[1],
    )[:40]
    out["kernels"] = kernels
    out["launch_gaps_us"] = {
        "count": len(launch_gaps_ns),
        "p50": (statistics.median(launch_gaps_ns) / 1e3
                if launch_gaps_ns else None),
    }
    out["idle_gaps"] = _attribute_gaps(
        planes, names, gaps(busy_first, lo, hi), session)
    return out


def _attribute_gaps(planes, names: dict, idle: List[Interval],
                    session: dict, most: int = 200) -> List[list]:
    """Idle seconds of the first device, by the host event that overlapped
    each gap most; only the ``most`` longest gaps are attributed, the rest
    go under ``short gaps``."""
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    long_gaps, rest = sorted(idle[:most]), idle[most:]
    host: List[tuple] = []
    for plane in planes:
        if not re.search(names["host_plane"], plane.name):
            continue
        for line in plane.lines:
            host += [span for span in _spans(line, session)
                     if span[1] > span[0]]
    host.sort()
    starts = [span[0] for span in host]
    longest = max((e - s for s, e, _ in host), default=0.0)
    by_name: Dict[str, float] = {}
    for g0, g1 in long_gaps:
        overlap: Dict[str, float] = {}
        # No event that starts before g0 - longest can reach the gap.
        for s, e, n in host[bisect.bisect_left(starts, g0 - longest):]:
            if s >= g1:
                break
            o = min(e, g1) - max(s, g0)
            if o > 0:
                overlap[n] = overlap.get(n, 0.0) + o
        name = "unattributed"
        if overlap:
            best = max(overlap, key=overlap.get)
            if overlap[best] >= 0.1 * (g1 - g0):
                name = best
        by_name[name] = by_name.get(name, 0.0) + (g1 - g0) / 1e9
    if rest:
        by_name["short gaps"] = total(rest) / 1e9
    return sorted(([n, s] for n, s in by_name.items()), key=lambda r: -r[1])


def load_names(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(HERE, "trace_names.json")) as f:
        return json.load(f)


def read_profile(trace_dir: Optional[str], text_proto: Optional[str]):
    from jax.profiler import ProfileData

    if text_proto:
        with open(text_proto) as f:
            return ProfileData.from_text_proto(f.read())
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def dump(profile) -> None:
    for plane in profile.planes:
        print(f"PLANE {plane.name!r} stats={list(plane.stats)[:8]}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            by_name: Dict[str, list] = {}
            for e in events:
                by_name.setdefault(e.name, []).append(e)
            print(f"  LINE {line.name!r}: {len(events)} events, from "
                  f"{min(e.start_ns for e in events):.0f} to "
                  f"{max(e.start_ns + e.duration_ns for e in events):.0f} ns")
            ranked = sorted(by_name.items(),
                            key=lambda kv: -sum(e.duration_ns for e in kv[1]))
            for name, group in ranked[:12]:
                total_ns = sum(e.duration_ns for e in group)
                print(f"    {len(group):7d} x {name[:90]!r} total "
                      f"{total_ns / 1e6:.3f} ms; stats of one: "
                      f"{[(k, str(v)[:80]) for k, v in group[0].stats][:8]}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    source = parser.add_mutually_exclusive_group(required=True)
    source.add_argument("--trace-dir")
    source.add_argument("--text-proto")
    parser.add_argument("--window-ns", type=int, nargs=2,
                        metavar=("START", "END"),
                        help="the traced window, on the wall clock")
    parser.add_argument("--names")
    parser.add_argument("--out")
    parser.add_argument("--dump", action="store_true",
                        help="print planes, lines and their commonest events "
                        "with stats, for reading a new trace by hand")
    args = parser.parse_args()
    if args.dump:
        dump(read_profile(args.trace_dir, args.text_proto))
        return 0
    try:
        reduced = reduce_profile(
            read_profile(args.trace_dir, args.text_proto),
            load_names(args.names),
            tuple(args.window_ns) if args.window_ns else None,
        )
    except ValueError as exc:
        print(f"xplane: {exc}", file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
