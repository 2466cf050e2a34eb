"""Median idle gap between one program's end and the next one's start on
the device (dispatch; device trace, the XLA Modules line)."""


def read(run):
    reduced = run.trace_reduced or {}
    return (reduced.get("launch_gaps_us") or {}).get("p50")
