"""Signatures the validators verified over the window, per kernel launch
the service made in it (collector / pipeline: how full the 256-lane
launches are in a fleet)."""
from benchmark import readers


def read(run):
    launches = readers.dispatches(run)
    verified = readers.node_deltas(run, "verified_signatures_total")
    if not launches or not verified:
        return None
    return sum(verified) / launches
