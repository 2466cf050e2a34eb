"""Signatures the validators verified over the window, per kernel launch
the service made in it, as sigs_per_dispatch.fleet reads it: how often the
service's coalescer still engages once a block's nine receivers see it up
to 115 ms apart."""
from benchmark import readers


def read(run):
    launches = readers.dispatches(run)
    verified = readers.node_deltas(run, "verified_signatures_total")
    if not launches or not verified:
        return None
    return sum(verified) / launches
