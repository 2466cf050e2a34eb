"""Signatures the live validators verified over the window, per kernel
launch the service made in it, as sigs_per_dispatch.fleet reads it: seven
clients' requests where the service's table holds ten keys."""
from benchmark import readers


def read(run):
    launches = readers.dispatches(run)
    verified = readers.node_deltas(run, "verified_signatures_total")
    if not launches or not verified:
        return None
    return sum(verified) / launches
