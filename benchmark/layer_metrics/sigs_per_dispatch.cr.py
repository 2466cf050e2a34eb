"""Signatures the validators that never died verified over the window, per
kernel launch the service made in it, as ``sigs_per_dispatch.f3`` reads
it: the launches hold the returned validator's catch-up requests too (its
counter started again at zero inside the window, so it is not summed)."""
from benchmark import readers


def read(run):
    launches = readers.dispatches(run)
    verified = readers.node_deltas(run, "verified_signatures_total")
    if not launches or not verified:
        return None
    return sum(verified) / launches
