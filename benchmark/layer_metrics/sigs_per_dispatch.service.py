"""Signatures answered inside the window per kernel launch the service
made in it (collector / pipeline, with no fleet: the clients' own count
over the service's)."""
from benchmark import readers


def read(run):
    launches = readers.dispatches(run)
    answered = (run.observed.get("client") or {}).get("signatures_in_window")
    if not launches or not answered:
        return None
    return answered / launches
