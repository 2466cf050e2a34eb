"""Seconds of the window in which the verifier service's loop and at least
eight validators' loops each hold a lag sample over 100 ms: every loop of
the machine late at once is the machine, not the program (launch) - what
tells a stall of the fleet that the host made from one the gate made; as
``host_stall_seconds.py``, whose list of cells is pinned."""
from benchmark import node_readers


def read(run):
    return node_readers.host_stall_seconds(run)
