"""Whole seconds of the window in which a validator's threshold clock
advanced by under half its own median second, median over the validators
that left a flight-recorder document (core): a stall of the nine - a slot
waited out, the machine stopped - shows here as the seconds it took; as
``slow_seconds.py``, whose list of cells is pinned."""
from benchmark import node_readers


def read(run):
    return node_readers.slow_seconds(run)
