"""Whole seconds of the window in which a validator's threshold clock
advanced by under half its own median second (``rounds`` of the ring's
stamps in its flight-recorder document), median over the validators that
left one: 0 in a calm run, 2-4 in a run that held an episode of slow rounds
- the number that says this traced line held one (core)."""
from benchmark import node_readers


def read(run):
    return node_readers.slow_seconds(run)
