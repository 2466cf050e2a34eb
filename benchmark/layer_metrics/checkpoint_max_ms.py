"""Longest checkpoint, whole and with its fsync, on a validator's core
owner in the window (``checkpoint``), max over validators, in ms; 0.0 where
the window holds none (storage)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "checkpoint")
