"""Largest ``loop_lag`` sample any validator booked in the window: the
overshoot of its loop probe's 0.25 s sleep, in ms (hostattr.py; core)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "loop_lag")
