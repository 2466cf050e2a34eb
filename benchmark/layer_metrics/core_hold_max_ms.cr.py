"""Longest synchronous command on a validator's core owner in the window,
max over validators, in ms (core_task.py; core): here the returned
validator's ``add_blocks`` of a parked cascade; as ``core_hold_max_ms.py``,
whose list of cells is pinned."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "core_command")
