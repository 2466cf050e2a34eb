"""Longest synchronous command on a validator's core owner in the window:
largest ``max_wall_s`` of ``core_command`` over the window's seconds, max
over validators, in ms (core_task.py; core)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "core_command")
