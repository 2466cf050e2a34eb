"""Share of a validator's event loop its core owner holds: wall seconds of
``core_command`` over the window's seconds, in percent, median over
validators (core_task.py; core)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_wall_share(run, "core_command")
