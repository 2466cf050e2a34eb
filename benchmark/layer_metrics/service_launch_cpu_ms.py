"""CPU milliseconds a clocked request in service_launch: the jitted calls
returning their handles, argument transfer included.  Where the thread CPU
clock moves in 10 ms ticks this is some twenty ticks a window: good to a
quarter of its value (run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.stage_cpu_ms(run, ("service_launch",))
