"""CPU milliseconds a clocked request in service_unpack + service_pack:
slicing the wire records, key lookup, pack_blob_indexed, tile grouping.
Where the thread CPU clock moves in 10 ms ticks this is a handful of ticks
a window: right in the mean over many runs, not in one
(run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.stage_cpu_ms(run, ("service_unpack", "service_pack"))
