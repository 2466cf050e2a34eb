"""Mean wait of a job for a thread of the loop's default executor
(``executor_wait``: handed over -> first instruction; the gateway's
signature check and the collector's two hops share that pool) over the
window, median over validators, in ms (collector and pipeline)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_mean_ms(run, "executor_wait")
