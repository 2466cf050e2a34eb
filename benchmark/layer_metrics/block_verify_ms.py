"""Mean of block_stage_seconds{stage="verify"} over the window, median over
nodes: block received -> verdict, i.e. the collector's window plus the
request's round trip to the service (run.observed["nodes"])."""
from benchmark import stage_readers


def read(run):
    return stage_readers.block_stage_ms(run, "verify")
