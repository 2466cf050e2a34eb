"""Mean of block_stage_seconds{stage="dag_add"} over the window, median
over nodes: core-task queue + insertion (run.observed["nodes"])."""
from benchmark import stage_readers


def read(run):
    return stage_readers.block_stage_ms(run, "dag_add")
