"""Mean wall milliseconds of service_pool_wait: handed to the pool -> a
pool thread has it (run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.mean_wall_ms(run, "service_pool_wait")
