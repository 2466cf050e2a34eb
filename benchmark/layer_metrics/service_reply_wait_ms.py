"""Mean wall milliseconds of service_reply_wait: reply built -> written
(the loop's wake-up, the connection's earlier replies, the write;
run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.mean_wall_ms(run, "service_reply_wait")
