"""Seconds the verifier service spent collecting garbage (service_gc, every
thread stopped) over the window's seconds, in percent
(run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.wall_share_percent(run, "service_gc")
