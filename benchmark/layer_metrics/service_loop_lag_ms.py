"""Mean overshoot of the service's event-loop probe in the window, in
milliseconds: what a read or a write waits for the loop and no stage of a
request sees (service_loop_lag in run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.mean_wall_ms(run, "service_loop_lag")
