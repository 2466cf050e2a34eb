"""As service_cpu_cores.service, under the fleet's own requests: the cores
the verifier service's loop and pool threads used per second of window
(run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.cpu_cores(run)
