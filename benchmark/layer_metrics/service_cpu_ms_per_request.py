"""CPU milliseconds of the verifier service's loop and pool threads per
request answered in the window: service_cpu_cores.service over the
requests a second (run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.cpu_ms_per_request(run)
