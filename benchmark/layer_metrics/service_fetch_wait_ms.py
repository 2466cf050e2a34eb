"""Mean wall milliseconds of service_fetch: device run + transfer +
getting the GIL back, in VerifyDispatch.result (run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.mean_wall_ms(run, "service_fetch")
