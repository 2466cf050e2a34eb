"""CPU seconds per second of window of the threads that carry the verifier
service's stages, its event loop and its sixteen pool threads: every
working stage, the collections, and what lies between stages (each
thread's CPU clock, read once a second by the service).  Of the one
process that holds the chip; the runtime's own threads are not in it.
Read from the ring in the service's report (run.service_report)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.cpu_cores(run)
