"""Seconds of the window in which the verifier service's
``service_loop_lag`` and at least eight validators' ``loop_lag`` (all of
them where fewer left a document) each hold a sample over 100 ms: every
loop of the machine late at once is the machine, not the program (launch)."""
from benchmark import node_readers


def read(run):
    return node_readers.host_stall_seconds(run)
