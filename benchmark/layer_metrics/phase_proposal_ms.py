"""Mean mempool accept -> drained into a proposal over the window
(ingress.py drain, core.py proposal)."""
from benchmark import readers


def read(run):
    return readers.phase_ms(run, "proposal")
