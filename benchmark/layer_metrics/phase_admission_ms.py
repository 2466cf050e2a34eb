"""Mean submit -> mempool accept over the window (ingress.py)."""
from benchmark import readers


def read(run):
    return readers.phase_ms(run, "admission")
