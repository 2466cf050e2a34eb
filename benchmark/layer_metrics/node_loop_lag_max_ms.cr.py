"""Largest ``loop_lag`` sample any validator booked in the window, the
returned one among them, in ms (hostattr.py; core); as
``node_loop_lag_max_ms.py``, whose list of cells is pinned."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "loop_lag")
