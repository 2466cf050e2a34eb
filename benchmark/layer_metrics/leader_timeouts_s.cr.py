"""Leader timeouts fired a second over the window, summed over the
validators that answered at both of its edges - the nine that never died;
as ``leader_timeouts_s.py``, whose list of cells is another cell's.  0 is
the configuration's guarantee (a validator that is back and behind is not
waited for); the control that waits for it moves this."""
from benchmark import readers


def read(run):
    grown = readers.node_deltas(run, "leader_timeout_total")
    return sum(grown) / run.seconds if grown else None
