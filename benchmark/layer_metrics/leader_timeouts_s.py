"""Leader timeouts fired a second over the window, summed over the
validators that answered at both edges (leader_timeout_total): 0 where a
dead validator's slot is not waited for; each one is leader_timeout_s (2 s)
in which that validator proposed nothing."""
from benchmark import readers


def read(run):
    grown = readers.node_deltas(run, "leader_timeout_total")
    return sum(grown) / run.seconds if grown else None
