"""Rounds from a transaction's due time to its commit notice: the client's
median finality x rounds a second (the growth of threshold_clock_round,
median over the validators that answered at both edges).  The deployment's
own roofline: the rule decides a live leader in 3 rounds and a transaction
waits on average for the next live leader, whatever a round costs."""
import statistics

from benchmark import readers


def read(run):
    latencies = (run.observed.get("client") or {}).get("latencies")
    grown = readers.node_deltas(run, "threshold_clock_round")
    if not latencies or not grown or statistics.median(grown) <= 0:
        return None
    return statistics.median(latencies) * statistics.median(grown) / run.seconds
