"""Rounds a second over the window, median over nodes (the growth of the
threshold_clock_round gauge): under link delays a round lasts as long as
the quorum's farthest member is far."""
import statistics

from benchmark import readers


def read(run):
    deltas = readers.node_deltas(run, "threshold_clock_round")
    return statistics.median(deltas) / run.seconds if deltas else None
