"""Leaders committed a second over the window, median over nodes
(committed_leaders_total{status="committed"})."""
import statistics

from benchmark import readers


def read(run):
    deltas = readers.node_deltas(run, "committed_leaders_total",
                                 status="committed")
    return statistics.median(deltas) / run.seconds if deltas else None
