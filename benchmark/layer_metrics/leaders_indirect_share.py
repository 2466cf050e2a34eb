"""Leader slots decided by the indirect rule (a committed anchor one wave
ahead) over all decided in the window, summed over nodes
(mysticeti_commit_decision_total{rule,outcome}): the commit path no cell on
one host's localhost reaches."""
from benchmark import wan_readers


def read(run):
    return wan_readers.decision_share_percent(run, rule="indirect")
