"""Leader slots decided as skipped over all decided in the window, summed
over nodes (mysticeti_commit_decision_total{outcome="skip"}): a leader whose
block reached too few voters in time."""
from benchmark import wan_readers


def read(run):
    return wan_readers.decision_share_percent(run, outcome="skip")
