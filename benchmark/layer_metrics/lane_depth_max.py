"""Deepest an account's fairness lane stood in an ingress tick, the
largest over the validators and the window's two scrapes: how many
operations of one account waited for a proposal together (ingress)."""
from benchmark import smallbank_readers


def read(run):
    return smallbank_readers.gauge_max(run, "mysticeti_ingress_lane_depth_max")
