"""Longest request to a validator's metrics endpoint in the window and
the two seconds its edges cut - a traced run scrapes every validator AT
the edges (``scrape``: request read -> body written, rendered on the
validator's own event loop), max over validators, in ms (launch)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "scrape", edges=True)
