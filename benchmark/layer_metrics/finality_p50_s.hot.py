"""Median finality, due to the commit notification with an executed root,
of the window's operations signed by hotspot accounts: those that wait
behind their account's earlier operations (ingress; the client's clock)."""
import statistics


def read(run):
    hot = (run.observed.get("client") or {}).get("latencies_hot")
    return statistics.median(hot) if hot else None
