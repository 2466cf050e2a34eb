"""95th percentile of (commit notification received - submission due) over
the window's transactions (commit; the tail a client feels).  Per layer and
not end to end in this cell: on a shared host the tail comes in episodes, and
the driver's sets spread by more than any bound the contract allows."""
from benchmark.harness import quantile


def read(run):
    latencies = (run.observed.get("client") or {}).get("latencies")
    return quantile(latencies, 0.95) if latencies else None
