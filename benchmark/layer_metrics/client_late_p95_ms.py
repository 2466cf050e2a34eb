"""How late the load generator ran: 95th percentile of (sent - due) over
the window's submissions (load generator; a starved generator must not be
read as a fast system)."""
from benchmark.harness import quantile


def read(run):
    late = (run.observed.get("client") or {}).get("late_s")
    return 1e3 * quantile(late, 0.95) if late else None
