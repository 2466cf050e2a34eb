"""Seconds from the returned validator's restart to the first half-second
scrape from which its committed height stays within the configuration's
``in_step_commits`` of the other validators' median, to the end of the
drain (``gateway_open_loop_rejoin.in_step_at``; the benchmark's clock).
Left out of the line where that never happens in the run:
``rejoin_lag_commits`` then says how far behind it still was."""


def read(run):
    return (run.observed.get("rejoin") or {}).get("recover_s")
