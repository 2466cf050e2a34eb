"""What the returned validator's boot spent recovering from its WAL, in ms:
the one sample of ``block_stage_seconds{stage="wal_replay"}`` it booked
(the log opened, the newest checkpoint loaded, the tail replayed, a torn
end cut), read from its scrape at the window's end.  Nothing where the
series is not there (a program without the stage, a validator that did not
come back)."""
from benchmark import harness


def read(run):
    rejoin = run.observed.get("rejoin")
    nodes = run.observed.get("nodes")
    if not rejoin or not nodes:
        return None
    series = nodes["end"][rejoin["back"]]
    if not series or not harness.series_sum(
            series, "block_stage_seconds_count", stage="wal_replay"):
        return None
    return 1e3 * harness.series_sum(series, "block_stage_seconds_sum",
                                    stage="wal_replay")
