"""What the proposal gate cost a round: mean of
block_stage_seconds{stage="leader_wait"} over the window (the threshold
clock reached round r + 1 -> the validator's own proposal for it, one sample
a proposal), median over the validators that answered at both edges.  The
leader arriving, its connection closing or the 2 s leader timeout ends the
wait.  Nothing where no scrape has the series (a parent commit, an untraced
run); 0.0 where the series is there and the window holds no sample."""
import statistics

from benchmark import readers


def read(run):
    nodes = run.observed.get("nodes") or {"end": []}
    if not any(name == "block_stage_seconds_count"
               and labels.get("stage") == "leader_wait"
               for series in nodes["end"] for name, labels, _ in series or []):
        return None
    sums = readers.node_deltas(run, "block_stage_seconds_sum",
                               stage="leader_wait")
    counts = readers.node_deltas(run, "block_stage_seconds_count",
                                 stage="leader_wait")
    means = [s / c for s, c in zip(sums, counts) if c > 0]
    return 1e3 * statistics.median(means) if means else 0.0
