"""Median finality of the transactions of the validators that never died
that were due between the restart and the instant the returned validator
was in step (``recover_s``; the window's end where it never was): beside
the cell's median it prices what the rejoin costs healthy clients (the
client's clock)."""
import statistics


def read(run):
    rejoin = run.observed.get("rejoin")
    samples = run.observed.get("due_and_finality")
    if not rejoin or not samples or rejoin["restarted_at"] is None:
        return None
    start = rejoin["restarted_at"]
    end = (run.window[1] if rejoin["recover_s"] is None
           else start + rejoin["recover_s"])
    during = [finality for due, finality in samples if start <= due < end]
    return statistics.median(during) if during else None
