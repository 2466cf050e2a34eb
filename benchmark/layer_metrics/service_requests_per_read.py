"""Verify requests the service decoded for every socket read that held
one (verifier service; the ring's ``requests`` over its ``reads``, which
the service's loop counts, over the whole seconds inside the window).
1.0 while every client keeps one request a connection; towards a client's
depth where its requests in flight share one pipelined connection and
arrive together: how often that engages.  A ring without the two stamps
(a program from before them) leaves the metric out."""
import math


def read(run):
    ring = (run.service_report or {}).get("stages")
    if not ring or not run.window:
        return None
    requests = reads = 0
    for second in range(math.ceil(run.window[0]), math.floor(run.window[1])):
        entry = ring["seconds"].get(str(second))
        if entry is None:
            continue  # a second in which the service booked nothing
        if "requests" not in entry or "reads" not in entry:
            return None
        requests += entry["requests"]
        reads += entry["reads"]
    return requests / reads if reads else None
