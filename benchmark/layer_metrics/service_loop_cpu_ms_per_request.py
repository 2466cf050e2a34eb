"""CPU milliseconds of the service's event loop alone — the one thread
that reads every request and writes every reply — for every request
answered (verifier service; 1e3 x the ring's ``loop_cpu_s`` over its
``requests``, over the whole seconds inside the window).  It is the share
of service_cpu_ms_per_request that system calls and the hand-over cost,
without the launches'.  ``loop_cpu_s`` is ``time.thread_time`` of the
stamping thread, which the chip's host moves in 10 ms ticks: a window
holds some hundreds, so the sum is good to a few percent and no single
second means anything.  A ring without the stamp leaves the metric
out."""
import math


def read(run):
    ring = (run.service_report or {}).get("stages")
    if not ring or not run.window:
        return None
    requests, loop_cpu_s = 0, 0.0
    for second in range(math.ceil(run.window[0]), math.floor(run.window[1])):
        entry = ring["seconds"].get(str(second))
        if entry is None:
            continue  # a second in which the service booked nothing
        if "requests" not in entry or "loop_cpu_s" not in entry:
            return None
        requests += entry["requests"]
        loop_cpu_s += entry["loop_cpu_s"]
    return 1e3 * loop_cpu_s / requests if requests else None
