"""Device time of the verify kernels per REAL signature, both over the
traced window: the kernels' device seconds in the trace over the signatures
whose replies the clients received between the profiler's arming and the
traced window's end.  Padding lanes are paid for and not counted."""
from benchmark import readers


def read(run):
    timed = readers.verify_kernel_time(run)
    interval = run.traced_interval
    answered = (run.observed.get("client") or {}).get("answered")
    if not timed or not interval or not answered:
        return None
    signatures = sum(n for completed, n in answered
                     if interval[0] <= completed < interval[1])
    return 1e6 * timed[0] / signatures if signatures else None
