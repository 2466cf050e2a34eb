"""Median round trip of a request, from the client's submit to its reply,
over the window (service client: the benchmark's span in the client
processes)."""
import statistics


def read(run):
    rtts = (run.observed.get("client") or {}).get("rtt_s")
    return 1e3 * statistics.median(rtts) if rtts else None
