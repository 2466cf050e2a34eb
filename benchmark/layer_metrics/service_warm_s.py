"""Seconds from the service's spawn to its HELLO_OK, by the benchmark's
clock (launch; moves setup_s)."""


def read(run):
    return run.service_warm_s
