#!/usr/bin/env python3
"""What one request costs the verifier service's event loop, without a chip.

The service as it runs (``VerifierServer`` on a unix socket, its dispatcher
threads, stage clock and gauges) in front of a stub backend, under the
traffic shape of ``service10-catchup``: ten client processes, four requests
in flight each — all four down the client's one shared connection, as
``RemoteSignatureVerifier`` sends requests by committee signers since PR 34
(before it: every one on a pooled connection of its own, which
``PYTHONPATH=<an older tree>`` still shows) — eight signatures a request.  A
launch of the stub is ``--launch-cpu-ms`` of Python that holds the GIL (what
packing and the jitted call cost the interpreter; 0.5 shows how many
requests a launch gets when launches compete with the loop) and then
``--launch-ms`` of sleep (the fetch); between the two it says that it enters
its fetch, as the JAX backend does (``spans.request_fetch``: a part-full
launch's hold ends there, PR 47), unless ``--silent`` — the same tree both
ways, or a tree before PR 47.  What is left is the plumbing between
the socket and the launch, both ways: the loop's own CPU and the process's
CPU a request, the requests a second and a launch, and — where the tree
counts them — the launches by why the coalescer let them leave
(``VerifierServer._take``: the stub is calibrated like any backend, so a
part-full launch holds what arrives behind it; ``overlapped``: it left while
another was out in its fetch) and how many frames a read and replies a write
carried.

    python3 tools/loop_probe.py --launch-cpu-ms 0.5        # this tree
    python3 tools/loop_probe.py --launch-cpu-ms 0.5 --silent   # hold to landing
    PYTHONPATH=<other tree> python3 tools/loop_probe.py    # another one
    chiprun -- python3 tools/loop_probe.py                 # the chip's host

Touches no JAX and no device.  `tools/launch_probe.py` is its twin for the
launch itself.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import tempfile
import time

# Behind PYTHONPATH, so that the same file can probe another tree.
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KEYS = [bytes([i + 1]) * 32 for i in range(10)]


def client(path: str, depth: int, signatures: int, seconds: float) -> None:
    """A closed loop of ``depth`` requests in flight, sent as a validator
    and the benchmark's client send them: the program's own
    ``RemoteSignatureVerifier.verify_signatures_async``, so the requests
    in flight — by committee signers, as a validator's block signatures
    are — share the client's one connection (the service may find several
    frames in a read and write several replies at once), and the oldest is
    awaited before the next is sent."""
    import collections

    from mysticeti_tpu.verifier_service import RemoteSignatureVerifier

    remote = RemoteSignatureVerifier(
        socket_path=path, committee_keys=KEYS, timeout_s=2.0)
    request = ([KEYS[i % len(KEYS)] for i in range(signatures)],
               [bytes(32)] * signatures, [bytes(64)] * signatures)
    inflight: collections.deque = collections.deque()
    answered, deadline = 0, time.monotonic() + seconds
    try:
        while time.monotonic() < deadline:
            while len(inflight) < depth:
                inflight.append(remote.verify_signatures_async(*request))
            inflight.popleft().result()
            answered += 1
    except OSError:
        pass  # the service went silent or away: the probe is over
    print(answered)


class Stub:
    """A backend whose launch is ``cpu_s`` of Python that holds the GIL
    (what packing and the jitted call cost the interpreter) and then a
    sleep of ``launch_s`` with the GIL free (the fetch); ``enters_fetch``
    is called between the two (None: a silent stub)."""

    def __init__(self, launch_s: float, cpu_s: float = 0.0,
                 enters_fetch=None) -> None:
        self.launch_s = launch_s
        self.cpu_s = cpu_s
        self.enters_fetch = enters_fetch

    def warmup(self) -> None:
        pass

    def resolved_backend(self) -> str:
        return "cpu"

    def warmed_batch(self) -> int:
        return 256

    def verify_signatures(self, public_keys, digests, signatures):
        spin_until = time.perf_counter() + self.cpu_s
        while time.perf_counter() < spin_until:
            pass
        if self.enters_fetch is not None:
            self.enters_fetch()
        time.sleep(self.launch_s)
        return [True] * len(signatures)


async def serve(args) -> dict:
    from mysticeti_tpu import spans
    from mysticeti_tpu.metrics import Metrics
    from mysticeti_tpu.verifier_service import VerifierServer

    # A tree before PR 47 has nothing to tell: every stub is silent there.
    enters_fetch = (
        None if args.silent else getattr(spans, "request_fetch", None))
    path = os.path.join(tempfile.mkdtemp(prefix="loop_probe"), "v.sock")
    server = VerifierServer(
        path, committee_keys=KEYS, metrics=Metrics(),
        backend=Stub(args.launch_ms / 1e3, args.launch_cpu_ms / 1e3,
                     enters_fetch))
    await server.start()
    await asyncio.get_running_loop().run_in_executor(None, server.prewarm)
    clients = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--client", path,
             "--depth", str(args.depth), "--signatures", str(args.signatures),
             "--seconds", str(args.seconds + 2.0)],
            stdout=subprocess.PIPE)
        for _ in range(args.connections)
    ]
    await asyncio.sleep(1.0)  # every client is in its loop
    # The service's own counts (``ServiceCounts``; on a tree before PR 39
    # the stage clock kept them).
    stages = getattr(server, "counts", None) or server.stages

    def reading():
        return (time.monotonic(), time.thread_time(), time.process_time(),
                stages.requests, stages.launches,
                getattr(stages, "reads", None), getattr(stages, "writes", None),
                list(getattr(stages, "left", ())))

    t0, loop0, process0, requests0, launches0, reads0, writes0, left0 = reading()
    await asyncio.sleep(args.seconds)
    t1, loop1, process1, requests1, launches1, reads1, writes1, left1 = reading()
    for proc in clients:  # off the loop: it still answers them
        await asyncio.get_running_loop().run_in_executor(None, proc.wait)
    await server.stop()
    requests = requests1 - requests0
    out = {
        "requests_s": round(requests / (t1 - t0), 1),
        "loop_cpu_us_per_request": round(1e6 * (loop1 - loop0) / requests, 2),
        "process_cpu_us_per_request": round(
            1e6 * (process1 - process0) / requests, 2),
        "requests_per_launch": round(requests / (launches1 - launches0), 2),
        "launches_s": round((launches1 - launches0) / (t1 - t0), 1),
    }
    if left0:  # why the coalescer let them leave (``VerifierServer._take``)
        out["launches_left"] = {
            why: after - before
            for why, before, after in zip(stages.LEFT, left0, left1)}
    if reads0 is not None:
        out["requests_per_read"] = round(requests / (reads1 - reads0), 2)
        out["requests_per_write"] = round(requests / (writes1 - writes0), 2)
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--client", help=argparse.SUPPRESS)
    parser.add_argument("--connections", type=int, default=10)
    parser.add_argument("--depth", type=int, default=4)
    parser.add_argument("--signatures", type=int, default=8)
    parser.add_argument("--launch-ms", type=float, default=1.2)
    parser.add_argument("--launch-cpu-ms", type=float, default=0.0)
    parser.add_argument("--silent", action="store_true",
                        help="the stub never says that it enters its fetch")
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args()
    if args.client:
        client(args.client, args.depth, args.signatures, args.seconds)
    else:
        print(json.dumps(asyncio.run(serve(args))))


if __name__ == "__main__":
    main()
