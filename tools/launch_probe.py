#!/usr/bin/env python3
"""What one launch of the verifier service costs its host, by stage.

One thread, nothing else at the GIL: real launches of the warmed backend
through ``VerifierServer._verify_batch``, timed on ``time.perf_counter`` at
the ``spans.request_stage`` boundaries (``service_unpack`` / ``service_pack``
/ ``service_launch`` / ``service_fetch`` / ``service_reply_build``).  In the
running service the stage clock reads the same stages on a CPU clock of
10 ms ticks with three dispatcher threads and the loop passing one GIL
around (PERF.md section 5); this is the same code without the contention.

    chiprun -- python3 tools/launch_probe.py            # on the chip's host
    JAX_PLATFORMS=cpu python3 tools/launch_probe.py --stub   # host code only

``--stub`` replaces the jitted calls by a constant, the keyed kernel's too,
so the sandbox sizes the host's packing alone (a launch of the XLA form
takes 0.8 s on a CPU); with ``MYSTICETI_VERIFY_BACKEND=pallas`` that
includes the decision whether a launch takes the keyed kernel.  Each shape
prints how many of its launches kept their key indices (``direct``) and
went into the keyed grouping (``keyed_tried``), on a tree that counts them,
and how many said that they enter their fetch (``spans.request_fetch``: where
the service ends a part-full launch's hold and counts the next launch as
``left_overlapped``, PR 47) with the milliseconds of the launch before that —
its host path, which is what the next launch still waits for.
``--late-fetch`` takes the early request for the result's copy to the host
(``ops.ed25519._fetch_early``) out again, to read it alone.  Runs on any
tree that has ``_verify_batch`` (``PYTHONPATH=<tree>``).
"""
from __future__ import annotations

import argparse
import os
import struct
import sys
import time

# Behind PYTHONPATH, so that the same file can probe another tree.
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (label, signatures a request, VERIFY frames?, signers to draw from)
SHAPES = (
    ("64 signatures, eight VERIFY requests", (4, 15, 4, 4, 15, 4, 14, 4), True, 9),
    ("169 signatures, two RAW requests by strangers", (128, 41), False, 40),
    ("3 signatures, one VERIFY request by one signer", (3,), True, 1),
    # service10-catchup's launch since PR 37: what a draining queue gathers.
    ("159 signatures, nineteen VERIFY requests, ten signers",
     (4, 15, 4, 1, 4, 38, 4, 15, 4, 4, 15, 4, 1, 4, 15, 4, 4, 15, 4), True, 10),
)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--stub", action="store_true",
                        help="no device call: size the host's packing alone")
    parser.add_argument("--launches", type=int, default=1500)
    parser.add_argument("--late-fetch", action="store_true",
                        help="do not ask for the result's copy at the launch")
    args = parser.parse_args()

    import numpy as np

    import jax

    from mysticeti_tpu import crypto, spans
    from mysticeti_tpu import verifier_service as VS
    from mysticeti_tpu.block_validator import TpuSignatureVerifier
    from mysticeti_tpu.ops import ed25519 as E

    device = jax.devices()[0]
    print("device", device.platform, device.device_kind,
          "backend", E._backend(), "stub" if args.stub else "", flush=True)
    committee = [crypto.Signer.from_seed(bytes([i + 1]) * 32) for i in range(10)]
    strangers = [crypto.Signer.from_seed(bytes([100 + i]) * 32) for i in range(40)]
    keys = [s.public_key.bytes for s in committee]

    def body(n, indexed, pool, salt):
        records = []
        for i in range(n):
            at = (i + salt) % pool
            signer = committee[at] if indexed else strangers[at]
            digest = crypto.blake2b_256(b"probe-%d-%d" % (salt, i))
            head = struct.pack("<H", at) if indexed else signer.public_key.bytes
            records.append(head + digest + signer.sign(digest))
        return memoryview(b"".join(records))

    if args.late_fetch or args.stub:
        E._fetch_early = lambda handle: None
    if args.stub:
        accepted = np.ones(E.BUCKETS[0], bool)
        E._dispatch_indexed = lambda *args, **kwargs: accepted
        E._dispatch_blob = lambda *args, **kwargs: accepted
        if E._backend() == "pallas":
            from mysticeti_tpu.ops import ed25519_pallas as PK

            PK.verify_keyed_blob = lambda *args, **kwargs: accepted
            # The chip's tile, so that the sandbox sizes the chip's decision
            # (the interpreter's own tile is 8 lanes).
            PK.default_tile = lambda: 256
    server = VS.VerifierServer(
        "/tmp/launch_probe.sock", committee_keys=keys,
        backend=TpuSignatureVerifier(mesh=None, committee_keys=keys))
    if args.stub:
        server._warmed.set()
    else:
        started = time.monotonic()
        server.prewarm()
        print("warm %.1f s" % (time.monotonic() - started), flush=True)

    spent: dict = {}
    now_in = [None, 0.0]

    def stage(name):
        now = time.perf_counter()
        if now_in[0] is not None:
            spent[now_in[0]] = spent.get(now_in[0], 0.0) + now - now_in[1]
        now_in[0], now_in[1] = name, now

    spans.request_stage = stage
    on_fetch = getattr(spans, "on_fetch", None)  # a tree before PR 47: None
    said = [0, 0.0, 0.0]  # launches that said so, seconds before, the start

    def enters_fetch():
        said[0] += 1
        said[1] += time.perf_counter() - said[2]

    for label, sizes, indexed, pool in SHAPES:
        batch = [
            VS._Pending(VS.T_VERIFY if indexed else VS.T_RAW, i + 1, n,
                        body(n, indexed, pool, i), "c0", None, None)
            for i, n in enumerate(sizes)
        ]
        for _ in range(20):
            server._verify_batch(batch)
        spent.clear()
        roads = getattr(server._backend, "road_counts", lambda: None)
        roads_before = roads()
        said[:2] = 0, 0.0
        started = time.perf_counter()
        for _ in range(args.launches):
            stage("service_unpack")
            if on_fetch is not None:
                said[2] = now_in[1]
                on_fetch(enters_fetch)
            server._verify_batch(batch)
            stage(None)
        whole = (time.perf_counter() - started) / args.launches
        print("%s: %.3f ms a launch;" % (label, 1e3 * whole), " ".join(
            "%s %.3f" % (name, 1e3 * seconds / args.launches)
            for name, seconds in spent.items()), flush=True)
        if roads_before is not None:
            print("    direct %d keyed_tried %d of %d launches" % (
                *(now - was for now, was in zip(roads(), roads_before)),
                args.launches), flush=True)
        if said[0]:
            print("    entered its fetch %d of %d launches, %.3f ms in" % (
                said[0], args.launches, 1e3 * said[1] / said[0]), flush=True)
    print("dispatches", E.dispatch_counts())


if __name__ == "__main__":
    main()
