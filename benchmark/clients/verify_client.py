"""One validator of a host that is catching up: the program's own
``RemoteSignatureVerifier`` (never JAX), ``in_flight`` requests at the
service at a time, every reply compared bit for bit with the oracle's
verdicts.

The requests are this client's own and no two are alike: it signs them at
start, from ``--seed``, while the service boots (``<out>.pool`` appears when
they are made), with the sizes ``traffic.request_sizes`` gives and one
signature in ``traffic.corrupted_one_in`` corrupted in one bit; the oracle
judges every one of them here, before anything is sent.  Sending starts
when ``<out>.go`` appears and stops when ``<out>.stop`` does; what is in
flight then is still awaited.  ``<out>`` gets one record per request:
[submitted, completed (time.monotonic), signatures, wrong bits, error].  A
client that runs out of requests starts over and says so in
``<out>.replayed``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import random
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.reference import ed25519_oracle as oracle  # noqa: E402


def committee_keys(seed: int, config: dict) -> list:
    """The committee's key pairs: from the run's seed alone, so the parent
    and every client make the same."""
    return oracle.seeded_keys(random.Random(seed), int(config["committee"]))


def make_requests(rng: random.Random, keys: list, config: dict,
                  traffic: dict, signatures: int) -> list:
    """Requests of ``traffic.request_sizes`` ([[signatures, weight], ...])
    until ``signatures`` signatures are made; signer uniform over the
    authorities other than ``local_authority``."""
    signers = [i for i in range(len(keys))
               if i != int(config["local_authority"])]
    sizes = [int(size) for size, _ in traffic["request_sizes"]]
    weights = [float(weight) for _, weight in traffic["request_sizes"]]
    one_in = int(traffic["corrupted_one_in"])
    requests, made = [], 0
    while made < signatures:
        n = rng.choices(sizes, weights)[0]
        lanes = [signers[rng.randrange(len(signers))] for _ in range(n)]
        corrupt = [i for i in range(n) if rng.randrange(one_in) == 0]
        requests.append(oracle.signed_request(rng, keys, lanes, corrupt))
        made += n
    return requests


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--socket", required=True)
    parser.add_argument("--spec", required=True,
                        help="JSON: the run's seed, config and traffic")
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    from mysticeti_tpu.verifier_service import RemoteSignatureVerifier

    with open(args.spec) as f:
        spec = json.load(f)
    config, traffic = spec["config"], spec["traffic"]
    keys = committee_keys(spec["seed"], config)
    requests = make_requests(random.Random(args.seed), keys, config, traffic,
                             int(traffic["signatures_per_validator"]))
    open(args.out + ".pool", "w").close()
    go_path, stop_path = args.out + ".go", args.out + ".stop"
    while not os.path.exists(go_path):
        if os.path.exists(stop_path):
            return 0
        time.sleep(0.02)

    client = RemoteSignatureVerifier(
        socket_path=args.socket,
        committee_keys=[public for _, public in keys],
        timeout_s=float(traffic["timeout_s"]),
    )
    depth = int(traffic["in_flight"])
    records = []
    inflight: collections.deque = collections.deque()
    sent = 0
    while True:
        stopping = os.path.exists(stop_path)
        while not stopping and len(inflight) < depth:
            request = requests[sent % len(requests)]
            sent += 1
            submitted = time.monotonic()
            handle = client.verify_signatures_async(
                request["public_keys"], request["digests"],
                request["signatures"])
            inflight.append((submitted, request, handle))
        if not inflight:
            break
        submitted, request, handle = inflight.popleft()
        expected = request["expected"]
        try:
            got = handle.result()
            error = None
            wrong = sum(bool(g) != e for g, e in zip(got, expected))
            wrong += abs(len(got) - len(expected))
        except Exception as exc:  # noqa: BLE001 - recorded, the run fails
            error, wrong = f"{type(exc).__name__}: {exc}", 0
        records.append([submitted, time.monotonic(), len(expected), wrong,
                        error])
    if sent > len(requests):
        with open(args.out + ".replayed", "w") as f:
            f.write(str(sent - len(requests)))
    with open(args.out + ".tmp", "w") as f:
        json.dump(records, f)
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
