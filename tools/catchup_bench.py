#!/usr/bin/env python3
"""Crash-recovery catch-up race per verifier backend.

A validator rejoining after downtime must verify its whole missed backlog —
deep, multi-author batches arriving as fast as peers can stream them.  This
is the fleet-level regime where signature verification (not the consensus
engine) binds, i.e. the regime BASELINE configs #4/#5 describe: the
threshold-aggregate verifier skips quorum-endorsed interior blocks
(crypto.rs:77-84's layering licenses the skip) and the TPU path batches the
frontier, while the CPU oracle pays ~125 µs per signature serially.

Reference anchors: crash-recovery faults (orchestrator/src/faults.rs:104-160),
WAL replay recovery (state.rs:23-95), the verifier seam
(block_validator.rs:10-14).

Measured per verifier {cpu, cpu-agg, tpu, tpu-agg}:
  * reboot_to_metrics_s       — process boot + WAL replay until /metrics serves
  * reboot_to_first_verify_s  — until the first peer block passes verification
    (for tpu flavors this includes the persistent-cache kernel load)
  * reboot_to_caught_up_s     — until the rebooted node's commit_round reaches
    the live fleet's (within MARGIN rounds)
  * catchup verification counters — direct vs aggregate-skipped

Usage:
  python tools/catchup_bench.py --verifiers cpu cpu-agg tpu-agg --down 45 \
      --out CATCHUP.json
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MARGIN_ROUNDS = 20


def parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name_labels, _, rest = line.partition(" ")
        try:
            value = float(rest.split()[0])
        except ValueError:
            continue
        out[name_labels] = value
    return out


def metric(samples: dict, name: str, default=0.0) -> float:
    return samples.get(name, default)


def sig_counters(samples: dict) -> dict:
    direct = skipped = rejected = 0.0
    for key, value in samples.items():
        if not key.startswith("verified_signatures_total{"):
            continue
        if 'outcome="skipped"' in key:
            skipped += value
        elif 'outcome="rejected"' in key:
            rejected += value
        elif 'outcome="accepted"' in key:
            direct += value
    total = direct + skipped
    return {
        "direct": int(direct),
        "skipped": int(skipped),
        "rejected": int(rejected),
        "skip_frac": round(skipped / total, 3) if total else 0.0,
    }


async def scrape_parsed(runner, authority):
    text = await runner.scrape(authority)
    return parse_metrics(text) if text is not None else None


async def wait_for(predicate, timeout_s: float, interval_s: float = 0.5):
    """Poll an async predicate; returns (elapsed_s, value) or (None, None)."""
    started = time.monotonic()
    while time.monotonic() - started < timeout_s:
        value = await predicate()
        if value is not None:
            return time.monotonic() - started, value
        await asyncio.sleep(interval_s)
    return None, None


async def run_one(verifier: str, nodes: int, load: int, down_s: float,
                  workdir: str) -> dict:
    from mysticeti_tpu.orchestrator.runner import LocalProcessRunner

    # The shared verifier service made tpu warmup a non-event (the runner
    # blocks until the service is warm before booting nodes), so the load
    # delay no longer needs a tpu asymmetry.  Pinned (not setdefault) so an
    # ambient INITIAL_DELAY cannot skew one flavor's steady window.
    os.environ["INITIAL_DELAY"] = "1"
    runner = LocalProcessRunner(
        os.path.join(workdir, f"fleet-{verifier}"), verifier=verifier
    )
    result = {"verifier": verifier, "nodes": nodes,
              "offered_load_tx_s": load, "down_s": down_s}
    await runner.configure(nodes, load)
    for a in range(nodes):
        await runner.boot_node(a)

    # Steady state: commits flowing on node 0 (tpu flavors pay their one-time
    # warmup here, against the persistent compile cache).
    async def committing():
        m = await scrape_parsed(runner, 0)
        # Steady = consensus cadence AND transaction flow: opening the
        # window on commit_round alone can catch the pre-generator phase
        # (boot contention delays tx flow ~tens of seconds on a 1-core
        # host), recording steady_tps=0 for a fleet that is fine.
        if (
            m
            and metric(m, "commit_round") > 30
            and metric(m, 'latency_s_count{workload="shared"}') > 0
        ):
            return m
        return None

    elapsed, m0 = await wait_for(committing, timeout_s=300, interval_s=1.0)
    if m0 is None:
        await runner.cleanup()
        result["error"] = "fleet never reached steady commits"
        return result
    result["boot_to_steady_s"] = round(elapsed, 1)

    # Fleet commit cadence + tps over a short steady window.
    r_start = metric(m0, "commit_round")
    c_start = metric(m0, 'latency_s_count{workload="shared"}')
    window_t0 = time.monotonic()
    await asyncio.sleep(10)
    # A single transient scrape failure must not abort the whole verifier
    # run — retry briefly instead of calling metric(None, ...).
    _, m0 = await wait_for(
        lambda: scrape_parsed(runner, 0), timeout_s=30, interval_s=0.5
    )
    if m0 is None:
        await runner.cleanup()
        result["error"] = "steady-window scrape failed"
        return result
    # Divide by the MEASURED window: scrape retries can stretch it past the
    # nominal 10 s, and dividing by 10 would inflate the degraded runs.
    window_s = time.monotonic() - window_t0
    r_now = metric(m0, "commit_round")
    result["steady_rounds_per_s"] = round((r_now - r_start) / window_s, 1)
    result["steady_tps"] = round(
        (metric(m0, 'latency_s_count{workload="shared"}') - c_start)
        / window_s, 1
    )

    victim = nodes - 1
    await runner.kill_node(victim)
    round_at_kill = r_now
    await asyncio.sleep(down_s)
    _, m0 = await wait_for(
        lambda: scrape_parsed(runner, 0), timeout_s=30, interval_s=0.5
    )
    if m0 is None:
        await runner.cleanup()
        result["error"] = "reboot-backlog scrape failed"
        return result
    fleet_round_at_reboot = metric(m0, "commit_round")
    result["backlog_rounds"] = int(fleet_round_at_reboot - round_at_kill)

    t0 = time.monotonic()
    await runner.boot_node(victim)

    async def metrics_up():
        return await scrape_parsed(runner, victim)

    elapsed, mv = await wait_for(metrics_up, timeout_s=120, interval_s=0.25)
    result["reboot_to_metrics_s"] = (
        round(elapsed, 2) if elapsed is not None else None
    )

    async def first_verify():
        m = await scrape_parsed(runner, victim)
        if m is None:
            return None
        c = sig_counters(m)
        return c if (c["direct"] + c["skipped"]) > 0 else None

    elapsed, _ = await wait_for(first_verify, timeout_s=240, interval_s=0.25)
    result["reboot_to_first_verify_s"] = (
        round(time.monotonic() - t0, 2) if elapsed is not None else None
    )

    async def caught_up():
        mv = await scrape_parsed(runner, victim)
        m0 = await scrape_parsed(runner, 0)
        if mv is None or m0 is None:
            return None
        lead = metric(m0, "commit_round")
        own = metric(mv, "commit_round")
        if own > 0 and lead - own <= MARGIN_ROUNDS:
            return mv
        return None

    elapsed, mv = await wait_for(caught_up, timeout_s=600, interval_s=0.5)
    result["reboot_to_caught_up_s"] = (
        round(time.monotonic() - t0, 2) if elapsed is not None else None
    )
    if mv is not None:
        result["catchup_verification"] = sig_counters(mv)
    host = await runner.host_sample()
    if host is not None:
        result["host_after_recovery"] = {
            k: host[k] for k in ("cpu_pct", "load_1m") if k in host
        }
    # Fleet health snapshot at the finish line (health plane): the artifact
    # says whether the recovered fleet is actually green — participation,
    # stragglers, SLO alerts — not just that the victim's round caught up.
    from mysticeti_tpu.health import cluster_snapshot_from_texts

    texts = {}
    for authority in range(nodes):
        texts[str(authority)] = await runner.scrape(authority)
    result["health_after_recovery"] = cluster_snapshot_from_texts(texts, nodes)
    await runner.cleanup()
    return result


def main() -> None:
    parser = argparse.ArgumentParser()
    # 7 nodes so one crash leaves the fleet well above quorum (5): commits
    # keep pace during the downtime and a real backlog accumulates (4 nodes
    # minus one is EXACTLY quorum — the fleet crawls and there is nothing to
    # catch up on).  More than ~7 JAX client processes thrash this 1-core
    # host for the tpu flavors.
    parser.add_argument("--nodes", type=int, default=7)
    parser.add_argument("--load", type=int, default=3200)
    parser.add_argument("--down", type=float, default=45.0)
    parser.add_argument("--workdir", default="/tmp/mysticeti-catchup")
    parser.add_argument("--out", default="CATCHUP.json")
    parser.add_argument("--max-block-tx", type=int, default=16)
    parser.add_argument(
        "--verifiers", nargs="+", default=["cpu", "cpu-agg", "tpu-agg"],
        choices=["accept", "cpu", "tpu", "tpu-only", "cpu-agg", "tpu-agg"],
    )
    args = parser.parse_args()

    # Genesis-time + node env: small blocks raise the block (= signature)
    # rate, and the retain window must cover the whole downtime's rounds or
    # peers prune the backlog the victim needs to fetch.
    os.environ["MYSTICETI_MAX_BLOCK_TX"] = str(args.max_block_tx)
    os.environ["MYSTICETI_RETAIN_ROUNDS"] = "100000"
    os.environ["MYSTICETI_LEADER_TIMEOUT"] = "0.25"

    # This process stays off JAX: each tpu fleet's verifier service is the
    # one process that holds the chip, and the runner waits for it to warm.
    runs = []
    for verifier in args.verifiers:
        print(f"catch-up race verifier={verifier}...", flush=True)
        run = asyncio.run(
            run_one(verifier, args.nodes, args.load, args.down, args.workdir)
        )
        runs.append(run)
        print(json.dumps(run), flush=True)

    artifact = {
        "metric": "crash_recovery_catchup_by_verifier",
        "config": {
            "nodes": args.nodes,
            "offered_load_tx_s": args.load,
            "down_s": args.down,
            "max_block_tx": args.max_block_tx,
            "note": (
                "A rebooted validator must verify its missed backlog in deep"
                " multi-author batches — the fleet-level regime where"
                " signature verification binds (BASELINE #4/#5). Caught-up ="
                f" commit_round within {MARGIN_ROUNDS} rounds of the live"
                " fleet."
            ),
        },
        "runs": runs,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
