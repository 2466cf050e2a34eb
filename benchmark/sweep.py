#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one fleet boot, rungs of rising
offered rate, each ``--rung-seconds`` long, and the reference orchestrator's
own rule for "out of capacity" (``BASELINE.md``): average latency above 5x
the previous rung's, or committed under 2/3 of offered.

    python3 benchmark/sweep.py --workload paper10-steady --rates 2000 4000 ...

Not part of a run: the rate it finds goes into the cell's traffic file as a
number (four fifths of the knee), its table into ``PERF.md``.  Prints one
JSON object with the rungs as its last line, and only from a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import harness  # noqa: E402
from benchmark.harness import log  # noqa: E402


async def climb(run, fleet, driver, rates, rung_s, drain_s) -> list:
    client = driver.OpenLoopClient(
        fleet.ports("gateway"), run.seed,
        int(run.cell["config"]["transaction_bytes"]),
        float(run.cell["traffic"]["tick_s"]))
    await client.connect()
    client.rate_tx_s = rates[0]
    tick_s = client.tick_s
    begin = time.monotonic() + tick_s
    schedule = asyncio.ensure_future(client.run_schedule(begin))
    await asyncio.sleep(5.0)  # connections and admission settle
    rungs = []
    for rate in rates:
        client.rate_tx_s = rate
        start = begin + len(client.ticks) * tick_s + tick_s
        end = start + rung_s
        await driver.sleep_until(end)
        # Between rungs the load falls back to the first rate, so that what
        # is still queued leaves before the next rung is judged.
        client.rate_tx_s = rates[0]
        ticks = client.ticks_due(start, end)
        await client.wait_notified(ticks, end + drain_s)
        await driver.sleep_until(end + drain_s)
        if schedule.done():
            raise schedule.exception() or RuntimeError("schedule ended")
        out = client.outcome(ticks, end + drain_s)
        latencies = out["latencies"]
        rung = {
            "offered_tx_s": rate,
            "submitted": out["submitted"],
            "committed_tx_s": len(latencies) / rung_s,
            "committed_share": len(latencies) / max(1, out["submitted"]),
            "shed": out["shed"],
            "latency_avg_s": statistics.fmean(latencies) if latencies else None,
            "latency_avg_halves_s": out["latency_avg_halves_s"],
            "latency_p50_s": statistics.median(latencies) if latencies else None,
            "latency_p95_s": (harness.quantile(latencies, 0.95)
                              if latencies else None),
            "client_late_p95_ms": 1e3 * harness.quantile(out["late_s"], 0.95),
            "died": run.unexpected_exits(),
        }
        previous = rungs[-1]["latency_avg_s"] if rungs else None
        rung["out_of_capacity"] = bool(
            rung["committed_share"] < 2 / 3
            or (previous and rung["latency_avg_s"]
                and rung["latency_avg_s"] > 5 * previous)
            or rung["latency_avg_s"] is None)
        log(f"rung {rung}")
        rungs.append(rung)
        if rung["died"]:
            break
    client.stop = True
    await asyncio.gather(schedule, return_exceptions=True)
    await client.close()
    return rungs


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    parser.add_argument("--rung-seconds", type=float, default=15.0)
    parser.add_argument("--drain-seconds", type=float, default=6.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--spec")
    args = parser.parse_args()
    from mysticeti_tpu import native

    native.active_functions()
    cell = harness.find_cell(args.workload, args.spec)
    run = harness.Run(cell, args.seed, args.rung_seconds, False)
    driver = harness.load_module(cell["driver"], "bench_driver")
    fleet = driver.Fleet(run)
    rungs = []
    try:
        keys = fleet.genesis()
        fleet.assert_ports_free()
        run.start_service(keys)
        fleet.boot()
        rungs = asyncio.run(climb(run, fleet, driver, args.rates,
                                  args.rung_seconds, args.drain_seconds))
    finally:
        fleet.stop()
        run.stop_service()
        platform = (run.device_file or {}).get("platform")
        run.close(keep=False)
    if platform != "tpu":
        print(f"sweep: no TPU (the service ran on {platform!r})",
              file=sys.stderr)
        return harness.EXIT_NO_TPU
    print(json.dumps({"workload": args.workload, "rungs": rungs,
                      "device": run.device_file}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
