#!/usr/bin/env python3
"""What does a validator that is catching up send to the verifier service?

Not a cell: the one observation that ``traffic/catchup.json`` is set from
(``PERF.md`` section 4 cites the run).  The ``paper10-steady`` fleet under
its own load; once warm, one validator is SIGKILLed, stays down for
``--down-s`` and is started again on its own WAL.  From then on its
``/metrics`` are read every ``--every-s``: the histogram of signatures per
request it sends to the service (``verify_dispatch_batch_size``), how many
requests it keeps in flight (``verify_pipeline_inflight`` of
``verify_pipeline_depth``) and how far behind the others it is.  A healthy
validator is read beside it, and the service's dispatch counts by kernel.

    python3 benchmark/observe_catchup.py --seed 7 --out chiprun_out/observe.json

Prints the request-size mix and depth of the catch-up stretch: from the
restart to the first read from which on the node commits in step with the
healthy one (the gap between their committed-leader counts stays within
``--in-step`` of its last value; the counts themselves start again at zero
with the process and say nothing).  Runs on whatever platform the service
finds; it prints no result line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.harness import log  # noqa: E402

HISTOGRAMS = ("verify_dispatch_batch_size", "verify_batch_size")
GAUGES = ("verify_pipeline_inflight", "verify_pipeline_depth")


def read_node(series) -> dict:
    """What this observation wants of one node's /metrics."""
    if series is None:
        return {}
    out: dict = {"t": time.monotonic()}
    for hist in HISTOGRAMS:
        buckets = {}
        for name, labels, value in series:
            if name == hist + "_bucket":
                buckets[labels["le"]] = value
        out[hist] = {
            "buckets": buckets,
            "sum": harness.series_sum(series, hist + "_sum"),
            "count": harness.series_sum(series, hist + "_count"),
        }
    for gauge in GAUGES:
        out[gauge] = harness.series_sum(series, gauge)
    out["leaders"] = harness.series_sum(
        series, "committed_leaders_total", status="committed")
    out["verified"] = harness.series_sum(series, "verified_signatures_total")
    return out


def scrape(port: int) -> dict:
    text = harness.http_get(port, timeout=2.0)
    return read_node(harness.parse_metrics(text) if text else None)


def per_bucket(later: dict, earlier: dict) -> dict:
    """Requests in each histogram bucket between two reads (the buckets
    are cumulative in ``le``)."""
    def flat(doc):
        edges = sorted(doc["buckets"], key=float)
        counts, below = {}, 0.0
        for edge in edges:
            counts[edge] = doc["buckets"][edge] - below
            below = doc["buckets"][edge]
        return counts
    a, b = flat(later), flat(earlier) if earlier else {}
    return {edge: a[edge] - b.get(edge, 0.0) for edge in a}


async def observe(run, fleet, gw, args) -> dict:
    victim, witness = fleet.n - 1, 0
    ports = fleet.ports("metrics")
    traffic = run.cell["traffic"]
    client = gw.OpenLoopClient(
        fleet.ports("gateway")[:victim], run.seed,
        int(run.cell["config"]["transaction_bytes"]),
        float(traffic["tick_s"]))
    await client.connect()
    # The same load a gateway as in the cell; the victim's share is not
    # offered while it may be down.
    client.rate_tx_s = float(traffic["rate_tx_s"]) * victim / fleet.n
    loop = asyncio.get_running_loop()
    schedule = asyncio.ensure_future(
        client.run_schedule(time.monotonic() + 0.1))
    doc: dict = {"victim": victim, "rate_tx_s": client.rate_tx_s}
    try:
        await asyncio.sleep(float(traffic["warmup_s"]))
        doc["steady"] = {
            "victim_before": await loop.run_in_executor(
                None, scrape, ports[victim]),
            "witness_before": await loop.run_in_executor(
                None, scrape, ports[witness]),
        }
        await asyncio.sleep(args.steady_s)
        doc["steady"]["victim_after"] = await loop.run_in_executor(
            None, scrape, ports[victim])
        doc["steady"]["witness_after"] = await loop.run_in_executor(
            None, scrape, ports[witness])
        doc["service_at_kill"] = run.snapshot("kill")
        proc = run.children.pop(f"node-{victim}")
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        killed = time.monotonic()
        log(f"node-{victim} SIGKILLed; down for {args.down_s}s")
        await asyncio.sleep(args.down_s)
        doc["service_at_restart"] = run.snapshot("restart")
        fleet.spawn_node(victim, f"node-{victim}-again")
        restarted = time.monotonic()
        log(f"node-{victim} started again on its WAL")
        samples = []
        while time.monotonic() < restarted + args.watch_s:
            await asyncio.sleep(args.every_s)
            v, w = await asyncio.gather(
                loop.run_in_executor(None, scrape, ports[victim]),
                loop.run_in_executor(None, scrape, ports[witness]))
            samples.append({"since_restart": time.monotonic() - restarted,
                            "victim": v, "witness_leaders": w.get("leaders"),
                            "witness_inflight":
                                w.get("verify_pipeline_inflight")})
        doc["service_at_end"] = run.snapshot("end")
        doc.update(down_s=restarted - killed, samples=samples,
                   in_step=args.in_step)
    finally:
        client.stop = True
        await asyncio.gather(schedule, return_exceptions=True)
        await client.close()
    return doc


def summarise(doc: dict) -> dict:
    """The request-size mix and depth of the catch-up stretch, and of a
    steady validator beside it."""
    out = {}
    steady = doc["steady"]
    for who in ("victim", "witness"):
        before, after = steady[who + "_before"], steady[who + "_after"]
        h = "verify_dispatch_batch_size"
        out[f"steady_{who}"] = {
            "requests_by_le": per_bucket(after[h], before[h]),
            "signatures": after[h]["sum"] - before[h]["sum"],
            "requests": after[h]["count"] - before[h]["count"],
            "depth": after["verify_pipeline_depth"],
        }
    seen = [s for s in doc["samples"] if s["victim"]]

    gaps = [s["witness_leaders"] - s["victim"]["leaders"] for s in seen]
    # Up to the first read from which on the gap no longer moves.
    out_of_step = [i for i, gap in enumerate(gaps)
                   if abs(gap - gaps[-1]) > doc["in_step"]]
    stretch = seen[:out_of_step[-1] + 2] if out_of_step else []
    if stretch:
        last = stretch[-1]["victim"]
        h = last["verify_dispatch_batch_size"]
        out["catching_up"] = {
            "seconds": stretch[-1]["since_restart"],
            "first_metrics_after_s": seen[0]["since_restart"],
            "requests_by_le": per_bucket(h, None),
            "signatures": h["sum"], "requests": h["count"],
            "collector_flushes_by_le": per_bucket(
                last["verify_batch_size"], None),
            "inflight_samples": [s["victim"]["verify_pipeline_inflight"]
                                 for s in stretch],
            "depth_samples": sorted({s["victim"]["verify_pipeline_depth"]
                                     for s in stretch}),
        }
        after = [s for s in seen if s not in stretch]
        if after:
            h2 = after[-1]["victim"]["verify_dispatch_batch_size"]
            out["after_catching_up"] = {
                "seconds": after[-1]["since_restart"]
                - stretch[-1]["since_restart"],
                "requests_by_le": per_bucket(h2, h),
                "signatures": h2["sum"] - h["sum"],
                "requests": h2["count"] - h["count"],
            }

    def kernels(snap):
        return {f"{d['kernel']}/{d['bucket']}": d["count"]
                for d in snap["dispatches"]}
    a, b, c = (kernels(doc[k]) for k in
               ("service_at_kill", "service_at_restart", "service_at_end"))
    out["service_dispatches_while_down"] = {k: b[k] - a.get(k, 0) for k in b}
    out["service_dispatches_after_restart"] = {
        k: c[k] - b.get(k, 0) for k in c}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="paper10-steady")
    parser.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--steady-s", type=float, default=5.0)
    parser.add_argument("--down-s", type=float, default=10.0)
    parser.add_argument("--watch-s", type=float, default=30.0)
    parser.add_argument("--every-s", type=float, default=0.25)
    parser.add_argument("--in-step", type=float, default=3.0,
                        help="committed leaders the gap to the healthy "
                        "node may still move by")
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    from mysticeti_tpu import native
    native.active_functions()
    cell = harness.find_cell(args.workload, args.spec)
    run = harness.Run(cell, args.seed, 0.0, False)
    gw = harness.load_module(cell["driver"], "bench_driver")
    fleet = gw.Fleet(run)
    try:
        keys = fleet.genesis()
        fleet.assert_ports_free()
        run.start_service(keys)
        fleet.boot()
        doc = asyncio.run(observe(run, fleet, gw, args))
        doc["summary"] = summarise(doc)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f)
        print(json.dumps(doc["summary"], indent=1))
    finally:
        for name in [n for n in run.children if n.startswith("node-")]:
            harness.stop_process(run.children.pop(name))
        run.stop_service()
        run.close(keep=False)
    print("platform:", (run.device_file or {}).get("platform"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
