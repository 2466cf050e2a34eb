#!/usr/bin/env python3
"""Max-sustainable-load search over a local fleet, per verifier backend.

Reproducible generator of the MAXLOAD artifacts: runs the orchestrator's
binary search (benchmark.rs:202-271 semantics — double until out-of-capacity,
then bisect; out-of-capacity = avg latency > 5x previous or tps < 2/3
offered) with the chosen --verifier and records every probe.

Weather pinning: the same box moves 20k->32k tx/s across
hours, so a lone peak is not evidence.  Every probe embeds the hostmon
weather summary AND wall-clock window, and every non-cpu run is followed
immediately by a fixed-load cpu reference probe at that run's peak — so each
artifact is a self-contained same-window A/B, and round-over-round deltas
never need to reach across windows.

Usage:
  python tools/maxload_bench.py --verifier cpu --out MAXLOAD_r03.json
  python tools/maxload_bench.py --verifiers cpu tpu --out MAXLOAD_TPU.json
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _probe_dicts(collections) -> list:
    probes = []
    for c in collections:
        probe = {
            "offered_load_tx_s": c.parameters["load"],
            "tps": round(c.aggregate_tps(), 1),
            "avg_latency_s": round(c.aggregate_average_latency_s(), 4),
            "stdev_latency_s": round(c.aggregate_stdev_latency_s(), 4),
        }
        host = c.host_summary()
        if host is not None:
            probe["host"] = host
        health = c.health_summary()
        if health is not None:
            # The probe ships with its own diagnosis (health plane): a peak
            # taken while participation dipped or SLO alerts fired is
            # visible in the artifact itself, not just in hindsight.
            probe["health"] = health
        probes.append(probe)
    return probes


async def run_fixed_probe(verifier: str, nodes: int, load: int,
                          duration: float, workdir: str) -> dict:
    """One fixed-load probe — the same-window reference leg of the A/B."""
    from mysticeti_tpu.orchestrator.benchmark import LoadType, ParametersGenerator
    from mysticeti_tpu.orchestrator.orchestrator import Orchestrator
    from mysticeti_tpu.orchestrator.runner import LocalProcessRunner

    runner = LocalProcessRunner(
        os.path.join(workdir, f"fleet-ref-{verifier}"), verifier=verifier
    )
    generator = ParametersGenerator(
        nodes, LoadType.fixed([load]), duration_s=duration
    )
    orch = Orchestrator(
        runner,
        generator,
        results_dir=os.path.join(workdir, f"results-ref-{verifier}"),
        scrape_interval_s=duration / 3,
    )
    started = time.time()
    collections = await orch.run_benchmarks()
    probes = _probe_dicts(collections)
    probe = probes[0] if probes else {"error": "reference probe recorded nothing"}
    probe["verifier"] = verifier
    probe["window_utc"] = [round(started, 1), round(time.time(), 1)]
    return probe


async def search_one(verifier: str, nodes: int, start_load: int,
                     duration: float, iterations: int, workdir: str) -> dict:
    from mysticeti_tpu.orchestrator.benchmark import LoadType, ParametersGenerator
    from mysticeti_tpu.orchestrator.orchestrator import Orchestrator
    from mysticeti_tpu.orchestrator.runner import LocalProcessRunner

    # The shared verifier service removed the per-node warmup that used to
    # force 240 s tpu probe windows (the runner blocks until the service is
    # warm BEFORE booting nodes; validators are jax-free and seed their
    # routers from HELLO_OK).  Identical delays keep probes comparable.
    os.environ["INITIAL_DELAY"] = "1"
    runner = LocalProcessRunner(
        os.path.join(workdir, f"fleet-{verifier}"), verifier=verifier
    )
    generator = ParametersGenerator(
        nodes,
        LoadType.search(start_load, max_iterations=iterations),
        duration_s=duration,
    )
    orch = Orchestrator(
        runner,
        generator,
        results_dir=os.path.join(workdir, f"results-{verifier}"),
        scrape_interval_s=duration / 3,
    )
    started = time.time()
    collections = await orch.run_benchmarks()
    probes = _probe_dicts(collections)
    peak = max((p["tps"] for p in probes), default=0.0)
    return {
        "verifier": verifier,
        # The platform the fleet's verifier service resolved (HELLO_OK);
        # None for flavors that run no service.
        "service_backend": runner.service_backend,
        "nodes": nodes,
        "max_sustainable_load_tx_s": generator.max_sustainable_load(),
        "peak_committed_tx_s": round(peak, 1),
        "window_utc": [round(started, 1), round(time.time(), 1)],
        "probes": probes,
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--start-load", type=int, default=400)
    parser.add_argument("--duration", type=float, default=30.0)
    parser.add_argument("--iterations", type=int, default=7)
    parser.add_argument("--workdir", default="/tmp/mysticeti-maxload")
    parser.add_argument("--out", default="MAXLOAD.json")
    parser.add_argument(
        "--verifiers", nargs="+", default=["cpu"],
        choices=["accept", "cpu", "tpu", "tpu-only", "cpu-agg", "tpu-agg"],
    )
    args = parser.parse_args()

    # This process stays off JAX: each tpu fleet's verifier service is the
    # one process that holds the chip, and the runner waits for it to warm.
    runs = []
    for verifier in args.verifiers:
        print(f"max-load search verifier={verifier}...", flush=True)
        run = asyncio.run(
            search_one(verifier, args.nodes, args.start_load, args.duration,
                       args.iterations, args.workdir)
        )
        if verifier != "cpu":
            # Same-window reference leg: a cpu probe at THIS run's peak,
            # back-to-back so both legs share the box's current weather.
            ref_load = int(run["peak_committed_tx_s"]) or args.start_load
            print(
                f"  same-window cpu reference probe at {ref_load} tx/s...",
                flush=True,
            )
            run["cpu_reference_probe"] = asyncio.run(
                run_fixed_probe("cpu", args.nodes, ref_load, args.duration,
                                args.workdir)
            )
            ref_tps = run["cpu_reference_probe"].get("tps")
            if ref_tps:
                run["peak_vs_same_window_cpu"] = round(
                    run["peak_committed_tx_s"] / ref_tps, 3
                )
        runs.append(run)
        print(json.dumps(run), flush=True)

    artifact = {
        "metric": "max_sustainable_load_tx_s",
        "host": "single-core CI box (all validators + load generators share one core)",
        "search_rule": (
            "double until out-of-capacity (latency>5x prev or tps<2/3 "
            "offered), then bisect (benchmark.rs:202-271 semantics)"
        ),
        "ab_rule": (
            "every non-cpu run carries a cpu_reference_probe at its peak "
            "load, run back-to-back in the same weather window; "
            "peak_vs_same_window_cpu is the self-contained A/B ratio"
        ),
        "runs": runs,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
