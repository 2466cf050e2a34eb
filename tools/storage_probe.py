#!/usr/bin/env python3
"""Storage lifecycle acceptance probe -> storage_probe.json.

Two deterministic sims on the virtual-time loop (no accelerator, no real
network), exercising the storage plane end-to-end at the scale the
acceptance criteria name:

1. **bounded-disk**: a 4-node fleet under load with small segments,
   checkpoints every few commits, and an aggressive GC depth; one node
   crash-restarts mid-run.  Evidence: segments below the GC round are
   deleted while the fleet keeps committing (live bytes << lifetime bytes
   written), and the restarted node boots from a checkpoint, replaying only
   post-checkpoint segments (replay bytes << lifetime WAL bytes).

2. **snapshot-catchup**: a node is absent for >= 1000 rounds (its history
   GC'd fleet-wide, so block-by-block pull from round zero is impossible),
   rejoins via the snapshot stream (wire tags 9/10), and commits the same
   leader sequence as the fleet — asserted at every shared height, plus the
   adopted anchor.  Catch-up wall-clock (virtual and host), blocks, and
   bytes are recorded.

Usage::

    python tools/storage_probe.py [--out storage_probe.json] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mysticeti_tpu.chaos import CrashFault, FaultPlan, run_chaos_sim  # noqa: E402
from mysticeti_tpu.config import Parameters, StorageParameters  # noqa: E402


def bounded_disk_scenario(quick: bool) -> dict:
    duration = 20.0 if quick else 60.0
    parameters = Parameters(
        leader_timeout_s=1.0,
        storage=StorageParameters(
            segment_bytes=16 * 1024, checkpoint_interval=10, gc_depth=30
        ),
    )
    plan = FaultPlan(
        seed=8,
        crashes=[CrashFault(node=2, at_s=duration * 0.6, downtime_s=2.0)],
    )
    wal_dir = tempfile.mkdtemp(prefix="storage-probe-disk-")
    t0 = time.monotonic()
    report, harness = run_chaos_sim(
        plan, 4, duration, wal_dir, parameters=parameters, with_metrics=True
    )
    wall = time.monotonic() - t0
    nodes = {}
    for authority in range(4):
        node = harness.nodes[authority]
        writer = node.core.wal_writer
        metrics = harness.metrics[authority]
        nodes[str(authority)] = {
            "commit_height": harness.committed_height(authority),
            "lifetime_wal_bytes": writer.position(),
            "live_wal_bytes": writer.size_bytes(),
            "live_segments": writer.segment_count(),
            "first_live_offset": writer.first_base(),
            "reclaimed_bytes": metrics.wal_reclaimed_bytes_total._value.get(),
            "last_checkpoint_height": metrics.checkpoint_last_commit_index._value.get(),
            "retired_round": node.core.storage.retired_round,
        }
    restarted = harness.nodes[2].core.storage
    lifetime = harness.nodes[2].core.wal_writer.position()
    result = {
        "virtual_duration_s": duration,
        "wall_s": round(wall, 2),
        "nodes": nodes,
        "restart": {
            "node": 2,
            "recovered_checkpoint_height": restarted.recovered_checkpoint_height,
            "replay_start": restarted.replay_start,
            "replayed_bytes": restarted.replayed_bytes,
            "lifetime_wal_bytes": lifetime,
            "replay_fraction": round(restarted.replayed_bytes / lifetime, 4),
        },
    }
    # Acceptance: disk bounded + checkpoint boot replays only the tail.
    assert all(n["reclaimed_bytes"] > 0 for n in nodes.values()), "GC never ran"
    assert all(
        n["live_wal_bytes"] < n["lifetime_wal_bytes"] for n in nodes.values()
    ), "disk not bounded"
    assert restarted.recovered_checkpoint_height > 0, "restart missed the checkpoint"
    assert restarted.replayed_bytes * 5 < lifetime, "replay not << lifetime bytes"
    result["pass"] = True
    return result


def snapshot_catchup_scenario(quick: bool) -> dict:
    # With one node down, every 4th round waits out the 1 s leader timeout,
    # so rounds advance ~2.2/s of virtual time; >= 1000 rounds of absence
    # needs ~480 virtual seconds of downtime.
    downtime = 60.0 if quick else 480.0
    duration = downtime + 60.0
    parameters = Parameters(
        leader_timeout_s=1.0,
        storage=StorageParameters(
            segment_bytes=32 * 1024,
            checkpoint_interval=10,
            gc_depth=40,
            snapshot_catchup=True,
            catchup_threshold_commits=60,
        ),
    )
    plan = FaultPlan(
        seed=21, crashes=[CrashFault(node=3, at_s=4.0, downtime_s=downtime)]
    )
    wal_dir = tempfile.mkdtemp(prefix="storage-probe-catchup-")
    t0 = time.monotonic()
    report, harness = run_chaos_sim(
        plan, 4, duration, wal_dir, parameters=parameters, with_metrics=True
    )
    wall = time.monotonic() - t0
    node3 = harness.nodes[3]
    lifecycle = node3.core.storage
    crash_event = report.crash_events[0]
    anchors_fleet = harness.checker._anchors[0]
    anchors_rejoined = harness.checker._anchors[3]
    rejoined_heights = sorted(anchors_rejoined)
    crashed_at = crash_event["committed_height"]
    resumed_at = min(h for h in rejoined_heights if h > crashed_at)
    shared = sorted(set(anchors_fleet) & set(anchors_rejoined))
    mismatches = [h for h in shared if anchors_fleet[h] != anchors_rejoined[h]]
    served_blocks = sum(
        harness.nodes[a].snapshot_blocks_served
        + sum(
            d.snapshot_blocks_sent
            for d in harness.nodes[a]._disseminators.values()
        )
        for a in range(4)
        if harness.nodes[a] is not None
    )
    served_bytes = sum(
        harness.nodes[a].snapshot_bytes_served
        + sum(
            d.snapshot_bytes_sent
            for d in harness.nodes[a]._disseminators.values()
        )
        for a in range(4)
        if harness.nodes[a] is not None
    )
    adopted_leader_round = (
        lifecycle.last_committed_leader.round
        if lifecycle.last_committed_leader
        else 0
    )
    # Rounds absent, measured on the committed-anchor ROUNDS themselves:
    # first anchor committed after rejoining minus last anchor committed
    # before the crash.
    rounds_absent = (
        anchors_rejoined[resumed_at].round
        - anchors_rejoined[crashed_at].round
    )
    result = {
        "virtual_duration_s": duration,
        "downtime_s": downtime,
        "wall_s": round(wall, 2),
        "crashed_at_height": crashed_at,
        "resumed_at_height": resumed_at,
        "adopted_heights_skipped": resumed_at - crashed_at - 1,
        "rounds_absent": rounds_absent,
        "final_heights": {
            str(a): harness.committed_height(a) for a in range(4)
        },
        "snapshots_adopted": lifecycle.snapshots_adopted,
        "adopted_floor_round": lifecycle.retired_round,
        "adopted_leader_round": adopted_leader_round,
        "snapshot_blocks_served": served_blocks,
        "snapshot_bytes_served": served_bytes,
        "shared_heights_checked": len(shared),
        "prefix_mismatches": len(mismatches),
    }
    assert lifecycle.snapshots_adopted >= 1, "snapshot never adopted"
    assert result["rounds_absent"] >= (
        100 if quick else 1000
    ), f"absence too short: {result['rounds_absent']}"
    assert not mismatches, f"prefix divergence at heights {mismatches[:5]}"
    assert harness.committed_height(3) > resumed_at + 20, "rejoined node stalled"
    result["pass"] = True
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default="storage_probe.json")
    parser.add_argument("--quick", action="store_true",
                        help="shortened scenarios (smoke, not acceptance)")
    args = parser.parse_args(argv)
    artifact = {
        "probe": "storage-lifecycle",
        "revision": "r08",
        "quick": bool(args.quick),
        "config_defaults": {
            "segment_bytes": StorageParameters().segment_bytes,
            "checkpoint_interval": StorageParameters().checkpoint_interval,
            "gc_depth": StorageParameters().gc_depth,
        },
    }
    print("== bounded-disk scenario ==", flush=True)
    artifact["bounded_disk"] = bounded_disk_scenario(args.quick)
    print(json.dumps(artifact["bounded_disk"], indent=1))
    print("== snapshot catch-up scenario ==", flush=True)
    artifact["snapshot_catchup"] = snapshot_catchup_scenario(args.quick)
    print(json.dumps(artifact["snapshot_catchup"], indent=1))
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
