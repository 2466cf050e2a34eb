#!/usr/bin/env python3
"""One-command chip-window capture: same-window tpu-vs-cpu fleet A/B.

A single command with zero setup decisions left.  This tool runs the
VERIFICATION-BOUND fleet regime (tools/catchup_bench.py's configuration:
small blocks to raise the block/signature rate, deep retain window, fast
leader timeout) as back-to-back cpu and tpu-flavor max-load searches in one
weather window, and records:

  * the platform the tpu fleet's verifier service resolved ("tpu" on a
    chip; "cpu" only when the run was started with JAX_PLATFORMS=cpu) — so
    the artifact is honest about what it measured;
  * per-probe hostmon weather + a same-window cpu reference probe
    (inherited from tools/maxload_bench.py), so the A/B is self-contained;
  * the headline ratio `tpu_peak / cpu_peak`.

Under JAX_PLATFORMS=cpu (no chip) the tpu flavor verifies on the service's
XLA ladder over the socket: the ratio then prices that path, not a chip.

Usage:
  python tools/chip_window_ab.py --out MAXLOAD_TAX_r06.json
  python tools/chip_window_ab.py --tpu-flavor tpu-agg --duration 30 \
      --iterations 6 --out CHIPWINDOW_r06.json
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from maxload_bench import search_one  # noqa: E402 - sibling tool module


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--start-load", type=int, default=400)
    parser.add_argument("--duration", type=float, default=20.0)
    parser.add_argument("--iterations", type=int, default=5)
    parser.add_argument("--max-block-tx", type=int, default=16)
    parser.add_argument("--tpu-flavor", default="tpu",
                        choices=["tpu", "tpu-only", "tpu-agg"])
    parser.add_argument("--workdir", default="/tmp/mysticeti-chipwindow")
    parser.add_argument("--out", default="CHIPWINDOW.json")
    args = parser.parse_args()

    # The verification-bound regime (catchup_bench.py's genesis-time env):
    # small blocks raise the signature rate per committed tx, the retain
    # window keeps sync streams deep, and the short leader timeout keeps
    # stalls from hiding verification cost.
    os.environ["MYSTICETI_MAX_BLOCK_TX"] = str(args.max_block_tx)
    os.environ["MYSTICETI_RETAIN_ROUNDS"] = "100000"
    os.environ["MYSTICETI_LEADER_TIMEOUT"] = "0.25"

    # This process stays off JAX: the tpu fleet's verifier service is the
    # one process that holds the chip, and the platform recorded below is
    # what that service advertised over HELLO_OK.
    window_start = time.time()
    runs = []
    for verifier in ("cpu", args.tpu_flavor):
        print(f"max-load search verifier={verifier}...", flush=True)
        run = asyncio.run(
            search_one(verifier, args.nodes, args.start_load, args.duration,
                       args.iterations, args.workdir)
        )
        runs.append(run)
        print(json.dumps(run), flush=True)

    cpu_peak = runs[0]["peak_committed_tx_s"]
    tpu_peak = runs[1]["peak_committed_tx_s"]
    platform = runs[1]["service_backend"]
    print(f"verifier service platform: {platform}", flush=True)
    artifact = {
        "metric": "same_window_tpu_vs_cpu_peak_committed_tx_s",
        "resolved_platform": platform,
        "chip_attached": platform != "cpu",
        "regime": {
            "max_block_tx": args.max_block_tx,
            "retain_rounds": 100000,
            "leader_timeout_s": 0.25,
            "note": (
                "verification-bound fleet shape (catchup_bench regime): "
                "small blocks maximize signatures per committed tx"
            ),
        },
        "window_utc": [round(window_start, 1), round(time.time(), 1)],
        "cpu_peak_committed_tx_s": cpu_peak,
        "tpu_peak_committed_tx_s": tpu_peak,
        "tpu_over_cpu": round(tpu_peak / cpu_peak, 3) if cpu_peak else None,
        "acceptance": (
            "on a chip: tpu_over_cpu > 1; under JAX_PLATFORMS=cpu "
            "(chip_attached=false): tpu_over_cpu >= 0.9 shows the "
            "zero-tax data plane"
        ),
        "runs": runs,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
