#!/usr/bin/env python3
"""Multi-chip sharding scaling curve over virtual device meshes.

Extends the driver's one-shot ``dryrun_multichip`` into a measured curve:
for each mesh size, the SAME fixed global batch is sharded
over an n-device ``jax.sharding.Mesh`` through the deployed committee-
indexed path (``parallel/mesh.py:sharded_verify_batch_indexed``), asserting
per-shard shapes and the psum'd global valid count, and timing the jitted
step.  Each mesh size runs in its own subprocess because XLA parses the
virtual-device-count flag once per process.

HONESTY NOTE (recorded in the artifact): virtual CPU devices share this
host's single physical core, so wall-clock here measures shard_map +
collective LOWERING overhead at fixed total work — flat-or-slowly-rising
wall time with correct psum totals is the pass criterion, NOT a speedup
claim.  On a real TPU slice the same code path shards over ICI; run with
``--real`` on multi-chip hardware to measure actual scaling (bench.py
accepts BENCH_MESH=N for the same thing fleet-shaped).

Usage:
  python tools/mesh_scaling.py --out MULTICHIP_SCALING_r05.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, os, time
import numpy as np
import jax
jax.config.update("jax_platforms", os.environ.get("MESH_PLATFORM", "cpu"))
import random
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
from mysticeti_tpu.ops import ed25519 as E
from mysticeti_tpu.parallel.mesh import make_mesh, sharded_verify_batch_indexed

n = int(os.environ["MESH_DEVICES"])
batch = int(os.environ["MESH_BATCH"])
devices = jax.devices()
assert len(devices) >= n, f"need {n} devices, have {len(devices)}"
mesh = make_mesh(n, devices=devices[:n])

rng = random.Random(5)
keys = [
    Ed25519PrivateKey.from_private_bytes(bytes(rng.randrange(256) for _ in range(32)))
    for _ in range(16)
]
table = E.KeyTable([k.public_key().public_bytes_raw() for k in keys])
pks, msgs, sigs = [], [], []
for i in range(batch):
    k = keys[i % 16]
    m = bytes(rng.randrange(256) for _ in range(32))
    pks.append(k.public_key().public_bytes_raw())
    msgs.append(m)
    sigs.append(k.sign(m))

# Warm/compile, and the correctness assertions the dryrun makes.
ok, total = sharded_verify_batch_indexed(mesh, table, pks, msgs, sigs)
ok = np.asarray(ok)
assert ok.shape == (batch,), ok.shape
assert ok.all(), "all signatures must verify"
assert int(total) == batch, f"psum'd valid count {total} != {batch}"

iters = 3
t0 = time.perf_counter()
for _ in range(iters):
    ok, total = sharded_verify_batch_indexed(mesh, table, pks, msgs, sigs)
    assert int(total) == batch
elapsed = (time.perf_counter() - t0) / iters
print(json.dumps({
    "devices": n,
    "global_batch": batch,
    "per_shard_batch": batch // n,
    "psum_total_ok": True,
    "step_s": round(elapsed, 4),
    "sig_per_s": round(batch / elapsed, 1),
}))
"""


def run_point(n: int, batch: int, real: bool) -> dict:
    env = dict(os.environ)
    env["MESH_DEVICES"] = str(n)
    env["MESH_BATCH"] = str(batch)
    if not real:
        env["MESH_PLATFORM"] = "cpu"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    out = subprocess.run(
        [sys.executable, "-c", CHILD],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"mesh point n={n} failed:\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes", type=int, nargs="+", default=[1, 2, 4, 8])
    parser.add_argument("--batch", type=int, default=4096)
    parser.add_argument("--real", action="store_true",
                        help="use the real attached devices (TPU slice) "
                        "instead of virtual CPU devices")
    parser.add_argument("--out", default="MULTICHIP_SCALING.json")
    args = parser.parse_args()

    points = []
    for n in args.sizes:
        print(f"mesh point: {n} device(s), global batch {args.batch}...",
              flush=True)
        point = run_point(n, args.batch, args.real)
        points.append(point)
        print(json.dumps(point), flush=True)

    artifact = {
        "metric": "sharded_verify_scaling_curve",
        "config": {
            "path": "parallel/mesh.py:sharded_verify_batch_indexed "
                    "(committee-indexed blob, batch-axis sharding, psum "
                    "valid-count reduction)",
            "global_batch_fixed": args.batch,
            "platform": "real devices" if args.real else
                        "virtual CPU devices (one physical core)",
            "note": (
                "Virtual-device points validate shard_map lowering, "
                "per-shard shapes and psum totals at fixed global work; "
                "they share one physical core, so step_s measures "
                "partitioning overhead, not speedup.  On a real slice the "
                "same code path shards over ICI (run with --real, or "
                "BENCH_MESH=N through bench.py)."
            ),
        },
        "points": points,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
