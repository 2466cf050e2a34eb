#!/usr/bin/env python3
"""Headline benchmark: END-TO-END batched Ed25519 verification throughput on
the attached accelerator.  A run that finds no accelerator fails; there is
no CPU measurement under this metric's name.

End-to-end means raw bytes in, accept/reject bits out: host packing (pure
numpy byte concatenation), transfer, device SHA-512 of R||A||M, mod-L
reduction, point decompression, the double-scalar ladder, and the canonical
compare are ALL inside the timed region.  The measured path is the one a
validator deploys (``ops.ed25519.verify_batch_table``): the signer set is a
known committee, so each signature ships as R||M||s + a key index into a
device-resident key table — not a kernel-only figure, and not a
hypothetical unknown-signer workload either.

Process layout: a chip belongs to one process, so this parent never imports
JAX and starts exactly ONE worker, which holds the device for the whole run.
``BENCH_MESH=N`` (>1) makes that one worker shard every dispatch over an
N-device ``jax.sharding.Mesh`` (one process drives all the chips of a host).

Prints exactly ONE JSON line:
  {"metric": "ed25519_verifies_per_sec", "value": N, "unit": "sig/s",
   "vs_baseline": R, "device": {"platform": ..., "kind": ..., "count": ...}}

``vs_baseline`` is measured against the BASELINE.json north-star target of
500k sig-verifies/sec/host (the reference itself publishes no number — its
dalek CPU path verifies serially per block, ~15-25k/s/core).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_TARGET = 500_000.0  # sig-verifies/sec/host (BASELINE.json north star)


def _build_batch(batch: int, seed: int):
    """A realistic batch: a 16-signer committee over 32-byte block digests
    (the framework's signed message is always a blake2b-256 digest)."""
    import random

    from mysticeti_tpu.crypto import Ed25519PrivateKey
    from mysticeti_tpu.ops import ed25519 as E

    rng = random.Random(seed)
    n_keys = 16
    keys = [
        Ed25519PrivateKey.from_private_bytes(
            bytes(rng.randrange(256) for _ in range(32))
        )
        for _ in range(n_keys)
    ]
    pks, msgs, sigs = [], [], []
    for i in range(batch):
        key = keys[i % n_keys]
        msg = bytes(rng.randrange(256) for _ in range(32))
        pks.append(key.public_key().public_bytes_raw())
        msgs.append(msg)
        sigs.append(key.sign(msg))
    table = E.KeyTable([k.public_key().public_bytes_raw() for k in keys])
    return table, pks, msgs, sigs


def _run_trial(table, pks, msgs, sigs, iters: int) -> float:
    """One timed trial: ``iters`` full batches, packing + index lookup inside
    the timed region, every dispatch async, ONE combined fetch at the end."""
    from mysticeti_tpu.ops import ed25519 as E

    batch = len(sigs)
    start = time.perf_counter()
    handles = []
    for _ in range(iters):
        idx = table.indices_for(pks)
        blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
        handles.extend(E.dispatch_indexed_chunks(blob, table))
    results = E.fetch_handles(handles)
    elapsed = time.perf_counter() - start
    assert results.shape[0] == batch * iters and bool(results.all())
    return elapsed


def _run_trial_mesh(mesh, table, pks, msgs, sigs, iters: int) -> float:
    """Multi-chip trial: the committee-indexed blob sharded over the mesh's
    batch axis (parallel/mesh.py).  Same discipline as ``_run_trial``: every
    dispatch async, ONE combined fetch after the timed region's dispatches."""
    import jax.numpy as jnp

    from mysticeti_tpu.ops import ed25519 as E
    from mysticeti_tpu.parallel.mesh import _cached_indexed_kernel

    kernel = _cached_indexed_kernel(mesh)
    batch = len(sigs)
    start = time.perf_counter()
    handles = []
    for _ in range(iters):
        idx = table.indices_for(pks)
        blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
        for s, count, b in E.iter_buckets(batch):
            handles.append((
                count,
                kernel(
                    jnp.asarray(E._pad_to(blob[s:s + count], b)), table.words
                )[0],
            ))
    results = E.fetch_handles(handles)
    elapsed = time.perf_counter() - start
    assert results.shape[0] == batch * iters and bool(results.all())
    return elapsed


def _worker() -> None:
    """Child-process mode — the one process that touches JAX.  Refuses a host
    with no accelerator, warms up, reports ``READY <device json>``, then runs
    one timed trial per GO line on stdin, reporting {"sigs": N, "elapsed": s}
    per trial on stdout."""
    import numpy as np

    import jax

    from mysticeti_tpu.ops import ed25519 as E

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator (platform 'cpu'); this "
            "benchmark measures the chip and has no CPU form"
        )
    batch = int(os.environ["BENCH_BATCH"])
    iters = int(os.environ["BENCH_ITERS"])
    mesh_n = int(os.environ.get("BENCH_MESH", "0"))
    table, pks, msgs, sigs = _build_batch(batch, seed=0)
    if mesh_n > 1:
        from mysticeti_tpu.parallel.mesh import (
            make_mesh,
            sharded_verify_batch_indexed,
        )

        if len(devices) < mesh_n:
            raise SystemExit(
                f"bench: BENCH_MESH={mesh_n} but only {len(devices)} "
                "device(s) attached"
            )
        mesh = make_mesh(mesh_n, devices=devices[:mesh_n])
        ok, total = sharded_verify_batch_indexed(
            mesh, table, pks, msgs, sigs
        )  # warm/compile + correctness (psum total checked once)
        assert int(total) == batch and bool(ok.all())
        trial = lambda: _run_trial_mesh(mesh, table, pks, msgs, sigs, iters)
    else:
        ok = E.verify_batch_table(table, pks, msgs, sigs)  # warm/compile
        assert bool(np.asarray(ok).all()), "benchmark batch must verify"
        trial = lambda: _run_trial(table, pks, msgs, sigs, iters)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print("READY " + json.dumps(device), flush=True)
    for line in sys.stdin:
        if line.strip() == "GO":
            print(json.dumps({"sigs": batch * iters, "elapsed": trial()}),
                  flush=True)


def _measure(batch: int, iters: int, trials: int):
    """Drive the one worker: returns (best sig/s over the trials, device).

    A stall watchdog kills the worker if no line arrives within the phase's
    limit (BENCH_READY_TIMEOUT_S covers the cold compile, BENCH_STALL_TIMEOUT_S
    one trial) — failing loud beats hanging the caller's whole bench step."""
    env = dict(os.environ)
    env.update({"BENCH_WORKER": "1", "BENCH_BATCH": str(batch),
                "BENCH_ITERS": str(iters)})
    # Worker stderr goes to a file, not DEVNULL: its failure reason must
    # reach the operator.
    err = tempfile.TemporaryFile(mode="w+")
    worker = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
        env=env, text=True,
    )

    def _stderr_tail(limit: int = 2000) -> str:
        err.seek(0)
        return err.read()[-limit:]

    stall = {
        "t": time.monotonic(),
        "limit": float(os.environ.get("BENCH_READY_TIMEOUT_S", "900")),
    }
    stop_guard = threading.Event()
    timed_out = threading.Event()

    def _watchdog() -> None:
        while not stop_guard.wait(5.0):
            if time.monotonic() - stall["t"] > stall["limit"]:
                timed_out.set()
                worker.kill()
                return

    threading.Thread(target=_watchdog, daemon=True).start()

    def _readline(phase: str) -> str:
        line = worker.stdout.readline().strip()
        stall["t"] = time.monotonic()
        if not line:
            if timed_out.is_set():
                raise RuntimeError(
                    f"bench: no worker progress within {stall['limit']:.0f}s "
                    f"during {phase}\n{_stderr_tail()}"
                )
            raise RuntimeError(
                f"bench: worker died during {phase} "
                f"(exit code {worker.wait()})\n{_stderr_tail()}"
            )
        return line

    try:
        ready = _readline("warmup")
        if not ready.startswith("READY "):
            raise RuntimeError(
                f"bench: worker failed to start: {ready!r}\n{_stderr_tail()}"
            )
        device = json.loads(ready[len("READY "):])
        sys.stderr.write(f"bench: worker ready on {device}\n")
        stall["limit"] = float(os.environ.get("BENCH_STALL_TIMEOUT_S", "420"))
        best = 0.0
        for trial in range(trials):
            worker.stdin.write("GO\n")
            worker.stdin.flush()
            rec = json.loads(_readline(f"trial {trial + 1}"))
            best = max(best, rec["sigs"] / rec["elapsed"])
        return best, device
    finally:
        stop_guard.set()
        try:
            worker.stdin.close()
        except OSError:
            pass
        try:
            worker.wait(timeout=60)
        except subprocess.TimeoutExpired:
            # A hung worker must not be left behind holding the device.
            worker.kill()
            worker.wait()
        err.close()


def main() -> None:
    if os.environ.get("BENCH_WORKER") == "1":
        _worker()
        return
    batch = int(os.environ.get("BENCH_BATCH", "16384"))
    iters = int(os.environ.get("BENCH_ITERS", "64"))
    trials = int(os.environ.get("BENCH_TRIALS", "4"))
    value, device = _measure(batch, iters, trials)
    print(json.dumps({
        "metric": "ed25519_verifies_per_sec",
        "value": round(value, 1),
        "unit": "sig/s",
        "vs_baseline": round(value / BASELINE_TARGET, 4),
        "device": device,
    }))


if __name__ == "__main__":
    main()
