#!/usr/bin/env python3
"""Decompose the e2e bench into its serial components on the live device:
null RTT, host->device bandwidth, kernel-only time, and the current e2e
number — the measurement discipline that separates chip weather from real
regressions.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from mysticeti_tpu.ops import ed25519 as E

    out = {"device": jax.devices()[0].platform}

    # 1. null RTT: tiny jitted op, block on result
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.int32)
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(f(x))
    out["null_rtt_ms"] = round((time.perf_counter() - t0) / 5 * 1e3, 1)

    # 2. h->d bandwidth: 8 MB transfer forced by a reduction fetch
    big = np.random.randint(0, 2**31, size=(2 * 1024 * 1024,), dtype=np.int32)
    g = jax.jit(lambda x: x.sum())
    np.asarray(g(jnp.asarray(big)))
    t0 = time.perf_counter()
    for _ in range(3):
        np.asarray(g(jnp.asarray(big)))
    dt = (time.perf_counter() - t0) / 3
    out["h2d_MBps"] = round(big.nbytes / dt / 1e6, 1)

    # 3. kernel-only: batch resident on device, dispatch N, block on last
    # (same batch construction as the headline bench — single source)
    from bench import _build_batch

    batch = int(os.environ.get("BENCH_BATCH", "16384"))
    table, pks, msgs, sigs = _build_batch(batch, seed=0)
    idx = table.indices_for(pks)
    blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
    dev_blob = jnp.asarray(blob)
    h = E._dispatch_indexed(dev_blob, table.words)
    np.asarray(h)  # warm
    iters = 8
    t0 = time.perf_counter()
    hs = [E._dispatch_indexed(dev_blob, table.words) for _ in range(iters)]
    np.asarray(hs[-1])
    dt = time.perf_counter() - t0
    out["kernel_ms_per_batch"] = round(dt / iters * 1e3, 1)
    out["kernel_only_sig_s"] = round(batch * iters / dt, 0)
    out["blob_bytes_per_batch"] = int(blob.nbytes)

    # 4. transfer+kernel serial estimate vs measured e2e (one bench trial)
    t0 = time.perf_counter()
    handles = []
    for _ in range(16):
        i2 = table.indices_for(pks)
        b2 = E.pack_blob_indexed(i2, msgs, sigs, num_keys=len(table))
        handles.extend(E.dispatch_indexed_chunks(b2, table))
    res = E.fetch_handles(handles)
    dt = time.perf_counter() - t0
    assert res.all()
    out["e2e_sig_s_16iters"] = round(batch * 16 / dt, 0)
    out["e2e_ms_per_batch"] = round(dt / 16 * 1e3, 1)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
