#!/usr/bin/env python3
"""Node-level verifier comparison: committed-tx/sec with verifier=cpu vs tpu.

Runs the SAME fixed load through the local orchestrator twice — once with the
serial OpenSSL verifier (reference behavior) and once with the batched
TPU kernel — and records both (BASELINE configs #3/#5 measurement semantics:
tps = latency_s_count / benchmark_duration, orchestrator/src/measurement.rs:92-142).

Writes one JSON artifact (default NODE_BENCH.json) with both runs.

The fleets come first and this process stays off JAX while they run (each
tpu fleet's verifier service is the one process that holds the chip); the
in-process saturation rows run after the last fleet has been stopped.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def run_one(verifier: str, nodes: int, load: int, duration: float,
                  workdir: str) -> dict:
    from mysticeti_tpu.orchestrator.benchmark import LoadType, ParametersGenerator
    from mysticeti_tpu.orchestrator.logs import analyze_logs
    from mysticeti_tpu.orchestrator.orchestrator import Orchestrator
    from mysticeti_tpu.orchestrator.runner import LocalProcessRunner

    fleet = os.path.join(workdir, f"fleet-{verifier}")
    results = os.path.join(workdir, f"results-{verifier}")
    # The shared verifier service removed the tpu warmup asymmetry: the
    # runner blocks until the service is warm before booting nodes.
    # Identical delays keep the rows comparable.
    os.environ["INITIAL_DELAY"] = "1"
    runner = LocalProcessRunner(fleet, verifier=verifier)
    generator = ParametersGenerator(
        nodes, LoadType.fixed([load]), duration_s=duration
    )
    orch = Orchestrator(
        runner, generator, results_dir=results, scrape_interval_s=duration / 4
    )
    collections = await orch.run_benchmarks()
    c = collections[0]
    logs = analyze_logs(fleet)
    return {
        "verifier": verifier,
        "nodes": nodes,
        "offered_load_tx_s": load,
        "duration_s": c.benchmark_duration(),
        "committed_tx_s": round(c.aggregate_tps(), 1),
        "avg_latency_s": round(c.aggregate_average_latency_s(), 4),
        "stdev_latency_s": round(c.aggregate_stdev_latency_s(), 4),
        "log_errors": logs.total_errors,
        "log_crashes": logs.total_crashes,
    }


def saturation(verifier: str, batch: int = 4096, iters: int = 5) -> dict:
    """Sustained throughput of the SignatureVerifier backend itself — the
    number that caps a node's verification rate once consensus stops being
    the bottleneck (large committees / per-certificate checks)."""
    import random
    import time

    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    from mysticeti_tpu.block_validator import CpuSignatureVerifier, TpuSignatureVerifier

    rng = random.Random(1)
    keys = [
        Ed25519PrivateKey.from_private_bytes(bytes(rng.randrange(256) for _ in range(32)))
        for _ in range(8)
    ]
    pks, msgs, sigs = [], [], []
    for i in range(batch):
        k = keys[i % 8]
        m = bytes(rng.getrandbits(8) for _ in range(32))
        pks.append(k.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(k.sign(m))
    # Deployed semantics: the signer set is the committee, keys ride as
    # indices into a device-resident table (validator._make_verifier).
    # "tpu" and "tpu-only" both send every batch to the kernel, so the pure
    # TPU backend measures both flavors.
    backend = (
        CpuSignatureVerifier()
        if verifier == "cpu"
        else TpuSignatureVerifier(
            committee_keys=[k.public_key().public_bytes_raw() for k in keys]
        )
    )
    assert all(backend.verify_signatures(pks, msgs, sigs))  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        backend.verify_signatures(pks, msgs, sigs)
    elapsed = time.perf_counter() - t0
    return {
        "verifier": verifier,
        "batch": batch,
        "sig_per_sec": round(batch * iters / elapsed, 1),
    }


def mesh_serialization(peers: int = 9, blocks: int = 50, txs: int = 16,
                       iters: int = 30) -> dict:
    """Mesh-serialization microbench: encode-once fan-out vs per-peer.

    Measures exactly the work ``write_loop`` does when a dissemination
    frame fans out to ``peers`` subscribers: the legacy path encodes the
    frame once PER PEER; the broadcast-once path encodes once and ships the
    cached payload (``EncodedFrame``).  Reports MB/s of fan-out payload
    production plus interpreter allocation counts — the second number is
    the GC-pressure story the throughput number hides.  Uses the
    ``mysticeti_tpu.crypto`` signers (pure-Python RFC 8032 fallback) so the
    rung runs on hosts without the ``cryptography`` package.
    """
    import time

    from mysticeti_tpu.committee import Committee
    from mysticeti_tpu.network import Blocks, EncodedFrame, encode_message, frame_payload
    from mysticeti_tpu.types import Share, StatementBlock

    signers = Committee.benchmark_signers(4)
    genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
    batch = tuple(
        StatementBlock.build(
            0, 1 + i, genesis, [Share(bytes(128) + i.to_bytes(4, "little"))] * txs,
            signer=signers[0],
        ).to_bytes()
        for i in range(blocks)
    )
    msg = Blocks(batch)
    frame_bytes = len(encode_message(msg))
    shipped = frame_bytes * peers * iters

    def measure(fn, encodes_per_fanout):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        elapsed = time.perf_counter() - t0
        return {
            "mb_per_s": round(shipped / 1e6 / elapsed, 1),
            # Every encoded byte is an allocated byte: the fan-out's
            # allocation volume is encodes × frame size (the GC-pressure
            # story the throughput number hides).
            "encodes_per_fanout": encodes_per_fanout,
            "alloc_bytes_per_fanout": encodes_per_fanout * frame_bytes,
            "elapsed_s": round(elapsed, 4),
        }

    def per_peer():
        for _ in range(peers):
            encode_message(msg)

    def encode_once():
        frame = EncodedFrame(msg)
        for _ in range(peers):
            frame_payload(frame)

    per_peer_row = measure(per_peer, peers)
    encode_once_row = measure(encode_once, 1)
    return {
        "metric": "mesh_serialization_fanout",
        "peers": peers,
        "blocks_per_frame": blocks,
        "frame_bytes": frame_bytes,
        "iters": iters,
        "per_peer": per_peer_row,
        "encode_once": encode_once_row,
        "speedup": round(
            encode_once_row["mb_per_s"] / max(per_peer_row["mb_per_s"], 1e-9), 2
        ),
    }


def dataplane_bench(blocks: int = 64, txs: int = 16, iters: int = 40) -> dict:
    """Native data-plane microbench: frame encode / parse / digest, native
    batched vs pure-Python per-block, on one realistic dissemination frame.

    The three stages are exactly the receive/send hot path the r19 native
    batch helpers cover: whole-frame encode (``encode_message`` on a
    ``Blocks`` fan-out), whole-frame parse (``decode_message`` splitting the
    payload into per-block views), and the per-block digest pair
    (block digest + signature pre-hash, batched into ONE native call).  The
    fallback rows force the pure interpreter path in-process by nulling the
    module-level native aliases — same bytes, same objects, so the ratio is
    the GIL-free batching win and nothing else.  Without the extension the
    native rows are absent and the artifact records fallback-only numbers.
    """
    import time

    import mysticeti_tpu.network as network_mod
    import mysticeti_tpu.types as types_mod
    from mysticeti_tpu import crypto
    from mysticeti_tpu.committee import Committee
    from mysticeti_tpu.native import native
    from mysticeti_tpu.network import Blocks, decode_message, encode_message
    from mysticeti_tpu.types import Share, StatementBlock

    signers = Committee.benchmark_signers(4)
    genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
    parts = tuple(
        StatementBlock.build(
            0, 1 + i, genesis,
            [Share(bytes(128) + i.to_bytes(4, "little"))] * txs,
            signer=signers[0],
        ).to_bytes()
        for i in range(blocks)
    )
    msg = Blocks(parts)
    payload = encode_message(msg)
    frame_bytes = len(payload)
    total_bytes = sum(len(p) for p in parts)

    def timed(fn):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        return (time.perf_counter() - t0) / iters

    def py_digest_per_block():
        for p in parts:
            crypto.blake2b_256(p)
            crypto.blake2b_256(p[:-64])

    saved = (
        network_mod._native_encode_frame,
        network_mod._native_parse_spans,
        types_mod._native_decode,
        types_mod._native_block_digests,
    )
    try:
        network_mod._native_encode_frame = None
        network_mod._native_parse_spans = None
        types_mod._native_decode = None
        types_mod._native_block_digests = None
        fb_encode = timed(lambda: encode_message(msg))
        fb_parse = timed(lambda: decode_message(payload))
        fb_digest = timed(py_digest_per_block)
        fb_decode_many = timed(
            lambda: StatementBlock.from_bytes_many(parts)
        )
    finally:
        (network_mod._native_encode_frame, network_mod._native_parse_spans,
         types_mod._native_decode, types_mod._native_block_digests) = saved

    def us(seconds):
        return round(seconds * 1e6, 1)

    row = {
        "metric": "native_dataplane",
        "native_active": native is not None,
        "blocks_per_frame": blocks,
        "txs_per_block": txs,
        "frame_bytes": frame_bytes,
        "iters": iters,
        "fallback": {
            "encode_us": us(fb_encode),
            "parse_us": us(fb_parse),
            "digest_per_block_us": us(fb_digest),
            "decode_many_us": us(fb_decode_many),
            "encode_mb_s": round(frame_bytes / 1e6 / fb_encode, 1),
            "parse_mb_s": round(frame_bytes / 1e6 / fb_parse, 1),
            "digest_mb_s": round(total_bytes / 1e6 / fb_digest, 1),
        },
    }
    if native is None:
        return row

    nat_encode = timed(lambda: encode_message(msg))
    nat_parse = timed(lambda: decode_message(payload))
    nat_digest = timed(lambda: native.block_digests(parts))
    nat_digest_per_block = timed(
        lambda: [native.block_digests([p]) for p in parts]
    )
    nat_decode_many = timed(lambda: StatementBlock.from_bytes_many(parts))
    combined = (fb_encode + fb_parse + fb_digest) / max(
        nat_encode + nat_parse + nat_digest, 1e-12
    )
    row["native"] = {
        "encode_us": us(nat_encode),
        "parse_us": us(nat_parse),
        "digest_batched_us": us(nat_digest),
        "digest_per_block_us": us(nat_digest_per_block),
        "decode_many_us": us(nat_decode_many),
        "encode_mb_s": round(frame_bytes / 1e6 / nat_encode, 1),
        "parse_mb_s": round(frame_bytes / 1e6 / nat_parse, 1),
        "digest_mb_s": round(total_bytes / 1e6 / nat_digest, 1),
    }
    row["speedups"] = {
        "encode": round(fb_encode / nat_encode, 2),
        "parse": round(fb_parse / nat_parse, 2),
        "digest": round(fb_digest / nat_digest, 2),
        # One GIL round-trip per frame vs one per block, both native: the
        # batching win isolated from the C-vs-interpreter win.
        "digest_batched_vs_per_block": round(
            nat_digest_per_block / nat_digest, 2
        ),
        "decode_many": round(fb_decode_many / nat_decode_many, 2),
        # The acceptance ratio: whole native hot path vs pure per-block.
        "combined_encode_parse_digest": round(combined, 2),
    }
    return row


def append_dataplane_trend(row: dict, round_: int, path: str) -> None:
    """NODE_DATAPLANE trend family: every recorded value is higher-is-better
    (MB/s and speedup ratios — the budget-row inversion PERF_ATTR uses for
    per-leader costs), so the stock >10%-below-best regression gate applies
    directly round-over-round."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import bench_trend

    source = f"DATAPLANE_r{round_:02d}.json"
    fresh = []

    def rec(metric, value, unit):
        fresh.append(bench_trend._record(
            round_, source, f"NODE_DATAPLANE.{metric}", value, unit,
        ))

    rec("fallback_encode_mb_s", row["fallback"]["encode_mb_s"], "MB/s")
    rec("fallback_parse_mb_s", row["fallback"]["parse_mb_s"], "MB/s")
    rec("fallback_digest_mb_s", row["fallback"]["digest_mb_s"], "MB/s")
    if row.get("native"):
        rec("native_encode_mb_s", row["native"]["encode_mb_s"], "MB/s")
        rec("native_parse_mb_s", row["native"]["parse_mb_s"], "MB/s")
        rec("native_digest_mb_s", row["native"]["digest_mb_s"], "MB/s")
        sp = row["speedups"]
        rec("encode_speedup", sp["encode"], "x")
        rec("parse_speedup", sp["parse"], "x")
        rec("digest_speedup", sp["digest"], "x")
        rec("digest_batched_vs_per_block", sp["digest_batched_vs_per_block"],
            "x")
        rec("decode_many_speedup", sp["decode_many"], "x")
        rec("combined_speedup", sp["combined_encode_parse_digest"], "x")
    index = bench_trend.load_index(path)
    if bench_trend.merge_index(index, fresh):
        bench_trend.write_index(index, path)


def append_mesh_trend(row: dict, round_: int, path: str) -> None:
    """Track the fan-out win round-over-round in the trend index under its
    own MESH_SERIALIZATION family (never mixed with the fleet families —
    a serialization microbench must not gate a fleet search and vice
    versa)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import bench_trend

    source = f"MESH_SERIALIZATION_r{round_:02d}.json"
    fresh = [
        bench_trend._record(
            round_, source, "MESH_SERIALIZATION.encode_once_mb_s",
            row["encode_once"]["mb_per_s"], "MB/s",
        ),
        bench_trend._record(
            round_, source, "MESH_SERIALIZATION.per_peer_mb_s",
            row["per_peer"]["mb_per_s"], "MB/s",
        ),
        bench_trend._record(
            round_, source, "MESH_SERIALIZATION.fanout_speedup",
            row["speedup"], "x",
        ),
    ]
    index = bench_trend.load_index(path)
    if bench_trend.merge_index(index, fresh):
        bench_trend.write_index(index, path)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--load", type=int, default=200)
    parser.add_argument("--duration", type=float, default=40.0)
    parser.add_argument("--workdir", default="/tmp/mysticeti-node-bench")
    parser.add_argument("--out", default="NODE_BENCH.json")
    parser.add_argument(
        "--verifiers", nargs="+", default=["cpu", "tpu"],
        choices=["accept", "cpu", "tpu", "tpu-only"],
    )
    parser.add_argument(
        "--mesh-bench", action="store_true",
        help="run ONLY the mesh-serialization microbench (encode-once vs "
        "per-peer fan-out); appended under the MESH_SERIALIZATION family "
        "to the trend index BENCH_TREND_PATH names, if it is set",
    )
    parser.add_argument(
        "--dataplane-bench", action="store_true",
        help="run ONLY the native data-plane microbench (frame encode/parse"
        "/digest, native batched vs pure-Python per-block); appended under "
        "the NODE_DATAPLANE family to the trend index BENCH_TREND_PATH "
        "names, if it is set",
    )
    parser.add_argument(
        "--round", type=int, default=10,
        help="PR round recorded with --mesh-bench/--dataplane-bench trend "
        "records",
    )
    args = parser.parse_args()
    # A run writes into no tracked file: the trend index is only appended
    # to where the caller names one.
    trend_path = os.environ.get("BENCH_TREND_PATH")

    if args.dataplane_bench:
        row = dataplane_bench()
        print(json.dumps(row, indent=2))
        if trend_path:
            append_dataplane_trend(row, args.round, trend_path)
            print(f"appended NODE_DATAPLANE records to {trend_path}")
        return

    if args.mesh_bench:
        row = mesh_serialization()
        print(json.dumps(row, indent=2))
        if trend_path:
            append_mesh_trend(row, args.round, trend_path)
            print(f"appended MESH_SERIALIZATION records to {trend_path}")
        return

    runs = []
    for verifier in args.verifiers:
        print(f"running verifier={verifier}...", flush=True)
        for attempt in range(2):
            run = asyncio.run(
                run_one(verifier, args.nodes, args.load, args.duration, args.workdir)
            )
            if run["committed_tx_s"] > 0 or attempt == 1:
                break
            print("no commits (warmup overran the window); retrying", flush=True)
        runs.append(run)
        print(json.dumps(runs[-1]), flush=True)

    saturation_rows = []
    for verifier in args.verifiers:
        if verifier == "accept":
            continue
        print(f"saturation verifier={verifier}...", flush=True)
        saturation_rows.append(saturation(verifier))
        print(json.dumps(saturation_rows[-1]), flush=True)

    import jax

    artifact = {
        "metric": "committed_tx_per_sec_by_verifier",
        "backend": jax.default_backend(),
        "verifier_saturation": saturation_rows,
        "environment_note": (
            "saturation rows ran on the local TPU"
            if jax.default_backend() == "tpu"
            else "JAX_PLATFORMS=cpu (the verifier service starts without an "
            "accelerator only when that names the CPU): 'tpu' rows ran the "
            "service architecture with XLA:CPU behind it, and no row is a "
            "chip rate."
        ),
        "runs": runs,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
