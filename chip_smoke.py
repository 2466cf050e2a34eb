#!/usr/bin/env python3
"""The quickest proof that the validator fleet still starts on the chip.

Drives the system's main path once, through the entry points a user calls,
on one TPU, and checks what comes out by the repo's own means:

* **verifier leg** (a child process, the only one that touches JAX while it
  runs): ``TpuSignatureVerifier`` compiles every kernel it can dispatch at
  every bucket (256 / 1,024 / 4,096 / 16,384) — compiled Pallas, never the
  interpreter, never the XLA form — then a 16,384-signature batch against a
  10-key committee table, the RFC 8032 vectors and >= 12k seeded
  valid/corrupt cases (``tools/kernel_parity.py``) through both deployed
  paths (keyed table and raw), every accept/reject bit compared with the
  OpenSSL oracle (``CpuSignatureVerifier``).
* **fleet leg**: ``orchestrator.runner.LocalProcessRunner`` boots one
  ``python -m mysticeti_tpu verifier-service`` process — the one process
  that holds the chip — and 10 ``python -m mysticeti_tpu run`` validators
  (flavor ``tpu-only``: every received block's signature goes to the
  kernel) over real sockets, 512 B transactions, 2,000 tx/s offered through
  the nodes' own generators and ingress plane, reference pacing, >= 60 s.
  Every node must be alive at the end, have committed leaders and
  transactions, agree with every other node on the committed leader at every
  shared height (read back from each node's WAL), have verified signatures
  on ``tpu-remote`` only, and have fallen back to the host zero times.  The
  service must report platform ``tpu``, advertise it over HELLO_OK, have
  dispatched Pallas kernels only, and — being the second process to need
  them — have loaded every kernel it warms (the 256 bucket, all a
  validator's 256-signature window can reach): its lowered program from
  the program store and its executable from the compilation cache, both as
  the verifier leg left them.  A warm boot traces and compiles nothing:
  about a second a kernel, where the verifier leg's first call of each
  paid 7-40 s (the table printed below says which it was: ``program=``,
  ``cache=``).
* **four-chip leg**, only where the service saw four devices: the same
  fleet with the service sharding over the whole host (``shard_map``), in a
  fresh process; every device must hold a shard.  With one device it is
  reported as not run.

This parent never imports JAX: a chip belongs to one process, and the
children need it.  The native extension is built here, once, before eleven
processes would race to.  Exit code 0 and, as the last line of stdout,
``{"ok": true, "device": {...}}`` only when every check held; otherwise a
non-zero exit with the reasons on stderr and no result line.  Where JAX
finds no TPU the run stops at the first child: "no TPU found".
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

EXIT_FAILED = 1
EXIT_NO_TPU = 3
# ops.ed25519.BUCKETS, restated: the parent cannot import the JAX side.
BUCKETS = (256, 1024, 4096, 16384)
# The paper's deployment as BASELINE.json config 3 names it, and the
# kernel's full width.  One size: a pass at any other is not this proof.
NODES = 10
OFFERED_TX_S = 2000
TRANSACTION_SIZE = 512
DURATION_S = 60.0
BATCH = BUCKETS[-1]
PARITY_CASES = 12288


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# Verifier leg (child process: the one place this script touches JAX)


def _seeded_batch(n: int, n_keys: int, seed: int):
    """``n`` signatures over 32-byte digests from an ``n_keys`` committee;
    about a quarter corrupted (R, s or digest), so accept and reject lanes
    interleave.  Returns (committee keys, pks, digests, sigs)."""
    from mysticeti_tpu.crypto import Signer

    rng = random.Random(seed)
    signers = [Signer.from_seed(rng.randbytes(32)) for _ in range(n_keys)]
    pks, digests, sigs = [], [], []
    for _ in range(n):
        signer = signers[rng.randrange(n_keys)]
        digest = rng.randbytes(32)
        sig = signer.sign(digest)
        flip = rng.randrange(4)
        if flip == 0:
            pos = rng.randrange(96)
            bit = 1 << rng.randrange(8)
            if pos < 64:
                sig = sig[:pos] + bytes([sig[pos] ^ bit]) + sig[pos + 1:]
            else:
                pos -= 64
                digest = (
                    digest[:pos] + bytes([digest[pos] ^ bit]) + digest[pos + 1:]
                )
        pks.append(signer.public_key.bytes)
        digests.append(digest)
        sigs.append(sig)
    return [s.public_key.bytes for s in signers], pks, digests, sigs


def verifier_leg(args) -> int:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX resolved platform "
            f"{device['platform']!r})",
            file=sys.stderr,
        )
        return EXIT_NO_TPU

    from mysticeti_tpu.block_validator import (
        CpuSignatureVerifier,
        TpuSignatureVerifier,
    )
    from mysticeti_tpu.ops import ed25519 as E
    from tools.kernel_parity import run_parity

    keys, pks, digests, sigs = _seeded_batch(BATCH, NODES, args.seed)

    # One chip always; the whole host as well where there is more than one.
    meshes = [1] + ([len(devices)] if len(devices) > 1 else [])
    legs = []
    for n_devices in meshes:
        verifier = TpuSignatureVerifier(mesh=n_devices, committee_keys=keys)
        started = time.monotonic()
        try:
            verifier.warmup(every_shape=True)
        except Exception as exc:  # noqa: BLE001 - the other legs still run
            report = verifier.device_report()
            legs.append({
                "devices": n_devices,
                "path": report["path"],
                "error": f"{type(exc).__name__}: {exc}"[-4000:],
            })
            continue
        warm_s = time.monotonic() - started
        report = verifier.device_report()
        started = time.monotonic()
        got = [bool(b) for b in verifier.verify_signatures(pks, digests, sigs)]
        batch_s = time.monotonic() - started
        want = CpuSignatureVerifier().verify_signatures(pks, digests, sigs)
        legs.append({
            "devices": n_devices,
            "path": report["path"],
            "warm_seconds": round(warm_s, 3),
            "kernels": report["kernels"],
            "batch": {
                "signatures": BATCH,
                "committee_keys": len(keys),
                "accepted": sum(got),
                "mismatches": sum(g != w for g, w in zip(got, want)),
                "first_call_seconds": round(batch_s, 3),
            },
        })
    parity = run_parity(PARITY_CASES, args.seed, n_keys=NODES)
    out = {
        "device": device,
        "jax": report["jax"],
        "jaxlib": report["jaxlib"],
        "libtpu": report["libtpu"],
        "compilation_cache_dir": report["compilation_cache_dir"],
        "legs": legs,
        "parity": parity,
        "compile_stats": dict(E.COMPILE_STATS),
        "dispatches": E.dispatch_counts(),
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


# ---------------------------------------------------------------------------
# Fleet leg (parent: JAX-free)


def _series(text: str, name: str) -> list:
    """[(labels, value)] of one counter, with or without ``_total``."""
    from mysticeti_tpu.orchestrator.measurement import iter_series

    names = {name, name + "_total", name.removesuffix("_total")}
    return [(lb, v) for n, lb, v in iter_series(text) if n in names]


def _maps_jax(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
    except OSError:
        return False
    return "jaxlib" in maps or "libtpu" in maps


async def fleet_leg(args, workdir: str, service_devices) -> dict:
    from mysticeti_tpu.orchestrator.measurement import Measurement
    from mysticeti_tpu.orchestrator.runner import LocalProcessRunner
    from tools.wal_inspect import committed_leaders

    runner = LocalProcessRunner(
        workdir,
        transaction_size=TRANSACTION_SIZE,
        verifier="tpu-only",
        service_devices=service_devices,
    )
    out: dict = {
        "nodes": NODES,
        "offered_tx_s": OFFERED_TX_S,
        "transaction_size": TRANSACTION_SIZE,
        "duration_s": DURATION_S,
        "verifier": "tpu-only",
        "service_devices": service_devices,
    }
    texts: dict = {}
    try:
        log(f"fleet: genesis + verifier service in {workdir}")
        await runner.configure(NODES, load_tx_s=OFFERED_TX_S)
        out["service_warm_seconds"] = runner.service_warm_seconds
        out["hello_ok_backend"] = runner.service_backend
        log(
            f"fleet: service warm in {runner.service_warm_seconds}s, "
            f"HELLO_OK backend {runner.service_backend!r}; booting "
            f"{NODES} validators"
        )
        for authority in range(NODES):
            await runner.boot_node(authority)
        started = time.monotonic()
        # INITIAL_DELAY (1 s) + boot come before the first offered
        # transaction; the window is counted from the last boot.
        while time.monotonic() - started < DURATION_S + 5.0:
            await asyncio.sleep(5.0)
            if runner.unexpected_exits():
                break
        for authority in range(NODES):
            texts[authority] = await runner.scrape(authority)
        out["unexpected_exits"] = runner.unexpected_exits()
        # Which live processes have the JAX runtime mapped: the service
        # must, no validator may (it would be a second claim on the chip).
        out["jax_mapped"] = {
            name: _maps_jax(pid) for name, pid in runner.live_pids().items()
        }
    finally:
        await runner.cleanup()
    out["exit_codes"] = dict(runner.exit_codes)
    out["service"] = runner.service_report

    per_node = []
    for authority in range(NODES):
        text = texts.get(authority)
        row: dict = {"authority": authority, "scraped": text is not None}
        if text is not None:
            m = Measurement.from_prometheus(text)
            row["committed_transactions"] = m.count
            row["committed_tx_s"] = round(m.tps(), 1)
            row["committed_leaders"] = int(sum(
                v for lb, v in _series(text, "committed_leaders_total")
                if lb.get("status") == "committed"
            ))
            by_backend: dict = {}
            rejected = 0
            for lb, v in _series(text, "verified_signatures_total"):
                by_backend[lb.get("backend")] = (
                    by_backend.get(lb.get("backend"), 0) + int(v)
                )
                if lb.get("outcome") == "rejected":
                    rejected += int(v)
            row["verified_signatures"] = by_backend
            row["rejected_signatures"] = rejected
            row["verifier_fallbacks"] = int(sum(
                v for _, v in _series(text, "verifier_fallback_total")
            ))
            row["verifier_reconnects"] = int(sum(
                v for _, v in _series(text, "verifier_reconnect_total")
            ))
        wal = os.path.join(workdir, f"validator-{authority}", "wal")
        row["wal_leaders"] = committed_leaders(wal) if os.path.exists(wal) else {}
        per_node.append(row)

    # Agreement: at every commit height two nodes both recorded, the leader
    # is the same block.
    reference: dict = {}
    disagreements = []
    for row in per_node:
        for height, leader in row["wal_leaders"].items():
            if reference.setdefault(height, leader) != leader:
                disagreements.append(
                    (height, row["authority"], leader, reference[height])
                )
    shared = [
        h for h in reference
        if all(h in row["wal_leaders"] for row in per_node)
    ]
    out["agreement"] = {
        "heights_any_node": len(reference),
        "heights_every_node": len(shared),
        "disagreements": disagreements[:5],
        "n_disagreements": len(disagreements),
    }
    for row in per_node:
        row["wal_committed_heights"] = len(row.pop("wal_leaders"))
    out["per_node"] = per_node
    return out


def _kernels(on_mesh: bool) -> tuple:
    """What ``TpuSignatureVerifier`` can dispatch with a committee table on
    a TPU (``_kernel_probes``)."""
    if on_mesh:
        return ("mesh-fused", "mesh-indexed", "packed")
    return ("blob", "indexed", "keyed", "packed")


def _check_shards(report: dict, fail) -> None:
    devices = list(range(report.get("device_count") or 0))
    for k in report.get("kernels", []):
        if k["kernel"].startswith("mesh-") and k["shard_devices"] != devices:
            fail(f"{k['kernel']}@{k['bucket']} held shards on "
                 f"{k['shard_devices']}, not every device")


def check_fleet(fleet: dict, failures: list, tag: str, verifier: dict) -> None:
    def fail(msg: str) -> None:
        failures.append(f"{tag}: {msg}")

    service = fleet.get("service") or {}
    if service.get("platform") != "tpu":
        fail(f"service platform is {service.get('platform')!r}, not 'tpu'")
    if fleet.get("hello_ok_backend") != "tpu":
        fail(f"HELLO_OK advertised {fleet.get('hello_ok_backend')!r}")
    for k in service.get("kernels", []):
        if k["backend"] != "pallas" or k["interpret"] is not False:
            fail(f"service kernel {k['kernel']}@{k['bucket']} ran as "
                 f"{k['backend']} interpret={k['interpret']}")
        if k["cache"] == "miss":
            fail(f"service kernel {k['kernel']}@{k['bucket']} was compiled, "
                 f"not loaded from the compilation cache ({k['seconds']}s)")
        if not k["kernel"].startswith("mesh-") and k["program"] != "loaded":
            fail(f"service kernel {k['kernel']}@{k['bucket']} was "
                 f"{k['program']}, not loaded from the program store "
                 f"({k['seconds']}s)")
    # Before HELLO_OK the service warms what a validator's 256-signature
    # window can reach; the verifier leg is where every bucket compiles.
    warmed = {(k["kernel"], k["bucket"]) for k in service.get("kernels", [])}
    on_mesh = fleet["service_devices"] != 1 and service.get("device_count") != 1
    want = {(k, BUCKETS[0]) for k in _kernels(on_mesh) if k != "packed"}
    if not want <= warmed:
        fail(f"service did not warm {sorted(want - warmed)}")
    _check_shards(service, fail)
    served = [d for d in service.get("dispatches", []) if d["count"]]
    if any(d["backend"] != "pallas" for d in served):
        fail(f"non-Pallas dispatches in the service: {served}")
    warm_calls = len(service.get("kernels", []))
    if sum(d["count"] for d in served) <= warm_calls:
        fail("the service dispatched nothing beyond its own warm-up")
    if fleet.get("unexpected_exits"):
        fail(f"processes died during the run: {fleet['unexpected_exits']}")
    mapped = fleet.get("jax_mapped", {})
    if [n for n, has in mapped.items() if has] != ["verifier-service"]:
        fail(f"JAX must be mapped in the service and nowhere else: {mapped}")
    for name, code in fleet.get("exit_codes", {}).items():
        # 0: stopped in order on SIGTERM; -15: SIGTERM before its handler.
        if code not in (0, -15):
            fail(f"{name} ended with exit code {code}")
    if len(fleet.get("exit_codes", {})) != NODES + 1:
        fail(f"expected {NODES} nodes + 1 service to stop, saw "
             f"{sorted(fleet.get('exit_codes', {}))}")
    for row in fleet["per_node"]:
        a = row["authority"]
        if not row["scraped"]:
            fail(f"node {a} did not answer its final scrape")
            continue
        if row["committed_leaders"] <= 0 or row["wal_committed_heights"] <= 0:
            fail(f"node {a} committed no leaders")
        if row["committed_transactions"] <= 0:
            fail(f"node {a} committed no transactions")
        sigs = row["verified_signatures"]
        if sigs.get("tpu-remote", 0) <= 0:
            fail(f"node {a} verified nothing on tpu-remote: {sigs}")
        if any(n for backend, n in sigs.items() if backend != "tpu-remote"):
            fail(f"node {a} verified signatures off the chip path: {sigs}")
        if row["rejected_signatures"]:
            fail(f"node {a}: {row['rejected_signatures']} honest block "
                 "signatures rejected")
        if row["verifier_fallbacks"]:
            fail(f"node {a} fell back to the host "
                 f"{row['verifier_fallbacks']} times")
    agreement = fleet["agreement"]
    if agreement["n_disagreements"]:
        fail(f"committed leader sequences disagree: "
             f"{agreement['disagreements']}")
    if agreement["heights_every_node"] <= 0:
        fail("no commit height is shared by every node")
    if verifier["device"] != {
        "platform": service.get("platform"),
        "kind": service.get("device_kind"),
        "count": service.get("device_count"),
    }:
        fail(f"service saw device {service.get('platform')}/"
             f"{service.get('device_kind')}x{service.get('device_count')}, "
             f"verifier leg saw {verifier['device']}")


def check_verifier(verifier: dict, failures: list) -> None:
    for leg in verifier["legs"]:
        tag = f"verifier leg ({leg['path']})"
        if "error" in leg:
            failures.append(f"{tag}: warm-up failed: {leg['error']}")
            continue
        for k in leg["kernels"]:
            if k["backend"] != "pallas" or k["interpret"] is not False:
                failures.append(
                    f"{tag}: kernel {k['kernel']}@{k['bucket']} ran as "
                    f"{k['backend']} interpret={k['interpret']}"
                )
        want = {(k, b) for k in _kernels(leg["devices"] > 1) for b in BUCKETS}
        warmed = {(k["kernel"], k["bucket"]) for k in leg["kernels"]}
        if not want <= warmed:
            failures.append(f"{tag}: did not compile {sorted(want - warmed)}")
        _check_shards(
            {"device_count": leg["devices"], "kernels": leg["kernels"]},
            lambda msg, tag=tag: failures.append(f"{tag}: {msg}"),
        )
        b = leg["batch"]
        if b["mismatches"] or not 0 < b["accepted"] < b["signatures"]:
            failures.append(f"{tag}: batch disagrees with the oracle: {b}")
    if any(d["backend"] != "pallas" for d in verifier["dispatches"]):
        failures.append(f"verifier leg: non-Pallas dispatches: "
                        f"{verifier['dispatches']}")
    if not verifier["parity"]["pass"]:
        failures.append(f"parity leg failed: {verifier['parity']}")


def _keep_logs(workdir: str, out_dir: str, tail: int = 65536) -> None:
    """The end of every fleet process's log, where chiprun carries it back."""
    for root, _dirs, files in os.walk(workdir):
        for name in files:
            if not name.endswith(".log"):
                continue
            dest = os.path.join(
                out_dir, "smoke_logs", os.path.relpath(root, workdir)
            )
            os.makedirs(dest, exist_ok=True)
            with open(os.path.join(root, name), "rb") as src:
                src.seek(max(0, os.path.getsize(src.name) - tail))
                with open(os.path.join(dest, name), "wb") as dst:
                    dst.write(src.read())


def run(args) -> int:
    try:
        import mysticeti_tpu  # noqa: F401
        from mysticeti_tpu import native
    except ImportError as exc:
        print(f"chip_smoke: the repository is not next to this script "
              f"({exc})", file=sys.stderr)
        return 2
    assert "jax" not in sys.modules, "the smoke's parent must stay JAX-free"

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    # Ten WALs of a minute's blocks are hundreds of MB: kept out of
    # chiprun_out/ (which is carried back) and removed when the run passes.
    workdir = os.path.abspath(args.workdir)
    if len(workdir) > 80:  # AF_UNIX paths hold ~107 bytes
        workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    failures: list = []
    report: dict = {"seed": args.seed, "argv": sys.argv[1:]}

    # Built once here, before a fleet of processes would race to run g++.
    report["native_functions"] = list(native.active_functions())
    log(f"native extension: {len(report['native_functions'])} functions "
        f"active {report['native_functions']}")
    if not report["native_functions"]:
        failures.append("the native extension is not active on this machine")

    log("verifier leg: warming every kernel at every bucket "
        "(cold compiles take minutes)")
    leg_out = os.path.join(workdir, "verifier-leg.json")
    if os.path.exists(leg_out):
        os.unlink(leg_out)
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--leg", "verifier",
         "--seed", str(args.seed), "--out", leg_out],
        timeout=args.leg_timeout,
    )
    if child.returncode != 0:
        print(f"chip_smoke: verifier leg exited with code "
              f"{child.returncode}", file=sys.stderr)
        return child.returncode
    with open(leg_out) as f:
        verifier = report["verifier"] = json.load(f)
    device = verifier["device"]
    log(f"device inside the child: {device}; jax {verifier['jax']}, jaxlib "
        f"{verifier['jaxlib']}, libtpu {verifier['libtpu']}; cache at "
        f"{verifier['compilation_cache_dir']}")
    for leg in verifier["legs"]:
        if "error" in leg:
            log(f"  {leg['path']}: warm-up FAILED: {leg['error'][-1500:]}")
            continue
        log(f"  {leg['path']}: warm in {leg['warm_seconds']}s")
        for k in leg["kernels"]:
            log(f"    {k['kernel']:>12}@{k['bucket']:<5} {k['backend']} "
                f"interpret={k['interpret']} tile={k['tile']} "
                f"{k['seconds']:8.2f}s cache={k['cache']} "
                f"program={k['program']}")
        log(f"    batch: {leg['batch']}")
    log(f"  parity: pass={verifier['parity']['pass']} "
        f"rfc8032={verifier['parity']['rfc8032']} "
        + str({k: (v['cases'], v['mismatches'])
               for k, v in verifier['parity']['randomized'].items()}))
    check_verifier(verifier, failures)

    legs = [("fleet, one chip", 1 if device["count"] > 1 else None)]
    if device["count"] >= 4:
        legs.append((f"fleet, {device['count']} chips", None))
    else:
        report["four_chip_leg"] = (
            f"not run: the service sees {device['count']} device(s)"
        )
    for i, (tag, service_devices) in enumerate(legs):
        try:
            fleet = asyncio.run(fleet_leg(
                args, os.path.join(workdir, f"fleet-{i}"), service_devices
            ))
        except Exception as exc:  # noqa: BLE001 - reported, run fails
            failures.append(f"{tag}: {type(exc).__name__}: {exc}")
            log(f"{tag}: FAILED to run: {exc!r}")
            continue
        report[tag] = fleet
        service = fleet.get("service") or {}
        log(f"{tag}: service saw {service.get('device_count')} x "
            f"{service.get('device_kind')} ({service.get('platform')}), "
            f"path: {service.get('path')}; warm in "
            f"{fleet.get('service_warm_seconds')}s")
        for k in service.get("kernels", []):
            log(f"    {k['kernel']:>12}@{k['bucket']:<5} {k['backend']} "
                f"{k['seconds']:8.2f}s cache={k['cache']} "
                f"program={k['program']}"
                + (f" shards on {k['shard_devices']}"
                   if "shard_devices" in k else ""))
        log(f"    warm-up by part: {service.get('warm_parts')}; "
            f"{service.get('compile_stats')}")
        log(f"  service dispatches: {service.get('dispatches')}")
        for row in fleet["per_node"]:
            log(f"  node {row}")
        log(f"  agreement: {fleet['agreement']}; exits {fleet['exit_codes']}")
        log(f"  JAX mapped in: "
            f"{[n for n, has in fleet.get('jax_mapped', {}).items() if has]}")
        check_fleet(fleet, failures, tag, verifier)
    if len(legs) > 1:
        report["four_chip_leg"] = "run"
    log(f"four-chip leg: {report['four_chip_leg']}")

    report["failures"] = failures
    with open(os.path.join(out_dir, "chip_smoke_report.json"), "w") as f:
        json.dump(report, f, indent=1)
    if failures:
        _keep_logs(workdir, out_dir)
        for failure in failures:
            print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        return EXIT_FAILED
    shutil.rmtree(workdir, ignore_errors=True)
    log(f"all checks held in {time.monotonic() - _T0:.0f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2026)
    parser.add_argument("--workdir", default=os.path.join(ROOT, ".chip_smoke"))
    parser.add_argument("--leg-timeout", type=float, default=900.0)
    parser.add_argument("--leg", choices=["verifier"], help=argparse.SUPPRESS)
    parser.add_argument("--out", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.leg == "verifier":
        return verifier_leg(args)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
