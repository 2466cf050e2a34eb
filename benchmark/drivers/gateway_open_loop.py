"""Driver: a validator fleet behind its gateways, open loop.

One verifier service on the chip, ``validators`` processes of ``python -m
mysticeti_tpu run --verifier tpu-only`` over localhost sockets, and this
process as the one client: a gateway connection and a commit subscription
to every node, transactions submitted on a fixed schedule (every ``tick_s``,
split evenly over the gateways) and each timed from when it was DUE to the
commit notification from the node it was submitted to.

Traffic file: ``rate_tx_s``, ``tick_s``, ``warmup_s``, ``drain_s``,
``grace_s``, ``trace`` {``seconds``}.  Configuration file:
``validators``, ``transaction_bytes``, ``parameters`` (merged over the
program's genesis), ``node_env``, ``probe``.

This is the benchmark's own copy of what it needs from
``orchestrator/runner.py`` and ``chip_smoke.py`` (genesis, spawn, scrape,
stop, the checks), so that a later change to either cannot move the
yardstick.
"""
from __future__ import annotations

import asyncio
import hashlib
import os
import random
import statistics
import struct
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import yaml

from benchmark import harness
from benchmark.harness import BenchError, log
from benchmark.reference import ed25519_oracle as oracle

# Gateway wire (docs/wire-format.md 5b): u32 length | u8 tag | fields.
TAG_SUBMIT, TAG_REPLY, TAG_SUBSCRIBE, TAG_COMMITS = 13, 14, 15, 16
STATUS_SHED = 2
_U32 = struct.Struct("<I")


# -- the fleet ---------------------------------------------------------------


def merge(into: dict, over: dict) -> dict:
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            merge(into[key], value)
        else:
            into[key] = value
    return into


class Fleet:
    """Genesis, boot, scrape and stop of the validators of one run."""

    def __init__(self, run: harness.Run) -> None:
        self.run = run
        self.config = run.cell["config"]
        self.n = int(self.config["validators"])
        self.dir = os.path.join(run.workdir, "fleet")
        self.parameters: dict = {}

    def genesis(self) -> List[bytes]:
        """The program's own benchmark genesis, with the configuration's
        stated parameters written over it by value; the committee's keys."""
        from mysticeti_tpu.cli import benchmark_genesis
        from mysticeti_tpu.committee import Committee

        benchmark_genesis(["127.0.0.1"] * self.n, self.dir)
        path = os.path.join(self.dir, "parameters.yaml")
        with open(path) as f:
            self.parameters = yaml.safe_load(f)
        merge(self.parameters, self.config["parameters"])
        with open(path, "w") as f:
            yaml.safe_dump(self.parameters, f, sort_keys=False)
        committee = Committee.load(os.path.join(self.dir, "committee.yaml"))
        return committee.public_key_bytes()

    def signing_keys(self) -> List[tuple]:
        """The committee's key pairs, from the seeds genesis wrote."""
        keys = []
        for i in range(self.n):
            with open(os.path.join(self.dir, f"validator-{i}", "seed"),
                      "rb") as f:
                keys.append(oracle.key_from_seed(f.read()))
        return keys

    def ports(self, kind: str) -> List[int]:
        if kind == "gateway":
            base = self.parameters["ingress"]["gateway_port_base"]
            return [base + i for i in range(self.n)]
        field = {"mesh": "port", "metrics": "metrics_port"}[kind]
        return [ident[field] for ident in self.parameters["identifiers"]]

    def assert_ports_free(self) -> None:
        import socket

        busy = []
        for port in (self.ports("mesh") + self.ports("metrics")
                     + self.ports("gateway")
                     + [self.config["service"]["metrics_port"]]):
            with socket.socket() as s:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                try:
                    s.bind(("127.0.0.1", port))
                except OSError:
                    busy.append(port)
        if busy:
            raise BenchError(f"ports already in use: {sorted(busy)}")

    def spawn_node(self, i: int, name: Optional[str] = None) -> None:
        env = dict(os.environ)
        env.update({k: str(v) for k, v in self.config["node_env"].items()})
        env["TRANSACTION_SIZE"] = str(self.config["transaction_bytes"])
        env["MYSTICETI_VERIFIER_SOCKET"] = self.run.socket
        self.run.spawn(
            name or f"node-{i}",
            [sys.executable, "-m", "mysticeti_tpu", "run",
             "--authority", str(i),
             "--committee-path", os.path.join(self.dir, "committee.yaml"),
             "--parameters-path", os.path.join(self.dir, "parameters.yaml"),
             "--private-config-path",
             os.path.join(self.dir, f"validator-{i}"),
             "--verifier", self.config["verifier"]],
            env=env,
        )

    def boot(self) -> None:
        for i in range(self.n):
            self.spawn_node(i)

    def scrape(self) -> List[Optional[list]]:
        """Every node's /metrics, fetched side by side, parsed after."""
        with ThreadPoolExecutor(self.n) as pool:
            texts = list(pool.map(harness.http_get, self.ports("metrics")))
        return [harness.parse_metrics(t) if t is not None else None
                for t in texts]

    def stop(self) -> None:
        names = [f"node-{i}" for i in range(self.n)]
        procs = [self.run.children.pop(n) for n in names
                 if n in self.run.children]
        with ThreadPoolExecutor(max(1, len(procs))) as pool:
            codes = list(pool.map(harness.stop_process, procs))
        self.run.exit_codes.update(zip(names, codes))

    def wal_leaders(self, authority: int) -> Dict[int, str]:
        """{commit height: leader} from the commit entries of one node's
        WAL, read with the program's own WAL reader."""
        import json

        from mysticeti_tpu.block_store import WAL_ENTRY_COMMIT, CommitData
        from mysticeti_tpu.serde import Reader
        from mysticeti_tpu.storage import MANIFEST_NAME
        from mysticeti_tpu.wal import WalReader

        path = os.path.join(self.dir, f"validator-{authority}", "wal")
        if os.path.isdir(path):
            with open(os.path.join(path, MANIFEST_NAME)) as f:
                files = [os.path.join(path, s["name"])
                         for s in json.load(f).get("segments", [])]
        elif os.path.exists(path):
            files = [path]
        else:
            return {}
        leaders: Dict[int, str] = {}
        for file in files:
            reader = WalReader(file)
            try:
                for _pos, tag, payload in reader.iter_until():
                    if tag != WAL_ENTRY_COMMIT:
                        continue
                    r = Reader(payload)
                    for _ in range(r.u32()):
                        commit = CommitData.decode(r)
                        leaders[commit.height] = repr(commit.leader)
            finally:
                reader.close()
        return leaders


# -- the client --------------------------------------------------------------


class Connection:
    """One gateway connection: submissions out, replies and commit
    notifications in."""

    def __init__(self, index: int, reader, writer) -> None:
        self.index = index
        self.reader = reader
        self.writer = writer
        self.awaiting_reply: list = []  # (tick, n) in the order sent
        self.replied = 0
        self.accepted: Dict[int, int] = {}  # tick -> admitted prefix
        self.shed: Dict[int, int] = {}
        self.pending: Dict[bytes, tuple] = {}  # key -> (tick, position)
        self.notified: List[tuple] = []  # (tick, received at)
        self.task: Optional[asyncio.Task] = None
        self.error: Optional[str] = None

    async def read_loop(self) -> None:
        reader = self.reader
        try:
            while True:
                header = await reader.readexactly(4)
                payload = await reader.readexactly(_U32.unpack(header)[0])
                now = time.monotonic()
                tag = payload[0]
                if tag == TAG_REPLY:
                    status = payload[1]
                    accepted, shed = struct.unpack_from("<II", payload, 2)
                    tick, n = self.awaiting_reply[self.replied]
                    self.replied += 1
                    self.accepted[tick] = accepted
                    if status == STATUS_SHED or shed:
                        self.shed[tick] = shed
                elif tag == TAG_COMMITS:
                    (count,) = _U32.unpack_from(payload, 9)
                    pending, notified = self.pending, self.notified
                    # count * (u32 16 | 16-byte key)
                    for at in range(17, 17 + 20 * count, 20):
                        entry = pending.pop(payload[at:at + 16], None)
                        if entry is not None:
                            notified.append((entry[0], now))
                else:
                    self.error = f"gateway {self.index} sent tag {tag}"
                    return
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as exc:
            self.error = f"gateway {self.index} closed: {exc!r}"


class OpenLoopClient:
    """Submits on a fixed schedule whatever the system does; every tick is
    recorded with when it was due and when it went out."""

    def __init__(self, ports: List[int], seed: int, transaction_bytes: int,
                 tick_s: float) -> None:
        self.ports = ports
        self.tick_s = tick_s
        self.size = transaction_bytes
        rng = random.Random(seed)
        self.filler = rng.randbytes(transaction_bytes - 16)
        self.nonce = rng.getrandbits(63)
        self.rate_tx_s = 0.0
        self.stop = False
        self.connections: List[Connection] = []
        self.ticks: List[dict] = []  # {"due", "per_node", "sent"}
        self._record_len = _U32.pack(transaction_bytes)

    async def connect(self, timeout_s: float = 90.0) -> None:
        deadline = time.monotonic() + timeout_s
        for index, port in enumerate(self.ports):
            while True:
                try:
                    reader, writer = await asyncio.open_connection(
                        "127.0.0.1", port)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise BenchError(
                            f"gateway {index} (port {port}) never listened")
                    await asyncio.sleep(0.2)
            conn = Connection(index, reader, writer)
            # SUBSCRIBE from height 0: u8 15 | u64 0
            body = bytes([TAG_SUBSCRIBE]) + struct.pack("<Q", 0)
            writer.write(_U32.pack(len(body)) + body)
            conn.task = asyncio.ensure_future(conn.read_loop())
            self.connections.append(conn)

    def _submit(self, conn: Connection, tick: int, n: int, stamp: bytes,
                ) -> None:
        parts = [b"", bytes([TAG_SUBMIT]), _U32.pack(0), b"\x00",
                 _U32.pack(n)]
        blake, filler, pending = hashlib.blake2b, self.filler, conn.pending
        record_len = self._record_len
        for position in range(n):
            self.nonce += 1
            tx = stamp + struct.pack("<Q", self.nonce) + filler
            pending[blake(tx, digest_size=16).digest()] = (tick, position)
            parts.append(record_len)
            parts.append(tx)
        parts[0] = _U32.pack(sum(len(p) for p in parts))
        conn.awaiting_reply.append((tick, n))
        conn.writer.write(b"".join(parts))

    async def run_schedule(self, first_due: float) -> None:
        """A tick every ``tick_s`` from ``first_due`` until ``stop`` is
        set, each splitting ``self.rate_tx_s`` evenly over the gateways,
        whatever the system does with them."""
        wall_offset = time.time() - time.monotonic()
        tick = 0
        while not self.stop:
            due = first_due + tick * self.tick_s
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            per_node = max(1, round(
                self.rate_tx_s * self.tick_s / len(self.connections)))
            stamp = struct.pack("<d", due + wall_offset)
            sent = []
            for conn in self.connections:
                if conn.error:
                    raise BenchError(conn.error)
                sent.append(time.monotonic())
                self._submit(conn, tick, per_node, stamp)
            self.ticks.append({"due": due, "per_node": per_node,
                               "sent": sent})
            tick += 1
            # Let the readers run even when the schedule is behind.
            await asyncio.sleep(0)

    def ticks_due(self, start: float, end: float) -> range:
        """The ticks due in [start, end), as indices into ``ticks``."""
        first_due = self.ticks[0]["due"]
        return range(max(0, round((start - first_due) / self.tick_s)),
                     max(0, round((end - first_due) / self.tick_s)))

    async def wait_notified(self, ticks: range, deadline: float) -> None:
        """Until every admitted transaction of ``ticks`` is notified, or
        ``deadline``."""
        while time.monotonic() < deadline:
            if not any(
                tick in ticks and position < conn.accepted.get(tick, 0)
                for conn in self.connections
                for tick, position in conn.pending.values()
            ):
                return
            await asyncio.sleep(0.1)

    async def close(self) -> None:
        for conn in self.connections:
            if conn.task is not None:
                conn.task.cancel()
            conn.writer.close()
        await asyncio.gather(*(c.task for c in self.connections
                               if c.task is not None),
                             return_exceptions=True)

    def outcome(self, ticks: range, notify_by: float) -> dict:
        """What became of the transactions of ``ticks``: finality from due
        to notification for those notified by ``notify_by``."""
        submitted = sum(self.ticks[t]["per_node"] for t in ticks) * len(
            self.connections)
        latencies, late_notified = [], 0
        for conn in self.connections:
            for tick, received in conn.notified:
                if tick in ticks:
                    if received <= notify_by:
                        latencies.append(received - self.ticks[tick]["due"])
                    else:
                        late_notified += 1
        shed = sum(n for conn in self.connections
                   for tick, n in conn.shed.items() if tick in ticks)
        unanswered = sum(
            n for conn in self.connections
            for tick, n in conn.awaiting_reply[conn.replied:] if tick in ticks)
        lost = sum(
            1 for conn in self.connections
            for tick, position in conn.pending.values()
            if tick in ticks and position < conn.accepted.get(tick, 0))
        late_s = [sent - self.ticks[t]["due"]
                  for t in ticks for sent in self.ticks[t]["sent"]]
        # Finality by the tick it was due in: a backlog that grows shows as
        # latency rising through the window.
        by_tick: Dict[int, list] = {}
        for conn in self.connections:
            for tick, received in conn.notified:
                if tick in ticks and received <= notify_by:
                    by_tick.setdefault(tick, []).append(
                        received - self.ticks[tick]["due"])
        per_second = max(1, round(1.0 / self.tick_s))
        by_second = [
            [x for t in range(at, min(at + per_second, ticks.stop))
             for x in by_tick.get(t, [])]
            for at in range(ticks.start, ticks.stop, per_second)]
        half = ticks.start + len(ticks) // 2
        halves = [[x for t, v in by_tick.items() if (t < half) == first
                   for x in v] for first in (True, False)]
        return {"submitted": submitted, "latencies": latencies,
                "latency_avg_by_second_s": [
                    round(statistics.fmean(v), 3) if v else None
                    for v in by_second],
                "latency_avg_halves_s": [
                    statistics.fmean(h) if h else None for h in halves],
                "late_notified": late_notified, "shed": shed,
                "unanswered": unanswered, "lost_after_ack": lost,
                "late_s": late_s}


# -- the probe ----------------------------------------------------------------


def make_probe(rng: random.Random, keys: List[tuple], spec: dict) -> List[dict]:
    """The probe's requests: seeded signatures by the committee's own keys,
    ``corrupted_share`` of them (drawn over the whole probe, so a request
    may be all valid, all corrupted or mixed) with one flipped bit, each
    request with the oracle's verdicts.  ``requests`` lists the shapes:
    ``count`` requests whose size cycles through ``signatures``, by ``one``
    signer (what a single block costs a validator: the keyed-tile kernel on
    the chip), by ``distinct`` signers (blocks of several authors in one
    collector window: the generic ladder) or by ``any``."""
    shapes = []
    for group in spec["requests"]:
        for i in range(int(group["count"])):
            n = int(group["signatures"][i % len(group["signatures"])])
            if group["signers"] == "one":
                lanes = [rng.randrange(len(keys))] * n
            elif group["signers"] == "distinct":
                lanes = rng.sample(range(len(keys)), n)
            else:
                lanes = [rng.randrange(len(keys)) for _ in range(n)]
            shapes.append(lanes)
    rng.shuffle(shapes)
    total = sum(len(lanes) for lanes in shapes)
    corrupt = set(rng.sample(range(total),
                             int(total * spec["corrupted_share"])))
    requests, at = [], 0
    for lanes in shapes:
        requests.append(oracle.signed_request(
            rng, keys, lanes,
            [i for i in range(len(lanes)) if at + i in corrupt]))
        at += len(lanes)
    return requests


def run_probe(run: harness.Run, requests: List[dict], in_flight: int,
              when: str) -> None:
    """Every probe request through the service socket, ``in_flight`` at a
    time, every bit against the oracle.  A verifier that checks less, at
    any request size or in either kernel, fails the cell here."""
    client = run.service_client()
    pending: list = []
    mismatches = accepted = answered = 0
    for request in requests + [None] * in_flight:
        if request is not None:
            pending.append((request, client.verify_signatures_async(
                request["public_keys"], request["digests"],
                request["signatures"])))
        if pending and (request is None or len(pending) >= in_flight):
            sent, handle = pending.pop(0)
            got = [bool(bit) for bit in handle.result()]
            answered += len(got)
            accepted += sum(got)
            mismatches += sum(g != w for g, w in zip(got, sent["expected"]))
            mismatches += abs(len(got) - len(sent["expected"]))
    del client  # its connection closes with it
    n = sum(len(r["expected"]) for r in requests)
    valid = sum(sum(r["expected"]) for r in requests)
    run.check(f"probe {when}: bits differing from the oracle", mismatches, 0,
              answered == n and mismatches == 0)
    run.check(f"probe {when}: signatures accepted", accepted,
              f"{valid} of {n}", accepted == valid and 0 < accepted < n)


def kernels_used(later: dict, earlier: dict) -> set:
    """The (kernel, lanes) pairs the service launched between two
    snapshots."""
    before = {(d["kernel"], d["bucket"]): d["count"]
              for d in earlier["dispatches"]}
    return {(d["kernel"], d["bucket"]) for d in later["dispatches"]
            if d["count"] > before.get((d["kernel"], d["bucket"]), 0)}


# -- the run -------------------------------------------------------------------


def check_fleet(run: harness.Run, fleet: Fleet, final: list,
                mapped: Dict[str, bool]) -> None:
    """``chip_smoke.py``'s fleet checks, on this run."""
    run.check_service(mapped)
    bad_codes = {n: c for n, c in run.exit_codes.items() if c not in (0, -15)}
    run.check("exit codes other than 0 / SIGTERM", bad_codes, {},
              not bad_codes and len(run.exit_codes) == fleet.n + 1)
    off_chip, rejected, fallbacks, idle = {}, 0, 0, []
    for i, series in enumerate(final):
        if series is None:
            idle.append(i)
            continue
        by_backend: Dict[str, float] = {}
        for name, labels, value in series:
            if name.removesuffix("_total") == "verified_signatures":
                by_backend[labels.get("backend")] = (
                    by_backend.get(labels.get("backend"), 0) + value)
                if labels.get("outcome") == "rejected":
                    rejected += int(value)
        fallbacks += int(harness.series_sum(series, "verifier_fallback_total"))
        if by_backend.get("tpu-remote", 0) <= 0:
            idle.append(i)
        for backend, count in by_backend.items():
            if backend != "tpu-remote" and count:
                off_chip[f"node-{i}/{backend}"] = count
    run.check("nodes that verified nothing on tpu-remote", idle, [], not idle)
    run.check("signatures verified off the chip path", off_chip, {},
              not off_chip)
    run.check("honest block signatures rejected", rejected, 0, rejected == 0)
    run.check("fallbacks to the host", fallbacks, 0, fallbacks == 0)

    wal = [fleet.wal_leaders(i) for i in range(fleet.n)]
    reference: Dict[int, str] = {}
    disagreements = 0
    for leaders in wal:
        for height, leader in leaders.items():
            if reference.setdefault(height, leader) != leader:
                disagreements += 1
    shared = sum(1 for h in reference if all(h in w for w in wal))
    run.check("commit heights where two WALs name different leaders",
              disagreements, 0, disagreements == 0)
    run.check("commit heights every WAL holds", shared, ">= 1", shared >= 1)

async def sleep_until(when: float) -> None:
    await asyncio.sleep(max(0.0, when - time.monotonic()))


async def _drive(run: harness.Run, fleet: Fleet, probe: List[dict]) -> dict:
    traffic = run.cell["traffic"]
    spec = run.cell["config"]["probe"]
    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(4)
    tick_s = float(traffic["tick_s"])
    client = OpenLoopClient(fleet.ports("gateway"), run.seed,
                            int(run.cell["config"]["transaction_bytes"]),
                            tick_s)
    await client.connect()
    client.rate_tx_s = float(traffic["rate_tx_s"])
    log(f"client connected to {len(client.connections)} gateways; "
        f"{client.rate_tx_s} tx/s, warm-up {traffic['warmup_s']}s")
    begin = time.monotonic() + tick_s
    schedule = asyncio.ensure_future(client.run_schedule(begin))
    try:
        # The window opens on a tick of the schedule; what is read at its
        # edges is read beside the schedule, not inside it.
        start = begin + round(float(traffic["warmup_s"]) / tick_s) * tick_s
        end = start + round(run.seconds / tick_s) * tick_s
        run.mark_window(start)
        # Only a traced run scrapes the nodes at the edges: what only its
        # metrics read is not paid for by the end-to-end numbers.
        scrape = fleet.scrape if run.trace else (lambda: None)
        # The probe again, now beside the fleet's own requests.
        await sleep_until(begin + float(spec["load_after_s"]))
        await loop.run_in_executor(pool, run_probe, run, probe,
                                   int(spec["in_flight"]), "under load")
        spare = start - time.monotonic()
        run.check("probe under load ended before the window (s to spare)",
                  round(spare, 3), "> 0", spare > 0)
        await sleep_until(start)
        edge = [loop.run_in_executor(pool, run.snapshot, "window_start"),
                loop.run_in_executor(pool, scrape)]
        await sleep_until(end)
        closing = [loop.run_in_executor(pool, run.snapshot, "window_end"),
                   loop.run_in_executor(pool, scrape)]
        # The load goes on through the drain and the grace, so that what
        # was due in the window finishes in the system the window measured.
        notify_by = end + float(traffic["drain_s"])
        await sleep_until(notify_by)
        window = client.ticks_due(start, end)
        await client.wait_notified(window,
                                   notify_by + float(traffic["grace_s"]))
        _, nodes_start = await asyncio.gather(*edge)
        _, nodes_end = await asyncio.gather(*closing)
        if run.trace:
            # After the window, under the same load: collecting a trace
            # freezes the service (harness.Run.start_trace).
            await loop.run_in_executor(pool, run.start_trace)
            await asyncio.sleep(float(traffic["trace"]["seconds"]))
            await loop.run_in_executor(pool, run.end_traced_window)
    finally:
        client.stop = True
        await asyncio.gather(schedule, return_exceptions=True)
    if schedule.exception() is not None:
        raise schedule.exception()
    if run.trace:
        await loop.run_in_executor(pool, run.stop_trace)
    outcome = client.outcome(window, notify_by)
    outcome["nodes_start"], outcome["nodes_end"] = nodes_start, nodes_end
    await client.close()
    pool.shutdown()
    return outcome


def drive(run: harness.Run) -> dict:
    fleet = Fleet(run)
    keys = fleet.genesis()
    fleet.assert_ports_free()
    run.start_service(keys)
    spec = run.cell["config"]["probe"]
    rng = random.Random(run.seed ^ 0x9E3779B9)
    probes = [make_probe(rng, fleet.signing_keys(), spec) for _ in range(2)]
    run.snapshot("probe_start")
    run_probe(run, probes[0], int(spec["in_flight"]), "on the idle service")
    run.snapshot("probe_end")
    fleet.boot()
    log(f"{fleet.n} validators booted")
    try:
        outcome = asyncio.run(_drive(run, fleet, probes[1]))
    finally:
        run.observed["unexpected_exits"] = run.unexpected_exits()
        mapped = {n: harness.maps_jax(p.pid)
                  for n, p in run.children.items() if p.poll() is None}
        final = fleet.scrape()
        fleet.stop()
        run.stop_service()
    latencies = outcome["latencies"]
    client = {k: outcome[k] for k in
              ("submitted", "shed", "unanswered", "late_notified",
               "lost_after_ack", "late_s", "latencies")}
    run.observed["client"] = client
    if outcome["nodes_start"] and outcome["nodes_end"]:
        run.observed["nodes"] = {"start": outcome["nodes_start"],
                                 "end": outcome["nodes_end"]}
    log("mean finality by the second it was due in: "
        f"{outcome['latency_avg_by_second_s']}")
    log(f"window: {client['submitted']} due, {len(latencies)} notified by "
        f"the drain's end, {client['late_notified']} later, "
        f"{client['shed']} shed, {client['unanswered']} unanswered, "
        f"{client['lost_after_ack']} acknowledged and never notified")
    check_fleet(run, fleet, final, mapped)
    # The idle probe's launches are its own, so the service's counts say
    # which kernels it reached; the window may have used no other.
    probed = kernels_used(run.snapshots["probe_end"],
                          run.snapshots["probe_start"])
    unprobed = sorted(kernels_used(run.snapshots["window_end"],
                                   run.snapshots["window_start"]) - probed)
    run.check("kernels the window ran and the probe did not", unprobed, [],
              bool(probed) and not unprobed)
    run.check("acknowledged transactions never notified",
              client["lost_after_ack"], 0, client["lost_after_ack"] == 0)
    beyond_p95 = len(latencies) - int(0.95 * len(latencies))
    run.check("finality samples beyond the 95th percentile", beyond_p95,
              ">= 10", beyond_p95 >= 10)
    end_to_end = {}
    if latencies:
        # The tail is per layer (layer_metrics/finality_p95_s.steady.py).
        end_to_end = {
            "committed_tx_s": len(latencies) / run.seconds,
            "finality_p50_s": statistics.median(latencies),
        }
        log(f"finality over {len(latencies)} samples: p50 "
            f"{end_to_end['finality_p50_s']:.4f}s p95 "
            f"{harness.quantile(latencies, 0.95):.4f}s")
    return {"attempted": client["submitted"],
            "failed": client["submitted"] - len(latencies),
            "end_to_end": end_to_end}
