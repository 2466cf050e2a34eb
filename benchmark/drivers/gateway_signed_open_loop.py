"""Driver: the validator fleet behind its gateways, open loop, every
transaction a signed transfer (configuration ``transfers10``).

Everything ``gateway_open_loop`` does, which this module imports and leaves
as it is, and on top of it:

* **genesis**: the program's ``python -m mysticeti_tpu genesis`` writes the
  allocation (``accounts`` funded accounts) while the service boots; the
  reference (``reference/transfers.py``) derives the same accounts in this
  process's pool, and the two files must be the same bytes;
* **the client** makes its accounts and signs every transfer of warm-up,
  window and drain from ``--seed`` with OpenSSL while the service boots,
  never inside the window, and sends none twice: one transfer a sender
  (nonce 0), destination uniform over all accounts, amount 1, one in
  ``corrupted_one_in`` with one bit of its signature flipped.  It
  subscribes with ``want_executed`` and counts a transfer as committed only
  when its notification carried an executed root;
* **correct**: corrupted transfers acknowledged and notified (both 0),
  sound transfers refused as ``bad_signature`` (0), the probe in this
  deployment's request shapes, the ten executed roots against each other
  at every height and against the reference's fold of the committed
  sequence read back from one validator's WAL, and what
  ``gateway_open_loop.check_fleet`` holds the fleet to.

Traffic file: ``gateway_open_loop``'s keys, and optionally ``transfers``
(how many to sign before the load starts; by default what the rate needs
for warm-up, window, drain, grace and trace, and a sweep's spec sets it).
Configuration file: ``paper10``'s keys and ``accounts``,
``starting_balance``, ``corrupted_one_in``, and a ``probe`` whose groups
may also be by ``block`` (1 committee signature + n account signatures:
what a validator sends for a received block) or ``accounts`` (n account
signatures: what a gateway sends for a frame).

``sweep.py`` drives this module through the names it shares with
``gateway_open_loop``: ``Fleet``, ``OpenLoopClient``, ``sleep_until``.
"""
from __future__ import annotations

import asyncio
import hashlib
import multiprocessing
import os
import random
import statistics
import struct
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import yaml

from benchmark import harness
from benchmark.drivers import gateway_open_loop as base
from benchmark.drivers.gateway_open_loop import (  # noqa: F401 (sweep.py)
    TAG_COMMITS,
    TAG_REPLY,
    TAG_SUBMIT,
    TAG_SUBSCRIBE,
    _U32,
    sleep_until,
)
from benchmark.harness import BenchError, log
from benchmark.reference import ed25519_oracle as oracle
from benchmark.reference import transfers as ref

BAD_SIGNATURE = b"bad_signature"
# Accounts whose private keys the probe holds (it signs 32-byte digests
# with them, not transfers).
PROBE_ACCOUNTS = 1024
KEYS_A_TASK = 8192
TRANSFERS_A_TASK = 2048


# -- made while the service boots --------------------------------------------


def _make_transfers(task: tuple) -> List[tuple]:
    """Transfers ``start..stop`` of a run (one argument: a pool maps it):
    sender = account ``start + i``, each exactly once."""
    (accounts_seed, accounts, seed, start, stop, size, corrupted_one_in,
     filler, keys) = task
    out = []
    for index in range(start, stop):
        rng = random.Random((seed << 24) ^ index)
        dest = rng.randrange(accounts)
        envelope = ref.make_transfer(
            ref.account(accounts_seed, index), 0, 1,
            keys[32 * dest:32 * dest + 32], size, filler)
        if rng.randrange(corrupted_one_in) == 0:
            envelope = ref.corrupt_signature(rng, envelope)
        # The oracle's verdict on what is sent, not assumed from the flip.
        out.append((envelope, ref.sound(envelope)))
    return out


def _sound(envelopes: List[bytes]) -> List[bool]:
    return [ref.sound(e) for e in envelopes]


class Prepared:
    """The reference's accounts and the client's transfers, made in a pool
    of this process beside the booting service."""

    def __init__(self, run: harness.Run, fleet_dir: str, count: int) -> None:
        config = run.cell["config"]
        self.accounts = int(config["accounts"])
        self.balance = int(config["starting_balance"])
        # The allocation is the deployment's, the same in every run; the
        # transfers (who pays whom, which are corrupted) are the seed's.
        self.accounts_seed = int(config["accounts_seed"])
        self.count = count
        if count + PROBE_ACCOUNTS > self.accounts:
            raise BenchError(
                f"{count} transfers need as many senders; the allocation "
                f"has {self.accounts} accounts")
        self.path = os.path.join(fleet_dir, "accounts.bin")
        self._program = subprocess.Popen(
            [sys.executable, "-m", "mysticeti_tpu", "genesis",
             "--accounts", str(self.accounts),
             "--seed", str(self.accounts_seed),
             "--balance", str(self.balance), "--out", self.path],
            cwd=harness.ROOT, stdout=subprocess.DEVNULL)
        self.pool = multiprocessing.get_context("fork").Pool(
            max(2, (os.cpu_count() or 2) - 2))
        self._keys = self.pool.map_async(ref.account_keys, [
            (self.accounts_seed, at, min(self.accounts, at + KEYS_A_TASK))
            for at in range(0, self.accounts, KEYS_A_TASK)])
        self._run, self._size = run, int(config["transaction_bytes"])
        self._one_in = int(config["corrupted_one_in"])
        self.keys: Optional[bytes] = None
        self.transfers: Optional[List[tuple]] = None

    def finish(self) -> None:
        """Wait for the accounts, sign the transfers, and hold the
        program's allocation to the reference's, byte for byte."""
        started = time.monotonic()
        self.keys = b"".join(self._keys.get(600))
        filler = random.Random(self._run.seed).randbytes(self._size)
        made = self.pool.map(_make_transfers, [
            (self.accounts_seed, self.accounts, self._run.seed, at,
             min(self.count, at + TRANSFERS_A_TASK), self._size,
             self._one_in, filler, self.keys)
            for at in range(0, self.count, TRANSFERS_A_TASK)])
        self.transfers = [t for chunk in made for t in chunk]
        if self._program.wait(600) != 0:
            raise BenchError("python -m mysticeti_tpu genesis failed")
        with open(self.path, "rb") as f:
            written = f.read()
        same = written == ref.allocation_bytes(self.balance, self.keys)
        self._run.check(
            "genesis allocation equal to the reference's (accounts)",
            self.accounts if same else "differs", self.accounts, same)
        log(f"{self.accounts} accounts and {self.count} signed transfers "
            f"({sum(1 for _, ok in self.transfers if not ok)} corrupted) "
            f"ready {time.monotonic() - started:.1f}s after the service")

    def probe_keys(self) -> List[tuple]:
        """Key pairs of the last accounts, which send no transfer."""
        first = self.accounts - PROBE_ACCOUNTS
        return [ref.account(self.accounts_seed, first + i)
                for i in range(PROBE_ACCOUNTS)]

    def close(self) -> None:
        self.pool.terminate()
        if self._program.poll() is None:
            self._program.kill()


# What ``Fleet.genesis`` started, for the ``OpenLoopClient`` that
# ``sweep.py`` constructs from the four arguments it knows.
_PREPARED: Dict[str, Prepared] = {}


def transfers_needed(run: harness.Run) -> int:
    traffic = run.cell["traffic"]
    if "transfers" in traffic:
        return int(traffic["transfers"])
    seconds = (float(traffic["warmup_s"]) + run.seconds
               + float(traffic["drain_s"]) + float(traffic["grace_s"])
               + float(traffic["trace"]["seconds"]) + 6.0)
    # Each tick rounds a gateway's share up to a whole transfer.
    a_tick = max(1, round(float(traffic["rate_tx_s"])
                          * float(traffic["tick_s"])
                          / int(run.cell["config"]["validators"])))
    return int(seconds / float(traffic["tick_s"]) + 1) * a_tick * int(
        run.cell["config"]["validators"])


class Fleet(base.Fleet):
    """``gateway_open_loop.Fleet`` with the genesis allocation."""

    def genesis(self) -> List[bytes]:
        keys = super().genesis()
        prepared = Prepared(self.run, self.dir, transfers_needed(self.run))
        _PREPARED["run"] = prepared
        self.parameters["genesis_allocation"] = prepared.path
        with open(os.path.join(self.dir, "parameters.yaml"), "w") as f:
            yaml.safe_dump(self.parameters, f, sort_keys=False)
        return keys

    def committed_payloads(self, authority: int) -> Dict[int, List[bytes]]:
        """{commit height: the Share payloads of its sub-dag, in linearized
        order} from one node's WAL, read with the program's own reader."""
        import json

        from mysticeti_tpu.block_store import (
            WAL_ENTRY_BLOCK,
            WAL_ENTRY_COMMIT,
            WAL_ENTRY_OWN_BLOCK,
            CommitData,
            OwnBlockData,
        )
        from mysticeti_tpu.serde import Reader
        from mysticeti_tpu.storage import MANIFEST_NAME
        from mysticeti_tpu.types import Share, StatementBlock
        from mysticeti_tpu.wal import WalReader

        path = os.path.join(self.dir, f"validator-{authority}", "wal")
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            files = [os.path.join(path, s["name"])
                     for s in json.load(f).get("segments", [])]
        blocks: Dict[object, List[bytes]] = {}
        commits: Dict[int, list] = {}
        for file in files:
            reader = WalReader(file)
            try:
                for _pos, tag, payload in reader.iter_until():
                    if tag == WAL_ENTRY_COMMIT:
                        r = Reader(payload)
                        for _ in range(r.u32()):
                            commit = CommitData.decode(r)
                            commits[commit.height] = commit.sub_dag
                        continue
                    if tag == WAL_ENTRY_BLOCK:
                        block = StatementBlock.from_bytes(bytes(payload))
                    elif tag == WAL_ENTRY_OWN_BLOCK:
                        block = OwnBlockData.from_bytes(bytes(payload)).block
                    else:
                        continue
                    blocks[block.reference] = [
                        bytes(st.transaction) for st in block.statements
                        if isinstance(st, Share)]
            finally:
                reader.close()
        return {height: [p for ref_ in sub_dag for p in blocks[ref_]]
                for height, sub_dag in commits.items()}


# -- the client -----------------------------------------------------------------


class Connection(base.Connection):
    """A gateway connection that knows which of its transfers are sound."""

    def __init__(self, index: int, reader, writer) -> None:
        super().__init__(index, reader, writer)
        self.corrupted: Dict[bytes, int] = {}  # key -> tick
        # Beside awaiting_reply, which holds (tick, sound transfers): the
        # corrupted ones of each frame, in the order sent.
        self.corrupted_in_frame: List[int] = []
        self.corrupted_sent = 0
        self.corrupted_replied = 0
        self.corrupted_acknowledged = 0
        self.corrupted_notified = 0
        self.sound_refused = 0  # as bad_signature
        self.unrooted = 0  # notified transfers whose frame had no root
        self.roots: Dict[int, bytes] = {}  # commit height -> executed root

    async def read_loop(self) -> None:
        reader = self.reader
        try:
            while True:
                header = await reader.readexactly(4)
                payload = await reader.readexactly(_U32.unpack(header)[0])
                now = time.monotonic()
                tag = payload[0]
                if tag == TAG_REPLY:
                    accepted, shed = struct.unpack_from("<II", payload, 2)
                    (reason_len,) = _U32.unpack_from(payload, 18)
                    reason = payload[22:22 + reason_len]
                    tick, sound = self.awaiting_reply[self.replied]
                    corrupted = self.corrupted_in_frame[self.replied]
                    self.replied += 1
                    self.accepted[tick] = accepted
                    self.corrupted_replied += corrupted
                    # Counts only: more accepted than were sound is a
                    # corrupted transfer acknowledged; more refused than
                    # were corrupted is a sound one refused.
                    self.corrupted_acknowledged += max(0, accepted - sound)
                    if shed > corrupted:
                        self.shed[tick] = shed - corrupted
                        if reason == BAD_SIGNATURE:
                            self.sound_refused += shed - corrupted
                elif tag == TAG_COMMITS:
                    (height,) = struct.unpack_from("<Q", payload, 1)
                    (count,) = _U32.unpack_from(payload, 9)
                    keys_end = 13 + 20 * count
                    # Behind the keys: u64 leader round | u64 commit time
                    # | the executed root as bytes (want_executed).
                    root = payload[keys_end + 20:keys_end + 52]
                    if len(root) == 32:
                        self.roots[height] = root
                    pending, notified = self.pending, self.notified
                    for at in range(17, keys_end, 20):
                        key = payload[at:at + 16]
                        entry = pending.pop(key, None)
                        if entry is not None:
                            if len(root) == 32:
                                notified.append((entry[0], now))
                            else:
                                self.unrooted += 1
                        elif key in self.corrupted:
                            self.corrupted_notified += 1
                else:
                    self.error = f"gateway {self.index} sent tag {tag}"
                    return
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as exc:
            self.error = f"gateway {self.index} closed: {exc!r}"


class OpenLoopClient(base.OpenLoopClient):
    """The open-loop schedule of ``gateway_open_loop`` over transfers that
    were signed before it started; ``submitted`` counts the sound ones."""

    def __init__(self, ports: List[int], seed: int, transaction_bytes: int,
                 tick_s: float) -> None:
        super().__init__(ports, seed, transaction_bytes, tick_s)
        prepared = _PREPARED["run"]
        if prepared.transfers is None:
            prepared.finish()
        self.transfers = prepared.transfers
        self.next = 0
        self.sound_by_tick: Dict[int, int] = {}

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
            # SUBSCRIBE from height 0 with the executed root:
            # u8 15 | u64 0 | u8 want_details | u8 want_executed
            body = bytes([TAG_SUBSCRIBE]) + struct.pack("<QBB", 0, 1, 1)
            writer.write(_U32.pack(len(body)) + body)
            conn.task = asyncio.ensure_future(conn.read_loop())
            self.connections.append(conn)

    def _submit(self, conn: Connection, tick: int, n: int, stamp: bytes,
                ) -> None:
        at = self.next
        if at + n > len(self.transfers):
            raise BenchError(
                f"the client ran out of its {len(self.transfers)} signed "
                "transfers: none is sent twice")
        self.next = at + n
        parts = [b"", bytes([TAG_SUBMIT]), _U32.pack(0), b"\x00",
                 _U32.pack(n)]
        blake, record_len = hashlib.blake2b, self._record_len
        sound = 0
        for envelope, ok in self.transfers[at:at + n]:
            key = blake(envelope, digest_size=16).digest()
            if ok:
                # Position among the frame's sound transfers: the gateway
                # admits a prefix of those.
                conn.pending[key] = (tick, sound)
                sound += 1
            else:
                conn.corrupted[key] = tick
            parts.append(record_len)
            parts.append(envelope)
        parts[0] = _U32.pack(sum(len(p) for p in parts))
        conn.awaiting_reply.append((tick, sound))
        conn.corrupted_in_frame.append(n - sound)
        conn.corrupted_sent += n - sound
        self.sound_by_tick[tick] = self.sound_by_tick.get(tick, 0) + sound
        conn.writer.write(b"".join(parts))

    def outcome(self, ticks: range, notify_by: float) -> dict:
        # (The base counts an unanswered frame by the second field of its
        # entry in awaiting_reply: the sound transfers here.)
        out = super().outcome(ticks, notify_by)
        out["submitted"] = sum(self.sound_by_tick.get(t, 0) for t in ticks)
        return out

    def signatures(self) -> dict:
        """What the whole run's replies and notifications said of the
        signatures, summed over the connections."""
        conns = self.connections
        return {name: sum(getattr(c, name) for c in conns) for name in (
            "corrupted_sent", "corrupted_replied", "corrupted_acknowledged",
            "corrupted_notified", "sound_refused", "unrooted")}


# -- the probe -------------------------------------------------------------------


def make_probe(rng: random.Random, committee: List[tuple],
               accounts: List[tuple], spec: dict) -> List[dict]:
    """``gateway_open_loop.make_probe`` with two more kinds of request:
    ``block`` (one committee signature, then ``signatures`` - 1 by distinct
    accounts) and ``accounts`` (``signatures`` by distinct accounts)."""
    keys = committee + accounts
    shapes = []
    for group in spec["requests"]:
        for i in range(int(group["count"])):
            n = int(group["signatures"][i % len(group["signatures"])])
            if group["signers"] == "one":
                lanes = [rng.randrange(len(committee))] * n
            elif group["signers"] == "distinct":
                lanes = rng.sample(range(len(committee)), n)
            elif group["signers"] == "any":
                lanes = [rng.randrange(len(committee)) for _ in range(n)]
            elif group["signers"] == "block":
                lanes = [rng.randrange(len(committee))] + rng.sample(
                    range(len(committee), len(keys)), n - 1)
            elif group["signers"] == "accounts":
                lanes = rng.sample(range(len(committee), len(keys)), n)
            else:
                raise BenchError(f"probe signers {group['signers']!r}")
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


# -- the run -----------------------------------------------------------------------


def check_signatures(run: harness.Run, fleet: Fleet, final: list,
                     client: OpenLoopClient, prepared: Prepared) -> None:
    """The guarantees of ``transfers10`` beyond ``paper10``'s."""
    said = client.signatures()
    run.check("corrupted transfers acknowledged",
              said["corrupted_acknowledged"], 0,
              said["corrupted_acknowledged"] == 0
              and said["corrupted_sent"] > 0)
    run.check("corrupted transfers notified as committed",
              said["corrupted_notified"], 0, said["corrupted_notified"] == 0)
    run.check("sound transfers refused as bad_signature",
              said["sound_refused"], 0, said["sound_refused"] == 0)
    run.check("transfers notified without an executed root",
              said["unrooted"], 0, said["unrooted"] == 0)
    # The validators' own count of the two checks.
    counts: Dict[tuple, int] = {}
    off_chip: Dict[str, int] = {}
    for i, series in enumerate(final):
        for name, labels, value in series or []:
            if name.removesuffix("_total") != "verified_tx_signatures":
                continue
            key = (labels.get("where"), labels.get("outcome"))
            counts[key] = counts.get(key, 0) + int(value)
            if labels.get("backend") != "tpu-remote" and value:
                off_chip[f"node-{i}/{labels.get('backend')}"] = int(value)
    run.check("transaction signatures verified off the chip path", off_chip,
              {}, not off_chip)
    run.check("transaction signatures the gateways rejected",
              counts.get(("gateway", "rejected"), 0),
              said["corrupted_replied"],
              counts.get(("gateway", "rejected"), 0)
              == said["corrupted_replied"])
    run.check("transaction signatures rejected on receipt",
              counts.get(("receipt", "rejected"), 0), 0,
              counts.get(("receipt", "rejected"), 0) == 0
              and counts.get(("receipt", "accepted"), 0) > 0)

    # Ten executed roots, equal at every height two of them share.
    agreed: Dict[int, bytes] = {}
    disagreements, seen_by = 0, {}
    for conn in client.connections:
        for height, root in conn.roots.items():
            seen_by[height] = seen_by.get(height, 0) + 1
            if agreed.setdefault(height, root) != root:
                disagreements += 1
    shared = sum(1 for n in seen_by.values() if n == len(client.connections))
    run.check("heights where two validators' executed roots differ",
              disagreements, 0, disagreements == 0)
    run.check("heights whose executed root all validators reported", shared,
              ">= 1", shared >= 1)
    # And equal to the reference's fold of the committed sequence.
    started = time.monotonic()
    sequence = fleet.committed_payloads(0)
    heights = sorted(sequence)
    contiguous = heights == list(range(1, len(heights) + 1))
    signed = sorted({p for payloads in sequence.values() for p in payloads
                     if p[:8] == ref.SIGNED_MAGIC})
    verdicts = prepared.pool.map(_sound, [
        signed[at:at + TRANSFERS_A_TASK]
        for at in range(0, len(signed), TRANSFERS_A_TASK)])
    is_sound = dict(zip(signed, (ok for chunk in verdicts for ok in chunk)))
    fold = ref.Fold(signed=True)
    fold.load_genesis(prepared.balance, prepared.keys)
    fold.sound = is_sound.__getitem__
    differing = compared = 0
    if contiguous:
        for height in heights:
            root = fold.commit(height, sequence[height])
            if height in agreed:
                compared += 1
                differing += root != agreed[height]
    run.check("heights where the executed root differs from the reference's "
              "fold of the WAL", differing if contiguous else "WAL has gaps",
              0, contiguous and differing == 0)
    run.check("heights compared with the reference's fold", compared, ">= 1",
              compared >= 1)
    forged = fold.verdicts.get(ref.BAD_SIGNATURE, 0)
    run.check("committed transfers whose signature OpenSSL rejects", forged,
              0, forged == 0)
    log(f"reference fold of {len(heights)} commits, {len(signed)} signed "
        f"transfers, verdicts {fold.verdicts}: "
        f"{time.monotonic() - started:.1f}s")


def resident_megabytes(pid: int) -> Optional[int]:
    """VmRSS of a live process (a validator holds the account state)."""
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) // 1024
    except OSError:
        pass
    return None


def request_sizes(nodes: dict) -> Dict[str, int]:
    """What the probe's shapes are set from: the histogram of
    ``verify_dispatch_batch_size`` (signatures a request a collector sent
    for received blocks), summed over the nodes, over the window."""
    out: Dict[str, int] = {}
    previous = 0
    bounds = sorted({labels["le"] for series in nodes["end"] if series
                     for name, labels, _ in series
                     if name == "verify_dispatch_batch_size_bucket"},
                    key=float)
    for le in bounds:
        upto = sum(
            harness.series_sum(end, "verify_dispatch_batch_size_bucket",
                               le=le)
            - harness.series_sum(start, "verify_dispatch_batch_size_bucket",
                                 le=le)
            for start, end in zip(nodes["start"], nodes["end"])
            if start is not None and end is not None)
        out[le] = int(upto - previous)
        previous = upto
    return out


async def _drive(run: harness.Run, fleet: Fleet, probe: List[dict],
                 client: OpenLoopClient) -> dict:
    traffic = run.cell["traffic"]
    spec = run.cell["config"]["probe"]
    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(4)
    tick_s = client.tick_s
    await client.connect()
    client.rate_tx_s = float(traffic["rate_tx_s"])
    log(f"client connected to {len(client.connections)} gateways; "
        f"{client.rate_tx_s} tx/s, warm-up {traffic['warmup_s']}s")
    begin = time.monotonic() + tick_s
    schedule = asyncio.ensure_future(client.run_schedule(begin))
    try:
        start = begin + round(float(traffic["warmup_s"]) / tick_s) * tick_s
        end = start + round(run.seconds / tick_s) * tick_s
        run.mark_window(start)
        scrape = fleet.scrape if run.trace else (lambda: None)
        await sleep_until(begin + float(spec["load_after_s"]))
        await loop.run_in_executor(pool, base.run_probe, run, probe,
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
        notify_by = end + float(traffic["drain_s"])
        await sleep_until(notify_by)
        window = client.ticks_due(start, end)
        await client.wait_notified(window,
                                   notify_by + float(traffic["grace_s"]))
        _, nodes_start = await asyncio.gather(*edge)
        _, nodes_end = await asyncio.gather(*closing)
        if run.trace:
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
    # Replies and notifications of what went out last: a gateway counts a
    # frame's rejected signatures whether or not its reply is read, so the
    # client reads every reply before it closes (a second on the chip; a
    # launch of the CPU rehearsal alone can take longer).
    waited_until = time.monotonic() + float(traffic["grace_s"])
    await asyncio.sleep(1.0)
    while (any(c.replied < len(c.awaiting_reply) for c in client.connections)
           and time.monotonic() < waited_until):
        await asyncio.sleep(0.1)
    await client.close()
    pool.shutdown()
    return outcome


def drive(run: harness.Run) -> dict:
    from mysticeti_tpu.config import Parameters

    if "signed_transactions" not in Parameters.__dataclass_fields__:
        # At once, before anything boots (a program from before PR 26).
        raise BenchError("this program has no signed transactions "
                         "(Parameters.signed_transactions)")
    fleet = Fleet(run)
    keys = fleet.genesis()
    prepared = _PREPARED["run"]
    try:
        fleet.assert_ports_free()
        run.start_service(keys)
        prepared.finish()
        spec = run.cell["config"]["probe"]
        rng = random.Random(run.seed ^ 0x9E3779B9)
        committee, accounts = fleet.signing_keys(), prepared.probe_keys()
        probes = [make_probe(rng, committee, accounts, spec)
                  for _ in range(2)]
        run.snapshot("probe_start")
        base.run_probe(run, probes[0], int(spec["in_flight"]),
                       "on the idle service")
        run.snapshot("probe_end")
        client = OpenLoopClient(
            fleet.ports("gateway"), run.seed,
            int(run.cell["config"]["transaction_bytes"]),
            float(run.cell["traffic"]["tick_s"]))
        fleet.boot()
        log(f"{fleet.n} validators booted")
        try:
            outcome = asyncio.run(_drive(run, fleet, probes[1], client))
        finally:
            run.observed["unexpected_exits"] = run.unexpected_exits()
            mapped = {n: harness.maps_jax(p.pid)
                      for n, p in run.children.items() if p.poll() is None}
            final = fleet.scrape()
            resident_mb = [resident_megabytes(p.pid)
                           for n, p in sorted(run.children.items())
                           if n.startswith("node-")]
            fleet.stop()
            run.stop_service()
        latencies = outcome["latencies"]
        record = {k: outcome[k] for k in
                  ("submitted", "shed", "unanswered", "late_notified",
                   "lost_after_ack", "late_s", "latencies")}
        record["signatures"] = client.signatures()
        run.observed["client"] = record
        if outcome["nodes_start"] and outcome["nodes_end"]:
            run.observed["nodes"] = {"start": outcome["nodes_start"],
                                     "end": outcome["nodes_end"]}
        if "nodes" in run.observed:
            log("signatures a request of the validators' collectors over "
                "the window (upper bound: requests): "
                f"{request_sizes(run.observed['nodes'])}")
        log(f"validators' resident memory at the end, MB: {resident_mb}")
        log("mean finality by the second it was due in: "
            f"{outcome['latency_avg_by_second_s']}")
        log(f"window: {record['submitted']} sound transfers due, "
            f"{len(latencies)} notified with an executed root by the "
            f"drain's end, {record['late_notified']} later, "
            f"{record['shed']} shed, {record['unanswered']} unanswered, "
            f"{record['lost_after_ack']} acknowledged and never notified; "
            f"signatures over the whole run: {record['signatures']}")
        base.check_fleet(run, fleet, final, mapped)
        check_signatures(run, fleet, final, client, prepared)
    finally:
        prepared.close()
    probed = base.kernels_used(run.snapshots["probe_end"],
                               run.snapshots["probe_start"])
    unprobed = sorted(base.kernels_used(run.snapshots["window_end"],
                                        run.snapshots["window_start"])
                      - probed)
    run.check("kernels the window ran and the probe did not", unprobed, [],
              bool(probed) and not unprobed)
    run.check("acknowledged transactions never notified",
              record["lost_after_ack"], 0, record["lost_after_ack"] == 0)
    beyond_p95 = len(latencies) - int(0.95 * len(latencies))
    run.check("finality samples beyond the 95th percentile", beyond_p95,
              ">= 10", beyond_p95 >= 10)
    end_to_end = {}
    if latencies:
        end_to_end = {
            "committed_tx_s": len(latencies) / run.seconds,
            "finality_p50_s": statistics.median(latencies),
        }
        log(f"finality over {len(latencies)} samples: p50 "
            f"{end_to_end['finality_p50_s']:.4f}s p95 "
            f"{harness.quantile(latencies, 0.95):.4f}s")
    return {"attempted": record["submitted"],
            "failed": record["submitted"] - len(latencies),
            "end_to_end": end_to_end}
