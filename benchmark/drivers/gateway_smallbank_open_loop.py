"""Driver: the validator fleet behind its gateways, open loop, every
transaction a signed SmallBank operation (configuration ``smallbank10``).

Everything ``gateway_signed_open_loop`` does, which this module imports and
leaves as it is (as that one does ``gateway_open_loop``), and on top of it:

* **genesis**: ``python -m mysticeti_tpu genesis --savings`` writes the
  two-balance allocation while the service boots; the reference
  (``reference/smallbank.py``) derives the same accounts in this process's
  pool, and the two files must be the same bytes;
* **the client** keeps a nonce an account.  The schedule (procedure by the
  mix, N1 and N2 by the hotspot rule) is the reference's, from ``--seed``;
  every operation is signed in order with OpenSSL while the service boots,
  never inside the window but for re-signing.  Account *i*'s operations go
  to gateway *i* mod ``validators`` in nonce order, the next not waiting
  for the last one's commit.  One envelope in ``corrupted_one_in`` is a
  corrupted copy of the operation its account sends next (same body and
  nonce, one signature bit flipped), sent just before it;
* **a refusal**: a gateway admits a prefix of a frame's sound operations
  (``IngressPlane._submit``) and the reply counts the rest.  A sound
  operation refused counts failed, and the client re-signs that account's
  later operations from the refused nonce on as it sends them, as a wallet
  would; what that signing costs inside the window is logged;
* **failed** = sound operations refused + acknowledged and not notified
  with an executed root by the deadline + executed as ``bad_nonce`` (by the
  reference's fold of the committed sequence).  The source's own aborts
  (``aborted``) did what SmallBank says and are not failures;
* **correct**: what ``gateway_signed_open_loop`` holds ``transfers10`` to
  (no corrupted envelope acknowledged or notified, no sound one refused as
  ``bad_signature``, the validators' own counts, ``check_fleet``), the ten
  executed roots against each other at every height and against the
  reference's fold of the committed sequence read back from one validator's
  WAL, from the allocation on, and ``sequencing``: no operation commits
  twice, an account without a refusal executes a prefix of what it was
  acknowledged, in nonce order, each ``applied`` or ``aborted``, the
  reference's final nonce equal to their count, and nothing executes as
  ``bad_nonce`` but behind a refusal; the probe, with a group of requests
  that hold the same signers several times, and the dispatch counts that
  say which kernel took those.

Traffic file: ``gateway_open_loop``'s keys, and optionally ``operations``
(how many to sign before the load starts; by default what the rate needs
for warm-up, window, drain, grace and trace, and a sweep's spec sets it).
Configuration file: ``transfers10``'s keys with ``starting_checking`` /
``starting_savings`` for ``starting_balance``, and ``hotspot_accounts``,
``hotspot_share``, ``mix``, ``amounts``; a ``probe`` group may also be by
``hotspot`` signers; ``node_main`` (optional: another wrapper of the
node's entry point, the controls in ``benchmark/tests/``).

``sweep.py`` drives this module through ``Fleet``, ``OpenLoopClient`` and
``sleep_until``.  It raises ``BenchError`` at once on a program without
the operations (the parent of the PR that added them).
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
from typing import Dict, List, Optional

import yaml

from benchmark import harness
from benchmark.drivers import gateway_open_loop as base
from benchmark.drivers import gateway_open_loop_wan as node_main
from benchmark.drivers import gateway_signed_open_loop as signed
from benchmark.drivers.gateway_open_loop import (  # noqa: F401 (sweep.py)
    TAG_COMMITS,
    TAG_REPLY,
    TAG_SUBMIT,
    _U32,
    sleep_until,
)
from benchmark.harness import BenchError, log
from benchmark.reference import ed25519_oracle as oracle
from benchmark.reference import smallbank as ref

PROBE_ACCOUNTS = signed.PROBE_ACCOUNTS
KEYS_A_TASK = signed.KEYS_A_TASK
OPERATIONS_A_TASK = 2048
# One operation of the schedule, as a list the client may re-sign in place.
OP, N1, N2, AMOUNT, NONCE, ENVELOPE, CORRUPTED = range(7)


def require_program() -> None:
    """At once, before anything boots."""
    from mysticeti_tpu import execution
    from mysticeti_tpu.config import Parameters

    if "signed_transactions" not in Parameters.__dataclass_fields__:
        raise BenchError("this program has no signed transactions "
                         "(Parameters.signed_transactions)")
    if not hasattr(execution, "OP_SEND_PAYMENT"):
        raise BenchError(
            "this program has no SmallBank operations (execution.py has no "
            "OP_SEND_PAYMENT: no second balance, no two-balance allocation)")


# -- made while the service boots --------------------------------------------


def _sign(task: tuple) -> List[tuple]:
    """Sign operations (one argument: a pool maps it): (envelope, corrupted
    copy or None) of each, the copy held to the oracle's verdict."""
    accounts_seed, seed, size, filler, one_in, rows = task
    out = []
    for index, op, n1, nonce, amount, dest_key in rows:
        envelope = ref.make_operation(ref.account(accounts_seed, n1), op,
                                      nonce, amount, dest_key, size, filler)
        out.append((envelope, corrupted_copy(seed, index, one_in, envelope)))
    return out


def corrupted_copy(seed: int, index: int, one_in: int,
                   envelope: bytes) -> Optional[bytes]:
    """The corrupted copy sent before operation ``index``, if the seed
    gives it one: one envelope in ``one_in`` of all that are sent."""
    rng = random.Random((seed << 24) ^ index)
    if rng.randrange(one_in - 1):
        return None
    copy = ref.corrupt_signature(rng, envelope)
    if ref.sound(copy):
        raise BenchError("a signature with one bit flipped still verifies")
    return copy


def _sound(envelopes: List[bytes]) -> List[bool]:
    return [ref.sound(e) for e in envelopes]


class Prepared:
    """The reference's accounts and the client's operations, made in a pool
    of this process beside the booting service."""

    def __init__(self, run: harness.Run, fleet_dir: str, count: int) -> None:
        config = run.cell["config"]
        self.config = config
        self.accounts = int(config["accounts"])
        self.checking = int(config["starting_checking"])
        self.savings = int(config["starting_savings"])
        self.hot = int(config["hotspot_accounts"])
        # The allocation is the deployment's, the same in every run; the
        # schedule, the filler and the corruptions are the seed's.
        self.accounts_seed = int(config["accounts_seed"])
        self.count = count
        self.path = os.path.join(fleet_dir, "accounts.bin")
        self._program = subprocess.Popen(
            [sys.executable, "-m", "mysticeti_tpu", "genesis",
             "--accounts", str(self.accounts),
             "--seed", str(self.accounts_seed),
             "--balance", str(self.checking),
             "--savings", str(self.savings), "--out", self.path],
            cwd=harness.ROOT, stdout=subprocess.DEVNULL)
        self.pool = multiprocessing.get_context("fork").Pool(
            max(2, (os.cpu_count() or 2) - 2))
        self._keys = self.pool.map_async(ref.account_keys, [
            (self.accounts_seed, at, min(self.accounts, at + KEYS_A_TASK))
            for at in range(0, self.accounts, KEYS_A_TASK)])
        self._run, self.size = run, int(config["transaction_bytes"])
        self.one_in = int(config["corrupted_one_in"])
        self.filler = random.Random(run.seed).randbytes(self.size)
        self.keys: Optional[bytes] = None
        self.ops: Optional[List[list]] = None

    def key_of(self, index: Optional[int]) -> bytes:
        return b"" if index is None else self.keys[32 * index:32 * index + 32]

    def finish(self) -> None:
        """Wait for the accounts, draw the schedule, sign it in nonce order
        and hold the program's allocation to the reference's, byte for
        byte."""
        started = time.monotonic()
        self.keys = b"".join(self._keys.get(600))
        config, seed = self.config, self._run.seed
        drawn = ref.schedule(seed, self.count, self.accounts, self.hot,
                             float(config["hotspot_share"]), config["mix"],
                             config["amounts"])
        nonces: Dict[int, int] = {}
        self.ops = []
        for op, n1, n2, amount in drawn:
            nonce = nonces.get(n1, 0)
            nonces[n1] = nonce + 1
            self.ops.append([op, n1, n2, amount, nonce, None, None])
        rows = [(i, o[OP], o[N1], o[NONCE], o[AMOUNT], self.key_of(o[N2]))
                for i, o in enumerate(self.ops)]
        made = self.pool.map(_sign, [
            (self.accounts_seed, seed, self.size, self.filler, self.one_in,
             rows[at:at + OPERATIONS_A_TASK])
            for at in range(0, len(rows), OPERATIONS_A_TASK)])
        for o, (envelope, copy) in zip(
                self.ops, (pair for chunk in made for pair in chunk)):
            o[ENVELOPE], o[CORRUPTED] = envelope, copy
        if self._program.wait(600) != 0:
            raise BenchError("python -m mysticeti_tpu genesis failed")
        with open(self.path, "rb") as f:
            written = f.read()
        same = written == ref.allocation_bytes(self.checking, self.keys,
                                               self.savings)
        self._run.check(
            "genesis allocation equal to the reference's (accounts)",
            self.accounts if same else "differs", self.accounts, same)
        by_hot = sum(1 for o in self.ops if o[N1] < self.hot)
        log(f"{self.accounts} accounts and {self.count} signed operations "
            f"({by_hot} by the {self.hot} hot accounts, deepest sequence "
            f"{max(nonces.values())}; "
            f"{sum(1 for o in self.ops if o[CORRUPTED])} corrupted copies) "
            f"ready {time.monotonic() - started:.1f}s after the service")

    def resign(self, index: int, nonce: int) -> None:
        """Operation ``index`` again, at ``nonce`` (in the caller's thread:
        a wallet's own signing)."""
        o = self.ops[index]
        o[NONCE] = nonce
        o[ENVELOPE] = ref.make_operation(
            ref.account(self.accounts_seed, o[N1]), o[OP], nonce, o[AMOUNT],
            self.key_of(o[N2]), self.size, self.filler)
        o[CORRUPTED] = corrupted_copy(self._run.seed, index, self.one_in,
                                      o[ENVELOPE])

    def probe_keys(self) -> List[tuple]:
        """Key pairs of the last accounts: the probe signs 32-byte digests
        with them, no operation, so no nonce of theirs moves."""
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


def operations_needed(run: harness.Run) -> int:
    traffic = run.cell["traffic"]
    if "operations" in traffic:
        return int(traffic["operations"])
    seconds = (float(traffic["warmup_s"]) + run.seconds
               + float(traffic["drain_s"]) + float(traffic["grace_s"])
               + float(traffic["trace"]["seconds"]) + 6.0)
    return int(seconds * float(traffic["rate_tx_s"]))


class Fleet(node_main.Fleet, signed.Fleet):
    """``gateway_signed_open_loop.Fleet`` with the two-balance allocation;
    ``node_main`` as the wide-area driver's fleet has it."""

    def genesis(self) -> List[bytes]:
        require_program()
        keys = base.Fleet.genesis(self)
        prepared = Prepared(self.run, self.dir, operations_needed(self.run))
        _PREPARED["run"] = prepared
        self.parameters["genesis_allocation"] = prepared.path
        with open(os.path.join(self.dir, "parameters.yaml"), "w") as f:
            yaml.safe_dump(self.parameters, f, sort_keys=False)
        return keys


# -- the client -----------------------------------------------------------------


class Connection(signed.Connection):
    """A gateway connection that knows which operations each frame held."""

    def __init__(self, index: int, reader, writer, client) -> None:
        super().__init__(index, reader, writer)
        self.client = client
        # Beside awaiting_reply, (tick, sound operations): their indices
        # into the schedule, in the order sent.
        self.sound_in_frame: List[List[int]] = []
        # pending: key -> (tick, position among the frame's sound ones,
        # index into the schedule); notified: (index, tick, received at).
        self.refused: Dict[int, str] = {}  # index -> the reply's reason

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
                    held = self.sound_in_frame[self.replied]
                    self.replied += 1
                    self.accepted[tick] = (
                        self.accepted.get(tick, 0) + accepted)
                    self.corrupted_replied += corrupted
                    self.corrupted_acknowledged += max(0, accepted - sound)
                    if shed > corrupted:
                        self.shed[tick] = (self.shed.get(tick, 0)
                                           + shed - corrupted)
                        # The reply names one reason for all it refused,
                        # and bad_signature comes before bad_nonce: only a
                        # frame that holds nothing of an account refused
                        # before (whose stale nonces a gateway sheds) is
                        # evidence of a sound signature refused.
                        stale = self.client.next_nonce
                        if reason == signed.BAD_SIGNATURE and not any(
                                self.client.ops[i][N1] in stale for i in held):
                            self.sound_refused += shed - corrupted
                        # The gateway admits a prefix of the sound ones.
                        self.client.refused(self, held[accepted:],
                                            reason.decode("utf-8", "replace"))
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
                                notified.append((entry[2], entry[0], now))
                            else:
                                self.unrooted += 1
                        elif key in self.corrupted:
                            self.corrupted_notified += 1
                else:
                    self.error = f"gateway {self.index} sent tag {tag}"
                    return
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as exc:
            self.error = f"gateway {self.index} closed: {exc!r}"


class OpenLoopClient(signed.OpenLoopClient):
    """The open-loop schedule of ``gateway_open_loop`` over operations that
    were signed before it started: each tick takes the next operations of
    the schedule, as many envelopes as the rate gives the tick, and sends
    each down its account's gateway.  ``submitted`` counts the sound
    ones."""

    def __init__(self, ports: List[int], seed: int, transaction_bytes: int,
                 tick_s: float) -> None:
        base.OpenLoopClient.__init__(self, ports, seed, transaction_bytes,
                                     tick_s)
        self.prepared = prepared = _PREPARED["run"]
        if prepared.ops is None:
            prepared.finish()
        self.ops = prepared.ops
        self.next = 0
        self.sound_by_tick: Dict[int, int] = {}
        self.sent_tick: Dict[int, int] = {}  # index -> tick
        # Every account's operations, in the schedule's order.
        self.by_account: Dict[int, List[int]] = {}
        for index, o in enumerate(self.ops):
            self.by_account.setdefault(o[N1], []).append(index)
        # An account that was refused -> the nonce its next operation takes:
        # what it sends from then on is signed again as it goes out.
        self.next_nonce: Dict[int, int] = {}
        self.resigned_ops = 0
        self.resign_s = 0.0
        self._frames: List[List[tuple]] = []

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
            conn = Connection(index, reader, writer, self)
            # SUBSCRIBE from height 0 with the executed root:
            # u8 15 | u64 0 | u8 want_details | u8 want_executed
            body = bytes([base.TAG_SUBSCRIBE]) + struct.pack("<QBB", 0, 1, 1)
            writer.write(_U32.pack(len(body)) + body)
            conn.task = asyncio.ensure_future(conn.read_loop())
            self.connections.append(conn)

    def _take(self, tick: int, envelopes: int) -> None:
        """The tick's operations off the schedule, by gateway: (index,
        envelope, sound) in the order they go out."""
        frames: List[List[tuple]] = [[] for _ in self.connections]
        ops, gateways = self.ops, len(self.connections)
        while envelopes > 0:
            if self.next >= len(ops):
                raise BenchError(
                    f"the client ran out of its {len(ops)} signed "
                    "operations: none is sent twice")
            index = self.next
            self.next += 1
            o = ops[index]
            nonce = self.next_nonce.get(o[N1])
            if nonce is not None:
                self.next_nonce[o[N1]] = nonce + 1
                if nonce != o[NONCE]:
                    started = time.monotonic()
                    self.prepared.resign(index, nonce)
                    self.resigned_ops += 1
                    self.resign_s += time.monotonic() - started
            frame = frames[o[N1] % gateways]
            if o[CORRUPTED] is not None:
                frame.append((index, o[CORRUPTED], False))
                envelopes -= 1
            frame.append((index, o[ENVELOPE], True))
            self.sent_tick[index] = tick
            envelopes -= 1
        self._frames = frames

    def _submit(self, conn: Connection, tick: int, n: int, stamp: bytes,
                ) -> None:
        if conn.index == 0:
            self._take(tick, n * len(self.connections))
        rows = self._frames[conn.index]
        if not rows:
            return
        parts = [b"", bytes([TAG_SUBMIT]), _U32.pack(0), b"\x00",
                 _U32.pack(len(rows))]
        blake, record_len = hashlib.blake2b, self._record_len
        held: List[int] = []
        for index, envelope, ok in rows:
            key = blake(envelope, digest_size=16).digest()
            if ok:
                conn.pending[key] = (tick, len(held), index)
                held.append(index)
            else:
                conn.corrupted[key] = tick
            parts.append(record_len)
            parts.append(envelope)
        parts[0] = _U32.pack(sum(len(p) for p in parts))
        conn.awaiting_reply.append((tick, len(held)))
        conn.sound_in_frame.append(held)
        conn.corrupted_in_frame.append(len(rows) - len(held))
        conn.corrupted_sent += len(rows) - len(held)
        self.sound_by_tick[tick] = self.sound_by_tick.get(tick, 0) + len(held)
        conn.writer.write(b"".join(parts))

    def refused(self, conn: Connection, indices: List[int],
                reason: str) -> None:
        """Sound operations a gateway refused: they will not be notified,
        and what their accounts send from now on is signed again, from the
        first refused nonce on (``_take``)."""
        for index in indices:
            o = self.ops[index]
            conn.refused[index] = reason
            conn.pending.pop(
                hashlib.blake2b(o[ENVELOPE], digest_size=16).digest(), None)
            # The lowest refused nonce not yet signed again (a reply may
            # refuse what was sent before an earlier refusal was known).
            self.next_nonce[o[N1]] = min(
                self.next_nonce.get(o[N1], o[NONCE]), o[NONCE])

    async def wait_notified(self, ticks: range, deadline: float) -> None:
        while time.monotonic() < deadline:
            if not any(entry[0] in ticks for conn in self.connections
                       for entry in conn.pending.values()):
                return
            await asyncio.sleep(0.1)

    def outcome(self, ticks: range, notify_by: float) -> dict:
        """What became of the sound operations of ``ticks``: ``samples``
        are (index, finality from due to notification) of those notified
        with an executed root by ``notify_by``."""
        # (A schedule that fell behind has not reached every tick yet.)
        ticks = range(ticks.start, min(ticks.stop, len(self.ticks)))
        due = [t["due"] for t in self.ticks]
        samples, late_notified = [], 0
        by_tick: Dict[int, list] = {}
        for conn in self.connections:
            for index, tick, received in conn.notified:
                if tick in ticks:
                    if received <= notify_by:
                        samples.append((index, received - due[tick]))
                        by_tick.setdefault(tick, []).append(
                            received - due[tick])
                    else:
                        late_notified += 1
        refused = sum(1 for conn in self.connections for index in conn.refused
                      if self.sent_tick[index] in ticks)
        unanswered = sum(
            n for conn in self.connections
            for tick, n in conn.awaiting_reply[conn.replied:] if tick in ticks)
        # Acknowledged (its frame answered, itself not refused) and not
        # notified; an unanswered frame's are counted above.
        answered = [{t for t, _ in conn.awaiting_reply[:conn.replied]}
                    for conn in self.connections]
        lost = sum(1 for conn, seen in zip(self.connections, answered)
                   for tick, _, _ in conn.pending.values()
                   if tick in ticks and tick in seen)
        late_s = [sent - self.ticks[t]["due"]
                  for t in ticks for sent in self.ticks[t]["sent"]]
        per_second = max(1, round(1.0 / self.tick_s))
        by_second = [
            [x for t in range(at, min(at + per_second, ticks.stop))
             for x in by_tick.get(t, [])]
            for at in range(ticks.start, ticks.stop, per_second)]
        half = ticks.start + len(ticks) // 2
        halves = [[x for t, v in by_tick.items() if (t < half) == first
                   for x in v] for first in (True, False)]
        return {"submitted": sum(self.sound_by_tick.get(t, 0) for t in ticks),
                "samples": samples,
                "latencies": [latency for _, latency in samples],
                "latency_avg_by_second_s": [
                    round(statistics.fmean(v), 3) if v else None
                    for v in by_second],
                "latency_avg_halves_s": [
                    statistics.fmean(h) if h else None for h in halves],
                "late_notified": late_notified, "shed": refused,
                "unanswered": unanswered, "lost_after_ack": lost,
                "late_s": late_s}


# -- the probe -------------------------------------------------------------------


def make_probe(rng: random.Random, committee: List[tuple],
               accounts: List[tuple], spec: dict, hot: int) -> List[dict]:
    """``gateway_signed_open_loop.make_probe`` for the groups it knows, and
    ``hotspot``: alternately what a validator sends for a received block (a
    committee signature first) and what a gateway sends for a frame, each
    account signature by one of the first ``hot`` probe accounts with
    probability 0.25 and by any other one else, so that a request holds the
    same signer several times."""
    requests: List[dict] = []
    keys = committee + accounts
    for group in spec["requests"]:
        if group["signers"] != "hotspot":
            requests.extend(signed.make_probe(
                rng, committee, accounts, dict(spec, requests=[group])))
            continue
        shapes = []
        for i in range(int(group["count"])):
            n = int(group["signatures"][i % len(group["signatures"])])
            lanes = [rng.randrange(len(committee))] if i % 2 == 0 else []
            while len(lanes) < n:
                lanes.append(
                    len(committee) + (rng.randrange(hot)
                                      if rng.random() < 0.25
                                      else rng.randrange(hot, len(accounts))))
            shapes.append(lanes)
        total = sum(len(lanes) for lanes in shapes)
        corrupt = set(rng.sample(range(total),
                                 int(total * spec["corrupted_share"])))
        at = 0
        for lanes in shapes:
            requests.append(oracle.signed_request(
                rng, keys, lanes,
                [i for i in range(len(lanes)) if at + i in corrupt]))
            requests[-1]["repeats"] = len(lanes) - len(set(lanes))
            at += len(lanes)
    rng.shuffle(requests)
    return requests


def launches(later: dict, earlier: dict) -> Dict[str, int]:
    """{"kernel/lanes": launches} the service made between two snapshots."""
    before = {(d["kernel"], d["bucket"]): d["count"]
              for d in earlier["dispatches"]}
    return {f"{d['kernel']}/{d['bucket']}":
            d["count"] - before.get((d["kernel"], d["bucket"]), 0)
            for d in later["dispatches"]
            if d["count"] > before.get((d["kernel"], d["bucket"]), 0)}


# -- the run -----------------------------------------------------------------------


def check_ledger(run: harness.Run, fleet: Fleet, final: list,
                 client: OpenLoopClient, prepared: Prepared) -> Dict[int, str]:
    """The guarantees of ``smallbank10`` beyond ``paper10``'s; the
    reference's verdict on every operation of the schedule that was
    committed, by its index."""
    said = client.signatures()
    run.check("corrupted envelopes acknowledged",
              said["corrupted_acknowledged"], 0,
              said["corrupted_acknowledged"] == 0
              and said["corrupted_sent"] > 0)
    run.check("corrupted envelopes notified as committed",
              said["corrupted_notified"], 0, said["corrupted_notified"] == 0)
    run.check("sound operations refused as bad_signature",
              said["sound_refused"], 0, said["sound_refused"] == 0)
    run.check("operations notified without an executed root",
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
    envelopes = sorted({p for payloads in sequence.values() for p in payloads
                        if p[:8] == ref.SIGNED_MAGIC})
    verdicts = prepared.pool.map(_sound, [
        envelopes[at:at + OPERATIONS_A_TASK]
        for at in range(0, len(envelopes), OPERATIONS_A_TASK)])
    is_sound = dict(zip(envelopes, (ok for chunk in verdicts for ok in chunk)))
    fold = ref.Fold(signed=True)
    fold.load_genesis(prepared.checking, prepared.keys, prepared.savings)
    fold.sound = is_sound.__getitem__
    fold.log = []
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
    run.check("committed operations whose signature OpenSSL rejects", forged,
              0, forged == 0)

    # Sequencing: what the reference made of every operation that was sent.
    index_of = {o[ENVELOPE]: i for i, o in enumerate(client.ops[:client.next])}
    verdict_of: Dict[int, str] = {}
    twice = foreign = 0
    for payload, verdict in fold.log:
        index = index_of.get(payload)
        if index is None:
            foreign += 1  # a corrupted copy would be bad_signature above
        elif index in verdict_of:
            twice += 1
        else:
            verdict_of[index] = verdict
    run.check("operations committed more than once", twice, 0, twice == 0)
    run.check("committed operations the client never sent", foreign, 0,
              foreign == 0)
    refused = {index for conn in client.connections for index in conn.refused}
    broken = {client.ops[index][N1] for index in refused}
    out_of_order = wrong_nonce = bad_nonce_clean = unowed = 0
    for account, indices in client.by_account.items():
        sent = [i for i in indices if i < client.next]
        if not sent:
            continue
        executed = [i for i in sent if verdict_of.get(i) in ref.EXECUTED]
        unowed += sum(1 for i in sent if i in refused and i in verdict_of)
        nonce = fold.accounts[prepared.key_of(account)][1]
        wrong_nonce += nonce != len(executed)
        if account in broken:
            continue
        # No refusal: a prefix of what was sent, each applied or aborted.
        out_of_order += executed != sent[:len(executed)]
        bad_nonce_clean += sum(
            1 for i in sent if verdict_of.get(i, ref.APPLIED)
            not in ref.EXECUTED)
    run.check("accounts whose final nonce by the reference is not the count "
              "of their executed operations", wrong_nonce, 0,
              wrong_nonce == 0)
    run.check("accounts never refused whose executed operations are not a "
              "prefix of what they sent, in nonce order", out_of_order, 0,
              out_of_order == 0)
    run.check("operations of accounts never refused that executed neither "
              "applied nor aborted (bad_nonce and the like)",
              bad_nonce_clean, 0, bad_nonce_clean == 0)
    # Which of a frame's operations a gateway refused is the client's
    # reading of the reply's counts (a prefix is admitted), so this is
    # logged and not held against the system.
    log(f"refused operations that were committed all the same: {unowed}")
    by_op: Dict[str, int] = {}
    for index, verdict in verdict_of.items():
        name = f"{client.ops[index][OP]}/{verdict}"
        by_op[name] = by_op.get(name, 0) + 1
    negative = sum(1 for c, _, _ in fold.accounts.values() if c < 0)
    log(f"reference fold of {len(heights)} commits, {len(envelopes)} signed "
        f"operations, verdicts {fold.verdicts}, by operation/verdict "
        f"{dict(sorted(by_op.items()))}, accounts with checking below zero "
        f"at the end {negative}: {time.monotonic() - started:.1f}s")
    return verdict_of


def drive(run: harness.Run) -> dict:
    require_program()
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
        hot = int(run.cell["config"]["hotspot_accounts"])
        plain = dict(spec, requests=[g for g in spec["requests"]
                                     if g["signers"] != "hotspot"])
        repeated = dict(spec, requests=[g for g in spec["requests"]
                                        if g["signers"] == "hotspot"])
        idle = [make_probe(rng, committee, accounts, part, hot)
                for part in (plain, repeated)]
        loaded = make_probe(rng, committee, accounts, spec, hot)
        run.snapshot("probe_start")
        base.run_probe(run, idle[0], int(spec["in_flight"]),
                       "on the idle service")
        run.snapshot("probe_repeated")
        base.run_probe(run, idle[1], int(spec["in_flight"]),
                       "with repeated signers on the idle service")
        run.snapshot("probe_end")
        took = launches(run.snapshots["probe_end"],
                        run.snapshots["probe_repeated"])
        repeats = sum(r["repeats"] for r in idle[1])
        run.check("kernels that took the requests with repeated signers "
                  f"({repeats} repeats in {len(idle[1])} requests)", took,
                  "the unknown-signer kernel (blob) alone",
                  bool(took) and repeats > 0
                  and all(k.startswith("blob/") for k in took))
        client = OpenLoopClient(
            fleet.ports("gateway"), run.seed,
            int(run.cell["config"]["transaction_bytes"]),
            float(run.cell["traffic"]["tick_s"]))
        fleet.boot()
        log(f"{fleet.n} validators booted")
        try:
            outcome = asyncio.run(signed._drive(run, fleet, loaded, client))
        finally:
            run.observed["unexpected_exits"] = run.unexpected_exits()
            mapped = {n: harness.maps_jax(p.pid)
                      for n, p in run.children.items() if p.poll() is None}
            final = fleet.scrape()
            resident_mb = [signed.resident_megabytes(p.pid)
                           for n, p in sorted(run.children.items())
                           if n.startswith("node-")]
            free_mb = host_free_megabytes()
            fleet.stop()
            run.stop_service()
        record = {k: outcome[k] for k in
                  ("submitted", "shed", "unanswered", "late_notified",
                   "lost_after_ack", "late_s")}
        record["signatures"] = client.signatures()
        if outcome["nodes_start"] and outcome["nodes_end"]:
            run.observed["nodes"] = {"start": outcome["nodes_start"],
                                     "end": outcome["nodes_end"]}
        if "nodes" in run.observed:
            log("signatures a request of the validators' collectors over "
                "the window (upper bound: requests): "
                f"{signed.request_sizes(run.observed['nodes'])}")
        log(f"validators' resident memory at the end, MB: {resident_mb}; "
            f"the host's free memory, MB: {free_mb}")
        log("mean finality by the second it was due in: "
            f"{outcome['latency_avg_by_second_s']}")
        base.check_fleet(run, fleet, final, mapped)
        verdict_of = check_ledger(run, fleet, final, client, prepared)
        # An operation that executed as bad_nonce was notified, and failed.
        samples = [(index, latency) for index, latency in outcome["samples"]
                   if verdict_of.get(index) in ref.EXECUTED]
        record["executed_bad_nonce"] = (len(outcome["samples"])
                                        - len(samples))
        latencies = record["latencies"] = [x for _, x in samples]
        record["latencies_hot"] = [
            x for index, x in samples if client.ops[index][N1] < hot]
        record["resigned_operations"] = client.resigned_ops
        run.observed["client"] = record
        log(f"window: {record['submitted']} sound operations due, "
            f"{len(latencies)} notified with an executed root by the "
            f"drain's end and executed ({len(record['latencies_hot'])} of "
            f"them by hot accounts), {record['executed_bad_nonce']} "
            f"executed as bad_nonce, {record['late_notified']} notified "
            f"later, {record['shed']} refused, {record['unanswered']} "
            f"unanswered, {record['lost_after_ack']} acknowledged and never "
            f"notified; {client.resigned_ops} operations signed again in "
            f"{client.resign_s:.3f}s after refusals of {len(client.next_nonce)} "
            f"accounts; signatures over the whole run: "
            f"{record['signatures']}")
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
            f"{harness.quantile(latencies, 0.95):.4f}s"
            + (f"; of hot accounts p50 "
               f"{statistics.median(record['latencies_hot']):.4f}s"
               if record["latencies_hot"] else ""))
    return {"attempted": record["submitted"],
            "failed": record["submitted"] - len(latencies),
            "end_to_end": end_to_end}


def host_free_megabytes() -> Optional[int]:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) // 1024
    except OSError:
        pass
    return None
