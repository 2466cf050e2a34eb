# lint: ignore-module[sim-taint] — standalone socket-server process: runs
# outside any validator's event loop (real or simulated); its calibration
# clocks can never leak into a seeded sim's timeline.
"""Shared per-host verifier service: ONE warmed JAX runtime for the fleet.

Round-4 finding: giving every validator process its own JAX runtime
(``validator.py:_make_verifier``) made the TPU path lose to CPU at fleet
level — N processes serially paying import + PJRT init + trace/compile on a
shared host, then N independent connections to the accelerator.  The
reference never hits this because its verifier is a CPU function in-process
(``mysticeti-core/src/crypto.rs:174-189``); a TPU-first design wants the
opposite split: the accelerator runtime is a HOST resource, owned by one
process, shared by every co-located validator.

  * :class:`VerifierServer` — owns a single :class:`TpuSignatureVerifier`
    (one PJRT client, one compile cache, warmed once), serves signature
    batches over a unix-domain socket.  Requests from different validators
    share launches: a few dispatcher threads each take every request that
    is pending when they are free, up to the bucket the backend warmed,
    and verify them with ONE backend call (group commit: a request that
    finds the service idle is launched at once, alone; while a queue
    drains one part-full launch is on its host path at a time and what
    arrives behind it rides together when it lands — or, once it is in
    its fetch, as soon as they are as many as it carries,
    ``VerifierServer._take``).
  * :class:`RemoteSignatureVerifier` — the validator-side
    :class:`SignatureVerifier` that forwards batches to the service.  It
    never imports jax: a validator process using it boots import-light, and
    a REBOOTED validator re-attaches to the still-warm service instead of
    re-paying a cold runtime (the round-4 catch-up gap: 100 s+ of re-warm).

Wire protocol (little-endian, length-prefixed frames):

  frame    = u32 payload_len | u8 type | payload
  HELLO    (1)   u16 n_keys | n_keys * 32 B pk      -> HELLO_OK once warm
  VERIFY   (2)   u32 req_id | u32 n | n * (u16 key_idx | 32 B digest | 64 B sig)
  RAW      (3)   u32 req_id | u32 n | n * (32 B pk | 32 B digest | 64 B sig)
  HELLO_OK (128) f64 fixed_dispatch_s | f64 per_sig_s | utf-8 backend
                 (empty = uncalibrated; exactly 16 B = calibrated pre-r6
                 service, backend unknown)
  RESULT   (129) u32 req_id | n * u8 ok
  ERR      (255) utf-8 message (protocol error; connection closes)

The HELLO_OK ``backend`` suffix advertises the platform the service's JAX
runtime resolved to ("tpu" when a chip answered).  "cpu" appears only when
the service was started with ``JAX_PLATFORMS=cpu`` (the CPU test tier, or an
operator who asked for it by name): ``run_service`` refuses to start when JAX
found no accelerator and fell back to the host on its own, and a backend
whose warm-up or calibration fails takes the service down with it — a fleet
launched on a chip never ends up verifying on the host under the chip's
name.  A client of a service that resolved to "cpu" still sends every batch
over the socket and gets correct verdicts; the launchers that measure
(``chip_smoke.py``, ``benchmark/``) read the suffix and refuse such a fleet.
A client against a pre-suffix service sees exactly 16 bytes and leaves the
backend unknown.

HELLO doubles as the warmup gate: the reply is sent only after the backend's
one-time trace/compile finished, so a client's ``warmup()`` is "send HELLO,
wait" — seconds against a warm service, never minutes.  All clients must
present the same committee (one table per service); a mismatch is an ERR.

HELLO_OK carries the service's OWN dispatch calibration (a timed 1-signature
and batch dispatch after warmup).  No client reads it: it stays on the wire
because the backend suffix rides behind its 16 bytes (ROADMAP queue 3: it
goes with a wire-version bump that keeps the suffix).
"""
from __future__ import annotations

import asyncio
import collections
import functools
import gc
import itertools
import json
import os
import random
import socket
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

from . import spans
from .block_validator import (
    CpuSignatureVerifier,
    SignatureVerifier,
    VerifierProtocolError,
)
from .hostattr import LoopLagProbe
from .network import jittered_backoff
from .verify_pipeline import (
    CompletedDispatch,
    DeferredDispatch,
    VerifyPipeline,
)
from .tracing import logger

log = logger(__name__)

T_HELLO = 1
T_VERIFY = 2
T_RAW = 3
T_HELLO_OK = 128
T_RESULT = 129
T_ERR = 255

_IDX_REC = 2 + 32 + 64  # u16 idx | digest | sig
_RAW_REC = 32 + 32 + 64
_HEADER = struct.Struct("<IB")  # u32 payload_len | u8 type
_REQUEST = struct.Struct("<II")  # u32 req_id | u32 n
_REQ_ID = struct.Struct("<I")

ENV_SOCKET = "MYSTICETI_VERIFIER_SOCKET"



class ServiceCounts:
    """What the service's stage clock stamps once a second
    (``spans.StageClock``: ``stamps`` / ``read_stamps``), cumulative:
    replies written, the signatures in them and the launches that answered
    them, clocked or not; the socket reads that held at least one request
    and the writes that held at least one reply (``requests / reads`` and
    ``requests / writes``: how many frames a read and replies a write
    carried) — plain sums of the one thread that reads requests and writes
    replies, which also stamps; and ``left``: the launches that left, by
    why (LEFT), counted where the dispatcher threads decide it, under the
    service's condition; and what the backend counts of its own launches
    (``roads``: its ``road_counts``, once it is warm; a backend that counts
    none reads zero): ``direct``, the launches of VERIFY frames alone whose
    key indices went from the wire into the blob as they were, and
    ``keyed_tried``, the launches that went into the keyed-tile kernel's
    grouping."""

    # Why a launch left when it did (``VerifierServer._take``): ``left``
    # counts them in this order.
    LEFT = ("alone", "full", "drained", "expired", "overlapped")
    STAMPS = ("requests", "signatures", "launches",
              *("left_" + why for why in LEFT), "direct", "keyed_tried",
              "reads", "writes")

    __slots__ = ("requests", "signatures", "launches", "left", "roads",
                 "reads", "writes")

    def __init__(self) -> None:
        self.requests = self.signatures = self.launches = 0
        self.left = [0] * len(self.LEFT)
        self.roads = lambda: (0, 0)
        self.reads = self.writes = 0

    def read(self) -> tuple:
        """STAMPS now."""
        return (self.requests, self.signatures, self.launches, *self.left,
                *self.roads(), self.reads, self.writes)


# Why a launch left, as ``ServiceCounts.left`` is indexed.
_ALONE, _FULL, _DRAINED, _EXPIRED, _OVERLAPPED = range(
    len(ServiceCounts.LEFT))


class _Hold:
    """A part-full launch that is out.  ``until``: when its hold on what
    arrives behind it runs out, if nothing ends it before; ``riders``: the
    requests it carries; ``fetching``: its backend said that it is in its
    fetch (``spans.request_fetch``): its host path is over."""

    __slots__ = ("until", "riders", "fetching")

    def __init__(self, until: float, riders: int) -> None:
        self.until = until
        self.riders = riders
        self.fetching = False


# VerifierProtocolError (re-exported above from block_validator): the service
# answered but REJECTED the request.  Excluded from the client's retry loop
# AND from the circuit breaker — a misconfigured validator fails fast
# instead of hammering the service or silently degrading to the oracle.


def report_path(socket_path: str) -> str:
    """Where a service listening on ``socket_path`` leaves its device and
    kernel report (``VerifierServer._write_report``)."""
    return socket_path + ".json"


def _frame(type_: int, payload: bytes) -> bytes:
    """Small-frame builder (HELLO, HELLO_OK, ERR).  The hot path — VERIFY
    requests client-side — does NOT come through here: it packs into a
    reusable buffer so payload bytes are copied at most once (see
    ``_WireBuffer``)."""
    return struct.pack("<IB", len(payload), type_) + payload


class _WireBuffer:
    """Reusable pack/recv scratch buffer: grown geometrically, never shrunk
    or reallocated per dispatch, so steady-state requests write into (and
    replies land in) the same allocation every time.  One per (thread,
    direction) on the client — the executor threads that pack and fetch own
    their connections thread-locally, so per-thread IS per-connection."""

    __slots__ = ("buf", "grows")

    def __init__(self, size: int = 4096) -> None:
        self.buf = bytearray(size)
        self.grows = 0

    def reserve(self, n: int) -> bytearray:
        if len(self.buf) < n:
            size = len(self.buf)
            while size < n:
                size *= 2
            self.buf = bytearray(size)
            self.grows += 1
        return self.buf


def _peer_uid(sock) -> Optional[int]:
    """UID of the unix-socket peer via SO_PEERCRED, or None when the
    platform cannot say (non-Linux): directory permissions remain the
    defense there.  Module-level so tests can stub a foreign peer."""
    if sock is None:
        return None
    try:
        creds = sock.getsockopt(
            socket.SOL_SOCKET, socket.SO_PEERCRED, struct.calcsize("3i")
        )
        _pid, uid, _gid = struct.unpack("3i", creds)
        return uid
    except (AttributeError, OSError, struct.error):
        return None


class _Slot:
    """One reply a connection owes, in the order its frames came in
    (``_Connection.slots``).  ``frame``: the wire frame to write, None
    until it is known; ``req_id``: None for a HELLO_OK or an
    ERR; ``waiting``: the launches that still carry a piece of the request
    (one, but for a request wider than a launch, whose pieces' verdicts
    gather in ``parts``); ``built``: when its launch was done with it, for
    a request that is clocked; ``conn``: None once the slot was dropped
    behind a HELLO that was refused."""

    __slots__ = ("conn", "req_id", "frame", "waiting", "parts", "built")

    def __init__(self, conn, req_id, frame=None) -> None:
        self.conn = conn
        self.req_id = req_id
        self.frame = frame
        self.waiting = 0
        self.parts = None
        self.built = None


class _Pending:
    """One decoded VERIFY/RAW request — or the ``piece``-th piece of one
    wider than a launch — between its connection's read and the launch
    that answers it into ``slot``.  ``handed``: when it was handed over,
    None for a request that is not clocked; ``alone``: it found a launch
    slot asleep while the service held at most one request more than it
    has slots, so it is launched by itself; ``wire_id``: the request id's
    four bytes as the frame carried them, which its reply carries back."""

    __slots__ = ("type_", "req_id", "wire_id", "n", "body", "conn_label",
                 "handed", "slot", "piece", "alone")

    def __init__(self, type_, req_id, n, body, conn_label, handed,
                 slot, piece=0, wire_id=None) -> None:
        self.alone = False
        self.type_ = type_
        self.req_id = req_id
        self.wire_id = _REQ_ID.pack(req_id) if wire_id is None else wire_id
        self.n = n
        self.body = body
        self.conn_label = conn_label
        self.handed = handed
        self.slot = slot
        self.piece = piece


class _Connection(asyncio.Protocol):
    """One client connection: all of it on the loop, none of it a task.

    The loop works once a socket read and once a launch, never once a
    request.  A read decodes every complete frame it holds, gives each a
    slot in ``slots`` — the replies owed, in request order — and hands all
    its requests over at once (``VerifierServer._hand_over``: they join the
    pending list with the rest of that turn of the loop, under one
    acquisition of its condition).  A finished launch fills its requests'
    slots (``VerifierServer._resolve``) and each connection it touched
    writes the run of finished slots at the head of its deque with one
    ``write`` (``_flush``): replies leave strictly in request order,
    whichever launches the requests rode.

    Memory is bounded both ways: owed ``PIPELINE_DEPTH`` replies the
    connection stops reading (what it had read waits in ``held``) until one
    is written, and a client that does not read its replies
    (``pause_writing``) is not read either.  A HELLO runs on the server's
    HELLO thread and owns a slot like any reply, so HELLO_OK never
    overtakes a RESULT; a verify must not be LAUNCHED before the HELLO that
    establishes the committee finished (it would see no keys and report
    every slot invalid), so requests behind an unresolved HELLO wait in
    ``gated`` until ``_hello_done``."""

    __slots__ = ("server", "label", "transport", "slots", "partial", "need",
                 "held", "gate", "gated", "inflight", "counted",
                 "write_paused", "finishing", "lost")

    def __init__(self, server: "VerifierServer") -> None:
        self.server = server
        self.label = f"c{next(server._conn_ids)}"
        self.transport = None
        self.slots: collections.deque = collections.deque()
        # A frame that spans reads gathers in a bytearray of its own
        # (``need`` bytes when whole; 5 while its header is short).
        self.partial: Optional[bytearray] = None
        self.need = 0
        self.held: Optional[memoryview] = None
        # The slot of the last HELLO that has not completed.
        self.gate: Optional[_Slot] = None
        self.gated: List[_Pending] = []
        # The connection's child of verifier_service_inflight (made at its
        # first request) and how many requests it holds in the gauges.
        self.inflight = None
        self.counted = 0
        self.write_paused = False
        # Nothing more is decoded (a malformed frame, a HELLO refused, the
        # client's EOF): the connection closes once ``slots`` is empty.
        self.finishing = False
        self.lost = False

    # -- asyncio.Protocol --

    def connection_made(self, transport) -> None:
        self.transport = transport
        # Trust gate first: the socket lives in a 0700 dir, but an
        # unrelated local user who still reached it (shared parent mount,
        # pre-hardening dir) must not get to submit RAW batches to the
        # warmed backend.  Same-uid and root peers only.
        uid = _peer_uid(transport.get_extra_info("socket"))
        if uid is not None and uid not in (os.getuid(), 0):
            log.warning(
                "verifier service refusing foreign-uid peer (uid %d)", uid
            )
            self._close()
            return
        self.server._conns.add(self)

    def data_received(self, data: bytes) -> None:
        # A frame that lies whole in this read is a memoryview of it: the
        # bytes the transport produced are the LAST host copy before the
        # backend packs them device-ward.  One that spans reads is copied
        # once, into ``partial``.  (Never called at PIPELINE_DEPTH: the
        # connection stops reading the moment it gets there.)
        view = memoryview(data)
        partial = self.partial
        if partial is None:
            self._receive(view)
        elif len(partial) < 5:
            self.partial = None
            self._receive(memoryview(bytes(partial) + data))
        else:
            take = self.need - len(partial)
            partial += view[:take]
            if len(partial) == self.need:
                self.partial = None
                self._receive(memoryview(partial), view[take:])

    def eof_received(self) -> bool:
        # The client will send no more; what it is owed is still written.
        self.finishing = True
        if not self.slots:
            self._close()
        return True

    def pause_writing(self) -> None:
        self.write_paused = True
        self.transport.pause_reading()

    def resume_writing(self) -> None:
        self.write_paused = False
        self._read_on()

    def connection_lost(self, exc) -> None:
        self._close()

    # -- the read side --

    def _receive(self, *views: memoryview) -> None:
        """Decode every complete frame of ``views`` (one read; two views
        where it began with the end of a frame that spans reads), give each
        a slot, and hand every request among them over at once.

        ``service_decode`` is the read in hand -> handed over, for the
        requests of the read that are clocked (one in
        ``spans.SAMPLE_ONE_IN``): its CPU clock and the profiler's
        annotation start at the first of them, and its CPU is shared by
        the requests decoded from there on."""
        read_at = time.monotonic()
        server = self.server
        slots = self.slots
        depth = server.PIPELINE_DEPTH
        label = self.label
        sampled = server.stages.sampled
        items: List[_Pending] = []
        requests = 0
        decode = None  # the spans.stage of this read's clocked requests
        for view in views:
            at, end = 0, len(view)
            while at < end and not self.finishing:
                if len(slots) >= depth:
                    self.held = view[at:]
                    break
                if end - at < 5:
                    self.partial, self.need = bytearray(view[at:]), 5
                    break
                length, type_ = _HEADER.unpack_from(view, at)
                if end - at - 5 < length:
                    self.partial = bytearray(view[at:])
                    self.need = 5 + length
                    break
                payload = view[at + 5: at + 5 + length]
                at += 5 + length
                if type_ == T_VERIFY or type_ == T_RAW:
                    rec = _IDX_REC if type_ == T_VERIFY else _RAW_REC
                    if length >= 8:
                        req_id, n = _REQUEST.unpack_from(payload)
                    if length < 8 or length - 8 != n * rec:
                        self._refuse(b"malformed verify frame")
                        break
                    slot = _Slot(self, req_id)
                    slots.append(slot)
                    requests += 1
                    handed = None
                    if sampled():
                        if decode is None:
                            decode = spans.stage(
                                "service_decode", server.stages, since=read_at)
                            decode.refs = []
                            clocked_from = requests
                            decode.__enter__()
                        decode.refs.append((label, req_id))
                        handed = read_at  # clocked; the instant comes below
                    # A request wider than what the backend warmed is cut
                    # into pieces of at most that width, each pending like
                    # a request of its own (the last, short one rides with
                    # whatever else is pending), and its reply is their
                    # verdicts joined when the last lands: no launch is
                    # ever wider than what boot compiled, so a wide request
                    # — a collector window of blocks full of signed
                    # transactions — compiles nothing.
                    cap = server._launch_cap
                    body = payload[8:]
                    wire_id = bytes(payload[:4])
                    if cap is None or n <= cap:
                        slot.waiting = 1
                        items.append(_Pending(
                            type_, req_id, n, body, label, handed, slot,
                            0, wire_id))
                        continue
                    slot.waiting = -(-n // cap)
                    slot.parts = [None] * slot.waiting
                    for piece in range(slot.waiting):
                        items.append(_Pending(
                            type_, req_id, min(cap, n - piece * cap),
                            body[piece * cap * rec: (piece + 1) * cap * rec],
                            label, handed if piece == 0 else None, slot,
                            piece, wire_id))
                elif type_ == T_HELLO:
                    n_keys = (struct.unpack_from("<H", payload)[0]
                              if length >= 2 else -1)
                    if n_keys < 0 or length != 2 + 32 * n_keys:
                        self._refuse(b"malformed hello frame")
                        break
                    keys = [bytes(payload[2 + 32 * i: 2 + 32 * (i + 1)])
                            for i in range(n_keys)]
                    if items and self.gate is None:
                        # What this read held before the HELLO does not
                        # wait for it (nor fall with it).
                        server._hand_over(items)
                        items = []
                    self.gate = slot = _Slot(self, None)
                    slots.append(slot)
                    server._loop.run_in_executor(
                        server._hello_pool, server._hello_reply, keys
                    ).add_done_callback(
                        functools.partial(self._hello_done, slot))
                else:
                    self._refuse(b"unknown frame type")
                    break
        if requests:
            server.counts.reads += 1
            # The gauges move once a read: depth = requests handed over
            # and not yet answered (pending, or riding a launch); inflight
            # splits it per client connection so one flooding validator is
            # attributable.  They come back when the replies are written
            # (``_flush``) or dropped (``_close``, ``_resolve``).
            self.counted += requests
            metrics = server.metrics
            if metrics is not None:
                if self.inflight is None:
                    self.inflight = metrics.verifier_service_inflight.labels(
                        label)
                metrics.verifier_service_queue_depth.inc(requests)
                self.inflight.inc(requests)
            if decode is not None:
                decode.riders = requests - clocked_from + 1
                decode.__exit__(None, None, None)
                for item in items:
                    if item.handed is not None:
                        item.handed = decode.end
            if self.gate is not None:
                self.gated += items
            elif items:
                server._hand_over(items)
        if len(slots) >= depth:
            self.transport.pause_reading()
        if self.finishing:
            self._flush()

    def _refuse(self, message: bytes) -> None:
        """A frame that is none of the protocol's: an ERR in its slot —
        the replies before it are written, then the ERR, then the
        connection is closed — and nothing behind it is looked at."""
        self.slots.append(_Slot(self, None, _frame(T_ERR, message)))
        self.finishing = True
        self.transport.pause_reading()

    def _hello_done(self, slot: _Slot, future) -> None:
        """On the loop: the HELLO thread is done with the HELLO of
        ``slot``."""
        frame = None
        if not future.cancelled():
            if future.exception() is None:
                frame = future.result()
            else:
                log.error("verifier service hello failed",
                          exc_info=future.exception())
        if self.lost or slot.conn is None:
            return
        if frame is None:
            self._close()
            return
        slot.frame = frame
        if frame[4] == T_ERR:
            # Rejected (committee mismatch): the connection is severed
            # after the ERR, so what was pipelined behind the HELLO is
            # dropped and must NOT burn a backend dispatch (a
            # reconnect-looping misconfigured client would otherwise cost
            # a device round-trip per queued frame).  Nothing behind an
            # unresolved HELLO has been handed over.
            dropped = 0
            while self.slots[-1] is not slot:
                behind = self.slots.pop()
                behind.conn = None
                dropped += behind.req_id is not None
            self.gated.clear()
            self.gate = self.held = None
            self.finishing = True
            self.transport.pause_reading()
            self._release(dropped)
        elif slot is self.gate:
            self.gate = None
            gated, self.gated = self.gated, []
            if gated:
                now = time.monotonic()
                for item in gated:
                    if item.handed is not None:
                        item.handed = now
                self.server._hand_over(gated)
        self._flush()

    def _read_on(self) -> None:
        """A reply was written, or the client reads again: decode what
        waited in ``held``, and read on if that leaves room."""
        if self.write_paused or self.finishing or self.lost:
            return
        depth = self.server.PIPELINE_DEPTH
        if self.held is not None and len(self.slots) < depth:
            view, self.held = self.held, None
            self._receive(view)
        if (self.held is None and len(self.slots) < depth
                and not self.finishing):
            self.transport.resume_reading()

    # -- the write side --

    def _flush(self) -> None:
        """Write the run of finished slots at the head of ``slots`` with
        one ``write``: every reply a launch finished for this connection,
        and whatever waited behind them for their turn.  (One bytes object
        a reply and one a run: a reply is a dozen bytes and a run at most
        the few a client keeps in flight on its shared connection —
        ``send`` of one buffer, where ``writelines`` of a header, an id and
        the verdicts cost the loop a tenth more a request.)"""
        slots = self.slots
        if self.lost or not slots or slots[0].frame is None:
            return
        out: list = []
        answered = signatures = 0
        clocked = []
        while slots and slots[0].frame is not None:
            slot = slots.popleft()
            out.append(slot.frame)
            if slot.req_id is not None:
                answered += 1
                signatures += len(slot.frame) - 9
                if slot.built is not None:
                    clocked.append(slot)
        self.transport.write(out[0] if len(out) == 1 else b"".join(out))
        if answered:
            # Counted for every request: sums of this thread's, which the
            # clock's stamp reads once a second.
            counts = self.server.counts
            counts.writes += 1
            counts.requests += answered
            counts.signatures += signatures
            if clocked:
                # service_reply_wait: reply built -> written, i.e. the
                # loop's wake-up, the earlier replies of this connection,
                # then the write.
                stages = self.server.stages
                written = time.monotonic()
                for slot in clocked:
                    stages.book("service_reply_wait", written,
                                written - slot.built)
                    if stages.tracer is not None:
                        stages.tracer.record_span(
                            "service_reply_wait", (self.label, slot.req_id),
                            slot.built, written)
            self._release(answered)
        if self.finishing and not slots:
            self._close()
        else:
            self._read_on()

    def _release(self, requests: int) -> None:
        """``requests`` of this connection's leave the gauges: answered,
        or dropped.  Labels are minted per connection from an unbounded
        counter, so a reconnecting fleet would grow dead
        {connection="cN"} series forever: the label goes when the
        connection is lost — after its last request came back, since a
        dec() after remove() would re-mint the dead series at -1."""
        self.counted -= requests
        if self.inflight is None:
            return
        self.server.metrics.verifier_service_queue_depth.dec(requests)
        self.inflight.dec(requests)
        if self.lost and not self.counted:
            self.server.metrics.verifier_service_inflight.remove(self.label)
            self.inflight = None

    def _close(self) -> None:
        """The connection is over (its client went, a launch of its
        requests raised, an ERR was its last reply, ``stop()``): nothing
        more is read or written.  A request that was handed over stays in
        the gauges until its launch ends (``_resolve``) — releasing it now
        would show an idle service during real device work — and every
        other one leaves them here."""
        if self.lost:
            return
        self.lost = True
        self.server._conns.discard(self)
        self.transport.close()
        for item in self.gated:
            item.slot.waiting = 0  # never handed over
        riding = sum(1 for slot in self.slots if slot.waiting)
        self.slots.clear()
        self.gated.clear()
        self.held = self.partial = self.gate = None
        self._release(self.counted - riding)


# ---------------------------------------------------------------------------
# Server


class VerifierServer:
    """One accelerator runtime serving every validator on the host."""

    # Per-connection staged request window: request N+1 is decoded and
    # handed over while N waits for or rides a launch; replies are written
    # strictly in request order.  A connection that is owed this many
    # replies is not read (``_Connection``): the bound backpressures a
    # client pipelining faster than the backend drains.
    PIPELINE_DEPTH = 8
    # Launch slots: each is a thread that launches what is pending when it
    # is free and the coalescer's rule (``_take``) lets it leave.  The
    # fewer launches are out, the more requests share one and the fewer
    # threads queue for the GIL with the loop: on the chip one slot
    # verified a third more signatures a second than two or three (PERF.md,
    # PR 25, has the pairs).  The rule collects that without giving up
    # what the slots are for — at most ONE part-full launch is on its host
    # path at a time (unpacking, packing, the jitted call: the part that
    # needs the interpreter), and the other slots carry what must not wait
    # for it: a request that goes alone (a client that keeps four requests
    # in flight, the deepest a validator's verify pipeline goes, finds
    # each launched at once and by itself at an otherwise idle service:
    # three slots and one that waits its turn), a launch that is full (a
    # wide request's pieces take every slot), the launch after a hold has
    # run out (a slow backend call holds the others up for two normal
    # launches, not for its own length), and the next part-full launch
    # where those that are out wait for their results with the GIL free
    # and it carries as many requests as they do (``_may_overlap``) — so
    # this is also the most part-full launches that are out at once.
    DISPATCHERS = 3

    def __init__(self, socket_path: str, committee_keys: Optional[Sequence[bytes]] = None,
                 backend=None, metrics=None, devices: Optional[int] = None) -> None:
        self.socket_path = socket_path
        # How many of the host's chips the backend this server builds
        # shards over (None = all of them).
        self._devices = devices
        self._backend = backend
        self._owns_backend = backend is None
        self._keys: Optional[List[bytes]] = (
            list(committee_keys) if committee_keys else None
        )
        # Optional Metrics: queue depth / per-connection in-flight gauges +
        # dispatch shape series, scrapeable when the service CLI runs with
        # --metrics-port (the fleet's verify queue was invisible before).
        self.metrics = metrics
        # Where a request's milliseconds and the process's core-seconds go,
        # by stage (spans.SERVICE_STAGES): always on, one request in
        # spans.SAMPLE_ONE_IN clocked whole, scraped through ``metrics``
        # when there is one, and written as the last 600 whole seconds of
        # time.monotonic into the report at ``stop``.
        self.counts = ServiceCounts()
        self.stages = spans.StageClock(
            spans.SERVICE_STAGES,
            ring_seconds=spans.StageClock.RING_SECONDS,
            tracer=spans.active(),
            sample_one_in=spans.SAMPLE_ONE_IN,
            stamps=ServiceCounts.STAMPS,
            read_stamps=self.counts.read,
            lag_stage="service_loop_lag",
            gc_stage="service_gc",
        )
        if metrics is not None:
            metrics.verifier_service_stages.attach(self.stages, self.counts)
        self._conn_ids = itertools.count()
        self._warmed = threading.Event()
        self._warm_lock = threading.Lock()
        # Decoded requests that no launch has taken yet, in arrival order.
        # The loop appends (``_deliver``), the dispatcher threads take
        # (``_take``), both under the condition, on which a dispatcher
        # with nothing to take sleeps.
        self._pending: collections.deque = collections.deque()
        self._pending_cond = threading.Condition()
        self._arrived: List[_Pending] = []  # read this turn of the loop
        self._idle = 0  # dispatchers asleep on the condition
        self._watching = 0  # of them, asleep until a hold runs out
        self._promised = 0  # pending requests that each woke one of them
        # The part-full launches that are out: what is pending waits for
        # them to land, for the hold of the newest to run out, or — once
        # each is in its fetch — to be as many requests as they carry
        # (``_take``).
        self._part_full: List[_Hold] = []
        self._in_service = 0  # handed over and not yet resolved (the loop's)
        self._stopping = False
        self._dispatchers: List[threading.Thread] = []
        # The most signatures one launch may hold: what the backend warmed.
        # None until it is warm; a request that arrives before that is
        # launched alone and waits for the warm-up in ``_ensure_backend``.
        self._launch_cap: Optional[int] = None
        # (the committee's key list, its rows as an array): ``_key_rows``.
        self._key_rows_cache: Optional[tuple] = None
        # Signatures a launch held -> the lanes the backend padded them to
        # (``padded_batch``, asked once a size), and the counter of the
        # difference: ``_observe``.
        self._lanes: dict = {}
        self._wasted = (
            None if metrics is None
            else metrics.verify_padding_wasted_total.labels("service"))
        # HELLO and warm-up have a thread of their own: a warm-up takes
        # minutes, and HELLOs behind it wait for it anyway.
        self._hello_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="verify-hello",
        )
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: set = set()  # every live _Connection
        self._calibration: Optional[Tuple[float, float]] = None
        # A backend that cannot warm is fatal to the service: the failing
        # thread records the cause and wakes serve_forever, which raises
        # it.
        self._fatal: Optional[BaseException] = None
        self._warm_seconds: Optional[float] = None
        # The boot's seconds by part, in the order they were spent (the
        # report's ``warm_parts``): ``run_service`` notes what it spent
        # before there was a server, ``_ensure_backend`` the rest.
        self.warm_parts: dict = {}
        self._failed = asyncio.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    # -- backend lifecycle --

    def _ensure_backend(self, keys: List[bytes]):
        # A launch of a warm service asks with the service's own key list
        # and takes no lock on its way.
        if self._warmed.is_set() and (not keys or keys is self._keys):
            return self._backend
        # The whole init+warmup runs under the lock: concurrent HELLOs from a
        # booting fleet must not race two warmups through the JAX tracer —
        # the losers just block here until the first one finishes (which is
        # exactly the contract their HELLO wants anyway).
        with self._warm_lock:
            if keys:
                if self._keys is None:
                    # First NON-EMPTY committee establishes the service key
                    # set (ADVICE r5: an early zero-key HELLO from a RAW-only
                    # client must not pin the committee to [] and poison
                    # every later client with a permanent mismatch).  If a
                    # keyless backend was already built for such a client,
                    # rebuild it around the real committee's key table.
                    self._keys = keys
                    if self._backend is not None and self._owns_backend:
                        self._warmed.clear()
                        self._backend = None
                elif self._keys != keys:
                    raise ValueError(
                        "committee mismatch: this verifier service was warmed "
                        "for a different key set"
                    )
            if self._backend is None:
                from .block_validator import TpuSignatureVerifier

                started = time.monotonic()
                self._backend = TpuSignatureVerifier(
                    mesh="auto" if self._devices is None else self._devices,
                    committee_keys=self._keys,
                )
                self._owns_backend = True
                # The key table's upload (and the kernels' modules, where
                # nothing imported them before).
                self.warm_parts["key_table_s"] = round(
                    time.monotonic() - started, 3
                )
            if not self._warmed.is_set():
                started = time.monotonic()
                try:
                    self._backend.warmup()
                    warmed = time.monotonic()
                    self._calibrate()
                    self._warm_seconds = time.monotonic() - started
                    self.warm_parts.update(
                        getattr(self._backend, "warm_parts", {}),
                        calibrate_s=round(time.monotonic() - warmed, 3),
                    )
                    self._write_report()
                except BaseException as exc:
                    self._fail(exc)
                    raise
                self._launch_cap = self._warmed_signatures()
                self.counts.roads = getattr(
                    self._backend, "road_counts", self.counts.roads)
                self._warmed.set()
            return self._backend

    def _warmed_signatures(self) -> int:
        """The most signatures one backend call can hold without reaching
        a shape the warm-up did not compile: a wider launch would compile
        for seconds in the middle of serving.  A backend that says nothing
        (a host oracle compiles nothing) gets the kernels' smallest
        bucket, which is what the JAX backend warms."""
        warmed = getattr(self._backend, "warmed_batch", None)
        if warmed is not None:
            return warmed()
        from .ops.ed25519 import BUCKETS

        return BUCKETS[0]

    def _fail(self, exc: BaseException) -> None:
        log.error("verifier service backend failed to warm", exc_info=exc)
        self._fatal = exc
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._failed.set)

    def _write_report(self) -> None:
        """Leave what the warmed backend says about its device and kernels
        (``TpuSignatureVerifier.device_report``) next to the socket: only
        this process may touch the chip, so a launcher that wants to show
        the device reads it here.  Written once warm and again at ``stop``,
        when the per-kernel dispatch counts cover the whole run.
        Host-oracle stand-ins have no report."""
        describe = getattr(self._backend, "device_report", None)
        if describe is None or self._warm_seconds is None:
            return
        report = describe()
        report["warm_seconds"] = round(self._warm_seconds, 3)
        report["warm_parts"] = dict(self.warm_parts)
        report["calibration"] = self._calibration
        report["stages"] = self.stages.export()
        path = report_path(self.socket_path)
        with open(path + ".tmp", "w") as f:
            json.dump(report, f, indent=1)
        os.replace(path + ".tmp", path)

    def _calibrate(self) -> None:
        """Time the warmed backend once: a 1-signature launch (fixed cost)
        and a 256-signature launch (marginal cost), down the road a launch
        of VERIFY frames takes (``_wire_rows``, then the backend's call).
        Shared with every client via HELLO_OK; ``_hold_s`` reads it."""
        keys = self._keys or []
        if not keys:
            return
        import numpy as np

        n = 256
        records = np.zeros((n, _IDX_REC), np.uint8)
        records[:, 0] = np.arange(n) % len(keys)  # under 256: the low byte
        body = memoryview(records.tobytes())

        def launch(k: int) -> float:
            started = time.monotonic()
            self._backend.verify_signatures(*self._wire_rows([_Pending(
                T_VERIFY, 0, k, body[:k * _IDX_REC], "", None, None)]))
            return time.monotonic() - started

        fixed = launch(1)
        self._calibration = (fixed, max(0.0, (launch(n) - fixed) / n))
        log.info(
            "verifier service calibrated: %.1f ms fixed + %.1f µs/sig",
            1e3 * self._calibration[0], 1e6 * self._calibration[1],
        )

    def prewarm(self) -> None:
        """Warm before the first client connects (committee known at boot)."""
        if self._keys is None:
            raise ValueError("prewarm requires committee keys")
        self._ensure_backend(self._keys)

    # -- HELLO --

    def _resolved_backend(self) -> str:
        """The platform the warmed backend ACTUALLY dispatches on —
        advertised to every client via HELLO_OK so a launcher can refuse a
        fleet whose service has no accelerator behind it.  Backends without
        the introspection hook are host oracles: "cpu"."""
        resolve = getattr(self._backend, "resolved_backend", None)
        return "cpu" if resolve is None else str(resolve())

    def _hello_reply(self, keys: List[bytes]) -> bytes:
        """HELLO handling, on its own thread: warm (or adopt/upgrade) the
        backend and frame the reply — HELLO_OK with the calibration + resolved-backend
        advertisement, or ERR on a committee mismatch (which also severs the
        connection client-side).  The backend suffix rides only behind a
        calibration, and an UNcalibrated reply stays the old empty payload
        so it is never mistaken for a 16-byte calibration."""
        try:
            self._ensure_backend(keys)
        except ValueError as exc:
            return _frame(T_ERR, str(exc).encode())
        payload = b""
        if self._calibration is not None:
            payload = struct.pack("<dd", *self._calibration)
            payload += self._resolved_backend().encode("ascii", "replace")
        return _frame(T_HELLO_OK, payload)

    # -- the coalescer: pending requests -> launches --

    def _hand_over(self, items: List[_Pending]) -> None:
        """The requests of one read, in arrival order (on the loop).  They
        join the pending list with everything else this turn of the loop
        reads, once the turn is over (``_deliver``): a dispatcher that is
        woken for the first must not get the GIL at the next socket's
        ``recv`` and leave with a launch of one."""
        if not self._arrived:
            self._loop.call_soon(self._deliver)
        self._arrived += items

    def _deliver(self) -> None:
        """Everything the connections read in one turn of the loop joins
        the pending list under one acquisition of the condition, and wakes
        a slot for what may leave (``_take``)."""
        items, self._arrived = self._arrived, []
        with self._pending_cond:
            if self._stopping:
                return  # never launched: ``stop()`` has closed its connection
            shared = False
            for item in items:
                self._in_service += 1
                self._pending.append(item)
                if (self._idle > self._promised
                        and self._in_service <= self.DISPATCHERS + 1):
                    # A slot is asleep and nobody has woken it yet: this
                    # request does.  While the service holds at most one
                    # request more than it has slots, each can have a
                    # launch to itself (one waits its turn) and sharing
                    # gains nothing: it is launched alone, with the kernel
                    # of its own shape (what arrives before that slot is up
                    # does not ride with it).  With more in the service a
                    # queue is draining, and the request goes with others.
                    item.alone = True
                    self._promised += 1
                    self._pending_cond.notify()
                else:
                    shared = True
            if shared:
                self._wake_one()

    def _wake_one(self) -> None:
        """Wake a sleeping slot for pending requests that go with others:
        where they may leave now, or where no slot is awake at the instant
        their hold runs out (the one woken will be).  What is held and
        watched wakes nobody: a thread that wakes to find nothing to launch
        only meets the loop at the GIL."""
        if self._idle > self._promised and (
                not self._watching or self._may_leave()):
            self._pending_cond.notify()

    def _may_leave(self) -> bool:
        """Whether ``_take`` could let pending requests that go with others
        leave: no hold is in force, they may overlap what is out, or they
        fill a launch (where one of them goes alone the counts say so too
        soon, and a slot wakes in vain)."""
        if self._held_for(time.monotonic()) <= 0.0:
            return True
        if self._may_overlap(len(self._pending)):
            return True
        return sum(item.n for item in self._pending) >= self._launch_cap

    def _may_overlap(self, riders: int) -> bool:
        """Whether a launch of ``riders`` requests may leave while
        part-full launches are out: each of them is in its fetch — its
        thread waits for the result with the GIL free, so no two host
        paths overlap — and ``riders`` is at least the requests they
        carry.  Fewer would only cut what is in flight into more and
        smaller launches, each of which costs the one interpreter its
        fixed share (on the chip ending every hold at its fetch read 727
        launches a second of 9 requests where 395 of 19.5 verified 18%
        more: PERF.md, PR 47); as many are the answers to a whole launch
        come back, and have nothing to wait for."""
        out = 0
        for hold in self._part_full:
            if not hold.fetching:
                return False
            out += hold.riders
        return riders >= out

    def _held_for(self, now: float) -> float:
        """Seconds for which the newest part-full launch that is out still
        holds what is pending; none out, or zero and less: nothing is
        held."""
        return max(
            (hold.until for hold in self._part_full), default=now) - now

    def _hold_s(self) -> float:
        """How long a part-full launch holds what arrives behind it, if it
        does not land before: twice what the service timed a full launch to
        last when it calibrated (``_calibrate``: one thread at an idle
        service).  Twice, because under load the same launch lasts up to a
        half longer than that (it queues for the GIL with the loop), and a
        bound as tight as the calibration itself would run out in most
        cycles and put two part-full launches out again; not more, so that
        a launch ten times slower than it should be costs the others two
        normal launches, not its own length.  An uncalibrated service (no
        committee keys) holds nothing."""
        if self._calibration is None or self._launch_cap is None:
            return 0.0
        fixed, per_signature = self._calibration
        return 2.0 * (fixed + self._launch_cap * per_signature)

    def _take(self, now: float) -> Optional[Tuple[List[_Pending], Optional[_Hold]]]:
        """The coalescer's rule (Nagle's, for launches).  What would ride
        the next launch is everything pending, in arrival order, while the
        signatures sum to at most what the backend warmed: whole requests
        only, and the first whatever it holds (only a request that arrived
        before the backend was warm can be over the cap:
        ``_Connection._receive`` cuts the others).  It leaves now if

        * its first request is marked ``alone`` (``_deliver``): that one
          goes by itself, whatever else is out;
        * it is *full*: it ends because the next request does not fit (or
          the sum is the cap), not because the list ran out.  A full
          launch shares its fixed cost as widely as a launch can, so it
          never waits;
        * *drained*: no part-full launch — one that left under this
          clause or the next two — is out;
        * *expired*: the newest part-full launch has been out for longer
          than ``_hold_s``;
        * *overlapped*: every part-full launch that is out has said that it
          is in its fetch (``_enters_fetch``) and the requests that would
          ride are at least as many as those they carry
          (``_may_overlap``).  So one part-full launch is on its host path
          at a time — unpacking, packing, the jitted call: what needs the
          interpreter — and a launch's wait for its result hides under the
          next one's packing only where the next is a whole launch's worth
          of answers come back, not a few early ones.  Behind a backend
          that never says (a host oracle) one part-full launch is out at a
          time, as before.

        Otherwise it stays pending, but for a request in it that goes
        alone.  Returns the launch and its hold (None: it holds nothing),
        or None.  Called with the condition held and
        something pending."""
        pending = self._pending
        first = pending[0]
        if first.alone:
            pending.popleft()
            return self._leave([first], _ALONE, now)
        cap = self._launch_cap
        riders, total, full = 1, first.n, False
        if cap is not None:
            for item in itertools.islice(pending, 1, None):
                if item.alone:
                    break
                if total + item.n > cap:
                    full = True
                    break
                total += item.n
                riders += 1
            full = full or total >= cap
        if full:
            why = _FULL
        elif not self._part_full:
            why = _DRAINED
        elif self._held_for(now) <= 0.0:
            why = _EXPIRED
        elif self._may_overlap(riders):
            why = _OVERLAPPED
        else:
            for item in pending:
                if item.alone:
                    pending.remove(item)
                    return self._leave([item], _ALONE, now)
            return None
        return self._leave(
            [pending.popleft() for _ in range(riders)], why, now)

    def _leave(self, batch: List[_Pending], why: int, now: float):
        """``batch`` leaves because ``why`` (an index of
        ``ServiceCounts.LEFT``): counted, its hold begun where it is
        part-full, and another slot woken for what it left pending."""
        self.counts.left[why] += 1
        hold = None
        if why == _ALONE:
            self._promised -= 1
        elif why != _FULL:
            hold = _Hold(now + self._hold_s(), len(batch))
            self._part_full.append(hold)
        if self._pending:
            self._wake_one()
        return batch, hold

    def _dispatch_loop(self) -> None:
        """A launch slot: launch what the rule lets leave (``_take``), and
        where that lands what it lets leave then; sleep while nothing is
        pending, and while what is pending is held — then for no longer
        than the hold has left, which is the one timer here.  A request
        that finds a slot asleep wakes it if it may leave (``_deliver``)."""
        self.stages.adopt_thread()
        taken = None
        while True:
            if taken is None:
                with self._pending_cond:
                    taken = self._sleep_until_taken()
                if taken is None:
                    return  # ``stop()``
            taken = self._launch(*taken)

    def _sleep_until_taken(self):
        """Called with the condition held: wait on it until ``_take`` lets
        a launch leave, and return that; None once the service stops."""
        while not self._stopping:
            left = None
            if self._pending:
                now = time.monotonic()
                taken = self._take(now)
                if taken is not None:
                    return taken
                left = self._held_for(now)
            watching = left is not None
            self._idle += 1
            self._watching += watching
            self._pending_cond.wait(left)
            self._watching -= watching
            self._idle -= 1
        return None

    def _enters_fetch(self, hold: _Hold) -> None:
        """The backend says that the part-full launch of ``hold`` is handed
        to the device and that this thread, which made it, is about to
        block for the result (``spans.request_fetch``): its host path is
        over, and a sleeping slot is woken if what is pending may now leave
        beside it (``_may_overlap``) — on this thread and not at the loop's
        next turn, as where a launch lands (``_launch``)."""
        with self._pending_cond:
            hold.fetching = True
            if self._pending and not self._stopping:
                self._wake_one()

    def _launch(self, batch: List[_Pending], hold: Optional[_Hold]):
        """One backend call for every request of ``batch``, then each
        request's reply to its slot, with one wake-up of the loop.  The
        thread works for the launch from here to ``built``: every
        ``spans.request_stage`` below, in the backend too, names the stage
        it is in, for the clocked requests that ride it.  A launch that
        raises fails each of its requests and nothing else.

        Where the launch lands its ``hold`` ends, on this thread and not at
        the loop's next turn (what a launch carries is decided by who gets
        the GIL next: the loop's turn would add its lag to every hold), and
        this slot, which is free and awake, takes at once what the rule now
        lets leave: returned, for ``_dispatch_loop`` to launch."""
        stages = self.stages
        clocked = [((item.conn_label, item.req_id), item.handed)
                   for item in batch if item.handed is not None]
        if clocked:
            stages.begin_launch(clocked)
            spans.request_stage("service_unpack")
        replies = error = built = None
        if hold is not None:
            spans.on_fetch(functools.partial(self._enters_fetch, hold))
        try:
            replies = self._verify_batch(batch)
        except Exception as exc:  # noqa: BLE001 - ``_resolve`` logs it
            error = exc
        finally:
            spans.on_fetch(None)
            if clocked:
                built = stages.end_launch(len(batch))
        taken = None
        with self._pending_cond:
            if hold is not None:
                self._part_full.remove(hold)
            if self._pending and not self._stopping:
                taken = self._take(time.monotonic())
        try:
            self._loop.call_soon_threadsafe(
                self._resolve, batch, replies, error, built)
        except RuntimeError:
            pass  # the loop closed under a launch that stop() did not await
        if error is None and self.metrics is not None:
            self._observe(batch)
        return taken

    def _observe(self, batch: List[_Pending]) -> None:
        """The shape of the launch just made, for the scrape: after its
        replies are on their way, so that it costs no request anything.
        (The service owns the device, so it, not the jax-free clients, is
        where launch shape and padding waste are measurable.)"""
        metrics = self.metrics
        total = sum(item.n for item in batch)
        metrics.verify_dispatch_batch_size.observe(total)
        metrics.verifier_service_coalesced_requests.observe(len(batch))
        lanes = self._lanes.get(total)
        if lanes is None:
            padder = getattr(self._backend, "padded_batch", None)
            lanes = self._lanes[total] = (
                total if padder is None else padder(total))
        self._wasted.inc(max(0, lanes - total))

    def _resolve(self, batch: List[_Pending], replies, error, built) -> None:
        """On the loop: a launch is done.  Its requests' slots are filled
        and every connection it touched writes what it now can, once.  A
        launch that raised closes exactly the connections whose requests
        rode it; a request whose connection is lost is dropped here."""
        self.counts.launches += 1
        self._in_service -= len(batch)
        if error is not None:
            log.error("verifier service dispatch failed", exc_info=error)
        touched = set()
        for i, item in enumerate(batch):
            slot = item.slot
            conn = slot.conn
            slot.waiting -= 1
            if conn.lost:
                if not slot.waiting:
                    conn._release(1)
                continue
            if error is not None:
                conn._close()
                continue
            req_id, verdicts = replies[i]
            if item.handed is not None:
                slot.built = built
            if slot.parts is not None:
                slot.parts[item.piece] = verdicts
                if slot.waiting:
                    continue
                verdicts = b"".join(slot.parts)
            slot.frame = (
                _HEADER.pack(4 + len(verdicts), T_RESULT) + req_id + verdicts)
            touched.add(conn)
        for conn in touched:
            conn._flush()

    def _key_rows(self, keys: List[bytes]):
        """(K + 1, 32) uint8: the committee's keys by wire index, and below
        them the all-zero key that an out-of-range index gets (it cannot
        verify: that slot is rejected, not the request or the launch)."""
        import numpy as np  # the service's side only: validators stay off it

        rows = self._key_rows_cache
        if rows is None or rows[0] is not keys:
            table = np.zeros((len(keys) + 1, 32), np.uint8)
            if keys:
                table[:-1] = np.frombuffer(
                    b"".join(keys), np.uint8).reshape(len(keys), 32)
            rows = self._key_rows_cache = (keys, table)
        return rows[1]

    def _wire_rows(self, batch: List[_Pending]):
        """The public keys, digests and signatures of every request of
        ``batch``, in order, as three columns of one row a signature:
        slices of the wire records, which nothing walks.  Requests of one
        frame type that follow each other share one record array.

        A launch of VERIFY frames alone keeps its keys as the 2-byte
        indices the records carry, where the backend takes them so
        (``indexed_keys``: the JAX backend writes them into its blob as
        they are; an index out of range is a rejected lane).  In every
        other launch — a RAW frame among them, a backend that wants keys —
        a VERIFY record's index becomes its key's row with one gather."""
        import numpy as np

        by_index = getattr(self._backend, "indexed_keys", None)
        columns: Tuple[list, list, list] = ([], [], [])
        at, riders = 0, len(batch)
        while at < riders:
            type_ = batch[at].type_
            start, end = at, at + 1
            while end < riders and batch[end].type_ == type_:
                end += 1
            body = (batch[at].body if end == at + 1
                    else b"".join([item.body for item in batch[at:end]]))
            at = end
            if type_ == T_VERIFY:
                rows = np.frombuffer(body, np.uint8).reshape(-1, _IDX_REC)
                index = rows[:, :2].view("<u2")[:, 0]
                pks = None
                if by_index is not None and start == 0 and end == riders:
                    pks = by_index(index)
                if pks is None:
                    table = self._key_rows(self._keys or [])
                    pks = table[np.minimum(index, len(table) - 1)]
                digests, sigs = rows[:, 2:34], rows[:, 34:]
            else:
                rows = np.frombuffer(body, np.uint8).reshape(-1, _RAW_REC)
                pks, digests, sigs = rows[:, :32], rows[:, 32:64], rows[:, 64:]
            for column, part in zip(columns, (pks, digests, sigs)):
                column.append(part)
        return [c[0] if len(c) == 1 else np.concatenate(c) for c in columns]

    def _verify_batch(self, batch: List[_Pending]) -> List[tuple]:
        """Verify every signature of every request of ``batch`` with one
        backend call and return each request's reply parts, ``(req_id
        bytes, verdict bytes)`` — ``_resolve`` frames them.  The signatures
        travel as arrays from the wire records to the backend
        (``_wire_rows``) and the verdicts back as the bytes of the array
        the JAX backend fetched (a host oracle's list is made one first):
        nothing here runs once a signature."""
        import numpy as np

        backend = self._ensure_backend(self._keys or [])
        pks, digests, sigs = self._wire_rows(batch)
        # The backend's time is the fetch's (device run + transfer + getting
        # the GIL back; a host oracle's whole work), but for the stages it
        # names itself: the JAX backend packs and launches first
        # (ops/ed25519.py) and fetches in VerifyDispatch.result.
        spans.request_stage("service_fetch")
        oks = backend.verify_signatures(pks, digests, sigs)
        spans.request_stage("service_reply_build")
        total = len(sigs)
        if len(oks) != total:
            raise RuntimeError(
                f"backend returned {len(oks)} verdicts for {total} signatures"
            )
        verdicts = np.asarray(oks, bool).view(np.uint8).tobytes()
        replies, at = [], 0
        for item in batch:
            replies.append((item.wire_id, verdicts[at: at + item.n]))
            at += item.n
        return replies

    # -- lifecycle --

    @staticmethod
    def _secure_socket_dir(socket_path: str) -> None:
        """Bind-time trust check: the socket's parent
        directory must be OURS — created 0700 when absent, refused outright
        when another uid owns it (a foreign owner can rename/replace the
        socket under us), and stripped of group/other bits when we own a
        looser one.  SO_PEERCRED at accept covers the remaining window."""
        parent = os.path.dirname(os.path.abspath(socket_path)) or "."
        if not os.path.isdir(parent):
            os.makedirs(parent, mode=0o700, exist_ok=True)
        st = os.stat(parent)
        if st.st_uid != os.getuid():
            raise PermissionError(
                f"verifier socket dir {parent!r} is owned by uid {st.st_uid}"
                f" (we are {os.getuid()}): refusing to bind into a directory"
                " another user controls"
            )
        if st.st_mode & 0o077:
            os.chmod(parent, 0o700)

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._secure_socket_dir(self.socket_path)
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)
        self._dispatchers = [
            threading.Thread(
                target=self._dispatch_loop, name=f"verify-dispatch_{i}",
                daemon=True,
            )
            for i in range(self.DISPATCHERS)
        ]
        for thread in self._dispatchers:
            thread.start()
        self._server = await self._loop.create_unix_server(
            lambda: _Connection(self), path=self.socket_path
        )
        # Belt to the dir's braces: same-uid-or-root only, and the peercred
        # gate enforces it even where a path somehow stays reachable.
        os.chmod(self.socket_path, 0o600)
        log.info("verifier service listening on %s", self.socket_path)

    async def serve_forever(self) -> None:
        await self.start()
        if self._keys is not None and not self._warmed.is_set():
            # Warm while validators boot: their HELLOs block until done.
            await asyncio.get_running_loop().run_in_executor(
                self._hello_pool, self.prewarm
            )
            log.info("verifier service warmed (%d committee keys)",
                     len(self._keys))
        async with self._server:
            serving = asyncio.ensure_future(self._server.serve_forever())
            failed = asyncio.ensure_future(self._failed.wait())
            try:
                await asyncio.wait(
                    (serving, failed), return_when=asyncio.FIRST_COMPLETED
                )
            finally:
                serving.cancel()
                failed.cancel()
        if self._fatal is not None:
            raise RuntimeError(
                "verifier service backend failed to warm"
            ) from self._fatal

    async def stop(self) -> None:
        # Nothing pending is launched from here on, and the dispatchers end
        # once the launch they are in returns.
        with self._pending_cond:
            self._stopping = True
            abandoned = list(self._pending) + self._arrived
            self._pending.clear()
            self._pending_cond.notify_all()
        if self._server is not None:
            self._server.close()
            # Sever live client connections first: ``wait_closed`` waits
            # for every one of them.  (A client that does not read its
            # replies would never let ``close`` flush them.)
            for conn in list(self._conns):
                if conn.write_paused:
                    conn.transport.abort()
                conn._close()
            await self._server.wait_closed()
        for item in abandoned:  # as a launch's end would (``_resolve``)
            item.slot.waiting -= 1
            if not item.slot.waiting:
                item.slot.conn._release(1)
        self._hello_pool.shutdown(wait=False)
        self._write_report()
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)


# ---------------------------------------------------------------------------
# Client


class RemoteSignatureVerifier(SignatureVerifier):
    """Validator-side stub: forwards batches to the host's verifier service.

    jax-free by design — the validator process stays import-light and leans
    on the service's single warmed runtime.  Three roads to it, and the
    request itself chooses (no option):

    * ``verify_signatures`` (the gateway's transfer check, every deferred
      fallback) keeps one connection a calling thread (``threading.local``),
      one request on it at a time, and owns the reconnect-retry budget.
    * ``verify_signatures_async`` sends a request whose signers are all in
      the committee table (a VERIFY frame: short, never cut, answered by
      the launch that takes it) down the ONE connection every such request
      of this client shares (:class:`_SharedConnection`), without waiting
      for the replies owed on it: the service finds several frames in a
      read and writes several replies at once.
    * A request with any other signer (a RAW frame: a collector window of
      blocks full of signed transfers, hundreds of signatures wide and cut
      across launches by the service) keeps a pooled connection of its own.
      The service answers a connection strictly in request order, so down
      the shared socket a handful of committee signatures would wait behind
      every piece of the wide request sent before it.
    """

    backend_label = "tpu-remote"

    # Reconnect-retry budget per request: a service restart mid-burst is
    # routine (seconds of downtime), a fleet boot race is routine — neither
    # is an outage.  Only exhausting the budget propagates, and the circuit
    # breaker (``tpu`` flavor) takes it from there.
    MAX_ATTEMPTS = 4
    RETRY_BASE_BACKOFF_S = 0.05
    RETRY_MAX_BACKOFF_S = 1.0

    # Bound on the pooled connections of the async dispatch path: one a
    # request in flight that may not ride the shared connection (a RAW
    # frame, or a VERIFY frame beyond the shared connection's depth);
    # matches the deepest pipeline window the collector runs
    # (verify_pipeline.py).
    MAX_POOLED_CONNS = 4

    def __init__(self, socket_path: Optional[str] = None,
                 committee_keys: Optional[Sequence[bytes]] = None,
                 timeout_s: float = 300.0,
                 metrics=None,
                 max_attempts: Optional[int] = None) -> None:
        self.socket_path = socket_path or os.environ[ENV_SOCKET]
        self._keys = list(committee_keys or [])
        self._index = {pk: i for i, pk in enumerate(self._keys)}
        self.timeout_s = timeout_s
        self.metrics = metrics
        # Requests sent, every road (``_count_request``, from whichever
        # thread sends): the validator's stage clock stamps its growth.
        self.requests_sent = 0
        self._sent_lock = threading.Lock()
        self.max_attempts = max_attempts or self.MAX_ATTEMPTS
        self._retry_rng = random.Random(0x5E7C1E27)
        self._tls = threading.local()
        # Connection pool for the STAGED path (verify_signatures_async): the
        # submit and the fetch may run on different executor threads, so the
        # in-flight handle carries its connection instead of leaning on the
        # thread-local one.  _pool_size counts live pooled conns (idle +
        # checked out) so the pool stays bounded across threads.
        self._pool_conns: List[socket.socket] = []
        self._pool_lock = threading.Lock()
        self._pool_size = 0
        # The connection the VERIFY frames of the staged path share; made
        # at the first of them and again after it was lost.
        self._shared: Optional[_SharedConnection] = None
        self._async_req_ids = itertools.count(1)
        # (fixed_dispatch_s, per_sig_s) as measured by the SERVICE on its
        # own warmed backend (HELLO_OK payload); None until first connect.
        self.calibration: Optional[Tuple[float, float]] = None
        # The service's resolved platform from the HELLO_OK backend suffix
        # ("cpu" | "tpu" | ...); None against a pre-r6 service or before the
        # first connect.
        self.advertised_backend: Optional[str] = None

    # -- socket plumbing --

    def _connect(self) -> socket.socket:
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.settimeout(self.timeout_s)
        conn.connect(self.socket_path)
        payload = struct.pack("<H", len(self._keys)) + b"".join(self._keys)
        frame = _frame(T_HELLO, payload)
        conn.sendall(frame)
        self._count_wire("sent", len(frame))
        type_, reply = self._read_frame(conn)
        if type_ != T_HELLO_OK:
            conn.close()
            raise VerifierProtocolError(
                "verifier service rejected hello: "
                f"{bytes(reply).decode(errors='replace')}"
            )
        if len(reply) >= 16:
            self.calibration = struct.unpack_from("<dd", reply)
        # No suffix (pre-r6 service, or uncalibrated) = backend UNKNOWN —
        # overwrite, don't keep a replaced service's answer.
        self.advertised_backend = (
            bytes(reply[16:]).decode("ascii", errors="replace")
            if len(reply) > 16
            else None
        )
        return conn

    def _conn(self) -> socket.socket:
        conn = getattr(self._tls, "conn", None)
        if conn is None:
            conn = self._connect()
            self._tls.conn = conn
            self._tls.req_id = 0
        return conn

    def _count_wire(self, direction: str, nbytes: int) -> None:
        if self.metrics is not None:
            self.metrics.verify_wire_bytes_total.labels(direction).inc(nbytes)

    def _count_request(self, path: str) -> None:
        """One request sent down ``path`` (``shared`` / ``pooled`` /
        ``sync``); one that is re-run or deferred counts again, as
        ``sync``."""
        with self._sent_lock:
            self.requests_sent += 1
        if self.metrics is not None:
            self.metrics.verifier_client_requests_total.labels(path).inc()

    def _wire(self, attr: str) -> _WireBuffer:
        """Per-thread reusable buffer, one per direction: ``pack`` must stay
        intact across the retry loop's reconnects (which read HELLO_OK into
        ``recv``), and each thread owns its connections so per-thread is
        per-connection."""
        wire = getattr(self._tls, attr, None)
        if wire is None:
            wire = _WireBuffer()
            setattr(self._tls, attr, wire)
        return wire

    @staticmethod
    def _recv_exact(conn: socket.socket, view: memoryview) -> None:
        got, n = 0, len(view)
        while got < n:
            r = conn.recv_into(view[got:])
            if r == 0:
                raise ConnectionError("verifier service closed the connection")
            got += r

    def _read_frame(self, conn: socket.socket):
        """Read one frame into the per-thread recv buffer: the payload lands
        via ``recv_into`` (one kernel→buffer move, no per-chunk bytes
        concatenation) and is returned as a memoryview.  The view aliases
        the reusable buffer — callers consume it before this thread's next
        read, which every call site does (verdict bytes become a list, ERR
        text becomes a string, calibration floats are unpacked)."""
        wire = self._wire("recv")
        head = memoryview(wire.reserve(5))[:5]
        self._recv_exact(conn, head)
        length, type_ = struct.unpack_from("<IB", head)
        payload = memoryview(wire.reserve(length))[:length]
        if length:
            self._recv_exact(conn, payload)
        self._count_wire("recv", 5 + length)
        return type_, payload

    def _roundtrip(self, frame, req_id: int):
        """Send one request with bounded reconnect-retries.

        The round-5 reconnect-ONCE policy made a service restart during a
        fleet burst a fatal outage: every in-flight thread burned its single
        retry against the not-yet-listening socket and propagated.  Retries
        are bounded (``max_attempts``) with jittered exponential backoff so
        a thundering herd of dispatch threads does not hammer the recovering
        service in lockstep; each torn-down connection counts on
        ``verifier_reconnect_total``.  Protocol rejections
        (:class:`VerifierProtocolError`) are never retried, and exhausting
        the budget propagates — the circuit breaker takes it from
        there."""
        backoff = self.RETRY_BASE_BACKOFF_S
        for attempt in range(self.max_attempts):
            try:
                conn = self._conn()
                conn.sendall(frame)
                self._count_wire("sent", len(frame))
                type_, payload = self._read_frame(conn)
                break
            except VerifierProtocolError:
                raise
            except (ConnectionError, OSError, socket.timeout):
                stale = getattr(self._tls, "conn", None)
                self._tls.conn = None
                if stale is not None:
                    try:
                        stale.close()
                    except OSError:
                        pass
                if self.metrics is not None:
                    self.metrics.verifier_reconnect_total.inc()
                if attempt + 1 >= self.max_attempts:
                    raise
                time.sleep(jittered_backoff(backoff, self._retry_rng))
                backoff = min(backoff * 2.0, self.RETRY_MAX_BACKOFF_S)
        if type_ == T_ERR:
            raise VerifierProtocolError(
                "verifier service error: "
                f"{bytes(payload).decode(errors='replace')}"
            )
        assert type_ == T_RESULT
        (echoed,) = struct.unpack_from("<I", payload)
        assert echoed == req_id, "verifier service response out of order"
        return payload[4:]

    # -- connection pool (async dispatch path) --

    def _pool_checkout(self) -> Optional[socket.socket]:
        """An idle pooled connection, a fresh one, or None when the pool is
        at its live-connection cap (idle + checked out) — the caller then
        falls back to the sync path's thread-local connection."""
        with self._pool_lock:
            if self._pool_conns:
                return self._pool_conns.pop()
            if self._pool_size >= self.MAX_POOLED_CONNS:
                return None
            self._pool_size += 1
        try:
            return self._connect()
        except BaseException:
            with self._pool_lock:
                self._pool_size -= 1
            raise

    def _pool_checkin(self, conn: socket.socket) -> None:
        with self._pool_lock:
            if len(self._pool_conns) < self.MAX_POOLED_CONNS:
                self._pool_conns.append(conn)
                return
            self._pool_size -= 1
        try:
            conn.close()
        except OSError:
            pass

    def _pool_discard(self, conn: socket.socket) -> None:
        with self._pool_lock:
            self._pool_size -= 1
        try:
            conn.close()
        except OSError:
            pass

    # -- the shared connection (async dispatch path, VERIFY frames) --

    def _send_shared(self, frame, req_id, n, args):
        """``frame`` down the shared connection and its handle, or None
        where that connection already owes as many replies as a validator's
        verify pipeline goes deep (the caller takes a pooled connection:
        nothing here waits for the service to read).  The connect, and its
        wait for HELLO_OK, runs outside every lock — threads that find the
        service silent wait side by side, as the sync path's do — and of
        two that raced the first connection is kept."""
        shared = self._shared
        if shared is None or shared.lost:
            fresh = _SharedConnection(self._connect(), self.metrics)
            with self._pool_lock:
                shared = self._shared
                if shared is None or shared.lost:
                    shared = self._shared = fresh
            if shared is not fresh:
                fresh.sock.close()
        handle = _SharedDispatch(self, shared, req_id, n, args)
        if not shared.send(handle, frame):
            return None
        self._count_wire("sent", len(frame))
        self._count_request("shared")
        return handle

    # -- frame building --

    def _pack_request(self, public_keys, digests, signatures, req_id, n):
        """Frame one request directly into this thread's reusable wire
        buffer and return a memoryview of it, or None when the batch cannot
        ride the service wire format (non-digest messages -> local oracle).

        This is the zero-copy half of the request direction: each digest /
        signature / key is slice-assigned into the buffer exactly ONCE, the
        header and per-record indices are packed in place, and the socket
        sends straight from the buffer — no ``b"".join`` body, no
        header+payload concatenation, no per-dispatch allocation once the
        buffer has grown to the steady-state batch size."""
        if not all(len(d) == 32 for d in digests):
            # The service's fixed wire format carries 32-byte digests
            # (every deployed call site signs blake2b-256); anything else
            # is a test exotica — verify locally on the CPU oracle.
            return None
        indices = [self._index.get(pk) for pk in public_keys]
        indexed = all(i is not None for i in indices)
        rec = _IDX_REC if indexed else _RAW_REC
        total = 5 + 8 + n * rec
        buf = self._wire("pack").reserve(total)
        struct.pack_into(
            "<IBII", buf, 0,
            total - 5, T_VERIFY if indexed else T_RAW, req_id, n,
        )
        off = 13
        if indexed:
            for idx, digest, sig in zip(indices, digests, signatures):
                struct.pack_into("<H", buf, off, idx)
                buf[off + 2:off + 34] = digest
                buf[off + 34:off + 98] = sig
                off += _IDX_REC
        else:
            for pk, digest, sig in zip(public_keys, digests, signatures):
                buf[off:off + 32] = pk
                buf[off + 32:off + 64] = digest
                buf[off + 64:off + 128] = sig
                off += _RAW_REC
        return memoryview(buf)[:total]

    # -- SignatureVerifier surface --

    def warmup(self) -> None:
        """Connect + HELLO: returns once the service's runtime is warm."""
        self._conn()

    def verify_signatures_async(self, public_keys, digests, signatures):
        """Staged dispatch: send the request now and read the reply at
        ``result()`` — submit and fetch may run on different executor
        threads, so the handle carries its connection.  A VERIFY frame
        (every signer in the committee table) goes down the connection all
        such requests of this client share, up to ``VerifyPipeline.
        MAX_DEPTH`` owed on it; a RAW frame, and a VERIFY frame beyond that
        depth, takes a pooled connection of its own.  Either way several
        overlap through ONE warmed backend.  A connect or send that fails
        falls back to the deferred sync path, which owns the full
        reconnect-retry budget."""
        n = len(signatures)
        if n == 0:
            return CompletedDispatch([])
        req_id = next(self._async_req_ids)
        frame = self._pack_request(
            public_keys, digests, signatures, req_id, n
        )
        if frame is None:
            return DeferredDispatch(
                CpuSignatureVerifier().verify_signatures,
                public_keys, digests, signatures,
            )
        try:
            if frame[4] == T_VERIFY:
                handle = self._send_shared(
                    frame, req_id, n, (public_keys, digests, signatures))
                if handle is not None:
                    return handle
            conn = self._pool_checkout()
        except VerifierProtocolError:
            raise
        except (ConnectionError, OSError, socket.timeout):
            # No reconnect count for a connect that failed: the deferred
            # sync fallback runs the full retry loop and accounts each
            # torn-down attempt itself.  (A send that failed on the shared
            # connection took it down, once, with everything owed on it.)
            conn = None
        if conn is None:
            # Pool exhausted or unreachable: the sync path (thread-local
            # connection, bounded retries) carries the batch at fetch time.
            return DeferredDispatch(
                self.verify_signatures, public_keys, digests, signatures
            )
        try:
            conn.sendall(frame)
            self._count_wire("sent", len(frame))
        except (ConnectionError, OSError, socket.timeout):
            self._pool_discard(conn)
            if self.metrics is not None:
                self.metrics.verifier_reconnect_total.inc()
            return DeferredDispatch(
                self.verify_signatures, public_keys, digests, signatures
            )
        self._count_request("pooled")
        return _RemoteDispatch(
            self, conn, req_id, n, public_keys, digests, signatures
        )

    def verify_signatures(self, public_keys, digests, signatures) -> List[bool]:
        n = len(signatures)
        if n == 0:
            return []
        self._tls.req_id = req_id = getattr(self._tls, "req_id", 0) + 1
        frame = self._pack_request(
            public_keys, digests, signatures, req_id, n
        )
        if frame is None:
            return CpuSignatureVerifier().verify_signatures(
                public_keys, digests, signatures
            )
        self._count_request("sync")
        oks = self._roundtrip(frame, req_id)
        assert len(oks) == n
        return [bool(b) for b in oks]


class _RemoteDispatch:
    """An in-flight request to the verifier service on a pooled connection
    of its own (a RAW frame, or a VERIFY frame the shared connection had no
    room for).

    ``result()`` reads the reply off the handle's own connection and returns
    it to the pool.  A connection failure at fetch time is NOT fatal to the
    batch: the connection is discarded and the whole request re-runs through
    the sync path's bounded reconnect-retry budget (the service may have
    restarted mid-flight; re-verifying is idempotent)."""

    __slots__ = ("_client", "_conn", "_req_id", "_n", "_args")

    def __init__(self, client, conn, req_id, n, public_keys, digests,
                 signatures) -> None:
        self._client = client
        self._conn = conn
        self._req_id = req_id
        self._n = n
        self._args = (public_keys, digests, signatures)

    @property
    def req_id(self) -> int:
        """What the service's spans of this request carry too."""
        return self._req_id

    def result(self) -> List[bool]:
        client = self._client
        try:
            type_, payload = client._read_frame(self._conn)
        except VerifierProtocolError:
            client._pool_discard(self._conn)
            raise
        except (ConnectionError, OSError, socket.timeout):
            client._pool_discard(self._conn)
            if client.metrics is not None:
                client.metrics.verifier_reconnect_total.inc()
            return client.verify_signatures(*self._args)
        if type_ == T_ERR:
            client._pool_discard(self._conn)
            raise VerifierProtocolError(
                "verifier service error: "
                f"{bytes(payload).decode(errors='replace')}"
            )
        client._pool_checkin(self._conn)
        assert type_ == T_RESULT
        (echoed,) = struct.unpack_from("<I", payload)
        assert echoed == self._req_id, "verifier service response out of order"
        oks = payload[4:]
        assert len(oks) == self._n
        return [bool(b) for b in oks]

    def abandon(self) -> None:
        """Release without fetching (the flush was cancelled): a connection
        with an unread response must never return to the pool — the next
        request on it would read a stale frame — so it is discarded, which
        also keeps the pool's live-connection count honest."""
        self._client._pool_discard(self._conn)


_RERUN = object()  # the connection was lost under the request
_DROPPED = object()  # abandoned: its reply is read in its turn and dropped


class _SharedConnection:
    """The one connection a client's VERIFY frames share: requests go down
    it as they are submitted, ``owed`` holds their handles in the wire's
    order, and the service answers in that order.

    A handle's ``result()`` may run on any thread and in any order, so the
    reads are serialized: ONE thread at a time is the reader (``reading``),
    takes whatever the socket holds with one ``recv_into`` and gives every
    complete frame of it, copied out of ``buf``, to the handle at the head
    of ``owed`` — a client answered with one write reads its replies with
    one ``recv``, and the next fetches find theirs filled — until its own
    handle is answered.  A handle that finds another thread reading waits
    on ``cond`` and returns the moment its own reply is filled, whoever
    read it.  A connection that fails — send, read, timeout — or answers
    ERR is torn down once (``lose``): every handle still owed a reply
    re-runs through the sync path's bounded reconnect-retry at its own
    ``result()``, and the client connects anew at its next request.

    It holds no reference to its client, so a client that is dropped with
    nothing in flight takes its socket with it."""

    __slots__ = ("sock", "metrics", "cond", "owed", "reading", "lost",
                 "buf", "end")

    def __init__(self, sock: socket.socket, metrics) -> None:
        self.sock = sock
        self.metrics = metrics
        self.cond = threading.Condition()
        self.owed: collections.deque = collections.deque()
        self.reading = False
        self.lost = False
        # The reader's alone: buf[:end] is received and not yet a whole
        # frame.
        self.buf = bytearray(4096)
        self.end = 0

    def send(self, handle: "_SharedDispatch", frame) -> bool:
        """``frame`` onto the wire and ``handle`` to the tail of ``owed``,
        under one lock: a handle's place in line is its frame's place on
        the wire.  False, and nothing sent, where the connection owes the
        deepest window a validator runs (under what the service reads of
        one connection before it answers, ``VerifierServer.
        PIPELINE_DEPTH``: the service is still reading and ``sendall`` does
        not wait for it) or was just lost."""
        with self.cond:
            if self.lost or len(self.owed) >= VerifyPipeline.MAX_DEPTH:
                return False
            self.owed.append(handle)
            try:
                self.sock.sendall(frame)
            except (ConnectionError, OSError, socket.timeout):
                self.owed.pop()
                self.lose()
                raise
        return True

    def lose(self, count: bool = True) -> None:
        """Tear the connection down, once: whatever is owed re-runs on the
        sync path, and the teardown counts as one reconnect."""
        with self.cond:
            if self.lost:
                return
            self.lost = True
            for handle in self.owed:
                handle._fill(_RERUN)
            self.owed.clear()
            self.cond.notify_all()
        try:
            # A thread that still waits in ``recv_into`` (a send failed
            # first) is woken by the shutdown, not by the close.
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        if count and self.metrics is not None:
            self.metrics.verifier_reconnect_total.inc()

    def fetch(self, handle: "_SharedDispatch") -> None:
        """Returns with ``handle`` filled, by this thread or by another."""
        cond = self.cond
        with cond:
            while handle._reply is None and self.reading:
                cond.wait()
            if handle._reply is not None:
                return
            self.reading = True
        try:
            while handle._reply is None:
                self._read()
        except (ConnectionError, OSError, socket.timeout):
            self.lose()
        except BaseException:
            self.lose()  # it may stand in the middle of a frame
            raise
        finally:
            with cond:
                self.reading = False
                cond.notify_all()

    def _read(self) -> None:
        """One ``recv_into`` of whatever the socket holds, and every
        complete frame of it to the handle it answers."""
        buf, end = self.buf, self.end
        if end == len(buf):  # one frame, wider than the buffer
            self.buf = buf = buf + bytearray(len(buf))
        got = self.sock.recv_into(memoryview(buf)[end:])
        if got == 0:
            raise ConnectionError("verifier service closed the connection")
        if self.metrics is not None:
            self.metrics.verify_wire_bytes_total.labels("recv").inc(got)
        end += got
        at = 0
        with self.cond:
            while end - at >= 5 and not self.lost:
                length, type_ = _HEADER.unpack_from(buf, at)
                if end - at - 5 < length:
                    break
                self._answer(type_, bytes(buf[at + 5: at + 5 + length]))
                at += 5 + length
        if 0 < at < end:  # a frame that spans reads: its head to the front
            buf[: end - at] = buf[at:end]
        self.end = end - at

    def _answer(self, type_: int, payload: bytes) -> None:
        """One frame to the handle it answers: the head of ``owed`` (with
        ``cond`` held).  ``payload`` is a copy: ``buf`` is overwritten by
        the next read, and a handle may fetch long after.  The service
        closes behind an ERR, and a frame that is not the head's reply
        leaves nothing to trust: either way the request at the head fails
        alone and the rest re-run."""
        if not self.owed:
            raise ConnectionError(
                "verifier service sent a reply nobody waits for")
        head = self.owed.popleft()
        if (type_ == T_RESULT and len(payload) == 4 + head._n
                and struct.unpack_from("<I", payload)[0] == head._req_id):
            head._fill(payload[4:])
            self.cond.notify_all()
            return
        head._fill(
            VerifierProtocolError(
                "verifier service error: "
                f"{payload.decode(errors='replace')}")
            if type_ == T_ERR else
            AssertionError("verifier service response out of order"))
        self.lose(count=False)


class _SharedDispatch:
    """An in-flight request on the client's shared connection.

    ``result()`` takes the reply from the connection (``_SharedConnection.
    fetch``), whichever thread read it.  A connection failure is NOT fatal
    to the batch: the whole request re-runs through the sync path's bounded
    reconnect-retry budget (the service may have restarted mid-flight;
    re-verifying is idempotent).  ``_reply``: None while owed; the verdict
    bytes; the exception ``result()`` raises; ``_RERUN``; ``_DROPPED``."""

    __slots__ = ("_client", "_conn", "_req_id", "_n", "_args", "_reply")

    def __init__(self, client, conn, req_id, n, args) -> None:
        self._client = client
        self._conn = conn
        self._req_id = req_id
        self._n = n
        self._args = args
        self._reply = None

    @property
    def req_id(self) -> int:
        """What the service's spans of this request carry too."""
        return self._req_id

    def _fill(self, reply) -> None:
        """With the connection's condition held; the first to fill wins."""
        if self._reply is None:
            self._reply = reply

    def result(self) -> List[bool]:
        if self._reply is None:
            self._conn.fetch(self)
        reply = self._reply
        if reply is _RERUN:
            return self._client.verify_signatures(*self._args)
        if isinstance(reply, BaseException):
            raise reply
        return [bool(b) for b in reply]

    def abandon(self) -> None:
        """Release without fetching (the flush was cancelled).  The
        connection carries other requests and stays: this reply is read in
        its turn, by whoever reads then, and dropped.  Where nobody is owed
        anything else on it, nobody would read: it is discarded, as a
        pooled connection with an unread reply is."""
        conn = self._conn
        with conn.cond:
            if self._reply is not None:
                return  # answered already, or lost with its connection
            self._reply = _DROPPED
            orphaned = all(h._reply is _DROPPED for h in conn.owed)
        if orphaned:
            conn.lose(count=False)


def require_accelerator() -> str:
    """Resolve this process's JAX platform, refusing the host as a silent
    stand-in: with ``JAX_PLATFORMS`` unset JAX falls back to the CPU with a
    warning when it finds no accelerator, and a service started that way
    would serve every signature from the host under a device's name.  The
    CPU is accepted only when asked for by name (``JAX_PLATFORMS=cpu`` — the
    test tier)."""
    import jax

    platform = str(jax.default_backend())
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "verifier-service: JAX found no accelerator and fell back to "
            "the CPU; refusing to serve (set JAX_PLATFORMS=cpu to run the "
            "service on the host on purpose)"
        )
    return platform


def run_service(socket_path: str, committee_keys: Optional[Sequence[bytes]] = None,
                metrics_port: Optional[int] = None,
                devices: Optional[int] = None) -> None:
    """Blocking entry point for the CLI subcommand.  With ``metrics_port``
    the service also exposes /metrics + /healthz (queue depth, per-connection
    in-flight, dispatch batch sizes, padding waste, the stages of a request,
    JAX compiles and host<->device bytes) and probes its event loop's lag.
    ``MYSTICETI_TRACE`` records every stage as a span, as in a validator.

    SIGTERM stops the server and returns, so the interpreter exits in order
    and the JAX runtime lets go of the chip before the next holder starts
    (the runner stops the service this way, SIGKILL only after a timeout)."""
    import signal

    started = time.monotonic()
    platform = require_accelerator()
    runtime_s = time.monotonic() - started
    from .ops import ed25519  # noqa: F401 - the backend's import, timed

    modules_s = time.monotonic() - started - runtime_s
    log.info("verifier service starting on platform %r", platform)

    async def _main() -> None:
        metrics = None
        if metrics_port:
            from .metrics import Metrics, serve_metrics

            metrics = Metrics()
            await serve_metrics(metrics, "0.0.0.0", metrics_port)
        server = VerifierServer(
            socket_path, committee_keys=committee_keys, metrics=metrics,
            devices=devices,
        )
        # Importing JAX and starting its client on the device; importing
        # the kernels' modules (their constant tables are built with
        # Python ints and uploaded).
        server.warm_parts["runtime_s"] = round(runtime_s, 3)
        server.warm_parts["modules_s"] = round(modules_s, 3)
        # service_gc: a collection stops the dispatcher threads and the
        # loop at once, whichever thread trips it.  A hook of the process,
        # so it is set here and not by every VerifierServer a test builds.
        gc.callbacks.append(server.stages.gc_callback)
        # The loop reads every request and writes every reply while the
        # dispatcher threads compete with it for the GIL: its lag is the
        # part of a round trip that no stage of a request sees.  The probe books
        # into the stage clock alone (scraped as
        # verifier_service_stage_seconds{stage="service_loop_lag"}; the
        # probe's own prometheus series and percentile sort would run on
        # the loop at every tick), and its tick is what stamps the ring's
        # seconds with the requests answered and the CPU used.
        probe = LoopLagProbe(
            interval_s=0.1, on_lag=server.stages.loop_lag
        ).start()
        if metrics is not None:
            # This is the one process that compiles and transfers: the
            # mysticeti_jax_* and device-transfer series count here.
            from .ops.ed25519 import install_device_attribution

            install_device_attribution(metrics)
        serving = asyncio.ensure_future(server.serve_forever())
        asyncio.get_running_loop().add_signal_handler(
            signal.SIGTERM, serving.cancel
        )
        try:
            await serving
        except asyncio.CancelledError:
            await server.stop()
        finally:
            gc.callbacks.remove(server.stages.gc_callback)
            probe.stop()

    spans.start_from_env()

    try:
        asyncio.run(_main())
    except KeyboardInterrupt:
        pass
    finally:
        spans.stop_from_env()
