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
    is pending when they come free, up to the bucket the backend warmed,
    and verify them with ONE backend call (group commit: no timer, a
    request that finds the service idle is launched at once, alone).
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
import contextlib
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
from .verify_pipeline import CompletedDispatch, DeferredDispatch
from .tracing import logger
from .utils.tasks import spawn_logged

log = logger(__name__)

T_HELLO = 1
T_VERIFY = 2
T_RAW = 3
T_HELLO_OK = 128
T_RESULT = 129
T_ERR = 255

_IDX_REC = 2 + 32 + 64  # u16 idx | digest | sig
_RAW_REC = 32 + 32 + 64
# Stands where spans.stage would, for a request that is not clocked.
_NOT_CLOCKED = contextlib.nullcontext()

ENV_SOCKET = "MYSTICETI_VERIFIER_SOCKET"

# VerifierProtocolError (re-exported above from block_validator): the service
# answered but REJECTED the request.  Excluded from the client's retry loop
# AND from the circuit breaker — a misconfigured validator fails fast
# instead of hammering the service or silently degrading to the oracle.


def report_path(socket_path: str) -> str:
    """Where a service listening on ``socket_path`` leaves its device and
    kernel report (``VerifierServer._write_report``)."""
    return socket_path + ".json"


def _frame(type_: int, payload: bytes) -> bytes:
    """Small-frame builder (HELLO, HELLO_OK, ERR).  The hot paths — VERIFY
    requests client-side, RESULT replies service-side — do NOT come through
    here: they pack into reusable buffers / scatter-gather parts so payload
    bytes are copied at most once per direction (see ``_WireBuffer`` and
    ``VerifierServer._reply_writer``)."""
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


def _abandoned_reply(fut: asyncio.Future, cleanup) -> None:
    """Completion hook for a dispatch whose connection died before its reply
    could be written: retrieve the exception (so asyncio never logs it as
    never-retrieved at GC) and only then release the service gauges."""
    if not fut.cancelled() and fut.exception() is not None:
        log.error(
            "verifier service dispatch failed after client disconnect",
            exc_info=fut.exception(),
        )
    if cleanup is not None:
        cleanup()


class _Pending:
    """One decoded VERIFY/RAW request between its connection's reader and
    the launch that answers it.  ``future`` is what the connection's reply
    queue awaits; ``handed`` is when it was handed over, None for a request
    that is not clocked; ``alone``: it found a launch slot asleep while the
    service held at most one request more than it has slots, so it is
    launched by itself."""

    __slots__ = ("type_", "req_id", "n", "body", "conn_label", "handed",
                 "future", "alone")

    def __init__(self, type_, req_id, n, body, conn_label, handed,
                 future) -> None:
        self.alone = False
        self.type_ = type_
        self.req_id = req_id
        self.n = n
        self.body = body
        self.conn_label = conn_label
        self.handed = handed
        self.future = future


# ---------------------------------------------------------------------------
# Server


class VerifierServer:
    """One accelerator runtime serving every validator on the host."""

    # Per-connection staged request window: the reader decodes request N+1
    # while N waits for or rides a launch; replies are written strictly in
    # request order by a dedicated writer task.  The bound backpressures a
    # client pipelining faster than the backend drains.
    PIPELINE_DEPTH = 8
    # Launch slots: each is a thread that takes everything pending when it
    # comes free.  The fewer there are, the more requests share a launch
    # and the fewer threads queue for the GIL with the loop: on the chip
    # one slot verified a third more signatures a second than two or three
    # (PERF.md, PR 25, has the pairs).  But with one slot launches run one
    # after another, so a slow backend call makes the service stop-and-wait
    # and holds up every connection, and with two a client that keeps four
    # requests in flight (the deepest a validator's verify pipeline goes)
    # finds two of them merged; with three each of its requests is launched
    # at once and alone, as a lightly loaded service should.
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
        self.stages = spans.StageClock(
            spans.SERVICE_STAGES,
            ring_seconds=spans.StageClock.RING_SECONDS,
            tracer=spans.active(),
            sample_one_in=spans.SAMPLE_ONE_IN,
        )
        if metrics is not None:
            metrics.verifier_service_stages.attach(self.stages)
        self._conn_ids = itertools.count()
        self._warmed = threading.Event()
        self._warm_lock = threading.Lock()
        # Decoded requests that no launch has taken yet, in arrival order.
        # The loop appends (``_submit``), the dispatcher threads take
        # (``_take``), both under the condition, on which a dispatcher
        # with nothing to take sleeps.
        self._pending: collections.deque = collections.deque()
        self._pending_cond = threading.Condition()
        self._idle = 0  # dispatchers asleep on the condition
        self._promised = 0  # pending requests that each woke one of them
        self._in_service = 0  # handed over and not yet resolved (the loop's)
        self._stopping = False
        self._dispatchers: List[threading.Thread] = []
        # The most signatures one launch may hold: what the backend warmed.
        # None until it is warm; a request that arrives before that is
        # launched alone and waits for the warm-up in ``_ensure_backend``.
        self._launch_cap: Optional[int] = None
        # (the committee's key list, its rows as an array): ``_key_rows``.
        self._key_rows_cache: Optional[tuple] = None
        # HELLO and warm-up have a thread of their own: a warm-up takes
        # minutes, and HELLOs behind it wait for it anyway.
        self._hello_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="verify-hello",
        )
        self._server: Optional[asyncio.AbstractServer] = None
        self._writers: set = set()
        self._calibration: Optional[Tuple[float, float]] = None
        # A backend that cannot warm is fatal to the service: the failing
        # thread records the cause and wakes serve_forever, which raises
        # it.
        self._fatal: Optional[BaseException] = None
        self._warm_seconds: Optional[float] = None
        self._failed = asyncio.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    # -- backend lifecycle --

    def _ensure_backend(self, keys: List[bytes]):
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
                        self._backend = None
                        self._warmed.clear()
                elif self._keys != keys:
                    raise ValueError(
                        "committee mismatch: this verifier service was warmed "
                        "for a different key set"
                    )
            if self._backend is None:
                from .block_validator import TpuSignatureVerifier

                self._backend = TpuSignatureVerifier(
                    mesh="auto" if self._devices is None else self._devices,
                    committee_keys=self._keys,
                )
                self._owns_backend = True
            if not self._warmed.is_set():
                started = time.monotonic()
                try:
                    self._backend.warmup()
                    self._calibrate()
                    self._warm_seconds = time.monotonic() - started
                    self._write_report()
                except BaseException as exc:
                    self._fail(exc)
                    raise
                self._launch_cap = self._warmed_signatures()
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
        report["calibration"] = self._calibration
        report["stages"] = self.stages.export()
        path = report_path(self.socket_path)
        with open(path + ".tmp", "w") as f:
            json.dump(report, f, indent=1)
        os.replace(path + ".tmp", path)

    def _calibrate(self) -> None:
        """Time the warmed backend once: a 1-signature dispatch (fixed cost)
        and a 256-signature dispatch (marginal cost), on the deployed
        committee-indexed path.  Shared with every client via HELLO_OK."""
        keys = self._keys or []
        if not keys:
            return
        import numpy as np

        # As a launch hands them over (``_wire_rows``): rows of arrays.
        n = 256
        pks = self._key_rows(keys)[np.arange(n) % len(keys)]
        digests, sigs = np.zeros((n, 32), np.uint8), np.zeros((n, 64), np.uint8)
        t0 = time.monotonic()
        self._backend.verify_signatures(pks[:1], digests[:1], sigs[:1])
        fixed = time.monotonic() - t0
        t0 = time.monotonic()
        self._backend.verify_signatures(pks, digests, sigs)
        batch_t = time.monotonic() - t0
        self._calibration = (fixed, max(0.0, (batch_t - fixed) / n))
        log.info(
            "verifier service calibrated: %.1f ms fixed + %.1f µs/sig",
            1e3 * self._calibration[0], 1e6 * self._calibration[1],
        )

    def prewarm(self) -> None:
        """Warm before the first client connects (committee known at boot)."""
        if self._keys is None:
            raise ValueError("prewarm requires committee keys")
        self._ensure_backend(self._keys)

    # -- connection handling --

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        # Trust gate first: the socket lives in a 0700 dir,
        # but an unrelated local user who still reached it (shared parent
        # mount, pre-hardening dir) must not get to submit RAW batches to
        # the warmed backend.  Same-uid and root peers only.
        uid = _peer_uid(writer.get_extra_info("socket"))
        if uid is not None and uid not in (os.getuid(), 0):
            log.warning(
                "verifier service refusing foreign-uid peer (uid %d)", uid
            )
            writer.close()
            return
        # Staged per-connection request pipeline: the reader decodes and
        # hands over request N+1 while request N waits for or rides a
        # launch; a dedicated writer task emits replies strictly in request
        # order (the protocol contract clients rely on, whichever launches
        # the requests rode), so the service is no stop-and-wait RPC for a
        # client that pipelines its frames.
        loop = asyncio.get_running_loop()
        self._writers.add(writer)
        conn_label = f"c{next(self._conn_ids)}"
        replies: asyncio.Queue = asyncio.Queue(maxsize=self.PIPELINE_DEPTH)
        reply_task = spawn_logged(
            self._reply_writer(replies, writer, conn_label), log,
            name="verifier-replies",
        )

        def _accounted():
            metrics = self.metrics
            if metrics is None:
                return None
            # Depth = requests handed over and not yet answered (pending,
            # or riding a launch); inflight splits it per client connection
            # so one flooding validator is attributable.  Decremented by
            # the writer once the reply is built (cleanup runs even when
            # the launch raised).
            metrics.verifier_service_queue_depth.inc()
            metrics.verifier_service_inflight.labels(conn_label).inc()

            def _done():
                metrics.verifier_service_queue_depth.dec()
                metrics.verifier_service_inflight.labels(conn_label).dec()

            return _done

        # A pipelined client may send VERIFY frames behind a HELLO without
        # waiting for HELLO_OK; the HELLO runs on a thread of its own, so a
        # verify must not be LAUNCHED before the HELLO that establishes the
        # committee finished (it would see no keys and report every slot
        # invalid).  Replies stay ordered by the queue; the hand-over is
        # gated on the connection's last unresolved HELLO only.
        last_hello: Optional[asyncio.Future] = None

        async def _after_hello(gate, type_, req_id, n, body, clocked):
            try:
                hello_frame = await asyncio.shield(gate)
            except Exception:  # noqa: BLE001 - HELLO's own reply carries it
                hello_frame = None
            if hello_frame is None or hello_frame[4] == T_ERR:
                # The HELLO was rejected (committee mismatch) or crashed:
                # the connection is being severed and this reply would be
                # discarded in drain mode — do NOT burn a backend dispatch
                # for it (a reconnect-looping misconfigured client would
                # otherwise cost a device round-trip per queued frame).
                return None
            return await self._submit(
                loop, type_, req_id, n, body, conn_label,
                time.monotonic() if clocked else None,
            )

        try:
            while True:
                try:
                    header = await reader.readexactly(5)
                except asyncio.IncompleteReadError:
                    return
                t_header = time.monotonic()
                if reply_task.done():
                    return  # writer died (client gone, backend crash)
                length, type_ = struct.unpack("<IB", header)
                payload = await reader.readexactly(length) if length else b""
                if type_ == T_HELLO:
                    n_keys = (
                        struct.unpack_from("<H", payload)[0]
                        if length >= 2 else -1
                    )
                    if n_keys < 0 or length != 2 + 32 * n_keys:
                        await replies.put(
                            (_frame(T_ERR, b"malformed hello frame"),
                             None, True)
                        )
                        return
                    keys = [
                        bytes(payload[2 + 32 * i: 2 + 32 * (i + 1)])
                        for i in range(n_keys)
                    ]
                    # HELLO replies ride the same in-order queue as results:
                    # a client that pipelines frames must never see HELLO_OK
                    # overtake an earlier RESULT.
                    fut = loop.run_in_executor(
                        self._hello_pool, self._hello_reply, keys
                    )
                    last_hello = fut
                    await replies.put((fut, None, False))
                elif type_ in (T_VERIFY, T_RAW):
                    # service_decode: header read -> frame checked and
                    # handed over (the CPU clock and the profiler's
                    # annotation start here, with the payload in hand: no
                    # await lies between this line and the hand-over).
                    # One request in spans.SAMPLE_ONE_IN is clocked, whole:
                    # ``decode`` is None for the others.
                    malformed = False
                    with (spans.stage("service_decode", self.stages,
                                      since=t_header)
                          if self.stages.sampled()
                          else _NOT_CLOCKED) as decode:
                        if length >= 8:
                            req_id, n = struct.unpack_from("<II", payload)
                            if decode is not None:
                                decode.ref = (conn_label, req_id)
                            # memoryview, not a bytes slice: the request
                            # body is the bulk of every frame, and the
                            # per-record digest/sig slices below stay views
                            # too — the payload bytes the reader produced
                            # are the LAST host copy before the backend
                            # packs them device-ward.
                            body = memoryview(payload)[8:]
                            rec = _IDX_REC if type_ == T_VERIFY else _RAW_REC
                        if length < 8 or len(body) != n * rec:
                            malformed = True
                        elif last_hello is not None and last_hello.done():
                            rejected = last_hello.cancelled() or (
                                last_hello.exception() is not None
                                or last_hello.result()[4] == T_ERR
                            )
                            if rejected:
                                # The writer is severing after the HELLO's
                                # ERR: frames pipelined behind it must not
                                # burn backend dispatches for replies that
                                # will be discarded in drain mode.
                                return
                            last_hello = None  # accepted: no more gating
                        if not malformed:
                            done = _accounted()
                    if malformed:
                        await replies.put(
                            (_frame(T_ERR, b"malformed verify frame"),
                             None, True)
                        )
                        return
                    if last_hello is not None:
                        # Awaited by the reply writer in order, which
                        # observes its exception.
                        fut = asyncio.ensure_future(_after_hello(
                            last_hello, type_, req_id, n, body,
                            decode is not None,
                        ))
                    else:
                        fut = self._submit(
                            loop, type_, req_id, n, body, conn_label,
                            None if decode is None else decode.end,
                        )
                    await replies.put((fut, done, False))
                else:
                    await replies.put(
                        (_frame(T_ERR, b"unknown frame type"), None, True)
                    )
                    return
        except (ConnectionResetError, BrokenPipeError, OSError):
            return
        finally:
            # Let the writer drain everything already submitted, then stop.
            try:
                replies.put_nowait(None)
            except asyncio.QueueFull:
                reply_task.cancel()
            try:
                await reply_task
            except asyncio.CancelledError:
                reply_task.cancel()
            except Exception:  # noqa: BLE001 - writer logged its own failure
                pass
            # Anything left unqueued-for-write still owes its cleanup, but
            # its launch may still be running on a dispatcher: releasing
            # the gauges now would show an idle service during real device
            # work, and abandoning the future would leave its exception
            # unretrieved.  Defer both to the dispatch's own completion.
            abandoned = []
            while True:
                try:
                    item = replies.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if item is None:
                    continue
                frame, cleanup, _close_after = item
                if asyncio.isfuture(frame):
                    abandoned.append((frame, cleanup))
                elif cleanup is not None:
                    cleanup()

            def _remove_label() -> None:
                # Labels are minted per connection from an unbounded counter;
                # a reconnecting fleet would otherwise grow dead
                # {connection="cN"} series in the registry forever.
                if self.metrics is not None:
                    try:
                        self.metrics.verifier_service_inflight.remove(
                            conn_label
                        )
                    except KeyError:
                        pass  # connection closed before its first verify

            if abandoned:
                # The label must outlive every deferred cleanup: a dec()
                # after remove() would re-mint the dead series at -1 and
                # leak it forever.  The LAST abandoned dispatch to complete
                # removes it (done-callbacks run on the loop thread, so the
                # countdown needs no lock).
                remaining = {"n": len(abandoned)}

                def _finish(fut, cleanup) -> None:
                    _abandoned_reply(fut, cleanup)
                    remaining["n"] -= 1
                    if remaining["n"] == 0:
                        _remove_label()

                for fut, cleanup in abandoned:
                    fut.add_done_callback(
                        lambda f, cleanup=cleanup: _finish(f, cleanup)
                    )
            else:
                _remove_label()
            self._writers.discard(writer)
            writer.close()

    async def _reply_writer(self, replies: asyncio.Queue,
                            writer: asyncio.StreamWriter,
                            conn_label: str = "") -> None:
        """Emit queued replies in request order; ``None`` ends the stream.
        Queue items are ``(frame_or_future, cleanup, close_after)``.  A
        dispatch failure or a dead client socket flips to drain mode —
        remaining cleanups still run (gauge hygiene) but nothing is written,
        and the transport is closed so the reader unblocks.

        A reply is either a prebuilt ``bytes`` frame (HELLO_OK, ERR) or a
        ``(type, parts, built)`` tuple from the verify path (``built``: when
        its launch was done with it, None for a request that is not
        clocked): a fresh 5-byte header
        rides ``writer.writelines`` with the parts as-is — scatter-gather,
        no header+payload concatenation per reply.  The header must be a
        fresh immutable object per reply: since 3.12 the selector transport
        may hold a zero-copy view of writelines' buffers under
        backpressure, so a reused mutable scratch could be rewritten while
        frame N still sits unsent in the transport buffer."""
        dead = False
        stages = self.stages
        while True:
            item = await replies.get()
            if item is None:
                return
            frame, cleanup, close_after = item
            try:
                if asyncio.isfuture(frame):
                    try:
                        frame = await frame
                    except Exception:  # noqa: BLE001 - logged, conn severed
                        log.exception("verifier service dispatch failed")
                        frame = None
                if dead or frame is None or writer.is_closing():
                    # (closing: stop() severed the connection under a
                    # launch; a write to its transport would raise.)
                    dead = True
                    writer.close()
                    continue
                if isinstance(frame, tuple):
                    type_, parts, built = frame
                else:
                    type_, parts = frame[4], None
                if type_ == T_ERR:
                    # Protocol errors sever the connection after the reply
                    # (the pre-pipeline contract), wherever they were built.
                    close_after = True
                try:
                    if parts is not None:
                        header = struct.pack(
                            "<IB", sum(len(p) for p in parts), type_
                        )
                        writer.writelines((header, *parts))
                    else:
                        writer.write(frame)
                    await writer.drain()
                    if parts is not None:
                        # Counted for every request: two sums of this
                        # thread's, which the clock's stamp reads once a
                        # second.
                        stages.requests += 1
                        stages.signatures += len(parts[1])
                    if parts is not None and built is not None:
                        # service_reply_wait: reply built -> written, i.e.
                        # the loop's wake-up, the earlier replies of this
                        # connection, then the write.
                        written = time.monotonic()
                        stages.book(
                            "service_reply_wait", written, written - built
                        )
                        tracer = stages.tracer
                        if tracer is not None:
                            tracer.record_span(
                                "service_reply_wait",
                                (conn_label,
                                 struct.unpack("<I", parts[0])[0]),
                                built, written,
                            )
                except (ConnectionResetError, BrokenPipeError, OSError):
                    dead = True
                    continue
                if close_after:
                    dead = True
                    writer.close()
            finally:
                if cleanup is not None:
                    cleanup()

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

    def _submit(self, loop, type_: int, req_id: int, n: int, body,
                conn_label: str, handed: Optional[float]) -> asyncio.Future:
        """Hand a decoded request over (on the loop): it joins the pending
        list, and the future resolves to its reply, ``(T_RESULT, parts,
        built)``, once the launch that took it is done.  ``handed``: the
        instant, for a request that is clocked.

        A request wider than what the backend warmed is cut here into
        pieces of at most that width, each pending like a request of its
        own (the last, short one rides with whatever else is pending), and
        its reply is their verdicts rejoined: no launch is ever wider than
        what boot compiled, so a wide request — a collector window of
        blocks full of signed transactions — compiles nothing."""
        cap = self._launch_cap
        if cap is None or n <= cap:
            return self._enqueue(
                loop, type_, req_id, n, body, conn_label, handed)
        rec = _IDX_REC if type_ == T_VERIFY else _RAW_REC
        pieces = [
            self._enqueue(
                loop, type_, req_id, min(cap, n - at),
                body[at * rec: (at + cap) * rec], conn_label,
                handed if at == 0 else None,
            )
            for at in range(0, n, cap)
        ]
        return asyncio.ensure_future(self._rejoin(pieces))

    @staticmethod
    async def _rejoin(pieces: List[asyncio.Future]) -> tuple:
        """The reply of a request that was cut into ``pieces``: verdicts in
        order; a clocked request's ``built`` is its first piece's (the
        others are not clocked)."""
        results = await asyncio.gather(*pieces)
        _, (req_id, _), built = results[0]
        return (T_RESULT,
                (req_id, b"".join(parts[1] for _, parts, _ in results)),
                built)

    def _enqueue(self, loop, type_: int, req_id: int, n: int, body,
                 conn_label: str, handed: Optional[float]) -> asyncio.Future:
        future = loop.create_future()
        item = _Pending(type_, req_id, n, body, conn_label, handed, future)
        with self._pending_cond:
            if self._stopping:
                future.cancel()
            else:
                self._in_service += 1
                self._pending.append(item)
                if self._idle > self._promised:
                    # A slot is asleep and nobody has woken it yet: this
                    # request does.  While the service holds at most one
                    # request more than it has slots, each can have a
                    # launch to itself (one waits its turn) and sharing
                    # gains nothing: it is launched alone, with the kernel
                    # of its own shape (what arrives before that slot is up
                    # does not ride with it).  With more in the service,
                    # the slot takes all that is pending when it is up: a
                    # queue is draining.
                    if self._in_service <= self.DISPATCHERS + 1:
                        item.alone = True
                        self._promised += 1
                    self._pending_cond.notify()
        return future

    def _take(self) -> List[_Pending]:
        """Everything pending, in arrival order, while the signatures sum
        to at most what the backend warmed: whole requests only, and the
        first whatever it holds (only a request that arrived before the
        backend was warm can be over the cap: ``_submit`` cuts the others).
        A request
        marked ``alone`` (``_submit``) goes alone, whichever slot gets to
        it first.  Called with the condition held and something pending."""
        pending = self._pending
        first = pending.popleft()
        batch = [first]
        cap = self._launch_cap
        if first.alone:
            self._promised -= 1
        elif cap is not None:
            total = first.n
            while (pending and not pending[0].alone
                   and total + pending[0].n <= cap):
                total += pending[0].n
                batch.append(pending.popleft())
        return batch

    def _dispatch_loop(self) -> None:
        """A launch slot: take what is pending, launch it, again; sleep
        only when nothing is pending.  No timer anywhere: a launch holds
        what queued while every slot was busy, and a request that finds a
        slot asleep wakes it (``_submit``)."""
        self.stages.adopt_thread()
        cond, pending = self._pending_cond, self._pending
        while True:
            with cond:
                while not pending:
                    if self._stopping:
                        return
                    self._idle += 1
                    cond.wait()
                    self._idle -= 1
                batch = self._take()
            self._launch(batch)

    def _launch(self, batch: List[_Pending]) -> None:
        """One backend call for every request of ``batch``, then each
        request's reply to its future, with one wake-up of the loop.  The
        thread works for the launch from here to ``built``: every
        ``spans.request_stage`` below, in the backend too, names the stage
        it is in, for the clocked requests that ride it.  A launch that
        raises fails each of its requests and nothing else."""
        stages = self.stages
        clocked = [((item.conn_label, item.req_id), item.handed)
                   for item in batch if item.handed is not None]
        if clocked:
            stages.begin_launch(clocked)
            spans.request_stage("service_unpack")
        replies = error = built = None
        try:
            replies = self._verify_batch(batch)
        except Exception as exc:  # noqa: BLE001 - the reply writers log it
            error = exc
        finally:
            if clocked:
                built = stages.end_launch(len(batch))
        try:
            self._loop.call_soon_threadsafe(
                self._resolve, batch, replies, error, built)
        except RuntimeError:
            pass  # the loop closed under a launch that stop() did not await

    def _resolve(self, batch: List[_Pending], replies, error, built) -> None:
        """On the loop: a launch is done."""
        self.stages.launches += 1
        self._in_service -= len(batch)
        for i, item in enumerate(batch):
            future = item.future
            if future.done():
                continue  # cancelled: its connection's writer was, or stop()
            if error is not None:
                future.set_exception(error)
            else:
                future.set_result((
                    T_RESULT, replies[i],
                    built if item.handed is not None else None,
                ))

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
        ``batch``, in order, as three uint8 arrays of one row a signature:
        column slices of the wire records, which nothing walks.  Requests
        of one frame type that follow each other share one record array; a
        VERIFY record's 2-byte index becomes its key's row with one
        gather."""
        import numpy as np

        columns: Tuple[list, list, list] = ([], [], [])
        at = 0
        while at < len(batch):
            type_ = batch[at].type_
            end = at + 1
            while end < len(batch) and batch[end].type_ == type_:
                end += 1
            body = (batch[at].body if end == at + 1
                    else b"".join([item.body for item in batch[at:end]]))
            at = end
            if type_ == T_VERIFY:
                rows = np.frombuffer(body, np.uint8).reshape(-1, _IDX_REC)
                table = self._key_rows(self._keys or [])
                index = rows[:, :2].view("<u2")[:, 0]
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
        bytes, verdict bytes)`` — the writer scatter-gathers them behind a
        fresh header.  The signatures travel as arrays from the wire
        records to the backend (``_wire_rows``) and the verdicts back into
        bytes with one conversion: nothing here runs once a signature."""
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
        if self.metrics is not None:
            # The service owns the device, so it (not the jax-free clients)
            # is where launch shape and padding waste are measurable.
            self.metrics.verify_dispatch_batch_size.observe(total)
            self.metrics.verifier_service_coalesced_requests.observe(
                len(batch))
            padder = getattr(backend, "padded_batch", None)
            if padder is not None:
                self.metrics.verify_padding_wasted_total.labels(
                    "service"
                ).inc(max(0, padder(total) - total))
        verdicts = np.asarray(oks, bool).view(np.uint8).tobytes()
        replies, at = [], 0
        for item in batch:
            replies.append((struct.pack("<I", item.req_id),
                            verdicts[at: at + item.n]))
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
        self._server = await asyncio.start_unix_server(
            self._handle, path=self.socket_path
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
        # Nothing pending is launched from here on: its future is cancelled
        # (the connection's writer ends with it), and the dispatchers end
        # once the launch they are in returns.
        with self._pending_cond:
            self._stopping = True
            abandoned = list(self._pending)
            self._pending.clear()
            self._pending_cond.notify_all()
        for item in abandoned:
            item.future.cancel()
        if self._server is not None:
            self._server.close()
            # Sever live client connections first: since 3.12,
            # ``wait_closed`` waits for every connection HANDLER to finish,
            # and handlers block in readexactly on idle-but-open clients.
            for writer in list(self._writers):
                writer.close()
            await self._server.wait_closed()
        self._hello_pool.shutdown(wait=False)
        self._write_report()
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)


# ---------------------------------------------------------------------------
# Client


class RemoteSignatureVerifier(SignatureVerifier):
    """Validator-side stub: forwards batches to the host's verifier service.

    jax-free by design — the validator process stays import-light and leans
    on the service's single warmed runtime.  Called from the batching
    collector's executor threads: each thread keeps its own connection
    (``threading.local``) so concurrent flushes pipeline through the service
    rather than serializing on one socket.
    """

    backend_label = "tpu-remote"

    # Reconnect-retry budget per request: a service restart mid-burst is
    # routine (seconds of downtime), a fleet boot race is routine — neither
    # is an outage.  Only exhausting the budget propagates, and the circuit
    # breaker (``tpu`` flavor) takes it from there.
    MAX_ATTEMPTS = 4
    RETRY_BASE_BACKOFF_S = 0.05
    RETRY_MAX_BACKOFF_S = 1.0

    # Bound on idle pooled connections for the async dispatch path; matches
    # the deepest pipeline window the collector runs (verify_pipeline.py).
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
        """Staged dispatch: send the request now (on a pooled connection the
        handle carries — submit and fetch may run on different executor
        threads) and read the reply at ``result()``.  With the service's own
        per-connection request pipeline, several of these overlap through
        ONE warmed backend.  A send failure here falls back to the deferred
        sync path, which owns the full reconnect-retry budget."""
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
            conn = self._pool_checkout()
        except VerifierProtocolError:
            raise
        except (ConnectionError, OSError, socket.timeout):
            # No reconnect count here: the deferred sync fallback runs the
            # full retry loop and accounts each torn-down attempt itself.
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
        oks = self._roundtrip(frame, req_id)
        assert len(oks) == n
        return [bool(b) for b in oks]


class _RemoteDispatch:
    """An in-flight request to the verifier service.

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

    platform = require_accelerator()
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
