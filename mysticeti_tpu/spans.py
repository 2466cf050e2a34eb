"""Per-block span tracing: where did a committed block's latency go?

The end-to-end histograms in :mod:`metrics` say *how slow* commits are;
this module says *which stage* ate the time.  Every block is tracked through
the pipeline as a sequence of structured spans keyed by
``(stage, block reference, authority)``:

    receive        net_sync: frame decode + dedup + structure checks
    verify         net_sync: signature verification (collection window +
                   dispatch, through the pluggable verifier)
    verify_dispatch block_validator: one actual accelerator/CPU dispatch
                   (per block of the dispatched sub-batch)
    verify_pack    / verify_device / verify_fetch — the staged pipeline's
                   sub-stages of that dispatch (host packing, non-blocking
                   device submission, result fetch; verify_pipeline.py)
    dag_add        net_sync -> core: core-task queue wait + BlockManager
                   insertion (includes time parked on missing parents)
    proposal_wait  core -> commit_observer: accepted into the DAG until
                   sequenced by a committed sub-dag
    commit         syncer: leader decision + observer + commit persistence
    finalize       commit_observer: sub-dag linearization + tx accounting

Spans are clocked by the RUNTIME clock (:func:`mysticeti_tpu.runtime.now`):
virtual under :class:`~mysticeti_tpu.runtime.simulated.DeterministicLoop`
(so a seeded sim produces a byte-identical trace every run) and monotonic in
production.  Track identity reuses :data:`tracing.current_authority` as the
default, with explicit ``authority=`` at sites that know their validator
index — in a multi-node simulation all nodes share one process and one
tracer, and the authority keeps their pipelines on separate tracks.

Export is Chrome trace-event JSON, loadable in Perfetto / chrome://tracing:
set ``MYSTICETI_TRACE=/path/out.json`` (``%p`` expands to the pid, like
``MYSTICETI_PROFILE``) and the node CLI starts a tracer at boot and writes
the trace at shutdown.  A daemon thread flushes the file atomically every
few seconds so a SIGKILL'd benchmark node still leaves a complete snapshot
(same posture as ``profiling.SamplingProfiler``).  ``tools/trace_report.py``
prints per-stage latency breakdowns from a trace file.

Besides the opt-in per-block tracer there is one ALWAYS-ON stage clock
(:class:`StageClock`, :func:`request_stage`, :class:`stage`): the same stage
names, aggregated instead of recorded — a histogram a stage, CPU seconds a
working stage, and a ring of whole seconds that the verifier service writes
into its report and a live validator into its flight-recorder document
(``flight_recorder.py``; one clock a validator, made by ``validator.py``).
It is what the benchmark's per-layer
metrics read, so it is cheap: a few clock reads a stage, one request in
``SAMPLE_ONE_IN`` clocked in the service, no lock, nothing allocated that
outlives the call.  With a tracer the same calls also record the spans.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from array import array
from bisect import bisect_left
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

from .runtime import now as runtime_now
from .tracing import current_authority

# The central stage-name registry.  Instrumentation sites must use literal
# names from this tuple — the `span-names` lint rule in analysis/checker.py
# parses this assignment (it must stay a literal tuple of strings) and flags
# any span call whose stage is not registered.
STAGES = (
    # Fleet-trace stages (tools/fleet_trace.py): the author's proposal edge
    # (the journey's t=0) and the per-link wire transit measured from the
    # timestamped-frame extension (wire tag 12) — args carry the sending
    # peer and the RAW signed transit the skew estimator consumes.
    "propose",
    "transit",
    "receive",
    "verify",
    "verify_dispatch",
    # Staged dispatch pipeline sub-stages (verify_pipeline.py): host packing,
    # non-blocking device submission, and the result fetch — per dispatched
    # block, so a trace shows WHERE a dispatch's round-trip went.
    "verify_pack",
    "verify_device",
    "verify_fetch",
    "dag_add",
    "proposal_wait",
    "commit",
    "finalize",
    # The gateway's check of a submission's signatures (ingress.py; always
    # on through the validator's StageClock, where signatures are required).
    "admit_verify",
    # A mesh frame under an injected link delay (network.py: DelayLine;
    # always on through the validator's StageClock, where
    # ``Parameters.link_delay_ms`` holds a table): handed to the connection
    # -> written to the socket, one sample a frame.
    "mesh_hold",
    # What the proposal gate cost a round (syncer.py; always on through the
    # node's StageClock): the threshold clock reached round r + 1 (a quorum
    # of round r is in the DAG) -> this validator's own proposal for it,
    # one sample a proposal.  The previous round's leader arriving, its
    # connection closing or the leader timeout ends it.
    "leader_wait",
    # Where a validator's host time can hide (always on through the node's
    # StageClock, on a live node; NODE_STAGES below says where each is
    # taken): a synchronous command on the core owner, the event loop's
    # lag, a garbage collection, a job's wait for a thread of the loop's
    # default executor, a batch of WAL frames written, a WAL drain + fsync,
    # a checkpoint whole, one commit folded through the execution state, a
    # request to the metrics endpoint.
    "core_command",
    "loop_lag",
    "gc",
    "executor_wait",
    "wal_write",
    "wal_sync",
    "checkpoint",
    "exec_fold",
    "scrape",
    # A boot that recovered its state from the WAL (validator.py, once a
    # boot; always on through the node's StageClock): the log opened, the
    # newest checkpoint loaded, what follows it replayed and a torn tail
    # cut (storage.open_store), wall and the thread's CPU.
    "wal_replay",
    # The finality tracker's samples (finality.py), as they feed
    # ``mysticeti_e2e_finality_seconds{phase}``: submit -> admitted,
    # admitted -> proposed, proposed -> commit decision.
    "phase_admission",
    "phase_proposal",
    "phase_commit",
    # The verifier service's stages of one VERIFY/RAW request
    # (verifier_service.py, ops/ed25519.py; SERVICE_STAGES below): always on
    # through StageClock, and spans keyed by (connection, req_id) when a
    # tracer is active.
    "service_decode",
    "service_pool_wait",
    "service_unpack",
    "service_pack",
    "service_launch",
    "service_fetch",
    "service_reply_build",
    "service_reply_wait",
    "service_gc",
    "service_loop_lag",
)

# The per-block pipeline proper: the stages every committed block crosses.
# (verify_dispatch is per accelerator dispatch, absent under AcceptAll.)
PIPELINE_STAGES = (
    "receive",
    "verify",
    "dag_add",
    "proposal_wait",
    "commit",
    "finalize",
)

ENV_TRACE = "MYSTICETI_TRACE"

# tid for spans recorded with no authority context (e.g. tooling).
_UNTRACKED_TID = 1 << 20


def format_ref(ref) -> str:
    """Stable human-readable label for trace args: a block reference, or a
    service request's ``(connection label, req_id)``."""
    if isinstance(ref, tuple):
        return f"{ref[0]}#{ref[1]}"
    return f"A{ref.authority}R{ref.round}#{ref.digest[:4].hex()}"


class SpanTracer:
    """Collects per-block stage spans; exports Chrome trace-event JSON.

    Thread-safe (the periodic flusher reads from a daemon thread while the
    event loop records), but all recording sites live on the loop thread, so
    under the deterministic simulator the event sequence — and therefore the
    exported bytes — is a pure function of the seed.
    """

    # Hard caps: a long-lived production node must not grow without bound.
    # proposal_wait spans of blocks that never commit are the main leak;
    # beyond the cap new records are dropped (counted, never raising).
    MAX_EVENTS = 1_000_000
    MAX_OPEN = 200_000

    def __init__(
        self,
        flush_path: Optional[str] = None,
        flush_every_s: float = 5.0,
    ) -> None:
        # Completed spans: (stage, ref label, authority, t0, t1, extra args).
        self._events: List[Tuple[str, str, Optional[int], float, float,
                                 Optional[dict]]] = []
        # Clock anchor for cross-node trace merging (tools/fleet_trace.py):
        # one (runtime, wall) pair captured at the FIRST recorded span, on
        # the recording thread — the merger converts each trace's runtime
        # timestamps to wall time through it.  Captured once (not per
        # flush) so a seeded sim's exported bytes stay a pure function of
        # the seed.
        self._anchor: Optional[Tuple[float, float]] = None
        # Open spans: (stage, ref, authority) -> t0.
        self._open: Dict[Tuple[str, object, Optional[int]], float] = {}
        # Live subscribers called with (stage, ref, authority, t0, t1) for
        # every COMPLETED span (the critical-path analyzer in health.py).
        # Called outside the lock, on the recording thread; sinks must be
        # cheap and never raise.
        self._sinks: List = []
        self._lock = threading.Lock()
        # Serializes write(): the periodic flusher thread and an orderly-
        # shutdown flush_active() both target the same <path>.tmp — unlocked,
        # one thread's os.replace could publish the file while the other is
        # still appending to the fd, interleaving two JSON documents.
        self._write_lock = threading.Lock()
        self.dropped = 0
        self.flush_path = flush_path
        self.flush_every_s = flush_every_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- clock --

    @staticmethod
    def now() -> float:
        """The runtime clock: virtual under simulation, monotonic otherwise."""
        return runtime_now()

    # -- live span stream --

    def add_sink(self, sink) -> None:
        """Subscribe to the completed-span stream: ``sink(stage, ref,
        authority, t0, t1)`` per recorded span, event-cap independent (a
        dropped trace event still feeds attribution)."""
        self._sinks.append(sink)

    def _notify(self, stage, ref, authority, t0, t1) -> None:
        for sink in self._sinks:
            sink(stage, ref, authority, t0, t1)

    # -- recording --

    def record_span(
        self,
        stage: str,
        ref,
        t0: float,
        t1: Optional[float] = None,
        authority: Optional[int] = None,
        extra: Optional[dict] = None,
    ) -> None:
        """Append a completed span measured by the caller.  ``extra`` lands
        in the exported event's ``args`` (next to the block label) — the
        ``transit`` stage uses it to carry the sending peer and the raw
        signed transit for the skew estimator."""
        if authority is None:
            authority = current_authority.get()
        if t1 is None:
            t1 = runtime_now()
        self._notify(stage, ref, authority, t0, t1)
        with self._lock:
            if self._anchor is None:
                from .runtime import timestamp_utc

                self._anchor = (runtime_now(), timestamp_utc())
            if len(self._events) >= self.MAX_EVENTS:
                self.dropped += 1
                return
            self._events.append(
                (stage, format_ref(ref), authority, t0, t1, extra)
            )

    def begin_span(
        self,
        stage: str,
        ref,
        authority: Optional[int] = None,
        t: Optional[float] = None,
    ) -> None:
        """Open a span; a later :meth:`end_span` with the same key closes it.
        A key already open keeps its ORIGINAL start (duplicate deliveries
        must not shrink the measured wait)."""
        if authority is None:
            authority = current_authority.get()
        if t is None:
            t = runtime_now()
        key = (stage, ref, authority)
        with self._lock:
            if key not in self._open:
                if len(self._open) >= self.MAX_OPEN:
                    self.dropped += 1
                    return
                self._open[key] = t

    def end_span(
        self,
        stage: str,
        ref,
        authority: Optional[int] = None,
        t: Optional[float] = None,
    ) -> None:
        """Close an open span; silently ignored when no matching begin was
        seen (e.g. a block that entered the DAG before tracing started)."""
        if authority is None:
            authority = current_authority.get()
        key = (stage, ref, authority)
        if t is None:
            t = runtime_now()
        with self._lock:
            t0 = self._open.pop(key, None)
            if t0 is None:
                return
            if self._anchor is None:
                from .runtime import timestamp_utc

                self._anchor = (runtime_now(), timestamp_utc())
            if len(self._events) >= self.MAX_EVENTS:
                self.dropped += 1
            else:
                self._events.append(
                    (stage, format_ref(ref), authority, t0, t, None)
                )
        self._notify(stage, ref, authority, t0, t)

    @contextmanager
    def span(self, stage: str, ref, authority: Optional[int] = None):
        t0 = runtime_now()
        try:
            yield
        finally:
            self.record_span(stage, ref, t0, authority=authority)

    # -- export --

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable).

        One track ("thread") per authority, named ``A<n>``; spans are
        complete ("X") events with microsecond virtual/monotonic timestamps.
        Events are globally sorted on a total key so the output is
        deterministic (and per-track timestamps are monotone by
        construction).  Only COMPLETED spans are exported.
        """
        pid = os.getpid()
        with self._lock:
            events = list(self._events)
            anchor = self._anchor

        def tid_of(authority: Optional[int]) -> int:
            return _UNTRACKED_TID if authority is None else authority

        tids = {}
        for _, _, authority, _, _, _ in events:
            tid = tid_of(authority)
            tids[tid] = "untracked" if authority is None else f"A{authority}"
        trace_events = [
            {
                "args": {"name": "mysticeti-tpu"},
                "name": "process_name",
                "ph": "M",
                "pid": pid,
            }
        ]
        for tid in sorted(tids):
            trace_events.append(
                {
                    "args": {"name": tids[tid]},
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                }
            )
        spans = [
            {
                "args": (
                    {"block": label}
                    if not extra
                    else {"block": label, **extra}
                ),
                "cat": "pipeline",
                "dur": max(0, round((t1 - t0) * 1e6)),
                "name": stage,
                "ph": "X",
                "pid": pid,
                "tid": tid_of(authority),
                "ts": round(t0 * 1e6),
            }
            for stage, label, authority, t0, t1, extra in events
        ]
        spans.sort(key=lambda e: (e["ts"], e["tid"], e["name"], e["args"]["block"], e["dur"]))
        trace_events.extend(spans)
        trace = {"displayTimeUnit": "ms", "traceEvents": trace_events}
        if anchor is not None:
            # Cross-node merge anchor (tools/fleet_trace.py): the same
            # instant on the trace's runtime clock and the wall clock,
            # microseconds.  Virtual-deterministic under the simulator.
            trace["otherData"] = {
                "clock_runtime_us": round(anchor[0] * 1e6),
                "clock_wall_us": round(anchor[1] * 1e6),
            }
        return trace

    def write(self, path: str) -> None:
        """Atomic write (tmp + rename): a SIGKILL landing mid-flush must not
        replace the previous complete snapshot with a truncated file.
        Thread-safe: the flusher thread and shutdown flushes share the tmp."""
        tmp = f"{path}.tmp"
        with self._write_lock:
            with open(tmp, "w") as f:
                json.dump(
                    self.chrome_trace(), f, sort_keys=True,
                    separators=(",", ":"),
                )
                f.write("\n")
            os.replace(tmp, path)

    # -- periodic flush (survive SIGKILL, like profiling.SamplingProfiler) --

    def start(self) -> "SpanTracer":
        if self.flush_path and self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run_flusher, name="mysticeti-tracer", daemon=True
            )
            self._thread.start()
        return self

    def _run_flusher(self) -> None:
        while not self._stop.wait(self.flush_every_s):
            try:
                self.write(self.flush_path)
            except OSError:
                pass

    def stop(self) -> None:
        """Stop the flusher and write the final complete trace."""
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None
        if self.flush_path:
            self.write(self.flush_path)


# ---------------------------------------------------------------------------
# Process-global tracer (instrumentation sites read it on their hot path)

_active: Optional[SpanTracer] = None


def active() -> Optional[SpanTracer]:
    """The live tracer, or None when tracing is off (the common case: one
    global read and a None check per instrumentation site)."""
    return _active


def start_from_env() -> Optional[SpanTracer]:
    """Start trace collection when ``MYSTICETI_TRACE`` is set; the node CLI
    calls this at boot and :func:`stop_from_env` at shutdown.  ``%p`` in the
    path expands to the pid so one env var serves a whole local fleet."""
    global _active
    path = os.environ.get(ENV_TRACE)
    if not path or _active is not None:
        return None
    path = path.replace("%p", str(os.getpid()))
    _active = SpanTracer(flush_path=path).start()
    return _active


def flush_active() -> None:
    """Write the live tracer's current snapshot NOW (orderly-shutdown hook:
    ``Validator.stop`` calls this so short runs keep the span tail instead
    of losing everything since the last periodic flush).  The tracer stays
    active — stop_from_env still finalizes it."""
    tracer = _active
    if tracer is None or not tracer.flush_path:
        return
    try:
        tracer.write(tracer.flush_path)
    except OSError:
        pass


def stop_from_env() -> None:
    """Write the final trace and deactivate the global tracer."""
    global _active
    if _active is None:
        return
    _active.stop()
    _active = None


# ---------------------------------------------------------------------------
# The always-on stage clock.
#
# Clocks: ``time.monotonic`` for wall time (the clock every process of a
# host shares, and the runtime clock outside the simulator), and for a
# WORKING stage ``time.thread_time`` of the thread that does it: wall minus
# CPU is then time spent blocked or waiting for the GIL.  Where the kernel
# moves a thread's CPU clock in scheduler ticks (10 ms on the sandboxed
# hosts the chips sit in) one reading says nothing and only sums do: a
# stage's CPU is the ticks that happened to land in it, right in the mean
# and no finer than 1/sqrt(ticks summed).  So no sample's CPU is held to
# its wall, and what a whole thread used is read once a second beside it
# (``StageClock.stamp``).

# The verifier service's stages, in the order a request crosses them
# (``service_gc`` and ``service_loop_lag`` belong to the process, not to a
# request).  Every name is also in STAGES.
SERVICE_STAGES = STAGES[STAGES.index("service_decode"):]
# Per request, booked together when the launch it rode is done — each
# exactly once a request, with zero seconds where the backend has no such
# stage (a host oracle packs and launches nothing).  A launch carries every
# request that was pending when a dispatcher thread came free: each clocked
# one of them books the launch's stages with their wall whole (it did wait
# that long) and their CPU divided by the requests in the launch, so that
# the working stages' CPU times the request rate still sums to cores.
REQUEST_STAGES = SERVICE_STAGES[1:7]
# The eight stages of a request, header read to reply written: clocked for
# the requests that are sampled (SAMPLE_ONE_IN), so their counts are of
# those; ``service_gc`` and ``service_loop_lag`` see every occurrence.
SAMPLED_STAGES = SERVICE_STAGES[:8]
# A validator's verification path, one sample a received batch of blocks
# (net_sync.py): the always-on twins of the per-block spans of those names.
BLOCK_PATH_STAGES = ("receive", "verify", "dag_add")
# What a validator's one clock books (validator.py makes it and hands it
# on), and where: that path (net_sync.py, a received batch); the proposal
# gate's wait (syncer.py, a proposal); the gateway's signature check
# (ingress.py, a frame); a mesh frame under an injected delay (network.py);
# a command of the core owner (core_task.py, CPU beside wall); the loop
# probe's lag and a collection (hostattr.py, ``gc.callbacks``); the wait for
# a thread of the loop's default executor (``in_default_executor`` below);
# the WAL's writer and syncer threads (wal.py, storage.py), the checkpoint
# (storage.py), the execution fold (execution.py, a commit), the metrics
# endpoint (metrics.py, a request); the finality tracker's phases
# (finality.py, a sampled transaction); the recovery of a boot that found a
# WAL (validator.py, once).  What measures the host (the middle nine and
# the last) is off under the simulator; the rest is on the runtime clock.
NODE_STAGES = BLOCK_PATH_STAGES + (
    "leader_wait", "admit_verify", "mesh_hold",
    "core_command", "loop_lag", "gc", "executor_wait",
    "wal_write", "wal_sync", "checkpoint", "exec_fold", "scrape",
    "phase_admission", "phase_proposal", "phase_commit",
    "wal_replay",
)
# What a validator's clock stamps once a second (``Validator._read_stamps``
# reads them, cumulative, in this order): the threshold clock's round,
# leaders committed, own proposals, blocks received, transactions admitted
# and shed (all, and by ``lane_cap``), leader timeouts, the requests it
# sent to the verifier service, the execution transactions it folded as
# ``bad_nonce`` (a cascade of those beside ``shed`` is an account's sequence
# that an episode broke), and the highest round of any block it holds
# (beside ``rounds`` and ``proposals``: a validator that is catching up
# holds its peers' blocks rounds ahead of its own clock and proposals).
# ``leaders`` is the committed height (``StorageLifecycle.commit_height``).
NODE_STAMPS = ("rounds", "leaders", "proposals", "blocks_received",
               "tx_admitted", "shed", "shed_lane_cap", "leader_timeouts",
               "verify_requests", "exec_bad_nonce", "frontier_round")
# Stages in which a request waits (for a launch, the device, the loop, the
# GIL): wall time only, no CPU clock and no profiler annotation —
# the runtime's own events mark them in a trace already.
WAITING_STAGES = frozenset({
    "service_pool_wait", "service_fetch", "service_reply_wait",
    "service_loop_lag",
})

# The verifier service clocks one request in this many through its stages,
# whoever listens (a tracer, a profiler): the first and every 32nd after
# it.  Clocking every request cost the service 8% of its throughput on the
# chip's host, where ``time.thread_time`` is a 6 us system call (0.3 us on
# plain Linux) and an idle profiler annotation a stage another 3.5%
# (PERF.md, PR 24).  A sampled request is clocked whole, header read to
# reply written, so its stages still tile it; what is answered is counted
# for every request.  At 700 requests a second a 20 s window still holds
# over 400 clocked requests.
SAMPLE_ONE_IN = 32
# Upper bounds of the histogram every stage clock keeps (seconds).
STAGE_BUCKETS = (
    0.00002, 0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
    0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 5.0,
)


class _ThreadState:
    """What the stage clock knows of one thread: the frame it clocks its
    launches in, whether a profiler annotation is open on it (annotations
    are flat, never nested), and the CPU seconds it has spent collecting
    garbage (which a stage that a collection interrupted takes off its
    own)."""

    __slots__ = ("own_frame", "annotated", "gc_cpu", "gc_t0",
                 "gc_annotation")

    def __init__(self) -> None:
        self.own_frame = None
        self.annotated = False
        self.gc_cpu = 0.0
        self.gc_t0 = self.gc_annotation = None


class _Local(threading.local):
    """``state``: the thread's _ThreadState, made at its first use;
    ``frame``: the _Frame of the launch the thread works for now, if a
    clocked request rides it, None otherwise (a class default, so that
    reading it costs a thread that never clocked one nothing);
    ``fetch_hook``: who hears that the launch the thread works for has
    entered its fetch (``on_fetch``), None where nobody listens."""

    state = None
    frame = None
    fetch_hook = None


_tls = _Local()
_trace_annotation = None


def _state() -> _ThreadState:
    state = _tls.state
    if state is None:
        state = _tls.state = _ThreadState()
    return state


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of a working stage, in a process
    that has JAX already (validators stay off it: never imported here): the
    stage then shows on a profiler's host plane under its own name.  Made
    whether or not a profile is being taken — a clocked request does the
    same work either way."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation(name)


class _Frame:
    """One thread's accumulators for the launch it works for, and the
    stage that launch is in; made once a thread and reused, so a launch
    leaves no object behind.  ``members``: ``(ref, handed)`` of every
    clocked request that rides the launch."""

    __slots__ = ("clock", "state", "members", "taken", "wall", "cpu", "end",
                 "slot", "t0", "c0", "g0", "annotation")

    def __init__(self, clock: "StageClock", state: _ThreadState) -> None:
        self.clock = clock
        self.state = state
        self.members = ()
        n = len(clock.stages)
        self.wall = [0.0] * n
        self.cpu = [0.0] * n
        self.end = [0.0] * n
        self.slot = -1
        self.taken = self.t0 = self.c0 = self.g0 = 0.0
        self.annotation = None

    def switch(self, slot: int) -> float:
        """The launch leaves the stage it is in (-1: none yet) and enters
        ``slot`` (-1: none, its replies are built).  One read of each clock
        serves both sides of the boundary, so a launch's stages tile its
        time on the thread exactly; returns the boundary's instant."""
        clock, state = self.clock, self.state
        cur = self.slot
        working = clock._working
        cpu_now = time.thread_time()
        now = time.monotonic()
        if cur >= 0:
            self.wall[cur] += now - self.t0
            self.end[cur] = now
            if working[cur]:
                self.cpu[cur] += cpu_now - self.c0 - (state.gc_cpu - self.g0)
                if self.annotation is not None:
                    self.annotation.__exit__(None, None, None)
                    self.annotation = None
                    state.annotated = False
            if clock.tracer is not None:
                for ref, _ in self.members:
                    clock.tracer.record_span(
                        clock.stages[cur], ref, self.t0, now)
        self.slot = slot
        self.t0, self.c0, self.g0 = now, cpu_now, state.gc_cpu
        if slot >= 0 and working[slot]:
            annotation = self.annotation = _annotation(clock.stages[slot])
            if annotation is not None:
                state.annotated = True
                annotation.__enter__()
        return now


def request_stage(name: str) -> None:
    """The launch this thread works for is in stage ``name`` from now on,
    until the next stage is named or its replies are built
    (``StageClock.end_launch``).  For a launch that no clocked request
    rides, and outside any — a backend used in process, a warm-up — this
    does nothing."""
    frame = _tls.frame
    if frame is None:
        return
    slot = frame.clock._slot[name]
    if slot != frame.slot:
        frame.switch(slot)


def on_fetch(hook) -> None:
    """``hook()`` is called once, on this thread, when the launch it works
    for from now on enters its fetch (``request_fetch``); None: nobody
    listens any more.  The verifier service ends a part-full launch's hold
    there (``VerifierServer._launch``)."""
    _tls.fetch_hook = hook


def request_fetch() -> None:
    """The launch this thread works for is handed to the device and the
    thread is about to block for its result with the GIL free: its host
    path is over.  A backend says so between its last jitted call and the
    blocking fetch; whoever listens on this thread (``on_fetch``) hears it
    once, whether or not a clocked request rides the launch, and the launch
    is in ``service_fetch`` from here.  Outside the service it does
    nothing."""
    hook = _tls.fetch_hook
    if hook is not None:
        _tls.fetch_hook = None
        hook()
    request_stage("service_fetch")


class _Books:
    """One thread's books of one clock: nobody else writes them, so
    booking takes no lock (a lock a booking is held across a forced GIL
    switch now and then, and sixteen threads then queue behind it)."""

    __slots__ = ("totals", "buckets", "ring", "second", "gc")

    def __init__(self, stages: int, nbuckets: int, rows: int) -> None:
        # Cumulative, per stage: [count, wall_s, cpu_s], and one count a
        # bucket of STAGE_BUCKETS plus the overflow.
        self.totals = array("d", bytes(8 * 3 * stages))
        self.buckets = array("q", bytes(8 * nbuckets * stages))
        self.ring = array("d", bytes(8 * rows * 4 * stages))
        self.second = array("q", [-1]) * rows
        self.gc = array("d", bytes(8 * 6))  # [collections, seconds] * 3


_thread_cpu_clock = getattr(time, "pthread_getcpuclockid", None)


class StageClock:
    """Where a process's time goes, by stage.  Per stage, cumulatively: a
    count, wall seconds, CPU seconds and a histogram of the wall seconds
    (``metrics.StageSeries`` renders them at scrape time); and — with
    ``ring_seconds`` — a ring of the last whole seconds of
    ``time.monotonic``, each holding per stage ``[count, wall_s, cpu_s,
    max_wall_s]`` and, from ``stamp``, what was answered and what CPU was
    used in that second (``stamps`` / ``read_stamps``: the owner's counts).
    A sample is booked to the second its stage ENDED in.  Every thread
    books into preallocated arrays of its own (made when
    it is adopted, or at its first sample), summed when read: booking takes
    no lock and allocates nothing that outlives the call.  One request in
    ``sample_one_in`` is clocked through its stages (``sampled``).  With a
    ``tracer`` every clocked stage that has a reference is also recorded as
    a span (the tracer that was live when the clock was made, not whichever
    is live later: a clock outlives a test, a tracer must not hear it)."""

    RING_SECONDS = 600
    COLUMNS = ("count", "wall_s", "cpu_s", "max_wall_s")
    # The CPU clocks ``stamp`` reads once a second beside its owner's
    # counts (``stamps``): the ring's seconds hold the growth from one
    # stamp to the next (whole numbers, but for the seconds, ``*_s``).
    CPU_STAMPS = ("process_cpu_s", "threads_cpu_s", "loop_cpu_s")

    def __init__(self, stages: Sequence[str], ring_seconds: int = 0,
                 tracer: Optional[SpanTracer] = None,
                 sample_one_in: int = 1, stamps: Sequence[str] = (),
                 read_stamps=None, lag_stage: Optional[str] = None,
                 gc_stage: Optional[str] = None) -> None:
        """``stamps`` names what the clock's owner counts and
        ``read_stamps()`` returns those counts, cumulative, in that order
        (plain sums the stamping thread keeps or may read);
        ``lag_stage`` / ``gc_stage`` name the stages that ``loop_lag`` and
        ``gc_callback`` book."""
        self.stages = tuple(stages)
        self.tracer = tracer
        self.sample_one_in = sample_one_in
        self.ring_seconds = ring_seconds
        self.stamp_names = tuple(stamps) + self.CPU_STAMPS
        self._read_owner = read_stamps or (lambda: ())
        self.lag_stage, self.gc_stage = lag_stage, gc_stage
        self._slot = {name: i for i, name in enumerate(self.stages)}
        self._request_slots = [
            self._slot[name] for name in REQUEST_STAGES if name in self._slot
        ]
        self._working = [name not in WAITING_STAGES for name in self.stages]
        self._nbuckets = len(STAGE_BUCKETS) + 1
        self._rows = ring_seconds
        self._width = len(self.COLUMNS) * len(self.stages)
        self._zeros = array("d", bytes(8 * self._width))
        self._local = threading.local()
        self._all_books: List[_Books] = []
        # [CPU clock id, CPU seconds when adopted, last reading] of every
        # thread that books here.
        self._thread_clocks: List[list] = []
        self._lock = threading.Lock()  # guards the two lists alone
        self._turn = 0  # of the requests: which are clocked
        self._stamped = -1
        self._stamp_second = array("q", [-1]) * ring_seconds
        self._stamps = array(
            "d", bytes(8 * ring_seconds * len(self.stamp_names)))
        if ring_seconds:
            self.stamp(time.monotonic())

    def adopt_thread(self) -> _Books:
        """Make the calling thread's books and note its CPU clock, so that
        ``stamp`` counts what it uses from now on (a dispatcher thread's
        first call; any other thread is adopted at its first sample)."""
        books = self._local.books = _Books(
            len(self.stages), self._nbuckets, self._rows)
        entry = None
        if _thread_cpu_clock is not None and self._rows:
            used = time.thread_time()
            entry = [_thread_cpu_clock(threading.get_ident()), used, used]
        with self._lock:
            self._all_books.append(books)
            if entry is not None:
                self._thread_clocks.append(entry)
        return books

    def _books(self) -> _Books:
        try:
            return self._local.books
        except AttributeError:
            return self.adopt_thread()

    def sampled(self) -> bool:
        """Whether the next request is one that is clocked through its
        stages: the first and every ``sample_one_in``-th after it (called
        by the one thread that reads the requests)."""
        turn = self._turn
        self._turn = turn + 1
        return turn % self.sample_one_in == 0

    # -- booking --

    def _book(self, books: _Books, slot: int, end: float, wall: float,
              cpu: float) -> None:
        """One sample into the calling thread's ``books``."""
        totals = books.totals
        at = 3 * slot
        totals[at] += 1.0
        totals[at + 1] += wall
        totals[at + 2] += cpu
        books.buckets[self._nbuckets * slot
                      + bisect_left(STAGE_BUCKETS, wall)] += 1
        if not self._rows:
            return
        # The row of ``end``'s second in the thread's ring, cleared if the
        # ring has come round to it; a second overwritten already is lost.
        second = int(end)
        row = second % self._rows
        base = row * self._width
        held = books.second[row]
        if held != second:
            if held > second:
                return
            books.ring[base:base + self._width] = self._zeros
            books.second[row] = second
        ring = books.ring
        at = base + 4 * slot
        ring[at] += 1.0
        ring[at + 1] += wall
        ring[at + 2] += cpu
        ring[at + 3] = max(ring[at + 3], wall)

    def book(self, name: str, end: float, wall: float,
             cpu: float = 0.0) -> None:
        """One sample of one stage, ended at ``end``."""
        self._book(self._books(), self._slot[name], end, wall, cpu)

    def book_since(self, name: str, since: float) -> None:
        """One sample of a stage that began at ``since`` on the runtime
        clock (``SpanTracer.now``) and ends now: two clock reads and one
        booking at the call site."""
        end = runtime_now()
        self.book(name, end, end - since)

    # -- once a second --

    def _read_stamps(self) -> tuple:
        """``stamp_names`` now.  A thread's CPU clock can be read from
        another thread; one that has ended keeps what it had used."""
        threads = 0.0
        for entry in self._thread_clocks:
            try:
                entry[2] = time.clock_gettime(entry[0])
            except OSError:
                pass
            threads += entry[2] - entry[1]
        return (*self._read_owner(), time.process_time(), threads,
                time.thread_time())

    def stamp(self, now: float) -> None:
        """In the first call of a whole second of ``now``, read
        ``stamp_names`` into the ring (one CPU clock read a thread, a
        second); any other call returns at once.  Called by the thread that
        keeps the owner's counts, several times a second."""
        second = int(now)
        if second == self._stamped or not self._rows:
            return
        self._stamped = second
        row = second % self._rows
        n = len(self.stamp_names)
        self._stamps[row * n:(row + 1) * n] = array("d", self._read_stamps())
        self._stamp_second[row] = second

    def loop_lag(self, lag: float) -> None:
        """For ``hostattr.LoopLagProbe``: one sample of ``lag_stage``, and
        the tick that ``stamp`` needs."""
        now = time.monotonic()
        self.book(self.lag_stage, now, lag)
        self.stamp(now)

    # -- a launch on a dispatcher thread --

    def begin_launch(self, members: Sequence[tuple]) -> None:
        """This thread works from now on for a launch that the clocked
        requests ``members`` ride — ``(ref, handed)`` each: ``(connection
        label, req_id)`` and when the request was handed over.  Each was in
        ``service_pool_wait`` from then until now; name the launch's next
        stage with ``request_stage``."""
        state = _state()
        frame = state.own_frame
        if frame is None or frame.clock is not self:
            frame = state.own_frame = _Frame(self, state)
        frame.members = members
        frame.slot = -1
        frame.taken = taken = time.monotonic()
        if self.tracer is not None:
            for ref, handed in members:
                self.tracer.record_span(
                    "service_pool_wait", ref, handed, taken)
        _tls.frame = frame

    def end_launch(self, riders: int) -> float:
        """The replies are built: for every clocked request of this
        thread's launch book every REQUEST_STAGES stage, once each (zero
        seconds where the launch never was in one) — its own wait for the
        launch, then the launch's stages with their wall whole and their
        CPU divided by ``riders``, the requests the launch carried, clocked
        or not; returns the instant."""
        frame = _tls.frame
        _tls.frame = None
        done = frame.switch(-1)
        walls, cpus, ended = frame.wall, frame.cpu, frame.end
        books = self._books()
        waited = self._slot["service_pool_wait"]
        ended[waited] = taken = frame.taken
        for _, handed in frame.members:
            walls[waited] = taken - handed  # its own; a wait has no CPU
            for slot in self._request_slots:
                self._book(books, slot, ended[slot] or done, walls[slot],
                           cpus[slot] / riders)
        for slot in self._request_slots:
            walls[slot] = cpus[slot] = ended[slot] = 0.0
        return done

    # -- garbage collection --

    def gc_callback(self, phase: str, info: dict) -> None:
        """For ``gc.callbacks``: a collection stops every thread, on
        whichever thread tripped it."""
        state = _state()
        if phase == "start":
            state.gc_t0 = time.monotonic()
            if not state.annotated:
                annotation = state.gc_annotation = _annotation(self.gc_stage)
                if annotation is not None:
                    annotation.__enter__()
            return
        t0 = state.gc_t0
        if t0 is None:
            return  # installed in the middle of a collection
        t1 = time.monotonic()
        # A collection computes and never waits: its CPU is its wall (the
        # young generations are collected two dozen times a second, and the
        # CPU clock may be a slow system call).
        cpu = t1 - t0
        if state.gc_annotation is not None:
            state.gc_annotation.__exit__(None, None, None)
            state.gc_annotation = None
        state.gc_t0 = None
        state.gc_cpu += cpu
        generation = min(2, max(0, int(info.get("generation", 2))))
        books = self._books()
        books.gc[2 * generation] += 1.0
        books.gc[2 * generation + 1] += t1 - t0
        self._book(books, self._slot[self.gc_stage], t1, t1 - t0, cpu)
        if self.tracer is not None:
            self.tracer.record_span(
                self.gc_stage, ("gc", generation), t0, t1)

    # -- export --

    def _snapshot(self) -> List[_Books]:
        with self._lock:
            return list(self._all_books)

    def totals(self) -> Dict[str, dict]:
        """Cumulative ``{stage: {"count", "wall_s", "cpu_s", "buckets"}}``
        over every thread.  ``buckets`` holds one count a bound of
        STAGE_BUCKETS plus the overflow (not cumulative over the bounds)."""
        n = self._nbuckets
        totals = [0.0] * (3 * len(self.stages))
        buckets = [0] * (n * len(self.stages))
        for books in self._snapshot():
            totals = [a + b for a, b in zip(totals, books.totals)]
            buckets = [a + b for a, b in zip(buckets, books.buckets)]
        return {
            name: {
                "count": int(totals[3 * slot]),
                "wall_s": totals[3 * slot + 1],
                "cpu_s": totals[3 * slot + 2],
                "buckets": buckets[n * slot: n * slot + n],
            }
            for slot, name in enumerate(self.stages)
        }

    def export(self) -> dict:
        """The ring as the service's report and a validator's
        flight-recorder document carry it: ``seconds`` maps a whole second
        of ``clock`` to ``{stage: [count, wall_s, cpu_s, max_wall_s]}``,
        stages that saw nothing left out, and — for a second that was
        stamped — ``stamp_names``: what the owner counts
        (``verifier_service.ServiceCounts.STAMPS`` in the service,
        ``NODE_STAMPS`` on a validator), and the CPU seconds the process
        (``process_cpu_s``), the threads that book here (``threads_cpu_s``;
        left out where a thread's CPU clock cannot be read from outside it)
        and the stamping thread itself (``loop_cpu_s``) used, each from
        that second's stamp to the next one's (the last: to now).  A
        request stage's ``count`` is of the requests that were clocked (one
        in ``sample_one_in``) and its ``cpu_s`` their share of their
        launches' (``end_launch``).  Sum the seconds inside a window."""
        width = self._width
        rows: Dict[int, list] = {}  # second -> the threads' rows, summed
        collections = [0.0] * 6
        for books in self._snapshot():
            ring, seconds = books.ring[:], books.second[:]
            collections = [a + b for a, b in zip(collections, books.gc)]
            for row, second in enumerate(seconds):
                if second < 0:
                    continue
                mine = ring[row * width: (row + 1) * width]
                into = rows.get(second)
                if into is None:
                    rows[second] = list(mine)
                    continue
                for at in range(0, width, 4):
                    longest = max(into[at + 3], mine[at + 3])
                    for column in (0, 1, 2):
                        into[at + column] += mine[at + column]
                    into[at + 3] = longest
        n = len(self.stamp_names)
        stamps = sorted(
            (second, self._stamps[row * n:(row + 1) * n])
            for row, second in enumerate(self._stamp_second) if second >= 0
        )
        stamps.append((None, self._read_stamps()))
        out: Dict[str, dict] = {}
        newest = max(rows, default=0)
        for second in sorted(rows):
            if second <= newest - self._rows:
                continue  # a thread long idle still holds it
            row = rows[second]
            entry = out[str(second)] = {}
            for slot, name in enumerate(self.stages):
                cell = row[4 * slot: 4 * slot + 4]
                if cell[0]:
                    entry[name] = [int(cell[0]), cell[1], cell[2], cell[3]]
        for (second, at), (_, then) in zip(stamps, stamps[1:]):
            entry = out.setdefault(str(second), {})
            for i, name in enumerate(self.stamp_names):
                grown = then[i] - at[i]
                entry[name] = grown if name.endswith("_s") else int(grown)
            if not self._thread_clocks:
                del entry["threads_cpu_s"]
        return {
            "clock": "time.monotonic",
            "columns": list(self.COLUMNS),
            "sample_one_in": self.sample_one_in,
            "seconds": {s: out[s] for s in sorted(out, key=int)},
            "gc_generations": {
                str(g): [int(collections[2 * g]), collections[2 * g + 1]]
                for g in range(3)
            },
        }


class stage:  # noqa: N801 - reads as a statement: ``with stage(...):``
    """One occurrence of a stage that stands alone (not one of a dispatcher
    thread's launch) and books itself into ``clock``::

        with spans.stage("service_decode", clock, since=t_read) as decode:
            ...
            decode.ref = (connection, req_id)

    ``since`` moves the stage's start back (it began in a wait); ``ref``,
    set inside the block, names the span for the clock's tracer; ``end`` is
    the instant the block was left.  An occurrence that served several
    requests at once (one socket read of the verifier service) lists the
    clocked ones in ``refs`` and counts all it served in ``riders``: each
    of ``refs`` books a sample of its own, the wall whole and the CPU
    divided by ``riders``, as the requests of a launch do
    (``StageClock.end_launch``)."""

    __slots__ = ("name", "clock", "since", "ref", "refs", "riders", "end",
                 "_state", "_c0", "_g0", "_annotation")

    def __init__(self, name: str, clock: StageClock,
                 since: Optional[float] = None) -> None:
        self.name = name
        self.clock = clock
        self.since = time.monotonic() if since is None else since
        self.ref = None
        self.refs: Optional[list] = None
        self.riders = 1

    def __enter__(self) -> "stage":
        state = self._state = _state()
        self._annotation = None
        if not state.annotated:
            annotation = self._annotation = _annotation(self.name)
            if annotation is not None:
                state.annotated = True
                annotation.__enter__()
        self._g0 = state.gc_cpu
        self._c0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        state = self._state
        cpu = time.thread_time() - self._c0 - (state.gc_cpu - self._g0)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            state.annotated = False
        self.end = t1 = time.monotonic()
        clock = self.clock
        for ref in self.refs or (self.ref,):
            clock.book(self.name, t1, t1 - self.since, cpu / self.riders)
            if clock.tracer is not None and ref is not None:
                clock.tracer.record_span(self.name, ref, self.since, t1)
        return False


@contextmanager
def booked(clock: Optional[StageClock], name: str, cpu: bool = False):
    """One sample of stage ``name`` around the block — its wall and, with
    ``cpu``, the calling thread's CPU — booked as the block is left,
    however it is left; with no clock, just the block."""
    if clock is None:
        yield
        return
    t0 = time.monotonic()
    c0 = time.thread_time() if cpu else 0.0
    try:
        yield
    finally:
        end = time.monotonic()
        clock.book(name, end, end - t0,
                   time.thread_time() - c0 if cpu else 0.0)


def in_default_executor(loop, clock: Optional[StageClock], fn, *args):
    """``loop.run_in_executor(None, fn, *args)`` with the job's wait for a
    thread of the loop's default pool — handed over -> its first
    instruction — booked as ``executor_wait``: the pool is shared by the
    gateway's signature check and the collector's two hops, and a job
    queued behind the others waits here."""
    if clock is None:
        return loop.run_in_executor(None, fn, *args)
    handed = time.monotonic()

    def job():
        begun = time.monotonic()
        clock.book("executor_wait", begun, begun - handed)
        return fn(*args)

    return loop.run_in_executor(None, job)


# ---------------------------------------------------------------------------
# Shared trace loading + stage extraction (tools/trace_report.py AND
# tools/fleet_trace.py).  One implementation on purpose: the two consumers
# used to carry their own copies of the salvage/extraction logic, and a
# trace tail truncated mid-flush could land on different stage boundaries in
# each — the critical-path report and the fleet merge then disagreed about
# the same file.


def salvage_trace_events(text: str) -> List[dict]:
    """Recover complete event objects from a truncated trace: find the
    traceEvents array and raw-decode objects one at a time until the tear."""
    start = text.find('"traceEvents"')
    if start < 0:
        return []
    start = text.find("[", start)
    if start < 0:
        return []
    decoder = json.JSONDecoder()
    events: List[dict] = []
    pos = start + 1
    n = len(text)
    while pos < n:
        while pos < n and text[pos] in " \t\r\n,":
            pos += 1
        if pos >= n or text[pos] == "]":
            break
        try:
            event, pos = decoder.raw_decode(text, pos)
        except ValueError:
            break  # the tear: everything before it is intact
        if isinstance(event, dict):
            events.append(event)
    return events


def _salvage_other_data(text: str) -> dict:
    """The clock anchor survives most tears (sort_keys puts ``otherData``
    before ``traceEvents`` in our own exports); best-effort recover it."""
    start = text.find('"otherData"')
    if start < 0:
        return {}
    start = text.find("{", start + len('"otherData"'))
    if start < 0:
        return {}
    try:
        other, _ = json.JSONDecoder().raw_decode(text, start)
    except ValueError:
        return {}
    return other if isinstance(other, dict) else {}


def load_trace_events(path: str):
    """All events from a Chrome trace-event JSON file.

    Returns ``(events, note, other_data)``: a truncated/mid-flush tail is
    tolerated by salvaging the complete events before the tear (reported
    through ``note``); ``other_data`` carries the clock anchor when present.
    """
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except ValueError:
        events = salvage_trace_events(text)
        note = (
            f"note: trace is truncated (mid-flush tail?); salvaged "
            f"{len(events)} complete event(s)"
        )
        return events, note, _salvage_other_data(text)
    if isinstance(data, dict):
        events = data.get("traceEvents")
        if not isinstance(events, list):
            return [], "note: no traceEvents array in trace", {}
        return events, "", data.get("otherData") or {}
    if isinstance(data, list):
        return data, "", {}
    return [], "note: unrecognized trace shape", {}


def complete_spans(events: List[dict]) -> List[dict]:
    """Complete ("X") span events."""
    return [e for e in events if e.get("ph") == "X"]


def track_names(events: List[dict]) -> Dict[Tuple[int, int], str]:
    """(pid, tid) -> track name from the thread_name metadata events."""
    return {
        (e.get("pid", 0), e.get("tid", 0)): e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }


def stage_chains(
    span_events: List[dict], stages: Optional[Tuple[str, ...]] = None
) -> Dict[Tuple[Tuple[int, int], str], Dict[str, Tuple[int, int]]]:
    """Per-block stage chains: ``(track=(pid, tid), block label) ->
    {stage: (first ts µs, max dur µs)}``.

    The ONE extraction rule both offline consumers share: duplicate spans
    for the same (track, block, stage) — retransmits, flush overlap —
    keep the EARLIEST start and the LONGEST duration.  ``stages`` filters
    which span names participate (default: every registered stage).
    """
    allowed = set(stages if stages is not None else STAGES)
    chains: Dict[Tuple[Tuple[int, int], str], Dict[str, Tuple[int, int]]] = {}
    for e in span_events:
        name = e.get("name")
        if name not in allowed:
            continue
        label = (e.get("args") or {}).get("block")
        if not label:
            continue
        track = (e.get("pid", 0), e.get("tid", 0))
        ts = e.get("ts", 0)
        dur = e.get("dur", 0)
        entry = chains.setdefault((track, label), {})
        prev = entry.get(name)
        if prev is None:
            entry[name] = (ts, dur)
        else:
            entry[name] = (min(prev[0], ts), max(prev[1], dur))
    return chains
