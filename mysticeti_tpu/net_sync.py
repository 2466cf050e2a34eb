"""Node-level orchestration: connections, verify-then-add pipeline, timeouts.

Capability parity with ``mysticeti-core/src/net_sync.rs``:

* ``NetworkSyncer.start`` (:80-167) — Syncer + Signals, core dispatcher,
  connection accept loop, leader-timeout task, periodic cleanup task, WAL
  fsync thread.
* per-peer ``connection_task`` (:237-312) — subscribe to the peer's own blocks
  from our last-seen round, dispatch incoming messages.
* ``process_blocks`` (:314-386) — dedup via the core task, consensus-rule
  verification, then the pluggable ``BlockVerifier`` — here the
  **batched TPU signature path** (the reference verifies serially per
  connection; this framework batches across connections, block_validator.py).
* leader timeout (:401-444), cleanup every 10 s (:446-459), epoch-aware
  shutdown (:466-494), ``AsyncWalSyncer`` 1 s fsync cadence (:496-560).
"""
from __future__ import annotations

import asyncio
import contextlib
import threading
from typing import Dict, List, Optional, Set

from . import spans
from .block_validator import AcceptAllBlockVerifier, BlockVerifier
from .commit_observer import CommitObserver
from .config import Parameters, ROUNDS_IN_EPOCH_MAX
from .core import Core
from .core_task import CoreTaskDispatcher, DataPlaneOffload
from .network import (
    BlockNotFound,
    Blocks,
    Connection,
    EpochInfo,
    RequestBlocks,
    RequestBlocksResponse,
    RequestSnapshot,
    RequestSnapshotStream,
    SnapshotResponse,
    SubscribeOthersFrom,
    SubscribeOwnFrom,
    TimestampedBlocks,
    wall_jump_us,
)
from .syncer import Syncer, SyncerSignals
from .tracing import logger
from .utils.tasks import spawn_logged

log = logger(__name__)
from .synchronizer import (
    BlockDisseminator,
    BlockFetcher,
    FrameCache,
    HelperSubscriptions,
)
from .types import AuthoritySet, StatementBlock, VerificationError

CLEANUP_INTERVAL_S = 10.0

# Sender stamp pairs whose wall/monotonic deltas disagree by more than this
# mean the peer's wall clock stepped between frames (see network.wall_jump_us)
# — generous against NTP slew over the 1 s stream cadence, tight against
# actual steps.
WALL_JUMP_TOLERANCE_US = 50_000


class Notify:
    """Lost-wakeup-free notification (the tokio ``Notify::notified`` shape).

    ``subscribe()`` hands out the CURRENT event object; ``notify()`` sets it
    and installs a fresh one.  A consumer that subscribes BEFORE checking its
    condition can never miss a notification that follows the check — unlike
    the set-then-``call_soon``-clear Event pattern, where a task awaiting
    between set and clear lost the edge.

    ``generation`` counts notifications: the dissemination FrameCache keys
    entries on it, so a frame built before a new block landed can never be
    served after (the key simply stops matching) — cheap whole-cache
    invalidation without a registry of entries.
    """

    __slots__ = ("_event", "generation")

    def __init__(self) -> None:
        self._event = asyncio.Event()
        self.generation = 0

    def subscribe(self) -> asyncio.Event:
        return self._event

    def notify(self) -> None:
        self.generation += 1
        event, self._event = self._event, asyncio.Event()
        event.set()


class AsyncSignals(SyncerSignals):
    """Signals backed by asyncio primitives (syncer.rs:24-52)."""

    def __init__(self) -> None:
        self.block_ready = Notify()
        self.round_notify = Notify()
        self.current_round = 0

    def new_block_ready(self) -> None:
        self.block_ready.notify()

    def new_round(self, round_: int) -> None:
        self.current_round = round_
        self.round_notify.notify()


class NetworkSyncer:
    def __init__(
        self,
        core: Core,
        commit_observer: CommitObserver,
        network,  # TcpNetwork-like: .connections queue
        parameters: Optional[Parameters] = None,
        block_verifier: Optional[BlockVerifier] = None,
        metrics=None,
        start_wal_sync_thread: bool = False,
        recorder=None,
        stages=None,
    ) -> None:
        self.parameters = parameters or Parameters()
        self.signals = AsyncSignals()
        # block_stage_seconds{stage}: what part of a round the verification
        # path is — receive / verify / dag_add, one sample a batch — and
        # what the proposal gate cost it — leader_wait, one sample a
        # proposal (syncer.py); always on (the per-block spans of the first
        # three names stay opt-in).  ``stages`` is the validator's one
        # clock (validator.py, which attached it); a syncer assembled
        # without a validator clocks itself, with no ring.
        if stages is None and metrics is not None:
            stages = spans.StageClock(spans.NODE_STAGES)
            metrics.block_stages.attach(stages)
        self._block_stages = stages
        self.syncer = Syncer(
            core,
            self.parameters.wave_length,
            self.signals,
            commit_observer,
            metrics,
            stages=stages,
            # The ring's events (slow-round) go with the ring: a simulated
            # run has neither.
            recorder=(recorder if stages is not None and stages.ring_seconds
                      else None),
        )
        self.core = core
        self.network = network
        self.block_verifier = block_verifier or AcceptAllBlockVerifier()
        if self.parameters.signed_transactions:
            # The content check of the reference's BlockVerifier seam: on,
            # before a block is received, in every wiring of a validator —
            # with or without an ingress plane.  A verifier that cannot
            # check transactions (``--verifier accept``) is no deployment
            # of signed transactions.
            require = getattr(
                self.block_verifier, "require_transaction_signatures", None)
            if require is None:
                raise ValueError(
                    "Parameters.signed_transactions needs a block verifier "
                    "that checks transaction signatures; "
                    f"{type(self.block_verifier).__name__} checks none")
            require()
        self.metrics = metrics
        self.dispatcher = CoreTaskDispatcher(
            self.syncer, metrics=metrics, stages=stages)
        # Batched native decode+digest off the event loop (core_task.py):
        # inert (inline path) under sims, without the extension, or for
        # small frames — see DataPlaneOffload.should_offload.
        self.dataplane_offload = DataPlaneOffload(metrics=metrics)
        # Bound once: _decode_fresh is per-incoming-frame hot.
        self._utilization_timer = (
            metrics.utilization_timer
            if metrics is not None
            else (lambda _name: contextlib.nullcontext())
        )
        self.connections: Dict[int, Connection] = {}
        self.connected_authorities = AuthoritySet()
        self.fetcher = BlockFetcher(
            core.authority,
            self.dispatcher,
            self.connections,
            self.parameters.synchronizer,
            metrics,
        )
        self._tasks: List[asyncio.Task] = []
        self._disseminators: Dict[int, BlockDisseminator] = {}
        # Encode-once fan-out (synchronizer.FrameCache): one shared cache
        # across every peer's disseminator, so N-1 subscribers at the same
        # cursor ship one serialization.
        self.frame_cache = FrameCache(metrics)
        # Helper-stream bookkeeping (requester side; armed by the
        # disseminate_others_blocks knob): which connected peers relay which
        # unreachable authority's blocks for us, within the config caps.
        self._helper_subs = HelperSubscriptions(self.parameters.synchronizer)
        # Content-silence scoring (docs/adversary.md): consecutive missing-
        # parent fetches per author with no intervening DIRECT delivery of
        # that author's own blocks.  A live connection that never delivers
        # its own proposals (a withholder, or a grey-failed sender) looks
        # exactly like this; past the threshold we arm relay streams for it
        # as if its connection had dropped — the fetch path stops taxing
        # the quorum path one round-trip per round.
        self._fetch_gap_by_author: Dict[int, int] = {}
        self._stopped = asyncio.Event()
        self._wal_sync_thread: Optional[threading.Thread] = None
        self._start_wal_sync_thread = start_wal_sync_thread
        # Snapshot catch-up serving totals, surviving connection teardown
        # (the per-connection disseminator dies with its peer): the artifact
        # and tests read how much bootstrap data this node shipped.
        self.snapshot_blocks_served = 0
        self.snapshot_bytes_served = 0
        # Blocks received that passed decode, dedup and the structure
        # checks, for the clock's stamp (spans.NODE_STAMPS).
        self.blocks_received = 0
        # Flight recorder (flight_recorder.py): connection churn, leader
        # timeouts, and sync decisions are exactly the "seconds before the
        # incident" events its ring exists for.  None = not recording.
        self.recorder = recorder
        # Epoch reconfiguration (reconfig.py): last epoch each peer reported
        # over the tag-17 extension, plus the listener that re-derives the
        # relay/peer bookkeeping and re-broadcasts EpochInfo on a switch.
        self.peer_epochs: Dict[int, int] = {}
        if getattr(core, "reconfig", None) is not None:
            core.epoch_listeners.append(self._on_epoch_switch)

    def _record(self, kind: str, **fields) -> None:
        if self.recorder is not None:
            self.recorder.record(kind, **fields)

    def _note_connections(self) -> None:
        if self.metrics is not None:
            self.metrics.connected_nodes.set(len(self.connections))

    # -- lifecycle --

    async def start(self) -> "NetworkSyncer":
        self.dispatcher.start()
        self.connected_authorities.insert(self.core.authority)
        # Initial proposal attempt (validator genesis kick, net_sync.rs:97).
        await self.dispatcher.force_new_block(
            1, self.connected_authorities.copy(), genesis=True
        )
        self._tasks.append(spawn_logged(self._accept_loop(), log))
        self._tasks.append(spawn_logged(self._leader_timeout_task(), log))
        self._tasks.append(spawn_logged(self._cleanup_task(), log))
        if self.parameters.rounds_in_epoch < ROUNDS_IN_EPOCH_MAX:
            self._tasks.append(spawn_logged(self._epoch_watch_task(), log))
        self.fetcher.start()
        if self._start_wal_sync_thread:
            self._start_wal_syncer()
        return self

    def _start_wal_syncer(self) -> None:
        """Dedicated fsync thread, 1 s cadence (net_sync.rs:496-560)."""
        syncer = self.core.wal_syncer()
        stop = self._stopped
        size_gauge = self.metrics.wal_size_bytes if self.metrics else None
        segments_gauge = self.metrics.wal_segments if self.metrics else None
        wal_writer = self.core.wal_writer

        def run():
            import time as _time

            while not stop.is_set():
                _time.sleep(1.0)
                try:
                    syncer.sync()
                except OSError:
                    return
                if size_gauge is not None:
                    # Live bytes across every surviving segment — the old
                    # single-file read (the append position) over-reports
                    # by exactly the GC-reclaimed bytes once segments roll;
                    # sampled here so the gauge costs one set per second.
                    size_gauge.set(wal_writer.size_bytes())
                if segments_gauge is not None:
                    segments_gauge.set(wal_writer.segment_count())

        self._wal_sync_thread = threading.Thread(
            target=run, name="wal-syncer", daemon=True
        )
        self._wal_sync_thread.start()

    async def stop(self) -> None:
        self._stopped.set()
        self.fetcher.stop()
        for d in self._disseminators.values():
            d.stop()
        for t in self._tasks:
            if t is not asyncio.current_task():
                t.cancel()
        self.dispatcher.stop()
        self.dataplane_offload.stop()
        for c in self.connections.values():
            c.close()
        if hasattr(self.network, "stop"):
            await self.network.stop()

    async def await_completion(self) -> None:
        await self._stopped.wait()

    def backpressure(self) -> Dict[str, object]:
        """Live core backpressure signals for the ingress plane's admission
        controller (ingress.py): the consensus owner's queue depth and the
        WAL appender's drain state — cheap reads of state the node already
        maintains, no new bookkeeping."""
        return {
            "core_queue_depth": self.dispatcher.queue_depth(),
            "core_queue_capacity": self.dispatcher.queue_capacity,
            "wal_backlog": bool(self.core.wal_writer.pending()),
        }

    # -- connection handling --

    async def _accept_loop(self) -> None:
        while True:
            connection: Connection = await self.network.connections.get()
            self._tasks.append(
                spawn_logged(self._connection_task(connection), log)
            )

    # Max verification groups in flight per connection: deep enough that a
    # slow backend's per-dispatch round-trip overlaps many batches, small
    # enough to backpressure a flooding peer.
    VERIFY_PIPELINE_DEPTH = 32

    async def _connection_task(self, connection: Connection) -> None:
        """net_sync.rs:237-312.  A peer counts as connected, for the
        proposal gate (``connected_authorities``) as for ``connections``,
        from here until the connection that holds its slot in
        ``connections`` ends: a second connection to the peer takes the
        slot over, and the first one closing then takes nothing out."""
        peer = connection.peer
        log.debug("connection established with authority %d", peer)
        self._record("peer-connect", peer=peer)
        self.connections[peer] = connection
        self.connected_authorities.insert(peer)
        self._note_connections()
        disseminator = BlockDisseminator(
            connection,
            self.core.block_store,
            self.signals.block_ready,
            self.parameters.synchronizer,
            self.metrics,
            frame_cache=self.frame_cache,
        )
        self._disseminators[peer] = disseminator
        # Ask the peer for its own blocks we have not yet seen.
        last_seen = self.core.block_store.last_seen_by_authority(peer)
        await connection.send(SubscribeOwnFrom(last_seen))
        # A direct stream from this authority makes any relay of its blocks
        # redundant; forgetting the ask lets a later outage re-request.
        self._helper_subs.drop_authority(peer)
        if self.parameters.reconfig and self.core.reconfig is not None:
            # Tag-17 soft extension: advertise our epoch + committee digest
            # right after the fixed hello (version-skew safe — only sent
            # when the knob is on, and advisory on the receiving side).
            await connection.send(
                EpochInfo(self.core.committee.epoch, self.core.reconfig.digest())
            )
        if self.parameters.synchronizer.disseminate_others_blocks:
            await self._request_helper_streams(connection)
        if self.parameters.storage.snapshot_catchup:
            # Snapshot catch-up ask (storage.py): tell the peer our commit
            # height; a peer far enough ahead answers with its manifest +
            # the retained block window, anyone else ignores it.  Cheap (one
            # small frame per connect) and self-gating on both sides.
            await connection.send(RequestSnapshot(self.core.commit_height()))
        # Per-connection verification pipeline: the reader overlaps many
        # in-flight signature batches (the accelerator's round-trip would
        # otherwise serialize the connection at one batch per RTT), while the
        # accept loop awaits results IN ORDER so blocks enter the core in the
        # stream order the peer sent them (no spurious missing-parent
        # requests).
        pipeline: asyncio.Queue = asyncio.Queue(maxsize=self.VERIFY_PIPELINE_DEPTH)
        # Same-connection dedup window: dispatcher.processed only knows blocks
        # that finished the pipeline, so without this a peer retransmitting a
        # block back-to-back would get every copy signature-verified while the
        # first is still in flight.
        inflight: Set[bytes] = set()
        # Last sender stamp pair per tag-12 frame (wall-jump detection).
        last_stamp: Optional[tuple] = None
        # One-shot arming for the snapshot bulk stream: serving a manifest
        # to this peer arms exactly one RequestSnapshotStream (re-arming
        # requires another gap-checked RequestSnapshot), so a caught-up or
        # misbehaving peer cannot turn the one-u64 ask into a repeated
        # full-window push.
        snapshot_armed_floor: Optional[int] = None
        accept_task = asyncio.ensure_future(
            self._accept_ordered(pipeline, connection, inflight)
        )
        try:
            while True:
                msg = await connection.recv()
                if msg is None:
                    break
                if isinstance(msg, SubscribeOwnFrom):
                    disseminator.subscribe_own_from(msg.round)
                elif isinstance(msg, SubscribeOthersFrom):
                    # Serving side of the helper streams: answer whenever
                    # asked (the knob governs ASKING; the disseminator's
                    # absolute cap bounds what one peer can demand).
                    disseminator.subscribe_others_from(
                        msg.authority, msg.round
                    )
                elif isinstance(msg, (Blocks, RequestBlocksResponse)):
                    transit = None
                    if (
                        isinstance(msg, TimestampedBlocks)
                        and msg.sent_wall_ns
                    ):
                        # Wire-timestamp extension (tag 12): raw transit is
                        # SIGNED (clock skew can drive it negative) — the
                        # histogram clamps, the trace keeps the raw value
                        # for the fleet merger's skew estimator.  The
                        # monotonic stamp detects a sender wall-clock STEP
                        # between frames: that frame's wall-derived transit
                        # is garbage and is dropped (log once per step).
                        from .runtime import timestamp_utc

                        stamp = (msg.sent_monotonic_ns, msg.sent_wall_ns)
                        jumped = (
                            last_stamp is not None
                            and wall_jump_us(last_stamp, stamp)
                            > WALL_JUMP_TOLERANCE_US
                        )
                        last_stamp = stamp
                        if jumped:
                            log.warning(
                                "authority %d wall clock stepped between "
                                "frames; dropping transit sample", peer,
                            )
                        else:
                            raw_s = (
                                timestamp_utc() - msg.sent_wall_ns / 1e9
                            )
                            rtt_s = connection.latency()
                            if rtt_s == float("inf"):
                                rtt_s = None
                            if self.metrics is not None:
                                self.metrics.dissemination_transit_seconds.labels(
                                    str(peer)
                                ).observe(max(0.0, raw_s))
                            transit = (peer, raw_s, rtt_s)
                    verified = await self._decode_fresh(
                        msg.blocks, transit=transit, peer=peer
                    )
                    verified = [
                        b for b in verified
                        if b.reference.digest not in inflight
                    ]
                    if verified:
                        refs = [b.reference.digest for b in verified]
                        inflight.update(refs)
                        # Awaited in stream order by _accept_ordered, which
                        # observes its exception.  # lint: ignore[task-orphan]
                        fut = asyncio.ensure_future(
                            self._verify_accepted(verified)
                        )
                        try:
                            await pipeline.put((fut, refs))
                        except asyncio.CancelledError:
                            fut.cancel()
                            raise
                elif isinstance(msg, RequestSnapshot):
                    # Serving side: answer a genuinely far-behind peer with
                    # the MANIFEST only (cheap — every connected server may
                    # answer).  The bulk block window ships on an explicit
                    # RequestSnapshotStream from the one peer that adopted
                    # our manifest, so a rejoiner never receives N-1
                    # redundant copies of the whole retained window.
                    manifest = self.core.snapshot_manifest_for(
                        msg.commit_height
                    )
                    if manifest is not None:
                        log.info(
                            "serving snapshot manifest to authority %d (its "
                            "height %d, ours %d)", peer, msg.commit_height,
                            manifest.commit_height,
                        )
                        self._record(
                            "snapshot-served", peer=peer,
                            peer_height=msg.commit_height,
                            height=manifest.commit_height,
                        )
                        snapshot_armed_floor = manifest.gc_round
                        await connection.send(
                            SnapshotResponse(manifest.to_bytes())
                        )
                elif isinstance(msg, RequestSnapshotStream):
                    if (
                        self.parameters.storage.snapshot_catchup
                        and snapshot_armed_floor is not None
                    ):
                        # Serve from the floor we actually advertised (the
                        # peer's value cannot widen the walk), and hold GC
                        # so the window cannot be holed mid-stream.
                        disseminator.stream_snapshot(
                            max(msg.from_round, snapshot_armed_floor),
                            gc_hold=self.core.storage,
                        )
                        snapshot_armed_floor = None
                elif isinstance(msg, SnapshotResponse):
                    await self._handle_snapshot_response(connection, msg)
                elif isinstance(msg, EpochInfo):
                    # Advisory (tag 17): a skewed peer is probably mid-
                    # boundary — never a reason to sever; the committed
                    # sequence itself converges the fleet.
                    self.peer_epochs[peer] = msg.epoch
                    local_epoch = self.core.committee.epoch
                    if msg.epoch != local_epoch:
                        log.warning(
                            "authority %d reports epoch %d (local epoch %d);"
                            " transient skew expected around a boundary",
                            peer, msg.epoch, local_epoch,
                        )
                        self._record(
                            "epoch-skew", peer=peer, peer_epoch=msg.epoch,
                            local_epoch=local_epoch,
                        )
                elif isinstance(msg, RequestBlocks):
                    if self.metrics is not None:
                        self.metrics.block_sync_requests_received.labels(
                            str(peer)
                        ).inc(len(msg.references))
                    await disseminator.send_requested(list(msg.references))
                elif isinstance(msg, BlockNotFound):
                    if self.metrics is not None:
                        self.metrics.block_sync_requests_failed.inc(
                            len(msg.references)
                        )
        finally:
            log.debug("connection to authority %d closed", peer)
            self._record("peer-disconnect", peer=peer)
            # Drain what already entered the pipeline, then stop the acceptor.
            # If this task is itself being cancelled (node stop), don't wait —
            # cancel the acceptor instead of hanging in the finally.
            try:
                await pipeline.put(None)
                await accept_task
            except asyncio.CancelledError:
                accept_task.cancel()
                try:
                    await accept_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            # Cancel any verify futures still queued (nothing will await
            # them once the acceptor is gone).
            while True:
                try:
                    item = pipeline.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if item is not None:
                    item[0].cancel()
            disseminator.stop()
            self.snapshot_blocks_served += disseminator.snapshot_blocks_sent
            self.snapshot_bytes_served += disseminator.snapshot_bytes_sent
            self._disseminators.pop(peer, None)
            gone = self.connections.get(peer) is connection
            if gone:
                # Not where a reconnect that raced this teardown already
                # holds the slot: that peer is connected.
                del self.connections[peer]
                if peer != self.core.authority:  # its own index stays in
                    self.connected_authorities.remove(peer)
                self._note_connections()
            connection.close()
            # Helper-stream hygiene: relays this peer ran for us died with
            # the connection, and the peer's own blocks now need a relay —
            # ask the surviving peers (within the config caps) both for the
            # peer itself and for every authority it was relaying.
            orphaned = self._helper_subs.drop_helper(peer)
            if (
                self.parameters.synchronizer.disseminate_others_blocks
                and not self._stopped.is_set()
            ):
                self._ask_relays_for(peer)
                for authority in orphaned:
                    live = self.connections.get(authority)
                    if live is None or live.is_closed():
                        self._ask_relays_for(authority)
            if gone and not self._stopped.is_set():
                # A proposal held for this peer's leader slot
                # (core.ready_new_block waits for connected leaders only)
                # goes out now, not at the leader timeout — which stays the
                # backstop for a leader that is connected and silent.
                await self.dispatcher.try_new_block(
                    self.connected_authorities.copy()
                )

    async def _handle_snapshot_response(
        self, connection: Connection, msg: SnapshotResponse
    ) -> None:
        """Client side of snapshot catch-up: decode the manifest and adopt
        it on the consensus owner (which also releases any blocks already
        parked on sub-floor parents).  Stale/duplicate manifests — every
        connected peer may answer — are rejected by the owner's gap check;
        only the ADOPTED manifest's sender is asked to stream the bulk
        block window."""
        from .storage import SnapshotManifest

        if not self.parameters.storage.snapshot_catchup:
            # We never asked: an unsolicited manifest with a huge baseline
            # would otherwise poison the commit chain and raise the DAG
            # floor on a node that opted out of catch-up entirely.
            log.warning("ignoring unsolicited snapshot manifest from peer")
            return
        try:
            manifest = SnapshotManifest.from_bytes(msg.manifest)
        except Exception:  # noqa: BLE001 - byzantine peer: drop, don't die
            log.warning("dropping malformed snapshot manifest from peer")
            return
        adopted = await self.dispatcher.apply_snapshot(manifest)
        if adopted:
            log.info(
                "snapshot catch-up adopted: commit height %d, floor %d",
                manifest.commit_height, manifest.gc_round,
            )
            self._record(
                "snapshot-adopted", peer=connection.peer,
                height=manifest.commit_height, floor=manifest.gc_round,
            )
            await connection.send(RequestSnapshotStream(manifest.gc_round))

    def _on_epoch_switch(self, committee, records) -> None:
        """Epoch listener (core.epoch_listeners): runs on the consensus
        owner right after a boundary commit switched the committee.
        Sync-only — retire relay bookkeeping for departed authorities,
        refresh the signature verifier's key view, and re-broadcast our
        new coordinates.  Live connections to departed peers are NOT
        severed: in-flight catch-up streams finish naturally."""
        for authority in range(len(committee)):
            if authority == self.core.authority:
                continue
            if not committee.is_active(authority):
                # A departed authority needs no relays (its blocks are
                # settled history) and must not serve as one of ours.
                self._helper_subs.drop_authority(authority)
                self._helper_subs.drop_helper(authority)
            elif self.parameters.synchronizer.disseminate_others_blocks:
                # A JOINING authority we cannot reach directly yet gets
                # relays immediately — its first own blocks matter (they
                # un-stall its leader slots under the new stake table).
                live = self.connections.get(authority)
                if live is None or live.is_closed():
                    self._ask_relays_for(authority)
        note = getattr(self.block_verifier, "note_committee", None)
        if note is not None:
            note(committee)
        if self.parameters.reconfig and self.core.reconfig is not None:
            info = EpochInfo(committee.epoch, self.core.reconfig.digest())
            for conn in list(self.connections.values()):
                if not conn.is_closed():
                    conn.try_send(info)

    def _ask_relays_for(self, authority: int) -> None:
        """Ask connected peers to relay ``authority``'s blocks (its direct
        connection just dropped), up to maximum_helpers_per_authority."""
        if not self.core.committee.is_active(authority):
            return  # departed this epoch: its blocks are settled history
        last_seen = self.core.block_store.last_seen_by_authority(authority)
        for helper, conn in list(self.connections.items()):
            if helper == authority or conn.is_closed():
                continue
            if not self._helper_subs.may_ask(authority, helper):
                continue
            if conn.try_send(SubscribeOthersFrom(authority, last_seen)):
                self._helper_subs.note_asked(authority, helper)
                self._record("helper-ask", authority=authority, helper=helper)

    async def _request_helper_streams(self, connection: Connection) -> None:
        """On a fresh connection: ask it to relay every authority we have
        no live connection to (late joiner against a partitioned mesh, a
        peer behind an asymmetric fault), within the config caps."""
        for authority in range(len(self.core.committee)):
            if authority in (self.core.authority, connection.peer):
                continue
            if not self.core.committee.is_active(authority):
                continue  # departed this epoch: no relay needed
            live = self.connections.get(authority)
            if live is not None and not live.is_closed():
                continue
            if not self._helper_subs.may_ask(authority, connection.peer):
                continue
            last_seen = self.core.block_store.last_seen_by_authority(authority)
            await connection.send(SubscribeOthersFrom(authority, last_seen))
            self._helper_subs.note_asked(authority, connection.peer)
            self._record(
                "helper-ask", authority=authority, helper=connection.peer
            )

    async def _accept_ordered(
        self, pipeline: asyncio.Queue, connection, inflight: Set[bytes]
    ) -> None:
        while True:
            item = await pipeline.get()
            if item is None:
                return
            fut, refs = item
            try:
                accepted = await fut
                if accepted:
                    await self._add_accepted(accepted, connection)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - a bad batch must not kill the pipe
                log.exception("accept pipeline stage failed")
            finally:
                for ref in refs:
                    inflight.discard(ref)

    # -- the receive pipeline (net_sync.rs:314-386), three stages --
    #
    # Ingest batching invariant (audited for the broadcast-once plane, and
    # pinned by the whole-frame census test): a frame of K blocks crosses
    # the core owner exactly TWICE — one `processed()` dedup command for
    # the whole batch and one `add_blocks()` for the accepted batch.
    # Nothing in this pipeline may hop to the owner per block; a regression
    # here multiplies the owner queue by the frame size at saturation.

    def _count_invalid(self, authority, reason: str, count: int = 1) -> None:
        """Invalid-block attribution (docs/adversary.md): a rejection used
        to vanish into a log line — now every one lands on
        ``mysticeti_invalid_blocks_total{authority, reason}`` and in the
        flight-recorder ring, so a misbehaving peer is attributable from
        /health and fleetmon."""
        if self.metrics is not None:
            self.metrics.mysticeti_invalid_blocks_total.labels(
                str(authority), reason
            ).inc(count)
        self._record(
            "invalid-block", authority=authority, reason=reason, count=count
        )

    async def _decode_fresh(
        self, serialized_blocks, transit=None, peer=None
    ) -> List[StatementBlock]:
        """Stage 1 (host, fast): parse, dedup via the core task, consensus-
        rule checks.  ``transit`` is ``(src peer, raw signed transit s,
        rtt s or None)`` when the frame rode the timestamp extension — each
        fresh block then gets a ``transit`` span whose args carry the link
        and the raw value for the fleet merger's skew estimator.  ``peer``
        attributes malformed payloads (undecodable bytes name no author —
        the DELIVERING connection is the misbehaving party)."""
        tracer = spans.active()
        t_recv = spans.runtime_now()
        timer = self._utilization_timer
        offload = self.dataplane_offload
        if offload is not None and offload.should_offload(
            sum(len(raw) for raw in serialized_blocks)
        ):
            # Big batch + native extension + real node: decode all blocks
            # and hash all digests/signature-prehashes on the offload
            # worker, one GIL round-trip for the whole frame; the event
            # loop keeps scheduling meanwhile.  Stage time lands on
            # utilization_timer{proc="offload:decode"} (measured in the
            # worker) rather than net:decode.  Sims never take this branch
            # (offload inactive) — the inline path below introduces no new
            # awaits, keeping seeded schedules byte-identical.
            decoded = await offload.run(
                "decode", StatementBlock.from_bytes_many, serialized_blocks
            )
        else:
            with timer("net:decode"):
                decoded = StatementBlock.from_bytes_many(serialized_blocks)
        blocks: List[StatementBlock] = [b for b in decoded if b is not None]
        malformed = len(decoded) - len(blocks)
        if malformed:
            log.warning("dropping %d malformed block payload(s) from peer",
                        malformed)
            if peer is not None:
                self._count_invalid(peer, "malformed", malformed)
        if not blocks:
            return []
        # Dedup through the core task before paying for verification.
        processed = await self.dispatcher.processed([b.reference for b in blocks])
        fresh = [b for b, done in zip(blocks, processed) if not done]
        verified: List[StatementBlock] = []
        with timer("net:verify_structure"):
            for block in fresh:
                try:
                    # Epoch-matched structural rules: a pre-boundary block's
                    # threshold clock is judged by its OWN epoch's quorum
                    # (committee_for_epoch falls back to the current
                    # committee outside reconfiguration).
                    block.verify_structure(
                        self.core.committee_for_epoch(block.epoch)
                    )
                except VerificationError as exc:
                    log.warning("rejecting block %r: %s", block.reference, exc)
                    self._count_invalid(block.author(), "structure")
                    continue
                verified.append(block)
        if self.metrics is not None and verified:
            # Proposal-to-receipt per author (metrics.rs:81
            # block_receive_latency) — per block, so the cost scales with
            # block rate, not tx rate.
            from .runtime import timestamp_utc

            now = timestamp_utc()
            for block in verified:
                created = block.meta_creation_time_ns
                if created:
                    self.metrics.block_receive_latency.labels(
                        str(block.author())
                    ).observe(max(0.0, now - created / 1e9))
        if tracer is not None:
            if transit is not None and verified:
                src, raw_s, rtt_s = transit
                extra = {"src": src, "raw_us": int(round(raw_s * 1e6))}
                if rtt_s is not None:
                    extra["rtt_us"] = int(round(rtt_s * 1e6))
                t0_transit = t_recv - max(0.0, raw_s)
                for block in verified:
                    tracer.record_span(
                        "transit", block.reference, t0_transit, t1=t_recv,
                        authority=self.core.authority, extra=extra,
                    )
            for block in verified:
                tracer.record_span(
                    "receive", block.reference, t_recv,
                    authority=self.core.authority,
                )
        self.blocks_received += len(verified)
        if self._block_stages is not None:
            self._block_stages.book_since("receive", t_recv)
        return verified

    async def _verify_accepted(
        self, verified: List[StatementBlock]
    ) -> List[StatementBlock]:
        """Stage 2 (accelerator): signature + application check through the
        pluggable verifier (batched across connections on TPU)."""
        tracer = spans.active()
        t_verify = spans.runtime_now()
        results = await self.block_verifier.verify_blocks(verified)
        if self._block_stages is not None:
            # Block received -> verdict: the collector's window plus the
            # request's round trip to the verifier.
            self._block_stages.book_since("verify", t_verify)
        accepted = [b for b, ok in zip(verified, results) if ok]
        if tracer is not None:
            for block in accepted:
                tracer.record_span(
                    "verify", block.reference, t_verify,
                    authority=self.core.authority,
                )
        if len(accepted) < len(verified):
            log.warning(
                "block verifier rejected %d of %d blocks",
                len(verified) - len(accepted),
                len(verified),
            )
            rejected_by_author: Dict[int, int] = {}
            for block, ok in zip(verified, results):
                if not ok:
                    author = block.author()
                    rejected_by_author[author] = (
                        rejected_by_author.get(author, 0) + 1
                    )
            for author in sorted(rejected_by_author):
                self._count_invalid(
                    author, "signature", rejected_by_author[author]
                )
        return accepted

    async def _add_accepted(self, accepted: List[StatementBlock], origin) -> None:
        """Stage 3: hand to the core, chase missing causal history."""
        tracer = spans.active()
        t = spans.runtime_now()
        if tracer is not None:
            # Closed by Core.add_blocks when the block is actually inserted,
            # so the span covers the core-task queue AND any time parked on
            # missing parents.
            for block in accepted:
                tracer.begin_span(
                    "dag_add", block.reference,
                    authority=self.core.authority, t=t,
                )
        missing = await self.dispatcher.add_blocks(
            accepted, self.connected_authorities.copy()
        )
        if self._block_stages is not None:
            # The batch's view of dag_add: the core-task queue plus the
            # insertion (a block parked on missing parents ends later, in
            # its own span).
            self._block_stages.book_since("dag_add", t)
        if accepted and any(
            d.relay_serving for d in self._disseminators.values()
        ):
            # Freshly stored peer blocks must reach our relay subscribers
            # NOW — their next chance is our own next proposal, a round too
            # late for a parked child.  No-op when nothing was ever relayed
            # (the production-default clean path), and gated on the batch
            # actually carrying a RELAYED author — waking every stream per
            # honest batch is a quadratic wake storm under attack.
            served = set()
            for d in self._disseminators.values():
                if d.relay_serving:
                    served.update(d.relayed_authorities())
            if any(block.author() in served for block in accepted):
                self.signals.new_block_ready()
        if origin is not None and self._fetch_gap_by_author:
            # A direct own-block delivery clears the author's silence score
            # (an honest-but-jittery peer must never accumulate one).
            for block in accepted:
                if block.author() == origin.peer:
                    self._fetch_gap_by_author.pop(origin.peer, None)
                    self.core.content_silent.discard(origin.peer)
                    break
        if self.metrics is not None:
            from .runtime import timestamp_utc

            now = timestamp_utc()
            for block in accepted:
                created = block.meta_creation_time_ns
                if created:
                    self.metrics.add_block_latency.labels(
                        str(block.author())
                    ).observe(max(0.0, now - created / 1e9))
        if missing:
            if self.parameters.synchronizer.disseminate_others_blocks:
                self._score_missing(missing, origin)
            # Request missing causal history from the connection that
            # delivered the children — it is the peer most likely to have the
            # parents (net_sync.rs:276,388-399).  If that connection is stale
            # (replaced after a reconnect) or the send fails, fall back to any
            # live peer so the request is never silently dropped.
            request = RequestBlocks(tuple(missing[:50]))
            sent = False
            if origin is not None and self.connections.get(origin.peer) is origin:
                sent = origin.try_send(request)
            if not sent:
                for peer, conn in list(self.connections.items()):
                    if conn.try_send(request):
                        break

    # Missing-parent fetches tolerated for one author (with a LIVE direct
    # connection and no direct own-block delivery in between) before its
    # relay streams arm: low enough that a withholder costs a handful of
    # rounds, high enough that ordinary delivery jitter never trips it.
    CONTENT_SILENCE_FETCHES = 5

    def _score_missing(self, missing, origin) -> None:
        """Adversary-shaped gap scoring on the fetch path (two shapes):

        * **equivocation-shaped** — the store already holds a DIFFERENT
          digest at the missing reference's (authority, round): some peer
          included a sibling we were never sent.  One relay subscription
          makes every future variant arrive proactively instead of one
          pull round-trip per round.
        * **content silence** — repeated gaps for an author whose direct
          connection is alive but never delivers its own blocks (the
          withholder).  Past :data:`CONTENT_SILENCE_FETCHES`, arm relays
          exactly as if the connection had dropped.

        The relay is asked of ``origin`` first — the peer whose blocks
        referenced the missing digest PROVABLY stores it (an equivocation
        variant lives only on the subset the adversary favored with it;
        a blind helper pick would relay the copy we already hold)."""
        store = self.core.block_store
        for ref in missing:
            author = ref.authority
            if author == self.core.authority:
                continue
            if store.block_exists_at_authority_round(author, ref.round):
                self._record(
                    "equivocation-gap", authority=author, round=ref.round
                )
                self._ask_relay_of(author, origin)
                continue
            score = self._fetch_gap_by_author.get(author, 0) + 1
            self._fetch_gap_by_author[author] = score
            # >= with the content_silent set as the armed flag: an `==`
            # one-shot would disarm FOREVER if the connection happened to
            # be mid-reconnect at the exact threshold fetch.
            if (
                score >= self.CONTENT_SILENCE_FETCHES
                and author not in self.core.content_silent
            ):
                conn = self.connections.get(author)
                if conn is not None and not conn.is_closed():
                    self._record("content-silent", authority=author)
                    # Stop gating proposals on this author's leader slots
                    # too (core.ready_new_block): its blocks now arrive via
                    # relays — waiting for the relay hop on every one of
                    # its slots is the withholder's remaining tax.
                    self.core.content_silent.add(author)
                    self._ask_relay_of(author, origin)

    def _ask_relay_of(self, authority: int, origin) -> None:
        """Subscribe to ``origin``'s relay of ``authority``'s blocks
        (falling back to the blind helper pick when the origin is gone),
        within the same per-authority/total caps as drop-triggered asks."""
        if (
            origin is not None
            and origin.peer != authority
            and self.connections.get(origin.peer) is origin
            and self._helper_subs.may_ask(authority, origin.peer)
        ):
            last_seen = self.core.block_store.last_seen_by_authority(authority)
            if origin.try_send(SubscribeOthersFrom(authority, last_seen)):
                self._helper_subs.note_asked(authority, origin.peer)
                self._record(
                    "helper-ask", authority=authority, helper=origin.peer
                )
                return
        self._ask_relays_for(authority)

    # -- background tasks --

    # A leader timeout that fires this much after it was due says that the
    # process stood still, not that the leader did (``_leader_timeout_task``).
    LATE_TIMER_S = 0.25

    async def _leader_timeout_task(self) -> None:
        """net_sync.rs:401-444: force a proposal if the round stalls.

        The task must outlive individual command failures: it is the
        liveness backstop, and an exception escaping this loop would
        silently remove the fleet's only stall-recovery mechanism."""
        timeout = self.parameters.leader_timeout_s
        clock = asyncio.get_running_loop().time
        while True:
            waiter = self.signals.round_notify.subscribe()
            round_at_start = self.signals.current_round
            armed = clock()
            try:
                await asyncio.wait_for(waiter.wait(), timeout=timeout)
            except asyncio.TimeoutError:
                if self.core.epoch_closed():
                    continue
                if clock() - armed > timeout + self.LATE_TIMER_S:
                    # The timer fired late: this process was not running
                    # (a stopped machine, a SIGSTOP, the loop held), so
                    # what the leader sent meanwhile is still in its
                    # sockets.  It is heard first: the wait starts again.
                    self._record("leader-timeout-late", round=round_at_start,
                                 late_s=round(clock() - armed - timeout, 3))
                    continue
                log.debug(
                    "leader timeout at round %d: forcing proposal", round_at_start
                )
                self._record("leader-timeout", round=round_at_start)
                try:
                    await self.dispatcher.force_new_block(
                        round_at_start + 1, self.connected_authorities.copy()
                    )
                except asyncio.CancelledError:
                    raise
                except Exception:
                    log.exception("forced proposal failed; timeout task lives on")

    async def _epoch_watch_task(self) -> None:
        """Epoch-aware shutdown (net_sync.rs:466-494): once the epoch is SAFE
        TO CLOSE, keep serving for the grace period (so slower peers can reach
        the epoch-close quorum from our blocks), then stop the node."""
        while not self.core.epoch_closed():
            await asyncio.sleep(0.2)
        grace = self.parameters.shutdown_grace_period_s
        log.info(
            "epoch safe to close at round %d; shutting down after %.1fs grace",
            self.signals.current_round,
            grace,
        )
        await asyncio.sleep(grace)
        await self.stop()

    async def _cleanup_task(self) -> None:
        while True:
            await asyncio.sleep(CLEANUP_INTERVAL_S)
            if self.parameters.enable_cleanup:
                await self.dispatcher.cleanup()
