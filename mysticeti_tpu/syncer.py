"""Syncer: drives the Core on block arrival and leader timeouts, emits signals.

Capability parity with ``mysticeti-core/src/syncer.rs``:

* ``Signals`` {new_block_ready, new_round} (:24-52) — wake the dissemination
  streams / reset the leader-timeout clock.
* ``Syncer.add_blocks`` (:72-93) — feed core, signal round advance, maybe propose.
* ``Syncer.force_new_block`` (:95-108) — leader-timeout path, bypasses the
  ready gate.
* ``try_new_block`` (:110-167) — ready-gate -> propose -> signal -> commit ->
  observer -> persist commit + aggregator state.
"""
from __future__ import annotations

from typing import List, Sequence

from . import spans
from .commit_observer import CommitObserver
from .core import Core
from .tracing import logger
from .types import AuthoritySet, BlockReference, RoundNumber, StatementBlock

log = logger(__name__)


class SyncerSignals:
    """Interface; the asyncio node wires these to Event/condition primitives."""

    def new_block_ready(self) -> None:
        pass

    def new_round(self, round_: RoundNumber) -> None:
        pass


class Syncer:
    def __init__(
        self,
        core: Core,
        commit_period: int,
        signals: SyncerSignals,
        commit_observer: CommitObserver,
        metrics=None,
        stages=None,
        recorder=None,
    ) -> None:
        self.core = core
        self.force_new_block_flag = False
        self.commit_period = commit_period
        self.signals = signals
        self.commit_observer = commit_observer
        self.metrics = metrics
        # block_stage_seconds{stage="leader_wait"}: when the threshold clock
        # last advanced, until the proposal for that round goes out (the
        # node's spans.StageClock; None = not clocked).
        self.stages = stages
        self._round_reached_at = None
        # Own proposals and leader timeouts, for the clock's stamp
        # (spans.NODE_STAMPS).
        self.proposals = 0
        self.leader_timeouts = 0
        # ``slow-round`` events (flight_recorder.py; None = not recorded):
        # the threshold clock took over SLOW_ROUND_S to advance — the
        # round it sat in, the seconds, this validator's own wait at the
        # proposal gate in that round and what ended it (the ``leader``
        # arrived with a batch of blocks, a connection ``closed``, or the
        # leader ``timeout`` fired).
        self.recorder = recorder
        self._advanced_at = None
        self._wait_s = 0.0
        self._wait_ended = None

    def add_blocks(
        self, blocks: Sequence[StatementBlock], connected_authorities: AuthoritySet
    ) -> List[BlockReference]:
        previous_round = self.core.current_round()
        missing_references = self.core.add_blocks(blocks)
        new_round = self.core.current_round()
        if new_round > previous_round:
            if self.stages is not None:
                now = self._round_reached_at = spans.runtime_now()
                if self.recorder is not None:
                    self._note_advance(previous_round, now)
            self.signals.new_round(new_round)
            if self.metrics is not None:
                self.metrics.threshold_clock_round.set(new_round)
        self.try_new_block(connected_authorities, ended="leader")
        return missing_references

    SLOW_ROUND_S = 0.5

    def _note_advance(self, previous_round: RoundNumber, now: float) -> None:
        took = now - (self._advanced_at or now)
        self._advanced_at = now
        if took > self.SLOW_ROUND_S:
            self.recorder.record(
                "slow-round", round=previous_round, seconds=round(took, 6),
                wait_s=round(self._wait_s, 6), ended=self._wait_ended,
            )

    def force_new_block(
        self, round_: RoundNumber, connected_authorities: AuthoritySet,
        genesis: bool = False,
    ) -> bool:
        if self.core.last_proposed() < round_:
            self.leader_timeouts += 1
            if self.metrics is not None:
                self.metrics.leader_timeout_total.inc()
                if not genesis:
                    # Attribute the stall: the timeout fired because the
                    # leader(s) of the round being abandoned never showed —
                    # counted per authority so fleet health can name the
                    # validator whose slots keep timing out.  The boot-time
                    # genesis kick reaches here too and indicts nobody.
                    # ``round_`` is the clock's round + 1 (the timeout task
                    # asks for a proposal above the round it was stuck in),
                    # and the gate of the clock's round waits for the
                    # leader of the round BELOW it.
                    for leader in self.core.leaders(max(1, round_ - 2)):
                        channel = (
                            self.metrics.mysticeti_health_leader_timeout_total
                        )
                        channel.labels(str(leader)).inc()
            self.force_new_block_flag = True
            self.try_new_block(connected_authorities, ended="timeout")
            return True
        return False

    def cleanup(self) -> None:
        """Periodic maintenance on the consensus owner: cache eviction + GC
        (core) AND the observer's settled floor, in ONE step — the
        linearizer must never run a commit DFS with a floor older than the
        store's (a ref retired by this pass but below the linearizer's
        stale floor would fail the 'whole sub-dag must be stored' check)."""
        self.core.cleanup()
        floor = self.core.dag_floor()
        if floor:
            self.commit_observer.note_gc_round(floor)

    def apply_snapshot(self, manifest) -> bool:
        """Snapshot catch-up (storage.py): adopt the remote commit baseline
        on the core, then jump the observer's linearizer to the same
        baseline — both or neither, on the single consensus owner."""
        if not self.core.apply_snapshot(manifest):
            return False
        self.commit_observer.adopt_snapshot(manifest)
        return True

    def try_new_block(self, connected_authorities: AuthoritySet,
                      ended: str = "closed") -> None:
        """``ended``: what woke the proposal gate — a batch of blocks
        (``leader``), the leader ``timeout``, or, as the dispatcher calls
        it, a connection that ``closed``."""
        if self.force_new_block_flag or self.core.ready_new_block(
            self.commit_period, connected_authorities
        ):
            if self.core.try_new_block() is None:
                return
            self.proposals += 1
            if self._round_reached_at is not None:
                end = spans.runtime_now()
                self._wait_s = end - self._round_reached_at
                self._wait_ended = (
                    "timeout" if self.force_new_block_flag else ended)
                self.stages.book("leader_wait", end, self._wait_s)
                self._round_reached_at = None
            self.signals.new_block_ready()
            self.force_new_block_flag = False

            if self.core.epoch_closed():
                return  # no commits needed once the epoch is safe to close

            tracer = spans.active()
            t_commit = tracer.now() if tracer is not None else 0.0
            while True:
                newly_committed = self.core.try_commit()
                if newly_committed:
                    log.debug(
                        "committed %d leaders up to round %d",
                        len(newly_committed),
                        max(b.round() for b in newly_committed),
                    )
                committed_subdags = self.commit_observer.handle_commit(
                    newly_committed
                )
                self.core.handle_committed_subdag(
                    committed_subdags, self.commit_observer.aggregator_state()
                )
                if tracer is not None:
                    # One span per decided leader: decision + observer +
                    # commit/state persistence.
                    for block in newly_committed:
                        tracer.record_span(
                            "commit", block.reference, t_commit,
                            authority=self.core.authority,
                        )
                # Reconfiguration makes try_commit slot-sequential (one
                # decided leader per pass, so an epoch switch lands between
                # slots); drain the remaining decidable slots here.  With
                # the knob off a pass decides everything at once and this
                # loop runs exactly once — the seed behavior.
                if self.core.reconfig is None or not newly_committed:
                    break
