"""Node assembly: storage, handlers, network, syncer — the whole validator.

Capability parity with ``mysticeti-core/src/validator.rs``:

* ``Validator.start_benchmarking`` (:78-163) — benchmark fast-path handler +
  open-loop generator + TestCommitObserver + metrics endpoint.
* ``Validator.start_production`` (:165-212) — SimpleBlockHandler (application
  submits raw transactions, acked on proposal) + SimpleCommitObserver
  (sub-dags to a consumer queue with replay above last_sent_height).
* ``init_storage`` (:334-352) — WAL + BlockStore recovery.
* ``CommitConsumer`` (:50-66) — the application-facing commit stream handle.

TPU addition (the point of this framework): ``verifier=`` selects the signature
backend — "tpu" routes block verification through the batched JAX kernel
(block_validator.py), "cpu" uses the serial OpenSSL oracle (reference
behavior), "accept" skips signature checks (the reference's default
AcceptAllBlockVerifier wiring, validator.rs:137).
"""
from __future__ import annotations

import asyncio
import gc
import os
import time
from typing import List, Optional, Tuple

from .block_handler import BenchmarkFastPathBlockHandler, SimpleBlockHandler
from .block_validator import (
    AcceptAllBlockVerifier,
    BatchedSignatureVerifier,
    CpuSignatureVerifier,
    FallbackSignatureVerifier,
    TpuSignatureVerifier,
)
from .commit_observer import SimpleCommitObserver, TestCommitObserver
from .committee import Committee
from .config import Parameters, PrivateConfig
from .core import Core, CoreOptions
from .crypto import Signer
from .flight_recorder import (
    DEFAULT_CAPACITY,
    LIVE_CAPACITY,
    FlightRecorder,
    path_from_env,
)
from .health import HealthProbe, SLOThresholds
from .ingress import IngressGateway, IngressPlane
from .metrics import MetricReporter, Metrics, serve_metrics
from .net_sync import NetworkSyncer
from .tracing import current_authority, logger, setup_logging
from .network import TcpNetwork

log = logger(__name__)
from .transactions_generator import TransactionGenerator


class CommitConsumer:
    """Application handle for consuming committed sub-dags (validator.rs:50-66)."""

    def __init__(self, last_sent_height: int = 0) -> None:
        self.queue: asyncio.Queue = asyncio.Queue()
        self.last_sent_height = last_sent_height

    def send(self, sub_dag) -> None:
        self.queue.put_nowait(sub_dag)


def _make_verifier(kind: str, committee: Committee, metrics=None):
    """Signature verification is ON by default (the reference always verifies
    Ed25519 on every received block, types.rs:315-347 via net_sync.rs:352-372);
    "accept" is an explicit consensus-only escape hatch, not a default.

    The returned verifier carries a ``ready`` threading.Event: set once its
    one-time warmup is done (immediately for cpu/accept; after the JAX
    trace/compile, or the service's HELLO_OK, for tpu).  Load generators
    gate on it.  A warmup that raised never sets it and leaves the cause in
    ``warmup_error``: ``Validator.warmup_failure`` turns that into the
    node's exit, so a node whose kernels the chip refused does not carry on
    verifying nothing."""
    import threading

    ready = threading.Event()
    warm = None  # background warmup, started once the verifier is assembled
    tpu_backend = None
    aggregate = kind.endswith("-agg")
    if aggregate:
        kind = kind[: -len("-agg")]
    # The collector keeps its small default window in aggregate mode too: a
    # wide window would pace round advance (verification sits on the
    # round-advance critical path), costing more cadence than the skips
    # recover at steady state.  Aggregation instead engages through
    # BACKPRESSURE — when the verifier lags the arrival rate (catch-up
    # bursts, a recovering node's backlog, saturation), pending deepens,
    # flushes span many rounds from every peer, and quorum-endorsed interiors
    # skip their dispatch: a self-relieving valve exactly where verification
    # binds, at zero steady-state cost.
    collector_opts = dict(metrics=metrics, aggregate=aggregate)
    if kind in ("tpu", "tpu-only"):
        committee_keys = committee.public_key_bytes()
        if os.environ.get("MYSTICETI_VERIFIER_SOCKET"):
            # Shared per-host verifier service: the accelerator runtime is a
            # HOST resource — one warmed PJRT client serving every co-located
            # validator (verifier_service.py).  This process never imports
            # jax: boot is import-light and a rebooted node re-attaches to
            # the warm service instead of re-paying a cold runtime.
            from .verifier_service import RemoteSignatureVerifier

            tpu_backend = RemoteSignatureVerifier(
                committee_keys=committee_keys, metrics=metrics
            )
        else:
            tpu_backend = TpuSignatureVerifier(committee_keys=committee_keys)
            if metrics is not None:
                # In-process JAX: wire device-side attribution (compile
                # events, cache hits/misses, transfer bytes) into this
                # node's registry.  Behind the service socket this process
                # never imports jax and has nothing to count: the service,
                # the one process that compiles and transfers, wires the
                # same series into its own registry (run_service with
                # --metrics-port).
                from .ops import ed25519 as _ed25519

                _ed25519.install_device_attribution(metrics)
        # Both flavors send every batch to the accelerator.  "tpu" puts a
        # circuit breaker in front of it: a fleet that must stay live when
        # its service dies degrades to the CPU oracle and loses no future.
        # Under "tpu-only" failures surface: a measurement must not have a
        # fault hidden from it.
        backend = (
            tpu_backend
            if kind == "tpu-only"
            else FallbackSignatureVerifier(tpu=tpu_backend, metrics=metrics)
        )

        verifier = BatchedSignatureVerifier(committee, backend, **collector_opts)

        def warm() -> None:
            # Pay the JAX trace/compile (or cache load) off the hot path:
            # blocks arriving during warmup queue in the batching collector.
            try:
                backend.warmup()
            except BaseException as exc:
                verifier.warmup_error = exc
                raise
            ready.set()

    elif kind == "cpu":
        ready.set()
        verifier = BatchedSignatureVerifier(
            committee, CpuSignatureVerifier(), **collector_opts
        )
    elif kind == "accept":
        ready.set()
        verifier = AcceptAllBlockVerifier()
    else:
        raise ValueError(f"unknown verifier kind {kind!r}")
    verifier.ready = ready
    # The client of the verifier service, where this validator has one: its
    # ``requests_sent`` is the ``verify_requests`` the node's clock stamps.
    verifier.service_client = (
        tpu_backend if hasattr(tpu_backend, "requests_sent") else None)
    verifier.warmup_error = None
    if warm is not None:
        threading.Thread(target=warm, daemon=True, name="verifier-warmup").start()
    return verifier


class Validator:
    # Production health-probe cadence (seconds between samples).
    HEALTH_INTERVAL_S = 5.0

    def __init__(self) -> None:
        self.network_syncer: Optional[NetworkSyncer] = None
        self.metrics: Optional[Metrics] = None
        self.reporter: Optional[MetricReporter] = None
        self.generator: Optional[TransactionGenerator] = None
        self._metrics_server = None
        self.core: Optional[Core] = None
        self.health: Optional[HealthProbe] = None
        self.recorder: Optional[FlightRecorder] = None
        self.ingress: Optional[IngressPlane] = None
        self.gateway: Optional[IngressGateway] = None
        self.host_monitor = None
        # The validator's one stage clock (spans.StageClock over
        # spans.NODE_STAGES), and the flight recorder's file at shutdown.
        self.stages = None
        self._plane: Optional[IngressPlane] = None
        self._recorder_path: Optional[str] = None

    def _make_clock(self, private: PrivateConfig):
        """One clock a validator: every always-on stage of the node books
        into it, ``block_stage_seconds{stage}`` renders it, and on a live
        node it keeps the last ten minutes by the second, which the flight
        recorder's document carries.  Under the simulator it has no ring
        and what measures the host is not handed it (``_host_clock``)."""
        from . import spans
        from .runtime import is_simulated

        clock = self.stages = spans.StageClock(
            spans.NODE_STAGES,
            ring_seconds=(
                0 if is_simulated() else spans.StageClock.RING_SECONDS),
            stamps=spans.NODE_STAMPS,
            read_stamps=self._read_stamps,
            lag_stage="loop_lag",
            gc_stage="gc",
        )
        self.metrics.block_stages.attach(clock)
        self._recorder_path = os.path.join(
            private.storage_path, "flight-recorder.json")
        if clock.ring_seconds:
            gc.callbacks.append(clock.gc_callback)
        return clock

    def _host_clock(self):
        """The clock for what measures the host (the core owner's
        commands, the loop, the WAL's threads, the checkpoint, the
        execution fold, the endpoint): None under the simulator."""
        clock = self.stages
        return clock if clock is not None and clock.ring_seconds else None

    def _clock_storage(self, wal_writer, lifecycle) -> None:
        clock = self._host_clock()
        if clock is not None:
            wal_writer.stages = clock
            if lifecycle is not None:
                lifecycle.stages = clock

    def _report_recovery(self, recovered) -> None:
        """A boot that found a WAL says what it recovered: one line in the
        log, the ``wal_recovery{what}`` gauges (an operator's, and the
        benchmark's, which holds them to a plain reader of the same
        files), and one sample of ``wal_replay`` for what it cost.  A boot
        from genesis reports nothing."""
        own = recovered.last_own_block
        if own is None:
            return
        report = {
            "blocks": recovered.recovered_blocks,
            "own_round": own.block.round(),
            "commit_height": recovered.commit_height,
            "torn_bytes": recovered.torn_bytes,
            "replayed_entries": recovered.replayed_entries,
            "replayed_bytes": recovered.replayed_bytes,
            "checkpoint_height": recovered.checkpoint_height,
        }
        for what, value in report.items():
            self.metrics.wal_recovery.labels(what).set(value)
        log.info(
            "recovered from the WAL in %.3fs (cpu %.3fs): %s",
            recovered.replay_wall_s, recovered.replay_cpu_s,
            " ".join(f"{what}={value}" for what, value in report.items()),
        )
        clock = self._host_clock()
        if clock is not None:
            clock.book("wal_replay", time.monotonic(),
                       recovered.replay_wall_s, recovered.replay_cpu_s)

    def _read_stamps(self) -> tuple:
        """spans.NODE_STAMPS now, cumulative: plain sums the loop thread
        keeps (zeros until the node is assembled)."""
        from .ingress import SHED_LANE_CAP
        from .spans import NODE_STAMPS

        syncer, core, plane = self.network_syncer, self.core, self._plane
        if syncer is None or core is None:
            return (0,) * len(NODE_STAMPS)
        client = getattr(syncer.block_verifier, "service_client", None)
        return (
            core.current_round(),
            core.storage.commit_height if core.storage is not None else 0,
            syncer.syncer.proposals,
            syncer.blocks_received,
            plane.admitted_total if plane is not None else 0,
            plane.shed_total() if plane is not None else 0,
            plane.shed_for(SHED_LANE_CAP) if plane is not None else 0,
            syncer.syncer.leader_timeouts,
            client.requests_sent if client is not None else 0,
            core.execution.bad_nonce_total if core.execution is not None
            else 0,
            core.block_store.highest_round(),
        )

    async def warmup_failure(self) -> None:
        """Completes only by raising: the verifier's background warmup
        failed (``_make_verifier``).  Polls at the load generator's own
        ``ready`` cadence and parks once the warmup has succeeded."""
        verifier = self.network_syncer.block_verifier
        while not verifier.ready.is_set():
            if verifier.warmup_error is not None:
                raise RuntimeError(
                    "verifier warm-up failed"
                ) from verifier.warmup_error
            await asyncio.sleep(0.5)
        await asyncio.Event().wait()

    def _make_recorder(self, authority: int, lifecycle, observer):
        """The always-on flight recorder: ring in memory unconditionally,
        written at shutdown to ``flight-recorder.json`` in the storage
        directory (``stop``); ``MYSTICETI_FLIGHT_RECORDER`` moves that file
        and turns on the alert-triggered dumps."""
        recorder = FlightRecorder(
            authority=authority,
            # A simulated run's document is what it was, capacity and all.
            capacity=(LIVE_CAPACITY if self._host_clock() is not None
                      else DEFAULT_CAPACITY),
            dump_path=path_from_env(authority),
            metrics=self.metrics,
            stages=self.stages,
        )
        if lifecycle is not None:
            lifecycle.recorder = recorder
        observer.recorder = recorder
        self.recorder = recorder
        return recorder

    def _start_health(self, authority, committee, observer, block_verifier):
        """Wire the fleet health plane: probe + SLO watchdog + (when span
        tracing is active) commit critical-path attribution + the host
        attribution plane (hostattr.py: loop-lag probe, blocking-call
        detector, GIL convoy estimate)."""
        from . import profiling, spans
        from .hostattr import HostMonitor

        probe = HealthProbe(
            authority,
            len(committee),
            metrics=self.metrics,
            slo=SLOThresholds(
                max_round_stall_s=float(
                    os.environ.get("MYSTICETI_SLO_ROUND_STALL_S", "30")
                ),
                max_authority_lag_rounds=int(
                    os.environ.get("MYSTICETI_SLO_AUTHORITY_LAG", "100")
                ),
                max_breaker_open_fraction=0.5,
                max_loop_lag_s=float(
                    os.environ.get("MYSTICETI_SLO_LOOP_LAG_S", "0.25")
                ),
                max_blocking_call_ms=float(
                    os.environ.get("MYSTICETI_SLO_BLOCKING_CALL_MS", "50")
                ),
                max_finality_p99_s=float(
                    os.environ.get("MYSTICETI_SLO_FINALITY_P99_S", "5")
                ),
            ),
            recorder=self.recorder,
        )
        monitor = HostMonitor(
            metrics=self.metrics, recorder=self.recorder,
            stages=self._host_clock(),
        ).start()
        self.host_monitor = monitor
        if self.network_syncer is not None:
            # Every synchronous core command reports its wall duration to
            # the blocking-call detector (core_task.py).
            self.network_syncer.dispatcher.blocking_monitor = monitor
        probe.attach(
            core=self.core,
            net_syncer=self.network_syncer,
            block_verifier=block_verifier,
            commit_observer=observer,
            host_monitor=monitor,
        )
        # Normalize the sampler's per-subsystem CPU seconds by committed
        # leaders (mysticeti_cpu_us_per_leader) when MYSTICETI_PROFILE has
        # an accountant running.
        interpreter = getattr(observer, "commit_interpreter", None)
        profiling.bind_active(
            self.metrics,
            leaders_fn=(
                (lambda: interpreter.last_height)
                if interpreter is not None
                else None
            ),
        )
        tracer = spans.active()
        if tracer is not None:
            probe.attach_critical_path(tracer)
        self.health = probe.start(self.HEALTH_INTERVAL_S)

    # -- storage (validator.rs:334-352 + the storage lifecycle plane) --

    @staticmethod
    def init_storage(
        authority: int,
        committee: Committee,
        private: PrivateConfig,
        parameters: Optional[Parameters] = None,
        metrics=None,
    ):
        """Segmented WAL + checkpoint-seeded recovery (storage.py): boots
        from the newest valid checkpoint and replays only what follows it.
        Returns ``(recovered, observer_recovered, wal_writer, lifecycle)``."""
        from .storage import open_store

        return open_store(
            authority, private.wal(), committee, parameters, metrics
        )

    # -- benchmarking node (validator.rs:78-163) --

    @classmethod
    async def start_benchmarking(
        cls,
        authority: int,
        committee: Committee,
        parameters: Parameters,
        private: PrivateConfig,
        signer: Optional[Signer] = None,
        tps: Optional[int] = None,
        transaction_size: int = 512,
        verifier: str = "cpu",
        serve_metrics_endpoint: bool = True,
        network: Optional[object] = None,
    ) -> "Validator":
        v = cls()
        setup_logging()
        current_authority.set(authority)
        log.info("starting benchmarking validator %d (verifier=%s)", authority, verifier)
        v.metrics = Metrics()
        clock = v._make_clock(private)
        (recovered, observer_recovered, wal_writer, lifecycle) = cls.init_storage(
            authority, committee, private, parameters, v.metrics
        )
        v._clock_storage(wal_writer, lifecycle)
        v._report_recovery(recovered)
        # Overload-resilient ingress plane (ingress.py): every submission —
        # generator or gateway client — runs through the admission-controlled
        # mempool; proposals drain weighted-round-robin from it.
        plane = v._plane = (
            IngressPlane(parameters.ingress, authority=authority,
                         metrics=v.metrics, stages=clock)
            if parameters.ingress.enabled
            else None
        )
        handler = BenchmarkFastPathBlockHandler(
            committee,
            authority,
            certified_log_path=private.certified_transactions_log(),
            block_store=recovered.block_store,
            metrics=v.metrics,
            ingress=plane,
        )
        core = Core(
            block_handler=handler,
            authority=authority,
            committee=committee,
            parameters=parameters,
            recovered=recovered,
            wal_writer=wal_writer,
            # Reference benchmarking uses CoreOptions::default() (fsync=false,
            # validator.rs:247): durability rides the 1 s WAL-sync thread.
            options=CoreOptions(fsync=False),
            signer=signer,
            metrics=v.metrics,
            storage=lifecycle,
        )
        v.core = core
        if core.execution is not None:
            core.execution.stages = v._host_clock()
        observer = TestCommitObserver(
            core.block_store,
            committee,
            transaction_time=handler.transaction_time,
            metrics=v.metrics,
            recovered_state=observer_recovered,
        )
        tps = tps if tps is not None else int(os.environ.get("TPS", "10"))
        transaction_size = int(
            os.environ.get("TRANSACTION_SIZE", str(transaction_size))
        )
        recorder = v._make_recorder(authority, lifecycle, observer)
        # Equivocation detection events (block_store.py) ride the ring too,
        # as do decision-skip/flip events from the commit-rule ledger.
        core.block_store.recorder = recorder
        core.committer.ledger.recorder = recorder
        block_verifier = _make_verifier(verifier, committee, v.metrics)
        block_verifier.stages = v._host_clock()
        v.generator = TransactionGenerator(
            submit=handler.submit,
            seed=authority,
            tps=tps,
            transaction_size=transaction_size,
            initial_delay_s=float(os.environ.get("INITIAL_DELAY", "2")),
            ready=block_verifier.ready.is_set,
            # Client-observed finality: armed whenever the server-side
            # tracker runs, with the same content-based sampling stride so
            # both sides measure the same transactions.
            finality_sample_every=(
                parameters.ingress.finality_sample_every
                if plane is not None and plane.finality is not None
                else 0
            ),
            metrics=v.metrics,
        )
        if network is None:
            network = await TcpNetwork.start(
                authority,
                parameters.all_network_addresses(),
                metrics=v.metrics,
                max_latency_s=parameters.network_connection_max_latency_s,
                link_delays_s=parameters.link_delays_s(authority),
                stages=clock,
            )
        v.network_syncer = NetworkSyncer(
            core,
            observer,
            network,
            parameters=parameters,
            block_verifier=block_verifier,
            metrics=v.metrics,
            start_wal_sync_thread=True,
            recorder=recorder,
            stages=clock,
        )
        await v.network_syncer.start()
        v.generator.start()
        v.reporter = MetricReporter(v.metrics).start()
        v._start_health(authority, committee, observer, block_verifier)
        if plane is not None:
            plane.recorder = recorder
            observer.ingress = plane
            plane.attach(
                core=core,
                net_syncer=v.network_syncer,
                block_verifier=block_verifier,
                health=v.health,
            )
            if v.health is not None:
                v.health.attach(ingress=plane)
            if v.generator.finality is not None:
                # The loopback notification path: commit sinks fire on the
                # loop thread, same thread the generator stamps on.
                plane.add_commit_sink(
                    lambda height, keys, info, g=v.generator: (
                        g.note_commit_notification(keys, info)
                    )
                )
            v.ingress = plane.start()
            if parameters.ingress.gateway_port_base:
                v.gateway = await IngressGateway(
                    plane,
                    "0.0.0.0",
                    parameters.ingress.gateway_port_base + authority,
                ).start()
        if serve_metrics_endpoint and parameters.identifiers:
            host, port = parameters.metrics_address(authority)
            v._metrics_server = await serve_metrics(
                v.metrics, "0.0.0.0", port, health_probe=v.health,
                flight_recorder=recorder,
                consensus_debug=v._consensus_debug_doc,
                stages=v._host_clock(),
            )
        return v

    def _consensus_debug_doc(self) -> dict:
        """The live ``/debug/consensus`` document: DAG frontier, undecided
        slots, threshold-clock state, and the last-K decision records."""
        core = self.core
        store = core.block_store
        ledger = core.committer.ledger
        state = ledger.state()
        return {
            "authority": core.authority,
            "threshold_clock_round": core.current_round(),
            "last_decided": repr(core.last_decided_leader),
            "highest_round": store.highest_round(),
            "frontier": {
                str(a): store.last_seen_by_authority(a)
                for a in range(len(core.committee))
            },
            "undecided": state["undecided"],
            "recorded": state["recorded"],
            "dropped": state["dropped"],
            "ledger_digest": ledger.digest(),
            "records": ledger.records(64),
            **(
                {"execution": core.execution.state()}
                if core.execution is not None
                else {}
            ),
        }

    # -- production node (validator.rs:165-212) --

    @classmethod
    async def start_production(
        cls,
        authority: int,
        committee: Committee,
        parameters: Parameters,
        private: PrivateConfig,
        signer: Optional[Signer] = None,
        commit_consumer: Optional[CommitConsumer] = None,
        verifier: str = "tpu",
        network: Optional[object] = None,
    ) -> Tuple["Validator", SimpleBlockHandler, CommitConsumer]:
        v = cls()
        setup_logging()
        current_authority.set(authority)
        log.info("starting production validator %d (verifier=%s)", authority, verifier)
        v.metrics = Metrics()
        clock = v._make_clock(private)
        (recovered, observer_recovered, wal_writer, lifecycle) = cls.init_storage(
            authority, committee, private, parameters, v.metrics
        )
        v._clock_storage(wal_writer, lifecycle)
        v._report_recovery(recovered)
        handler = SimpleBlockHandler()
        core = Core(
            block_handler=handler,
            authority=authority,
            committee=committee,
            parameters=parameters,
            recovered=recovered,
            wal_writer=wal_writer,
            options=CoreOptions.production(),
            signer=signer,
            metrics=v.metrics,
            storage=lifecycle,
        )
        v.core = core
        if core.execution is not None:
            core.execution.stages = v._host_clock()
        consumer = commit_consumer or CommitConsumer()
        observer = SimpleCommitObserver(
            core.block_store,
            consumer.send,
            last_sent_height=consumer.last_sent_height,
            recovered_state=observer_recovered,
            metrics=v.metrics,
        )
        if network is None:
            network = await TcpNetwork.start(
                authority,
                parameters.all_network_addresses(),
                metrics=v.metrics,
                max_latency_s=parameters.network_connection_max_latency_s,
                link_delays_s=parameters.link_delays_s(authority),
                stages=clock,
            )
        recorder = v._make_recorder(authority, lifecycle, observer)
        core.block_store.recorder = recorder
        core.committer.ledger.recorder = recorder
        block_verifier = _make_verifier(verifier, committee, v.metrics)
        block_verifier.stages = v._host_clock()
        v.network_syncer = NetworkSyncer(
            core,
            observer,
            network,
            parameters=parameters,
            block_verifier=block_verifier,
            metrics=v.metrics,
            start_wal_sync_thread=True,
            recorder=recorder,
            stages=clock,
        )
        await v.network_syncer.start()
        v.reporter = MetricReporter(v.metrics).start()
        v._start_health(authority, committee, observer, block_verifier)
        return v, handler, consumer

    async def stop(self) -> None:
        if self.generator is not None:
            self.generator.stop()
        if self.gateway is not None:
            await self.gateway.stop()
        if self.ingress is not None:
            self.ingress.stop()
        if self.reporter is not None:
            # Final percentile sweep: an orderly shutdown publishes the tail
            # window instead of losing everything since the last 60 s tick.
            self.reporter.stop(final=True)
        if self.health is not None:
            self.health.stop()
        if self.host_monitor is not None:
            self.host_monitor.stop()
        if self._metrics_server is not None:
            self._metrics_server.close()
        if self.network_syncer is not None:
            await self.network_syncer.stop()
        # Span-trace tail: the periodic flusher runs every few seconds, so a
        # short run stopped between flushes would lose its newest spans.
        from . import spans

        spans.flush_active()
        # Flight-recorder tail: SIGTERM routes here too (the node CLI's
        # handler), so an operator-stopped node always leaves its incident
        # ring and its last ten minutes by the second on disk — beside its
        # WAL, or where MYSTICETI_FLIGHT_RECORDER says.
        if self._host_clock() is not None:
            try:
                gc.callbacks.remove(self.stages.gc_callback)
            except ValueError:
                pass  # stopped twice
        if self.recorder is not None:
            self.recorder.dump(
                "shutdown",
                path=self.recorder.dump_path or self._recorder_path,
            )
        if self.core is not None:
            self.core.wal_writer.close()
            # Release the WAL reader too (fd + whole-file mmap): embeddings
            # that cycle validators in one process would otherwise leak one
            # of each per stop.
            self.core.block_store.close()

    def committed_leaders(self) -> List:
        observer = self.network_syncer.syncer.commit_observer
        return list(getattr(observer, "committed_leaders", []))
