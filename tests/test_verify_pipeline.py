"""Verification pipelines, two layers:

* the per-connection receive pipeline (net_sync.py): a slow verifier must
  not serialize the receive path, and duplicate blocks inside the pipeline
  window must not be re-verified;
* the staged DISPATCH pipeline (verify_pipeline.py + the batching
  collector): pack/device/fetch overlap with a bounded in-flight window, so
  N fixed-latency dispatch windows finish in measurably less wall time than
  N x the fixed latency — with per-future results intact under interleaved
  flushes and breaker-mediated degradation on mid-pipeline backend failure.
"""
import asyncio
import threading
import time

import pytest

from mysticeti_tpu.block_validator import BlockVerifier
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Parameters
from mysticeti_tpu.types import Share, StatementBlock


class SlowCountingVerifier(BlockVerifier):
    """Counts concurrent verify_blocks calls; each takes ``delay_s``."""

    def __init__(self, delay_s: float = 0.05) -> None:
        self.delay_s = delay_s
        self.calls = 0
        self.in_flight = 0
        self.max_in_flight = 0
        self.seen_refs = []

    async def verify_blocks(self, blocks):
        self.calls += 1
        self.seen_refs.extend(b.reference for b in blocks)
        self.in_flight += 1
        self.max_in_flight = max(self.max_in_flight, self.in_flight)
        await asyncio.sleep(self.delay_s)
        self.in_flight -= 1
        return [True] * len(blocks)


class FakeConnection:
    """Minimal Connection surface for _connection_task: scripted recv()."""

    def __init__(self, peer, messages):
        self.peer = peer
        self._messages = list(messages)
        self.sent = []

    async def recv(self):
        if not self._messages:
            await asyncio.sleep(0.3)  # then let the task be torn down
            return None
        msg = self._messages.pop(0)
        await asyncio.sleep(0)  # yield so pipeline stages interleave
        return msg

    async def send(self, msg):
        self.sent.append(msg)

    def try_send(self, msg):
        self.sent.append(msg)
        return True

    def close(self):
        pass


@pytest.fixture
def syncer_env(tmp_path):
    """A NetworkSyncer with a scripted connection, no real network."""
    import os

    from mysticeti_tpu.block_handler import TestBlockHandler
    from mysticeti_tpu.block_store import BlockStore
    from mysticeti_tpu.commit_observer import TestCommitObserver
    from mysticeti_tpu.core import Core, CoreOptions
    from mysticeti_tpu.net_sync import NetworkSyncer
    from mysticeti_tpu.wal import walf

    committee = Committee.new_for_benchmarks(4)
    signers = Committee.benchmark_signers(4)
    wal_writer, wal_reader = walf(os.path.join(str(tmp_path), "wal-0"))
    recovered, observer_recovered = BlockStore.open(
        0, wal_reader, wal_writer, committee
    )
    core = Core(
        block_handler=TestBlockHandler(0, committee, 0),
        authority=0,
        committee=committee,
        parameters=Parameters(leader_timeout_s=10.0),
        recovered=recovered,
        wal_writer=wal_writer,
        options=CoreOptions.test(),
        signer=signers[0],
    )
    observer = TestCommitObserver(core.block_store, committee)

    class _NoNet:
        def __init__(self):
            self.connections = asyncio.Queue()  # accept loop idles on this

        async def stop(self):
            pass

    def make(verifier):
        return NetworkSyncer(
            core, observer, _NoNet(),
            parameters=Parameters(leader_timeout_s=10.0),
            block_verifier=verifier,
        )

    return committee, signers, make


def _peer_blocks(signers, rounds):
    """Valid-DAG layers: 3 authors per round (a quorum of 4), fully
    connected, so every block passes the threshold-clock structure check."""
    genesis = [StatementBlock.new_genesis(i) for i in range(4)]
    prev = [g.reference for g in genesis]
    out = []
    for r in range(1, rounds + 1):
        layer = [
            StatementBlock.build(
                a, r, prev, [Share(bytes([r, a]))], signer=signers[a]
            )
            for a in range(1, 4)
        ]
        out.extend(layer)
        prev = [b.reference for b in layer]
    return out


def test_pipeline_overlaps_slow_verification(syncer_env):
    """With a 50 ms verifier, N single-block messages must overlap their
    verification (serialized would take N*50 ms and max_in_flight == 1)."""
    from mysticeti_tpu.network import Blocks

    from mysticeti_tpu.runtime.simulated import run_simulation

    committee, signers, make = syncer_env
    verifier = SlowCountingVerifier(0.05)

    blocks = _peer_blocks(signers, 3)  # 9 blocks
    msgs = [Blocks((b.to_bytes(),)) for b in blocks]

    async def main():
        ns = make(verifier)
        await ns.start()
        conn = FakeConnection(1, msgs)
        task = asyncio.ensure_future(ns._connection_task(conn))
        # Virtual time: 9 x 50 ms serialized would need 450 ms.
        await asyncio.sleep(0.2)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        await ns.stop()

    run_simulation(main(), seed=1)
    assert verifier.calls == 9
    assert verifier.max_in_flight >= 4, verifier.max_in_flight


def test_pipeline_dedups_in_flight_duplicates(syncer_env):
    """The same block retransmitted while its first copy is still being
    verified must not be verified twice."""
    from mysticeti_tpu.network import Blocks

    from mysticeti_tpu.runtime.simulated import run_simulation

    committee, signers, make = syncer_env
    verifier = SlowCountingVerifier(0.05)

    blk = _peer_blocks(signers, 1)[0]
    msgs = [Blocks((blk.to_bytes(),)) for _ in range(5)]

    async def main():
        ns = make(verifier)
        await ns.start()
        conn = FakeConnection(1, msgs)
        task = asyncio.ensure_future(ns._connection_task(conn))
        await asyncio.sleep(0.25)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        await ns.stop()

    run_simulation(main(), seed=1)
    assert verifier.seen_refs.count(blk.reference) == 1, verifier.seen_refs


# ---------------------------------------------------------------------------
# The staged dispatch pipeline (verify_pipeline.py + BatchedSignatureVerifier)


class FixedLatencyVerifier:
    """Stub SignatureVerifier: every dispatch takes exactly ``delay_s`` of
    real (executor-thread) time; per-item verdicts come from a reject set so
    interleaved flushes are checkable.  Samples the inflight gauge from
    INSIDE the dispatch — the honest way to observe concurrency."""

    def __init__(self, delay_s, metrics=None, reject_digests=()):
        from mysticeti_tpu.block_validator import SignatureVerifier

        self.delay_s = delay_s
        self.metrics = metrics
        self.reject = set(reject_digests)
        self.calls = 0
        self.gauge_seen = []
        self._lock = threading.Lock()

    def padded_batch(self, n):
        return n

    def warmup(self):
        pass

    def verify_signatures_async(self, public_keys, digests, signatures):
        from mysticeti_tpu.verify_pipeline import DeferredDispatch

        return DeferredDispatch(
            self.verify_signatures, public_keys, digests, signatures
        )

    def verify_signatures(self, public_keys, digests, signatures):
        with self._lock:
            self.calls += 1
            if self.metrics is not None:
                self.gauge_seen.append(
                    self.metrics.verify_pipeline_inflight._value.get()
                )
        time.sleep(self.delay_s)
        return [d not in self.reject for d in digests]


def _signed_blocks(n):
    """n distinct valid blocks over a 4-authority benchmark committee."""
    from mysticeti_tpu.committee import Committee
    from mysticeti_tpu.types import Share

    committee = Committee.new_for_benchmarks(4)
    signers = Committee.benchmark_signers(4)
    blocks = [
        StatementBlock.build(
            a % 4, 1 + a // 4, [], [Share(bytes([a]))], signer=signers[a % 4]
        )
        for a in range(n)
    ]
    return committee, blocks


def _run_windows(committee, blocks, verifier, depth, metrics):
    """Drive N windows (max_batch=2) through the collector; returns
    (wall_seconds, results list aligned with blocks)."""
    from mysticeti_tpu.block_validator import BatchedSignatureVerifier

    collector = BatchedSignatureVerifier(
        committee, verifier, max_batch=2, max_delay_s=5.0,
        metrics=metrics, pipeline_depth=depth,
    )

    async def main():
        started = time.monotonic()
        results = await asyncio.gather(
            *(collector.verify(b) for b in blocks), return_exceptions=True
        )
        return time.monotonic() - started, results, collector

    return asyncio.run(main())


def test_staged_pipeline_overlaps_fixed_latency_dispatches():
    """Acceptance: with depth >= 2, N windows of fixed-latency work finish
    in measurably less wall time than N x the latency; the serial (depth-1)
    baseline is asserted in the same test, and verify_pipeline_inflight
    reaches >= 2 while dispatches are actually running."""
    from mysticeti_tpu.metrics import Metrics

    delay, windows = 0.08, 4
    committee, blocks = _signed_blocks(2 * windows)

    serial_metrics = Metrics()
    serial_wall, serial_results, serial_collector = _run_windows(
        committee, blocks, FixedLatencyVerifier(delay, serial_metrics),
        depth=1, metrics=serial_metrics,
    )
    assert all(r is None for r in serial_results)
    # Serial baseline: one dispatch at a time, N x delay end to end.
    assert serial_wall >= windows * delay * 0.95, serial_wall
    assert serial_collector.pipeline.max_inflight == 1

    metrics = Metrics()
    verifier = FixedLatencyVerifier(delay, metrics)
    wall, results, collector = _run_windows(
        committee, blocks, verifier, depth=4, metrics=metrics,
    )
    assert all(r is None for r in results)
    assert verifier.calls == windows
    # Overlap: strictly and measurably faster than the serial baseline.
    assert wall < serial_wall * 0.75, (wall, serial_wall)
    assert wall < windows * delay * 0.8, (wall, windows * delay)
    # The bounded window actually held >= 2 dispatches in flight, visible
    # both at the engine and on the scraped gauge DURING a dispatch.
    assert collector.pipeline.max_inflight >= 2
    assert max(verifier.gauge_seen) >= 2, verifier.gauge_seen
    # ...and the gauge returns to zero once the work drains.
    assert metrics.verify_pipeline_inflight._value.get() == 0
    scrape = metrics.expose().decode()
    assert "verify_pipeline_stage_seconds" in scrape


def test_pipeline_resolves_correct_futures_under_interleaved_flushes():
    """Verdicts must land on the RIGHT per-block futures even when several
    flush windows are in flight at once and complete out of order."""
    from mysticeti_tpu.metrics import Metrics
    from mysticeti_tpu.types import VerificationError

    committee, blocks = _signed_blocks(12)
    bad = {b.signed_digest() for b in blocks[::3]}  # every third block
    metrics = Metrics()
    verifier = FixedLatencyVerifier(0.03, metrics, reject_digests=bad)
    _, results, collector = _run_windows(
        committee, blocks, verifier, depth=4, metrics=metrics,
    )
    assert collector.pipeline.max_inflight >= 2
    for block, result in zip(blocks, results):
        if block.signed_digest() in bad:
            assert isinstance(result, VerificationError), block.reference
        else:
            assert result is None, (block.reference, result)


def test_mid_pipeline_backend_failure_degrades_with_zero_lost_futures():
    """A backend dying while dispatches are in flight trips the existing
    circuit breaker at FETCH time; every affected batch re-verifies on the
    oracle — zero futures lost, zero spurious rejections."""
    from mysticeti_tpu.block_validator import FallbackSignatureVerifier
    from mysticeti_tpu.metrics import Metrics

    class DyingBackend(FixedLatencyVerifier):
        def __init__(self, delay_s, die_after):
            super().__init__(delay_s)
            self.die_after = die_after

        def verify_signatures(self, public_keys, digests, signatures):
            with self._lock:
                self.calls += 1
                call = self.calls
            time.sleep(self.delay_s)
            if call > self.die_after:
                raise ConnectionError("accelerator link dropped")
            return [True] * len(signatures)

    committee, blocks = _signed_blocks(12)
    metrics = Metrics()
    # die_after=0: EVERY dispatch fails at fetch while others are in
    # flight.  (die_after=1 made the end-state racy: the one successful
    # dispatch could complete LAST and close the breaker the failures had
    # just tripped.)
    tpu = DyingBackend(0.03, die_after=0)
    cpu = FixedLatencyVerifier(0.0)
    hybrid = FallbackSignatureVerifier(tpu=tpu, cpu=cpu, metrics=metrics)
    _, results, collector = _run_windows(
        committee, blocks, hybrid, depth=4, metrics=metrics,
    )
    # Zero lost futures, zero spurious rejections.
    assert all(r is None for r in results), results
    assert hybrid.breaker_open
    assert metrics.verifier_fallback_total._value.get() >= 1.0
    assert cpu.calls >= 1  # degraded batches re-verified on the oracle


def test_verify_pipeline_depth_adapts_to_fixed_cost():
    from mysticeti_tpu.verify_pipeline import VerifyPipeline

    cost = {"s": 0.0}
    p = VerifyPipeline(fixed_cost_fn=lambda: cost["s"])
    assert p.depth() == VerifyPipeline.MIN_DEPTH  # co-located: nothing to hide
    cost["s"] = 0.01
    assert p.depth() == 3
    cost["s"] = 0.120  # remote chip
    assert p.depth() == VerifyPipeline.MAX_DEPTH
    assert VerifyPipeline(depth=7).depth() == 7  # pinned overrides


def test_cancelled_flush_mid_submit_abandons_the_handle():
    """A flush task cancelled while suspended on the submit executor hop
    must still release the dispatch handle's backend state (pooled service
    connection, the breaker's exclusive probe flag) — the executor job
    outlives the cancellation and its handle would otherwise leak."""
    from mysticeti_tpu.block_validator import BatchedSignatureVerifier

    events = {"abandoned": 0, "fetched": 0}
    submit_started = threading.Event()
    release_submit = threading.Event()

    class Handle:
        def result(self):
            events["fetched"] += 1
            return [True]

        def abandon(self):
            events["abandoned"] += 1

    class SlowSubmitVerifier:
        def verify_signatures_async(self, pks, digests, sigs):
            submit_started.set()
            release_submit.wait(5)
            return Handle()

        def verify_signatures(self, pks, digests, sigs):
            return [True] * len(sigs)

    committee, blocks = _signed_blocks(1)
    collector = BatchedSignatureVerifier(
        committee, SlowSubmitVerifier(), max_batch=10, max_delay_s=5.0
    )

    async def main():
        loop = asyncio.get_running_loop()
        future = loop.create_future()
        task = asyncio.ensure_future(
            collector._flush([(blocks[0], future)])
        )
        await asyncio.to_thread(submit_started.wait, 5)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        release_submit.set()  # the executor job now lands its handle
        for _ in range(100):
            if events["abandoned"]:
                break
            await asyncio.sleep(0.01)

    asyncio.run(main())
    assert events["abandoned"] == 1
    assert events["fetched"] == 0
