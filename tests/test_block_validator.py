"""Batched signature-verification seam tests: the collector's size/deadline
policy and the end-to-end validator path with real signature checking."""
import asyncio

import pytest

from mysticeti_tpu.block_validator import (
    BatchedSignatureVerifier,
    CpuSignatureVerifier,
)
from mysticeti_tpu.committee import Authority, Committee
from mysticeti_tpu.crypto import Signer
from mysticeti_tpu.types import StatementBlock, VerificationError


@pytest.fixture
def committee_and_signers():
    signers = Committee.benchmark_signers(4)
    committee = Committee([Authority(1, s.public_key) for s in signers])
    return committee, signers


class CountingVerifier(CpuSignatureVerifier):
    def __init__(self):
        self.calls = []

    def verify_signatures(self, public_keys, digests, signatures):
        self.calls.append(len(signatures))
        return super().verify_signatures(public_keys, digests, signatures)


def test_batch_collector_deadline(committee_and_signers):
    """Blocks arriving under max_batch are flushed by the deadline, as one call."""
    committee, signers = committee_and_signers

    async def main():
        backend = CountingVerifier()
        verifier = BatchedSignatureVerifier(
            committee, backend, max_batch=100, max_delay_s=0.02
        )
        blocks = [
            StatementBlock.build(a, 1, [], (), signer=signers[a]) for a in range(4)
        ]
        await asyncio.gather(*(verifier.verify(b) for b in blocks))
        assert backend.calls == [4], backend.calls

    asyncio.run(main())


def test_batch_collector_size_trigger(committee_and_signers):
    committee, signers = committee_and_signers

    async def main():
        backend = CountingVerifier()
        verifier = BatchedSignatureVerifier(
            committee, backend, max_batch=2, max_delay_s=10.0
        )
        blocks = [
            StatementBlock.build(a, 1, [], (), signer=signers[a]) for a in range(4)
        ]
        await asyncio.gather(*(verifier.verify(b) for b in blocks))
        assert sum(backend.calls) == 4
        assert max(backend.calls) <= 2

    asyncio.run(main())


def test_batch_collector_rejects_bad_signature(committee_and_signers):
    committee, signers = committee_and_signers

    async def main():
        verifier = BatchedSignatureVerifier(
            committee, CpuSignatureVerifier(), max_batch=10, max_delay_s=0.01
        )
        good = StatementBlock.build(0, 1, [], (), signer=signers[0])
        forged = StatementBlock.build(1, 1, [], (), signer=signers[0])  # wrong key
        results = await asyncio.gather(
            verifier.verify(good), verifier.verify(forged), return_exceptions=True
        )
        assert results[0] is None
        assert isinstance(results[1], VerificationError)

    asyncio.run(main())


def test_validators_with_cpu_signature_verification(tmp_path):
    """4 localhost validators with full signature verification through the
    batching collector still commit (BASELINE config #1)."""
    import socket

    from mysticeti_tpu.config import Identifier, Parameters, PrivateConfig
    from mysticeti_tpu.validator import Validator

    def free_ports(n):
        socks, ports = [], []
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        return ports

    async def main():
        ports = free_ports(8)
        identifiers = [
            Identifier("127.0.0.1", ports[2 * i], ports[2 * i + 1]) for i in range(4)
        ]
        parameters = Parameters(identifiers=identifiers, leader_timeout_s=0.5)
        signers = Committee.benchmark_signers(4)
        committee = Committee([Authority(1, s.public_key) for s in signers])
        validators = [
            await Validator.start_benchmarking(
                i,
                committee,
                parameters,
                PrivateConfig.new_in_dir(i, str(tmp_path / f"v{i}")),
                signer=signers[i],
                tps=20,
                serve_metrics_endpoint=False,
                verifier="cpu",
            )
            for i in range(4)
        ]
        try:

            async def poll():
                while True:
                    if all(len(v.committed_leaders()) >= 2 for v in validators):
                        return
                    await asyncio.sleep(0.2)

            await asyncio.wait_for(poll(), timeout=60)
        finally:
            for v in validators:
                await v.stop()

    asyncio.run(main())


def test_fallback_verifier_end_to_end_cpu_backends(committee_and_signers):
    """The breaker class with two CPU oracles behind it is behaviorally
    identical to the plain CPU path: good blocks pass, forged blocks fail."""
    committee, signers = committee_and_signers
    from mysticeti_tpu.block_validator import FallbackSignatureVerifier

    async def main():
        fallback = FallbackSignatureVerifier(
            tpu=CpuSignatureVerifier(),
            cpu=CpuSignatureVerifier(),
        )
        verifier = BatchedSignatureVerifier(
            committee, fallback, max_batch=10, max_delay_s=0.01
        )
        good = StatementBlock.build(0, 1, [], (), signer=signers[0])
        forged = StatementBlock.build(1, 1, [], (), signer=signers[0])
        results = await asyncio.gather(
            verifier.verify(good),
            verifier.verify(forged),
            return_exceptions=True,
        )
        assert results[0] is None
        assert isinstance(results[1], VerificationError)

    asyncio.run(main())


def test_adaptive_batching_window_tracks_dispatch_latency():
    """A remote accelerator (~100ms/dispatch) must widen the collection
    window to a fraction of the observed dispatch latency, so back-to-back
    tiny dispatches don't queue; a fast verifier keeps the 5ms floor."""
    import asyncio
    import time as _time

    from mysticeti_tpu.block_validator import (
        BatchedSignatureVerifier,
        SignatureVerifier,
    )
    from mysticeti_tpu.committee import Committee

    class SlowVerifier(SignatureVerifier):
        def verify_signatures(self, pks, digests, sigs):
            _time.sleep(0.05)
            return [True] * len(sigs)

    committee = Committee.new_for_benchmarks(4)
    signers = Committee.benchmark_signers(4)
    from mysticeti_tpu.types import Share, StatementBlock

    genesis = [StatementBlock.new_genesis(i).reference for i in range(4)]
    blk = StatementBlock.build(0, 1, genesis, [Share(b"tx")], signer=signers[0])

    async def main():
        v = BatchedSignatureVerifier(committee, SlowVerifier(), max_delay_s=0.005)
        assert v._effective_delay_s() == 0.005  # floor before any dispatch
        await v.verify(blk)
        await v.flush_now()
        assert v._dispatch_ema_s >= 0.05
        assert v._effective_delay_s() == pytest.approx(0.2 * v._dispatch_ema_s)
        # the window is capped: a compile stall cannot push it past the max
        v._dispatch_ema_s = 30.0
        assert v._effective_delay_s() == v.MAX_ADAPTIVE_DELAY_S
        # and outlier dispatches never enter the EMA
        assert v.EMA_OUTLIER_S < 30.0

    asyncio.run(main())


def test_collection_window_adapts_both_directions():
    """Round-4 weak #5: the fixed 5 ms window added pure latency at light
    load when dispatches are sub-ms (a host oracle).  The window is now
    20% of the dispatch EMA, clamped — wide for slow dispatches, sub-ms
    for cheap local dispatch, max_delay_s only before calibration."""
    from mysticeti_tpu.block_validator import BatchedSignatureVerifier
    from mysticeti_tpu.committee import Committee

    c = BatchedSignatureVerifier(Committee.new_for_benchmarks(4))
    assert c._effective_delay_s() == c.max_delay_s  # pre-calibration
    c._dispatch_ema_s = 0.0005  # light-load CPU route
    assert c._effective_delay_s() == c.MIN_ADAPTIVE_DELAY_S
    c._dispatch_ema_s = 0.030  # saturated CPU batch
    assert abs(c._effective_delay_s() - 0.006) < 1e-9
    c._dispatch_ema_s = 0.100  # remote accelerator
    assert abs(c._effective_delay_s() - 0.020) < 1e-9
    c._dispatch_ema_s = 10.0  # pathological: stays clamped
    assert c._effective_delay_s() == c.MAX_ADAPTIVE_DELAY_S


def test_collection_window_adapts_to_arrival_rate():
    """ISSUE 6 tentpole #3: the window only pays off when more arrivals are
    coming.  With ~2+ expected arrivals inside a full window the ceiling
    holds; below that the wait scales down linearly to the floor — a lone
    steady-state block stops paying the full batch window."""
    from mysticeti_tpu.block_validator import BatchedSignatureVerifier
    from mysticeti_tpu.committee import Committee

    c = BatchedSignatureVerifier(Committee.new_for_benchmarks(4))
    ceiling = c.max_delay_s  # pre-calibration ceiling (5 ms default)
    # Unseeded arrival EMA: full window (same-tick bursts keep this shape).
    assert c._effective_delay_s() == ceiling
    # Dense arrivals (gap << window): the full window still batches.
    c._arrival_gap_ema_s = 0.0005
    assert c._effective_delay_s() == ceiling
    # ~1 expected arrival per window: wait scales to half the ceiling.
    c._arrival_gap_ema_s = ceiling
    assert c._effective_delay_s() == pytest.approx(ceiling / 2)
    # Sparse arrivals (gap >> window): floor — no batch is coming.
    c._arrival_gap_ema_s = 0.5
    assert c._effective_delay_s() == c.MIN_ADAPTIVE_DELAY_S
    # The arrival scaling rides ON the dispatch-cost ceiling: a remote
    # accelerator's widened window still collapses when arrivals stop.
    c._dispatch_ema_s = 0.100  # remote chip -> 20 ms ceiling
    c._arrival_gap_ema_s = 0.002
    assert abs(c._effective_delay_s() - 0.020) < 1e-9
    c._arrival_gap_ema_s = 0.5
    assert c._effective_delay_s() == c.MIN_ADAPTIVE_DELAY_S


def test_collector_tracks_arrival_gaps_and_publishes_window(
    committee_and_signers,
):
    """verify() feeds the loop-clocked inter-arrival gap EMA (capped, so an
    idle stretch reads as low rate without poisoning the EMA) and each armed
    window is published on verify_collector_window_seconds."""
    from mysticeti_tpu.metrics import Metrics

    committee, signers = committee_and_signers
    metrics = Metrics()

    async def main():
        backend = CountingVerifier()
        v = BatchedSignatureVerifier(
            committee, backend, max_batch=100, max_delay_s=0.02,
            metrics=metrics,
        )
        blocks = [
            StatementBlock.build(a, 1, [], (), signer=signers[a])
            for a in range(4)
        ]
        first = asyncio.ensure_future(v.verify(blocks[0]))
        await asyncio.sleep(0.004)
        rest = [asyncio.ensure_future(v.verify(b)) for b in blocks[1:]]
        await asyncio.gather(first, *rest)
        await v.flush_now()
        # One real ~4 ms gap seeded the EMA; the same-tick trio pulled it
        # down (0.8 decay per zero sample).
        assert 0.0 < v._arrival_gap_ema_s <= 0.004 + 0.02
        assert metrics.verify_collector_window_seconds._value.get() > 0.0
        # The cap bounds what one idle stretch can inject.
        v._last_arrival_t = None
        v._arrival_gap_ema_s = 0.0
        loop = asyncio.get_running_loop()
        v._last_arrival_t = loop.time() - 500.0  # pretend: long idle
        await v.verify(blocks[0])
        assert v._arrival_gap_ema_s <= v.ARRIVAL_GAP_CAP_S
        await v.flush_now()

    asyncio.run(main())


class _NeverOracle(CpuSignatureVerifier):
    def verify_signatures(self, *args):
        raise AssertionError("a batch reached the oracle, breaker closed")


def _signed_batch(signers, n, corrupt=()):
    from mysticeti_tpu.crypto import blake2b_256

    pks, digests, sigs = [], [], []
    for i in range(n):
        signer = signers[i % len(signers)]
        digest = blake2b_256(b"fallback-batch-%d" % i)
        sig = signer.sign(digest)
        if i in corrupt:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        pks.append(signer.public_key.bytes)
        digests.append(digest)
        sigs.append(sig)
    return pks, digests, sigs


@pytest.mark.parametrize("n", [1, 8, 31, 32, 256])
def test_tpu_flavor_sends_every_batch_size_to_the_accelerator(
    committee_and_signers, n
):
    """With the breaker closed nothing but the accelerator backend verifies
    a batch, whatever its size (at the parent a cost model kept batches
    under 32 signatures on the oracle)."""
    from mysticeti_tpu.block_validator import FallbackSignatureVerifier
    from mysticeti_tpu.metrics import Metrics

    _, signers = committee_and_signers
    metrics = Metrics()
    tpu = CountingVerifier()  # the "accelerator": answers like the oracle
    fallback = FallbackSignatureVerifier(
        tpu=tpu, cpu=_NeverOracle(), metrics=metrics
    )
    batch = _signed_batch(signers, n, corrupt={n - 1})
    assert fallback.verify_signatures(*batch) == [True] * (n - 1) + [False]
    assert tpu.calls == [n]
    assert fallback.backend_label == "hybrid-tpu"
    assert fallback.dispatch_padded == n
    assert not fallback.breaker_open
    assert metrics.verifier_fallback_total._value.get() == 0.0
    assert fallback.verify_signatures([], [], []) == []
    assert tpu.calls == [n]
