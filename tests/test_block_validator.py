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


def test_hybrid_verifier_routes_by_batch_size():
    """Small batches take the CPU oracle, large ones the TPU backend; the
    threshold is the measured crossover, capped by the CPU time budget."""
    from mysticeti_tpu.block_validator import (
        HybridSignatureVerifier,
        SignatureVerifier,
    )

    class Recorder(SignatureVerifier):
        def __init__(self):
            self.calls = []

        def verify_signatures(self, pks, digests, sigs):
            self.calls.append(len(sigs))
            return [True] * len(sigs)

    tpu, cpu = Recorder(), Recorder()
    hybrid = HybridSignatureVerifier(tpu=tpu, cpu=cpu)
    # Pretend calibration: 100 ms accelerator round-trip, 100 µs/sig CPU.
    hybrid.tpu_dispatch_s = 0.100
    hybrid.cpu_per_sig_s = 100e-6
    # Pure-speed crossover would be 1000, but past the CPU budget (10 ms,
    # i.e. >100 sigs) batches offload to free the host core — the
    # accelerator's 100 ms turnaround is within MAX_OFFLOAD_LATENCY_S.
    assert hybrid.threshold() == 101

    args = lambda n: ([b"\0" * 32] * n, [b"\1" * 32] * n, [b"\2" * 64] * n)
    hybrid.verify_signatures(*args(5))
    assert cpu.calls == [5] and tpu.calls == []
    assert hybrid.backend_label == "hybrid-cpu"
    hybrid.verify_signatures(*args(256))
    assert tpu.calls == [256]
    assert hybrid.backend_label == "hybrid-tpu"
    # EMAs update from routed dispatches (values sane, not outliers)
    assert 0 < hybrid.tpu_dispatch_s < 0.2
    assert hybrid.verify_signatures([], [], []) == []


def test_hybrid_verifier_fixed_threshold_and_default():
    from mysticeti_tpu.block_validator import HybridSignatureVerifier

    h = HybridSignatureVerifier(threshold=7)
    assert h.threshold() == 7
    h2 = HybridSignatureVerifier()
    assert h2.threshold() == h2.DEFAULT_THRESHOLD  # uncalibrated


def test_hybrid_verifier_end_to_end_cpu_backends(committee_and_signers):
    """Hybrid with two CPU oracles behind it is behaviorally identical to the
    plain CPU path: good blocks pass, forged blocks fail, either route."""
    committee, signers = committee_and_signers
    from mysticeti_tpu.block_validator import HybridSignatureVerifier

    async def main():
        for threshold in (0, 100):  # force tpu-route and cpu-route
            hybrid = HybridSignatureVerifier(
                tpu=CpuSignatureVerifier(),
                cpu=CpuSignatureVerifier(),
                threshold=threshold,
            )
            verifier = BatchedSignatureVerifier(
                committee, hybrid, max_batch=10, max_delay_s=0.01
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
    load when dispatches are sub-ms (hybrid CPU route).  The window is now
    20% of the dispatch EMA, clamped — wide for remote accelerators, sub-ms
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


def test_router_shortcircuit_counter(committee_and_signers):
    """Batches the cost-model router keeps on the oracle never touch the
    accelerator backend, and each one counts on
    verify_shortcircuit_total{reason="router"}."""
    from mysticeti_tpu.block_validator import (
        HybridSignatureVerifier,
        SignatureVerifier,
    )
    from mysticeti_tpu.crypto import blake2b_256
    from mysticeti_tpu.metrics import Metrics

    class NeverBackend(SignatureVerifier):
        def verify_signatures(self, *args):
            raise AssertionError("router-rejected batch reached the backend")

    _, signers = committee_and_signers
    metrics = Metrics()
    h = HybridSignatureVerifier(
        tpu=NeverBackend(), cpu=CountingVerifier(), metrics=metrics
    )
    digest = blake2b_256(b"router-test")
    sig = signers[0].sign(digest)
    pk = signers[0].public_key.bytes
    # Below DEFAULT_THRESHOLD: the router keeps it in-process.
    assert h.verify_signatures([pk] * 2, [digest] * 2, [sig] * 2) == [
        True, True,
    ]
    count = metrics.verify_shortcircuit_total.labels("router")._value.get()
    assert count == 1


def test_pin_probe_abandon_releases_exclusivity(committee_and_signers):
    """A flush cancelled between submit and fetch abandons its probe-
    carrying handle: the shared probe-exclusivity flag is released (no
    permanently blocked probes), the pin stands, and a completed probe
    whose re-HELLO reports an UNKNOWN backend (pre-r6 service) unpins —
    unknown must never stay pinned."""
    from mysticeti_tpu.block_validator import (
        HybridSignatureVerifier,
        SignatureVerifier,
        _PinProbeDispatch,
    )
    from mysticeti_tpu.crypto import blake2b_256

    class StubRemote(SignatureVerifier):
        advertised_backend = "cpu"
        rehello_result = ("cpu", None)

        def rehello(self):
            return self.rehello_result

        def verify_signatures(self, *args):
            raise AssertionError("pinned batch reached the remote backend")

    _, signers = committee_and_signers
    digest = blake2b_256(b"pin-abandon")
    pks = [signers[0].public_key.bytes] * 2
    digests, sigs = [digest] * 2, [signers[0].sign(digest)] * 2
    remote = StubRemote()
    clock = {"t": 0.0}
    h = HybridSignatureVerifier(tpu=remote, cpu=CountingVerifier())
    h._breaker_clock = lambda: clock["t"]
    h._sync_pin_with_advertisement()
    assert h.pinned_backend == "cpu"
    clock["t"] = 100.0  # past the probe deadline
    handle = h.verify_signatures_async(pks, digests, sigs)
    assert isinstance(handle, _PinProbeDispatch)
    assert h._breaker_probing  # the handle owns the exclusive slot
    handle.abandon()
    assert not h._breaker_probing, "abandon leaked the probe flag"
    assert h.pinned_backend == "cpu"  # an abandoned probe is no evidence
    # The next window's probe still runs — and an unknown-backend answer
    # (old server replaced the advertiser) unpins.
    remote.rehello_result = (None, None)
    clock["t"] = 10_000.0
    handle = h.verify_signatures_async(pks, digests, sigs)
    assert isinstance(handle, _PinProbeDispatch)
    assert handle.result() == [True, True]
    assert h.pinned_backend is None
    assert not h._breaker_probing


def test_hybrid_never_offloads_to_a_degraded_backend():
    """Round-5 NODE_BENCH finding: a host whose JAX backend degraded to CPU
    measures seconds per dispatch — the budget-relief offload must refuse it
    (light-load latency collapsed ~25x when it didn't)."""
    from mysticeti_tpu.block_validator import HybridSignatureVerifier

    h = HybridSignatureVerifier()
    h.cpu_per_sig_s = 125e-6
    h.tpu_dispatch_s = 1.5  # degraded: pad-to-bucket on jax-CPU
    # 256 sigs: 32 ms of CPU is over budget, but 1.5 s of "accelerator"
    # would stall consensus -> stay on the oracle.
    assert not h._route_to_tpu(256)
    assert not h._route_to_tpu(4096)
    # A real accelerator (remote, ~150 ms fixed) takes the same batch.
    h.tpu_dispatch_s = 0.150
    assert h._route_to_tpu(256)
    # ...unless its LEARNED marginal cost makes the turnaround stall-grade.
    h.tpu_per_sig_s = 0.005
    assert not h._route_to_tpu(256)
    # Light load always stays local either way.
    h.tpu_per_sig_s = 0.0
    assert not h._route_to_tpu(3)


def test_hybrid_ema_splits_residual_between_fixed_and_marginal():
    """ADVICE r5: one slow dispatch used to feed its FULL residual to both
    cost parameters in the same update (each against the other's pre-update
    value), inflating the summed model by ~double the residual.  With the
    50/50 split the summed model moves by exactly one EMA step of the
    residual — a transient can no longer wrongly veto the saturation
    offload."""
    from mysticeti_tpu.block_validator import (
        HybridSignatureVerifier,
        SignatureVerifier,
    )

    class Stub(SignatureVerifier):
        def verify_signatures(self, pks, digests, sigs):
            return [True] * len(sigs)

    h = HybridSignatureVerifier(tpu=Stub(), cpu=Stub())
    h.tpu_dispatch_s = 0.1
    h.tpu_per_sig_s = 0.0005
    n = 100
    before = h._tpu_time(n)
    residual = 0.2
    h._absorb_tpu_sample(before + residual, n)
    after = h._tpu_time(n)
    assert after > before  # the model does track the slow sample...
    # ...but by ONE EMA step (alpha=0.2) of the residual, not two.
    assert after - before == pytest.approx(0.2 * residual, rel=1e-6)
    # Symmetric on the way down, and outliers never enter.
    h._absorb_tpu_sample(h._tpu_time(n) - 0.1, n)
    assert h._tpu_time(n) < after
    frozen = (h.tpu_dispatch_s, h.tpu_per_sig_s)
    h._absorb_tpu_sample(h.EMA_OUTLIER_S + 1.0, n)
    assert (h.tpu_dispatch_s, h.tpu_per_sig_s) == frozen
