"""Threshold-aggregate verification (BASELINE config #5's technique).

Safety contract: NO forged block is ever accepted unless a quorum (2f+1
distinct-authority stake — beyond the fault model if all are dishonest) of
accepted blocks references it; every acceptance chain terminates at directly
signature-verified frontier blocks.
"""
import asyncio

import pytest

from mysticeti_tpu.block_validator import (
    BatchedSignatureVerifier,
    CpuSignatureVerifier,
    ThresholdAggregateVerifier,
)
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.types import Share, StatementBlock


@pytest.fixture
def setup():
    signers = Committee.benchmark_signers(4)
    committee = Committee.new_for_benchmarks(4)
    return committee, signers


class CountingInner(BatchedSignatureVerifier):
    def __init__(self, committee):
        super().__init__(
            committee, CpuSignatureVerifier(), max_batch=64, max_delay_s=0.001
        )
        self.seen = []

    async def verify_blocks(self, blocks):
        self.seen.extend(b.reference for b in blocks)
        return await super().verify_blocks(blocks)


def _dag(signers, rounds, per_round=4, forge=()):
    """Rounds of fully-connected blocks; ``forge`` = set of (round, authority)
    whose signature bytes are corrupted after signing."""
    genesis = [StatementBlock.new_genesis(a) for a in range(per_round)]
    prev = [g.reference for g in genesis]
    out = []
    for r in range(1, rounds + 1):
        layer = []
        for a in range(per_round):
            blk = StatementBlock.build(
                a, r, prev, [Share(bytes([r, a]))], signer=signers[a]
            )
            if (r, a) in forge:
                bad = bytes([blk.signature[0] ^ 1]) + blk.signature[1:]
                blk = StatementBlock(
                    blk.reference, blk.includes, blk.statements,
                    blk.meta_creation_time_ns, blk.epoch_marker, blk.epoch,
                    bad, _bytes=None,
                )
            layer.append(blk)
        out.extend(layer)
        prev = [b.reference for b in layer]
    return out


def test_interior_blocks_skip_direct_verification(setup):
    committee, signers = setup

    async def main():
        inner = CountingInner(committee)
        agg = ThresholdAggregateVerifier(committee, inner)
        blocks = _dag(signers, rounds=5)
        results = await agg.verify_blocks(blocks)
        assert all(results)
        # Only the frontier (last round, no in-batch endorsers) was
        # signature-verified directly.
        assert len(inner.seen) == 4
        assert all(ref.round == 5 for ref in inner.seen)
        assert agg.aggregated_total == 16

    asyncio.run(main())


def test_forged_frontier_rejected(setup):
    committee, signers = setup

    async def main():
        agg = ThresholdAggregateVerifier(committee, CountingInner(committee))
        blocks = _dag(signers, rounds=3, forge={(3, 1)})
        results = await agg.verify_blocks(blocks)
        by_ref = dict(zip((b.reference for b in blocks), results))
        for b in blocks:
            expected = not (b.round() == 3 and b.author() == 1)
            assert by_ref[b.reference] == expected, b.reference

    asyncio.run(main())


def test_forged_interior_without_quorum_rejected(setup):
    """A forged block endorsed by fewer than quorum distinct authorities is
    verified directly and rejected."""
    committee, signers = setup

    async def main():
        agg = ThresholdAggregateVerifier(committee, CountingInner(committee))
        blocks = _dag(signers, rounds=2, forge={(1, 2)})
        forged_ref = next(
            b.reference for b in blocks if b.round() == 1 and b.author() == 2
        )
        # Strip the forged block's endorsements below quorum: only one
        # round-2 block keeps it in its includes.
        filtered = []
        for b in blocks:
            if b.round() == 2 and b.author() != 0:
                b = StatementBlock.build(
                    b.author(), 2,
                    [r for r in b.includes if r != forged_ref],
                    list(b.statements), signer=signers[b.author()],
                )
            filtered.append(b)
        results = await agg.verify_blocks(filtered)
        by_ref = dict(zip((b.reference for b in filtered), results))
        assert by_ref[forged_ref] is False
        assert sum(results) == len(filtered) - 1

    asyncio.run(main())


def test_collapsed_endorsement_falls_back_to_direct(setup):
    """If a block's endorsers fail verification, it must not be rejected
    outright — it gets its own direct check (valid -> accepted)."""
    committee, signers = setup

    async def main():
        inner = CountingInner(committee)
        agg = ThresholdAggregateVerifier(committee, inner)
        # Round 1 valid, the ENTIRE round-2 frontier forged.
        blocks = _dag(signers, rounds=2, forge={(2, a) for a in range(4)})
        results = await agg.verify_blocks(blocks)
        by_ref = dict(zip((b.reference for b in blocks), results))
        for b in blocks:
            assert by_ref[b.reference] == (b.round() == 1), b.reference
        # Round-1 blocks went through the direct path (second pass).
        assert sum(1 for r in inner.seen if r.round == 1) == 4

    asyncio.run(main())


def test_singletons_bypass_aggregation(setup):
    committee, signers = setup

    async def main():
        inner = CountingInner(committee)
        agg = ThresholdAggregateVerifier(committee, inner)
        blk = _dag(signers, rounds=1)[0]
        assert await agg.verify_blocks([blk]) == [True]
        assert agg.aggregated_total == 0 and len(inner.seen) == 1

    asyncio.run(main())


class CountingSigVerifier(CpuSignatureVerifier):
    def __init__(self):
        self.dispatched = 0

    def verify_signatures(self, pks, digests, sigs):
        self.dispatched += len(sigs)
        return super().verify_signatures(pks, digests, sigs)


def test_collector_aggregation_skips_interior(setup):
    """Blocks arriving concurrently (as from many peer connections) pool in
    one flush window; only the frontier pays a signature dispatch."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        blocks = _dag(signers, rounds=5)
        results = await collector.verify_blocks(blocks)
        assert all(results)
        assert sig.dispatched == 4  # frontier only (round 5)
        assert collector.aggregated_total == 16
        assert collector.direct_total == 4

    asyncio.run(main())


def test_collector_aggregation_rejects_forged_frontier(setup):
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        blocks = _dag(signers, rounds=3, forge={(3, 1)})
        results = await collector.verify_blocks(blocks)
        by_ref = dict(zip((b.reference for b in blocks), results))
        for b in blocks:
            expected = not (b.round() == 3 and b.author() == 1)
            assert by_ref[b.reference] == expected, b.reference

    asyncio.run(main())


def test_cross_flush_endorsement_skips_late_parent(setup):
    """Catch-up regime: a block whose quorum of verified children was
    accepted in EARLIER flushes (peers' streams run at different round
    offsets) skips its signature dispatch even when it arrives alone."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        blocks = _dag(signers, rounds=3)
        late = next(b for b in blocks if b.round() == 1 and b.author() == 0)
        rest = [b for b in blocks if b is not late]
        assert all(await collector.verify_blocks(rest))
        dispatched_before = sig.dispatched
        assert await collector.verify_blocks([late]) == [True]
        assert sig.dispatched == dispatched_before  # skipped via the index
        assert collector.aggregated_total >= 1

        # A single-author chain's parent never reaches quorum in the index.
        solo = CountingSigVerifier()
        c2 = BatchedSignatureVerifier(
            committee, solo, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        genesis = [StatementBlock.new_genesis(a) for a in range(4)]
        parent = StatementBlock.build(
            1, 1, [g.reference for g in genesis], [Share(b"p")],
            signer=signers[1],
        )
        child = StatementBlock.build(
            1, 2, [parent.reference], [Share(b"c")], signer=signers[1]
        )
        assert all(await c2.verify_blocks([child]))
        before = solo.dispatched
        assert await c2.verify_blocks([parent]) == [True]
        assert solo.dispatched == before + 1  # direct check, no quorum

    asyncio.run(main())


def test_collector_aggregation_single_author_stream_never_skips(setup):
    """One peer's own-block push stream (single author) can never reach
    quorum endorsement — every block is verified directly."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        genesis = [StatementBlock.new_genesis(a) for a in range(4)]
        prev = [g.reference for g in genesis]
        chain = []
        for r in range(1, 9):
            blk = StatementBlock.build(
                0, r, prev, [Share(bytes([r]))], signer=signers[0]
            )
            chain.append(blk)
            prev = [blk.reference]
        results = await collector.verify_blocks(chain)
        assert all(results)
        assert sig.dispatched == len(chain)
        assert collector.aggregated_total == 0

    asyncio.run(main())


def test_validators_commit_with_aggregate_verifier(tmp_path):
    """4 localhost validators with the threshold-aggregate wrapper over the
    CPU oracle still commit, and the finalization-safety oracle's input (the
    committed sequences) stays consistent."""
    import socket

    from mysticeti_tpu.config import Identifier, Parameters, PrivateConfig
    from mysticeti_tpu.committee import Authority
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
            Identifier("127.0.0.1", ports[2 * i], ports[2 * i + 1])
            for i in range(4)
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
                verifier="cpu-agg",
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


def _include_block(signers, author, round_, includes, forge=False):
    blk = StatementBlock.build(
        author, round_, includes, [Share(bytes([round_, author]))],
        signer=signers[author],
    )
    if forge:
        bad = bytes([blk.signature[0] ^ 1]) + blk.signature[1:]
        blk = StatementBlock(
            blk.reference, blk.includes, blk.statements,
            blk.meta_creation_time_ns, blk.epoch_marker, blk.epoch,
            bad, _bytes=None,
        )
    return blk


def test_collector_defers_unresolved_to_next_flush(setup):
    """An interior block whose optimistic endorsement collapses (its
    endorsers' signatures fail) must NOT trigger a second serialized
    dispatch in the same flush (the round-4 tpu-agg saturation collapse);
    it rides the next window and resolves there."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        dispatches = []
        orig = sig.verify_signatures

        def spy(pks, digests, sigs_):
            dispatches.append(len(sigs_))
            return orig(pks, digests, sigs_)

        sig.verify_signatures = spy
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
        b = _include_block(signers, 0, 1, genesis)
        # Three round-2 endorsers of b, two with forged signatures: b is
        # optimistically quorum-endorsed (3 authors) but actually reaches
        # only stake 1 — unresolved.
        children = [
            _include_block(signers, a, 2, [b.reference], forge=(a in (2, 3)))
            for a in (1, 2, 3)
        ]
        results = await collector.verify_blocks([b] + children)
        assert results == [True, True, False, False]
        # Flush 1 dispatched ONLY the frontier (3 children); b deferred and
        # resolved by its own dispatch in flush 2 — never two serialized
        # dispatches in one flush.
        assert dispatches == [3, 1]
        assert collector.direct_total == 4

    asyncio.run(main())


def test_collector_force_dispatches_on_second_deferral(setup):
    """Liveness guard: a Byzantine author minting fresh forged endorsers
    every window must not park a block in 'maybe' forever."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        dispatches = []
        orig = sig.verify_signatures

        def spy(pks, digests, sigs_):
            dispatches.append(len(sigs_))
            return orig(pks, digests, sigs_)

        sig.verify_signatures = spy
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=10.0, aggregate=True
        )
        # Pin the window so flushes fire ONLY when the test drives them —
        # the adaptive window would otherwise flush the deferred block
        # before wave2 arrives.
        collector._effective_delay_s = lambda: 10.0
        genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
        b = _include_block(signers, 0, 1, genesis)
        wave1 = [
            _include_block(signers, a, 2, [b.reference], forge=(a in (2, 3)))
            for a in (1, 2, 3)
        ]
        task = asyncio.ensure_future(collector.verify_blocks([b] + wave1))
        await asyncio.sleep(0.01)
        # Flush 1: b is deferred (optimistic quorum via authors {1,2,3},
        # actual stake 1 once the forged endorsers fail).
        await collector._flush()
        assert not task.done()
        # Fresh forged endorsers arrive in b's second window: optimistic
        # endorsement again reaches quorum (prior-accepted author 1 + forged
        # in-batch authors {2,3}) and again collapses — without the guard b
        # would defer forever.
        wave2 = [
            _include_block(signers, a, 3, [b.reference], forge=True)
            for a in (2, 3)
        ]
        task2 = asyncio.ensure_future(collector.verify_blocks(wave2))
        await asyncio.sleep(0.01)
        await collector._flush()
        assert await task == [True, True, False, False]
        assert await task2 == [False, False]
        # Flush 1: frontier wave1 (3).  Flush 2: frontier wave2 (2), then
        # the FORCED direct dispatch for b (1) — deferral is bounded.
        assert dispatches == [3, 2, 1]

    asyncio.run(main())


def test_evicted_endorsement_never_resurrects(setup):
    """Lemma F, route 2 (docs/aggregate-verification.md): endorsement stake
    scattered across FIFO evictions must never accumulate to quorum.  Three
    distinct-author accepted includers of a forged ref exist over the run,
    but the index is evicted between them — the forged block must be
    direct-checked (and rejected), not laundered through rebuilt state."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        collector.ENDORSEMENT_MAX_ENTRIES = 2  # force aggressive eviction
        genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
        forged = _include_block(signers, 3, 1, genesis, forge=True)

        # Flush A: genuine accepted blocks by authors 0 and 1 include the
        # forged ref -> index {0, 1}.
        wave_a = [
            _include_block(signers, a, 2, [forged.reference]) for a in (0, 1)
        ]
        assert await collector.verify_blocks(wave_a) == [True, True]
        assert collector._prior_endorsers(forged.reference) == {0, 1}

        # Flush B: unrelated accepted blocks churn the FIFO past its cap —
        # the forged ref's entry is evicted.
        filler = _dag(signers, rounds=1)
        assert all(await collector.verify_blocks(filler))
        assert collector._prior_endorsers(forged.reference) == frozenset()

        # Flush C: author 2 includes the forged ref -> rebuilt entry is {2}
        # only; the historical {0, 1} must NOT merge back.
        wave_c = [_include_block(signers, 2, 2, [forged.reference])]
        assert await collector.verify_blocks(wave_c) == [True]
        assert collector._prior_endorsers(forged.reference) == {2}

        # The forged block arrives: total historical endorsers {0,1,2}
        # would be quorum (3), but only stake 1 is visible — direct check,
        # rejected.
        dispatched_before = sig.dispatched
        results = await collector.verify_blocks([forged])
        assert results == [False]
        assert sig.dispatched == dispatched_before + 1

    asyncio.run(main())


def test_same_author_endorsement_counts_once(setup):
    """Lemma F, route 3: one author endorsing a ref both via the cross-flush
    index and in-batch must count its stake ONCE — quorum must not be
    reachable by double counting."""
    committee, signers = setup

    async def main():
        sig = CountingSigVerifier()
        collector = BatchedSignatureVerifier(
            committee, sig, max_batch=64, max_delay_s=0.02, aggregate=True
        )
        genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
        forged = _include_block(signers, 3, 1, genesis, forge=True)
        # Prior flush: authors 0 and 1 include the forged ref -> index {0,1}.
        prior = [
            _include_block(signers, a, 2, [forged.reference]) for a in (0, 1)
        ]
        assert await collector.verify_blocks(prior) == [True, True]
        # Same batch as the forged block: authors 0 and 1 AGAIN include the
        # ref.  Double counting would yield stake 4 >= quorum 3; correct
        # dedup sees {0, 1} = 2 -> direct check -> rejected.
        again = [
            _include_block(signers, a, 3, [forged.reference]) for a in (0, 1)
        ]
        results = await collector.verify_blocks(again + [forged])
        assert results == [True, True, False]

    asyncio.run(main())
