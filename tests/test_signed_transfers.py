"""Signed transfers (Parameters.signed_transactions, docs/execution.md): the
envelope, the gateway's check, the check on receipt, the fold against the
benchmark's plain reference, and the kernel and the service at the request
shapes of that deployment.  Small sizes: 4 validators in the simulator, a
few hundred accounts, the OpenSSL oracle and the JAX-CPU verifier.
"""
import asyncio
import os
import random
import struct

import pytest

from benchmark.reference import ed25519_oracle as oracle
from benchmark.reference import transfers as ref
from mysticeti_tpu import crypto, execution as X, spans
from mysticeti_tpu.block_validator import (
    BatchedSignatureVerifier,
    CpuSignatureVerifier,
)
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import IngressParameters, Parameters
from mysticeti_tpu.ingress import (
    SHED_BAD_SIGNATURE,
    IngressGateway,
    IngressPlane,
    ingress_key,
)
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.network import (
    GATEWAY_SHED,
    GatewaySubmit,
    GatewaySubmitReply,
    _read_frame,
    _write_frame,
    decode_message,
    encode_message,
)
from mysticeti_tpu.types import Share, StatementBlock

SEED, ACCOUNTS, BALANCE, SIZE = 11, 300, 1000, 512
FILLER = random.Random(5).randbytes(SIZE)


@pytest.fixture(scope="module")
def allocation(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("genesis") / "accounts.bin")
    X.write_genesis_allocation(path, ACCOUNTS, SEED, BALANCE)
    return path


def _transfer(sender: int, dest: int, nonce: int = 0, amount: int = 1):
    return ref.make_transfer(ref.account(SEED, sender), nonce, amount,
                             ref.account(SEED, dest)[1], SIZE, FILLER)


def _mixed(rng, senders, corrupt_one_in=4):
    """Transfers by ``senders``, some with one bit of the signature
    flipped, and the oracle's verdict on each."""
    txs = []
    for at, sender in enumerate(senders):
        tx = _transfer(sender, rng.randrange(ACCOUNTS))
        if at % corrupt_one_in == 1:
            tx = ref.corrupt_signature(rng, tx)
        txs.append(tx)
    return txs, [ref.sound(tx) for tx in txs]


def _plane(allocation, signed=True, metrics=None):
    state = X.ExecutionState(signed=signed)
    state.load_genesis(*X.read_genesis_allocation(allocation))

    class Core:
        execution = state
        execution_listeners: list = []

    committee = Committee.new_for_benchmarks(4)
    collector = BatchedSignatureVerifier(
        committee, CpuSignatureVerifier(), metrics=metrics)
    if signed:
        # What NetworkSyncer does where Parameters.signed_transactions is
        # set; the plane does not decide it.
        collector.require_transaction_signatures()
    # The validator hands its one stage clock to the plane (validator.py).
    stages = None
    if metrics is not None:
        stages = spans.StageClock(spans.NODE_STAGES)
        metrics.block_stages.attach(stages)
    plane = IngressPlane(IngressParameters(admission=False), metrics=metrics,
                         stages=stages)
    plane.attach(core=Core(), block_verifier=collector)
    return plane, collector, state


# -- (a) the envelope -----------------------------------------------------------


def test_envelope_roundtrip_matches_the_reference_byte_for_byte():
    signer = crypto.Signer.from_seed(X.account_seed(SEED, 3))
    dest = ref.account(SEED, 4)[1]
    tx = X.ExecTx(X.OP_TRANSFER, signer.public_key.bytes, 0, 7, dest)
    memo = FILLER[:SIZE - 72 - len(tx.to_bytes())]
    envelope = X.encode_signed_tx(
        tx, signer.sign(X.signed_digest(tx.to_bytes() + memo)), memo)
    assert len(envelope) == SIZE and envelope[0] == 0xFF
    # The reference's plain encoding is the same bytes (Ed25519 signatures
    # are deterministic), and each side decodes the other's.
    assert envelope == ref.make_transfer(
        ref.account(SEED, 3), 0, 7, dest, SIZE, FILLER)
    parsed = X.parse_signed_tx(envelope)
    assert parsed.tx == tx and parsed.signature == envelope[8:72]
    assert parsed.digest == ref.decode_envelope(envelope)["message"]
    assert oracle.verify(tx.account, parsed.digest, parsed.signature)
    assert X.account_seed(SEED, 3) == ref.account_seed(SEED, 3)


@pytest.mark.parametrize("garble", [
    lambda e: e[:40],  # the signature cut short
    lambda e: e[:72] + b"\x00" + e[73:],  # the inner magic broken
    lambda e: e[:100],  # the transaction cut short
    lambda e: e[:80] + b"\x09" + e[81:],  # an unknown op
    lambda e: X.SIGNED_MAGIC + bytes(64) + X.ExecTx(
        X.OP_TRANSFER, b"short-key", 0, 1, b"d").to_bytes(),  # no public key
])
def test_a_garbled_envelope_is_an_opaque_payload(garble):
    payload = garble(_transfer(1, 2))
    assert payload.startswith(X.SIGNED_MAGIC)
    assert X.parse_signed_tx(payload) is None
    assert ref.decode_envelope(payload) is None
    state = X.ExecutionState(signed=True)
    assert state.transaction_of(payload) is None
    block = StatementBlock.build(
        0, 1, [StatementBlock.new_genesis(i).reference for i in range(4)],
        [Share(payload)], signer=crypto.Signer.dummy())
    result = state.observe_commit(1, [block])
    assert result.applied == result.rejected == 0
    fold = ref.Fold()
    assert fold.commit(1, [payload]) == result.root


def test_a_bare_exectx_folds_as_unsigned_where_signatures_are_required(
        allocation):
    balance, keys = X.read_genesis_allocation(allocation)
    bare = X.ExecTx(X.OP_TRANSFER, keys[:32], 0, 5, keys[32:64]).to_bytes()
    state = X.ExecutionState(signed=True)
    state.load_genesis(balance, keys)
    assert state.transaction_of(bare) is X.REJECT_UNSIGNED
    block = StatementBlock.build(
        0, 1, [StatementBlock.new_genesis(i).reference for i in range(4)],
        [Share(bare), Share(_transfer(0, 1))], signer=crypto.Signer.dummy())
    result = state.observe_commit(1, [block])
    assert dict(result.verdicts) == {X.APPLIED: 1, X.REJECT_UNSIGNED: 1}
    assert state.probe(keys[:32]) == (BALANCE - 1, 1)
    fold = ref.Fold()
    fold.load_genesis(balance, keys)
    assert fold.commit(1, [bare, _transfer(0, 1)]) == result.root
    assert fold.verdicts == {ref.APPLIED: 1, ref.UNSIGNED: 1}


# -- the genesis allocation --------------------------------------------------------


def test_genesis_allocation_is_the_references_and_enters_the_root(
        allocation, tmp_path):
    balance, keys = X.read_genesis_allocation(allocation)
    assert keys == ref.account_keys((SEED, 0, ACCOUNTS))
    with open(allocation, "rb") as f:
        assert f.read() == ref.allocation_bytes(BALANCE, keys)
    state = X.ExecutionState(signed=True)
    state.load_genesis(balance, keys)
    assert state.root == ref.genesis_root(balance, keys) != X.GENESIS_ROOT
    assert state.account_count() == ACCOUNTS
    # Another allocation: another chain from the first root on.
    other = str(tmp_path / "other.bin")
    X.write_genesis_allocation(other, ACCOUNTS, SEED, BALANCE + 1)
    state_b = X.ExecutionState(signed=True)
    state_b.load_genesis(*X.read_genesis_allocation(other))
    assert (state.observe_commit(1, []).root
            != state_b.observe_commit(1, []).root)


def test_the_durable_state_holds_touched_accounts_over_the_allocation(
        allocation):
    balance, keys = X.read_genesis_allocation(allocation)
    state = X.ExecutionState(signed=True)
    state.load_genesis(balance, keys)
    block = StatementBlock.build(
        0, 1, [StatementBlock.new_genesis(i).reference for i in range(4)],
        [Share(_transfer(0, 1)), Share(_transfer(2, 1))],
        signer=crypto.Signer.dummy())
    state.observe_commit(1, [block])
    data = state.to_bytes()
    assert len(data) < 40 * ACCOUNTS  # three accounts, not three hundred
    again = X.ExecutionState(signed=True)
    again.load_genesis(balance, keys)
    again.recover(data)
    assert again.root == state.root and again.last_height == 1
    assert again.probe(keys[32:64]) == (BALANCE + 2, 0)
    assert again.probe(keys[96:128]) == (BALANCE, 0)
    assert again.account_count() == ACCOUNTS
    assert again.to_bytes() == data


def test_the_cli_writes_the_allocation(tmp_path):
    from mysticeti_tpu.cli import main

    out = str(tmp_path / "a.bin")
    assert main(["genesis", "--accounts", "5", "--seed", str(SEED),
                 "--balance", "9", "--out", out]) == 0
    assert X.read_genesis_allocation(out) == (
        9, ref.account_keys((SEED, 0, 5)))
    loaded = Parameters(execution=True, signed_transactions=True,
                        genesis_allocation=out)
    path = str(tmp_path / "parameters.yaml")
    loaded.dump(path)
    again = Parameters.load(path)
    assert again.signed_transactions and again.genesis_allocation == out


# -- (b) the gateway ----------------------------------------------------------------


def test_gateway_refuses_exactly_what_the_oracle_rejects(allocation):
    metrics = Metrics()
    plane, _, _ = _plane(allocation, metrics=metrics)
    rng = random.Random(21)
    txs, verdicts = _mixed(rng, range(40))
    assert 0 < sum(verdicts) < len(verdicts)

    async def main():
        gateway = await IngressGateway(plane, "127.0.0.1", 0).start()
        port = gateway._server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            _write_frame(writer, encode_message(
                GatewaySubmit(b"", 0, tuple(txs))))
            await writer.drain()
            return decode_message(await _read_frame(reader))
        finally:
            writer.close()
            await gateway.stop()

    reply = asyncio.run(main())
    assert isinstance(reply, GatewaySubmitReply)
    assert reply.status == GATEWAY_SHED
    assert reply.reason == SHED_BAD_SIGNATURE.encode()
    assert (reply.accepted, reply.shed) == (
        sum(verdicts), len(verdicts) - sum(verdicts))
    # Bit for bit: what reached the mempool is what the oracle accepts.
    pooled = set(plane.drain(1000))
    assert pooled == {tx for tx, ok in zip(txs, verdicts) if ok}
    assert plane.shed_by_reason == {
        SHED_BAD_SIGNATURE: len(verdicts) - sum(verdicts)}
    for tx, ok in zip(txs, verdicts):
        assert plane.verified_at_gateway(tx) == ok
    series = metrics.verified_tx_signatures_total
    label = "CpuSignatureVerifier"
    assert series.labels(label, "gateway", "accepted")._value.get() == sum(
        verdicts)
    assert series.labels(label, "gateway", "rejected")._value.get() == (
        len(verdicts) - sum(verdicts))
    assert metrics.mysticeti_ingress_shed_total.labels(
        SHED_BAD_SIGNATURE)._value.get() == len(verdicts) - sum(verdicts)
    stage = metrics.block_stages._clocks[-1].totals()["admit_verify"]
    assert stage["wall_s"] > 0.0


def test_the_gateways_loop_does_not_wait_for_the_verifier(allocation):
    """A frame's reply waits for its verdicts; the loop, and so every other
    connection, goes on meanwhile."""
    import threading

    plane, collector, _ = _plane(allocation)
    gate = threading.Event()
    inner = collector.verifier

    class Slow(CpuSignatureVerifier):
        def verify_signatures(self, pks, digests, sigs):
            gate.wait(10)
            return inner.verify_signatures(pks, digests, sigs)

    plane._tx_verifier = Slow()

    async def main():
        waiting = asyncio.ensure_future(
            plane.submit_checked("a", [_transfer(0, 1)]))
        await asyncio.sleep(0.05)
        assert not waiting.done()
        # An opaque payload from another connection is admitted meanwhile.
        other = await plane.submit_checked("b", [b"opaque-payload"])
        assert other.accepted == 1 and not waiting.done()
        gate.set()
        return await waiting

    assert asyncio.run(main()).accepted == 1


# -- (c) receipt ----------------------------------------------------------------------


def test_a_block_with_one_forged_transfer_is_rejected_on_receipt():
    committee = Committee.new_for_benchmarks(4)
    signers = Committee.benchmark_signers(4)
    genesis = [StatementBlock.new_genesis(i).reference for i in range(4)]
    rng = random.Random(3)
    good = [Share(_transfer(i, i + 1)) for i in range(6)]
    forged = Share(ref.corrupt_signature(rng, _transfer(9, 10)))
    assert not ref.sound(forged.transaction)
    # The Byzantine author signs its block properly: only the content check
    # can tell.
    bad_block = StatementBlock.build(
        3, 1, genesis, good[:3] + [forged] + good[3:], signer=signers[3])
    clean_block = StatementBlock.build(3, 1, genesis, good, signer=signers[3])
    bad_block.verify(committee)  # structure and own signature are sound

    async def receive(block, metrics):
        collector = BatchedSignatureVerifier(
            committee, CpuSignatureVerifier(), max_delay_s=0.001,
            metrics=metrics)
        collector.require_transaction_signatures()
        return await collector.verify_blocks([block])

    for honest in range(3):  # every honest validator, each its own collector
        metrics = Metrics()
        assert asyncio.run(receive(bad_block, metrics)) == [False]
        assert metrics.verify_rejected_blocks_total.labels(
            "transaction_signature")._value.get() == 1
        assert metrics.verify_rejected_blocks_total.labels(
            "block_signature")._value.get() == 0
        tx_series = metrics.verified_tx_signatures_total
        assert tx_series.labels(
            "CpuSignatureVerifier", "receipt", "rejected")._value.get() == 1
        assert tx_series.labels(
            "CpuSignatureVerifier", "receipt", "accepted")._value.get() == 6
        # The block's own signature counts where it always did.
        assert metrics.verified_signatures_total.labels(
            "CpuSignatureVerifier", "accepted")._value.get() == 1
        assert asyncio.run(receive(clean_block, Metrics())) == [True]


def test_receipt_skips_what_the_own_gateway_verified(allocation):
    plane, collector, _ = _plane(allocation)
    calls = []
    inner = collector.verifier

    class Counting(CpuSignatureVerifier):
        def verify_signatures(self, pks, digests, sigs):
            calls.append(len(sigs))
            return inner.verify_signatures(pks, digests, sigs)

    collector.verifier = plane._tx_verifier = Counting()
    assert collector._tx_verified == plane.verified_at_gateway
    txs = [_transfer(i, i + 1) for i in range(5)]
    assert plane.submit("c", txs[:3]).accepted == 3
    assert calls == [3]
    signers = Committee.benchmark_signers(4)
    block = StatementBlock.build(
        1, 1, [StatementBlock.new_genesis(i).reference for i in range(4)],
        [Share(tx) for tx in txs], signer=signers[1])
    assert asyncio.run(collector.verify_blocks([block])) == [True]
    # The block's own signature and the two transfers the gateway had not
    # seen: three signatures, not six.
    assert calls == [3, 3]


def test_through_the_service_a_flush_with_transfers_keeps_a_connection_of_its_own(
        allocation, tmp_path):
    """What a validator of this deployment sends the verifier service, by
    the road the request chooses (``verifier_client_requests_total``): a
    collector flush that holds a transfer to check has a signer the
    committee table lacks — a RAW frame, on a pooled connection of its
    own; the gateway's check blocks on the calling thread's; only a flush
    of committee signatures alone (a block without transfers, or whose
    transfers the own gateway verified) goes down the shared pipelined
    connection."""
    from mysticeti_tpu.verifier_service import (
        RemoteSignatureVerifier, VerifierServer)

    metrics = Metrics()
    plane, collector, _ = _plane(allocation, metrics=metrics)
    committee = Committee.new_for_benchmarks(4)
    keys = [committee.get_public_key(i).bytes for i in range(4)]
    signers = Committee.benchmark_signers(4)
    genesis = [StatementBlock.new_genesis(i).reference for i in range(4)]
    txs = [_transfer(i, i + 1) for i in range(8)]

    def roads():
        series = metrics.verifier_client_requests_total
        return [series.labels(path)._value.get()
                for path in ("shared", "pooled", "sync")]

    async def scenario():
        server = VerifierServer(
            str(tmp_path / "v.sock"), committee_keys=keys,
            backend=CpuSignatureVerifier())
        await server.start()
        try:
            remote = RemoteSignatureVerifier(
                socket_path=server.socket_path, committee_keys=keys,
                metrics=metrics)
            collector.verifier = plane._tx_verifier = remote
            full = StatementBlock.build(
                1, 1, genesis, [Share(tx) for tx in txs[:5]],
                signer=signers[1])
            assert await collector.verify_blocks([full]) == [True]
            assert roads() == [0, 1, 0]  # 1 + 5 signatures, one RAW frame
            empty = StatementBlock.build(2, 1, genesis, [], signer=signers[2])
            assert await collector.verify_blocks([empty]) == [True]
            assert roads() == [1, 1, 0]
            reply = await asyncio.to_thread(plane.submit, "c", txs[5:])
            assert reply.accepted == 3
            assert roads() == [1, 1, 1]  # the gateway's blocking check
            own = StatementBlock.build(
                3, 1, genesis, [Share(tx) for tx in txs[5:]],
                signer=signers[3])
            assert await collector.verify_blocks([own]) == [True]
            assert roads() == [2, 1, 1]  # nothing left to check but its own
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_without_an_ingress_plane_a_forged_transfer_is_rejected_on_receipt(
        allocation, tmp_path):
    """``signed_transactions`` on and no ingress plane anywhere (the
    generator feeds the handler): the parameter alone turns the receipt
    check on.  A Byzantine author proposes a forged transfer in a block it
    signs properly; every honest validator rejects that block, and the
    forgery is never committed or folded."""
    from mysticeti_tpu.chaos import FaultPlan, run_chaos_sim
    from mysticeti_tpu.config import StorageParameters

    nodes, byzantine = 4, 3
    forged = ref.corrupt_signature(random.Random(8), _transfer(40, 41))
    assert not ref.sound(forged)
    sound = [_transfer(i, i + 1) for i in range(12)]
    committed = {a: [] for a in range(nodes)}

    def real_crypto(authority, committee, metrics):
        return BatchedSignatureVerifier(
            committee, CpuSignatureVerifier(), max_delay_s=0.002,
            metrics=metrics)

    async def driver(harness):
        for a in range(nodes):
            collector = harness.nodes[a].block_verifier
            # On by the parameter, and no gateway to vouch for anything.
            assert collector.transaction_signatures
            assert collector._tx_verified is None
            execution = harness.nodes[a].core.execution
            fold = execution.observe_commit

            def spy(height, blocks, a=a, fold=fold):
                committed[a].extend(
                    bytes(st.transaction) for block in blocks
                    for st in block.statements if isinstance(st, Share))
                return fold(height, blocks)

            execution.observe_commit = spy
        await asyncio.sleep(0.5)
        for at, tx in enumerate(sound):
            harness.inject(at % byzantine, tx)
        harness.inject(byzantine, forged)

    params = Parameters(
        leader_timeout_s=1.0, execution=True, signed_transactions=True,
        genesis_allocation=allocation,
        storage=StorageParameters(checkpoint_interval=0),
    )
    _, harness = run_chaos_sim(
        FaultPlan(seed=4), nodes, 5.0, str(tmp_path), parameters=params,
        with_metrics=True, verifier_factory=real_crypto, extra_fault=driver,
        committee=Committee.new_for_benchmarks(nodes),
    )
    for honest in range(byzantine):
        assert forged not in committed[honest]
        assert set(sound) <= set(committed[honest])
        metrics = harness.metrics[honest]
        assert metrics.verify_rejected_blocks_total.labels(
            "transaction_signature")._value.get() >= 1
        assert metrics.verify_rejected_blocks_total.labels(
            "block_signature")._value.get() == 0
        assert metrics.verified_tx_signatures_total.labels(
            "CpuSignatureVerifier", "receipt", "rejected")._value.get() >= 1


def test_signed_transactions_refuse_a_verifier_that_checks_none(
        allocation, tmp_path):
    """``--verifier accept`` (the simulator's default) checks no signature:
    with the parameter on, the node does not start."""
    from mysticeti_tpu.chaos import FaultPlan, run_chaos_sim

    params = Parameters(execution=True, signed_transactions=True,
                        genesis_allocation=allocation)
    with pytest.raises(ValueError, match="checks none"):
        run_chaos_sim(FaultPlan(seed=1), 4, 1.0, str(tmp_path),
                      parameters=params)


def test_a_plane_attached_to_no_verifier_admits_nothing_unchecked(
        allocation):
    state = X.ExecutionState(signed=True)
    state.load_genesis(*X.read_genesis_allocation(allocation))

    class Core:
        execution = state
        execution_listeners: list = []

    plane = IngressPlane(IngressParameters(admission=False)).attach(
        core=Core())
    with pytest.raises(RuntimeError, match="no verifier"):
        plane.submit("c", [_transfer(0, 1)])
    assert plane.pending() == 0


# -- (g) the parameter off ---------------------------------------------------------------


def test_with_the_parameter_off_an_envelope_is_an_opaque_payload(allocation):
    plane, collector, state = _plane(allocation, signed=False)
    assert not plane.signed and not collector.transaction_signatures
    forged = ref.corrupt_signature(random.Random(1), _transfer(0, 1))
    result = plane.submit("c", [forged, _transfer(2, 3)])
    assert (result.accepted, result.shed) == (2, 0)
    assert plane._tx_verifier is None
    block = StatementBlock.build(
        0, 1, [StatementBlock.new_genesis(i).reference for i in range(4)],
        [Share(forged), Share(_transfer(2, 3))],
        signer=Committee.benchmark_signers(4)[0])
    folded = state.observe_commit(1, [block])
    assert folded.applied == folded.rejected == 0
    assert state.probe(ref.account(SEED, 2)[1]) == (BALANCE, 0)
    # And the collector sends the block's own signature alone.
    assert asyncio.run(collector.verify_blocks([block])) == [True]
    assert collector.direct_total == 1
    assert Parameters().signed_transactions is False
    assert Parameters().genesis_allocation == ""


# -- (d) end to end in the simulator ------------------------------------------------------


@pytest.mark.chaos
def test_four_validators_fold_to_the_references_roots(allocation, tmp_path):
    """Seeded transfers, one in five forged, through four gateways'
    admission in the simulator, real Ed25519 behind the collector: forged
    ones never commit, and at every height every node's executed root is
    the reference's fold of the committed sequence."""
    from mysticeti_tpu.chaos import FaultPlan, run_chaos_sim
    from mysticeti_tpu.config import StorageParameters

    nodes = 4
    committed = {a: [] for a in range(nodes)}
    offered, forged_keys, refused = [], set(), [0]

    def real_crypto(authority, committee, metrics):
        return BatchedSignatureVerifier(
            committee, CpuSignatureVerifier(), max_delay_s=0.002,
            metrics=metrics)

    async def driver(harness):
        rng = random.Random(77)
        planes = []
        for a in range(nodes):
            node = harness.nodes[a]
            execution = node.core.execution
            fold = execution.observe_commit

            def spy(height, blocks, a=a, fold=fold):
                committed[a].append((height, [
                    bytes(st.transaction) for block in blocks
                    for st in block.statements if isinstance(st, Share)]))
                return fold(height, blocks)

            execution.observe_commit = spy
            planes.append(IngressPlane(
                IngressParameters(admission=False)).attach(
                    core=node.core, block_verifier=node.block_verifier))
        sender = 0
        while sender + 5 * nodes <= ACCOUNTS:
            await asyncio.sleep(0.25)
            for a, plane in enumerate(planes):
                frame = []
                for _ in range(5):
                    tx = _transfer(sender, rng.randrange(ACCOUNTS))
                    if sender % 5 == 2:
                        tx = ref.corrupt_signature(rng, tx)
                        forged_keys.add(ingress_key(tx))
                    frame.append(tx)
                    sender += 1
                offered.extend(frame)
                result = await plane.submit_checked(f"client-{a}", frame)
                refused[0] += result.shed
                for tx in plane.drain(100):
                    harness.inject(a, tx)

    params = Parameters(
        leader_timeout_s=1.0, execution=True, signed_transactions=True,
        genesis_allocation=allocation,
        storage=StorageParameters(checkpoint_interval=0),
    )
    report, harness = run_chaos_sim(
        FaultPlan(seed=9), nodes, 6.0, str(tmp_path), parameters=params,
        with_metrics=True, verifier_factory=real_crypto, extra_fault=driver,
        committee=Committee.new_for_benchmarks(nodes),
    )
    assert refused[0] == len(forged_keys) > 0
    sequence = committed[0]
    assert sequence and [h for h, _ in sequence] == list(
        range(1, len(sequence) + 1))
    fold = ref.Fold()
    fold.load_genesis(*X.read_genesis_allocation(allocation))
    landed = set()
    for height, payloads in sequence:
        fold.commit(height, payloads)
        landed.update(ingress_key(p) for p in payloads)
    assert not landed & forged_keys  # no forged transfer was ever committed
    sound_offered = [tx for tx in offered
                     if ingress_key(tx) not in forged_keys]
    assert fold.verdicts.get(ref.APPLIED, 0) >= len(sound_offered) // 2
    assert ref.BAD_SIGNATURE not in fold.verdicts
    compared = 0
    for a in range(nodes):
        assert committed[a] == sequence[:len(committed[a])]
        for height, _ in committed[a]:
            assert harness.checker.state_root_at(a, height) == fold.roots[
                height], (a, height)
            compared += 1
    assert compared >= 4 * 10
    assert report.state_root_chain


# -- (e), (f) the kernel and the service at this deployment's shapes ------------------------


def _request(rng, committee_signers, n_accounts, first_sender):
    """What a validator sends for one block of ``n_accounts`` signed
    transfers: the author's signature, then theirs; a quarter corrupted."""
    pks, digests, sigs = [], [], []
    author = committee_signers[rng.randrange(len(committee_signers))]
    digest = rng.randbytes(32)
    pks.append(author.public_key.bytes)
    digests.append(digest)
    sigs.append(author.sign(digest))
    for i in range(n_accounts):
        private, public = ref.account(SEED, (first_sender + i) % ACCOUNTS)
        digest = rng.randbytes(32)
        pks.append(public)
        digests.append(digest)
        sigs.append(private.sign(digest))
    for at in rng.sample(range(len(sigs)), len(sigs) // 4):
        digests[at], sigs[at] = oracle.flip_one_bit(rng, digests[at], sigs[at])
    return pks, digests, sigs


@pytest.mark.parametrize("n_accounts", [1, 7, 100, 255, 300])
def test_unknown_signer_kernel_equals_the_oracle_at_block_shapes(n_accounts):
    """1 committee key + n account keys, to past the warmed width, through
    the committee-table entry point: one kernel (the unknown-signer one),
    every bit OpenSSL's."""
    from mysticeti_tpu.ops import ed25519 as E

    signers = Committee.benchmark_signers(4)
    table = E.KeyTable([s.public_key.bytes for s in signers])
    rng = random.Random(1000 + n_accounts)
    pks, digests, sigs = _request(rng, signers, n_accounts, 0)
    before = {(d["kernel"], d["bucket"]): d["count"]
              for d in E.dispatch_counts()}
    got = E.dispatch_batch_table(table, pks, digests, sigs).result()
    launched = {(d["kernel"], d["bucket"]): d["count"]
                - before.get((d["kernel"], d["bucket"]), 0)
                for d in E.dispatch_counts()}
    assert [bool(b) for b in got] == oracle.verify_all(pks, digests, sigs)
    used = {k: n for k, n in launched.items() if n}
    # Whole to the unknown-signer kernel: no indexed launch beside it.
    assert used == {("blob", 256): -(-len(sigs) // 256)}


def test_a_request_wider_than_the_warmed_width_compiles_nothing(tmp_path):
    """The service in front of the real JAX backend (on the CPU): after
    warm-up a request of 700 signatures by unknown signers is answered bit
    for bit, by launches no wider than what was warmed, and COMPILE_STATS
    does not move."""
    from mysticeti_tpu.block_validator import TpuSignatureVerifier
    from mysticeti_tpu.ops import ed25519 as E
    from mysticeti_tpu.verifier_service import (
        RemoteSignatureVerifier,
        VerifierServer,
    )

    signers = Committee.benchmark_signers(4)
    keys = [s.public_key.bytes for s in signers]
    rng = random.Random(8)
    wide = _request(rng, signers, 699, 0)
    narrow = _request(rng, signers, 20, 5)

    async def scenario():
        server = VerifierServer(
            str(tmp_path / "verifier.sock"), committee_keys=keys,
            backend=TpuSignatureVerifier(mesh=None, committee_keys=keys))
        await server.start()
        try:
            client = RemoteSignatureVerifier(
                socket_path=server.socket_path, committee_keys=keys,
                timeout_s=900.0)
            await asyncio.to_thread(client.warmup)
            warmed = server._launch_cap
            assert warmed == E.BUCKETS[0]
            first = await asyncio.to_thread(client.verify_signatures, *narrow)
            stats = dict(E.COMPILE_STATS)
            counts = {(d["kernel"], d["bucket"]): d["count"]
                      for d in E.dispatch_counts()}
            got = await asyncio.to_thread(client.verify_signatures, *wide)
            return first, got, stats, counts, warmed
        finally:
            await server.stop()

    first, got, stats, counts, warmed = asyncio.run(scenario())
    assert first == oracle.verify_all(*narrow)
    assert got == oracle.verify_all(*wide)
    assert 0 < sum(got) < len(got)
    assert {k: E.COMPILE_STATS[k] for k in ("cache_hits", "cache_misses")} == {
        k: stats[k] for k in ("cache_hits", "cache_misses")}
    after = {(d["kernel"], d["bucket"]): d["count"]
             for d in E.dispatch_counts()}
    grown = {k: n - counts.get(k, 0) for k, n in after.items()
             if n != counts.get(k, 0)}
    assert grown == {("blob", warmed): 3}  # 256 + 256 + 188, one request


# -- (g) an account that signs ahead: order from the gateway to the fold --------------------

from benchmark.reference import smallbank as bank  # noqa: E402

BANK_ACCOUNTS, HOT_OPS = 520, 50


@pytest.fixture(scope="module")
def bank_allocation(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("bank") / "accounts.bin")
    X.write_genesis_allocation(path, BANK_ACCOUNTS, SEED, 3000, 2000)
    return path


@pytest.fixture(scope="module")
def bank_signers():
    return [bank.account(SEED, i) for i in range(BANK_ACCOUNTS)]


def _hot_frames(signers, frames=5, one_shot=500):
    """Account 0's HOT_OPS operations in nonce order, cut into ``frames``
    frames, each among its share of ``one_shot`` accounts that sign once."""
    rng = random.Random(3)
    codes = [X.OP_DEPOSIT_CHECKING, X.OP_BALANCE, X.OP_SEND_PAYMENT,
             X.OP_WRITE_CHECK, X.OP_TRANSACT_SAVINGS, X.OP_AMALGAMATE]
    hot = [
        bank.make_operation(
            # Beyond what a deposit brings back after an Amalgamate: the
            # later payments abort.
            signers[0], codes[n % 6], n,
            700 if codes[n % 6] == X.OP_SEND_PAYMENT else 500,
            signers[1 + n][1] if codes[n % 6] in bank.WITH_DEST else b"",
            SIZE, FILLER)
        for n in range(HOT_OPS)
    ]
    others = [
        bank.make_operation(signers[10 + i], X.OP_DEPOSIT_CHECKING, 0, 130,
                            b"", SIZE, FILLER)
        for i in range(one_shot)
    ]
    out = []
    for f in range(frames):
        mine = hot[f * HOT_OPS // frames:(f + 1) * HOT_OPS // frames]
        theirs = others[f * one_shot // frames:(f + 1) * one_shot // frames]
        # The account's operations keep their order inside the frame.
        slots = sorted(rng.randrange(len(theirs) + 1) for _ in mine)
        frame, k = [], 0
        for at in range(len(theirs) + 1):
            while k < len(mine) and slots[k] == at:
                frame.append(mine[k])
                k += 1
            frame.extend(theirs[at:at + 1])
        out.append(frame)
    return hot, out


def test_one_accounts_operations_drain_in_nonce_order_over_many_cycles(
        bank_allocation, bank_signers):
    """Fifty operations of one account in five frames among 500 one-shot
    senders, a proposal of at most 24: whatever the number of drains, the
    account's operations leave the pool in the order they were sent, and
    the plane counts the nonces ahead and the lane's depth."""
    metrics = Metrics()
    plane, _, state = _plane(bank_allocation, metrics=metrics)
    plane.params.max_per_proposal = 24
    hot, frames = _hot_frames(bank_signers)
    drained = []
    for frame in frames:
        result = plane.submit("conn-1", frame)
        assert (result.accepted, result.shed) == (len(frame), 0)
        drained.extend(plane.drain(plane.max_per_proposal))
    plane._export_gauges(False)  # what a tick does with the lanes' depth
    while plane.pending():
        drained.extend(plane.drain(plane.max_per_proposal))
    assert len(drained) == HOT_OPS + 500
    assert [tx for tx in drained if tx in set(hot)] == hot
    # Nothing of the account has executed: every nonce but 0 is ahead.
    assert plane.nonce_ahead_total == HOT_OPS - 1
    assert metrics.mysticeti_ingress_nonce_ahead_total._value.get() == (
        HOT_OPS - 1)
    assert plane.lane_depth_max >= HOT_OPS // 5
    # In that order the fold applies or aborts every one.
    result = state.observe_commit(1, [type("B", (), {
        "statements": [Share(tx) for tx in drained]})()])
    assert dict(result.verdicts).get(X.REJECT_BAD_NONCE) is None
    assert state.probe(bank_signers[0][1])[1] == HOT_OPS


def test_a_connections_frames_are_admitted_in_the_order_it_sent_them(
        bank_allocation, bank_signers):
    """Two frames down one connection, the first one's verdicts back LAST:
    signatures are verified side by side, the pool still takes the first
    frame first."""
    import threading

    plane, collector, _ = _plane(bank_allocation)
    inner = collector.verifier
    second_done = threading.Event()
    calls = []

    class FirstIsSlow(CpuSignatureVerifier):
        def verify_signatures(self, pks, digests, sigs):
            calls.append(len(pks))
            if len(calls) == 1:
                second_done.wait(10)
            out = inner.verify_signatures(pks, digests, sigs)
            if len(calls) > 1:
                second_done.set()
            return out

    plane._tx_verifier = FirstIsSlow()
    hot, _ = _hot_frames(bank_signers, one_shot=0)
    first, second = hot[:3], hot[3:5]

    async def main():
        gateway = await IngressGateway(plane, "127.0.0.1", 0).start()
        port = gateway._server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            for frame in (first, second):
                _write_frame(writer, encode_message(
                    GatewaySubmit(b"", 0, tuple(frame))))
            await writer.drain()
            return [decode_message(await _read_frame(reader))
                    for _ in range(2)]
        finally:
            writer.close()
            await gateway.stop()

    replies = asyncio.run(main())
    assert [r.accepted for r in replies] == [3, 2]
    assert calls == [3, 2] and second_done.is_set()
    assert plane.drain(100) == first + second


@pytest.mark.chaos
def test_four_validators_fold_an_account_that_signs_ahead_in_order(
        bank_allocation, bank_signers, tmp_path):
    """One account's fifty operations through one gateway's admission among
    one-shot senders on all four, proposals cut small, in the simulator:
    every one commits, none as ``bad_nonce``, with nonces admitted ahead
    and a lane more than one deep; the roots are the reference's."""
    from mysticeti_tpu.chaos import FaultPlan, run_chaos_sim
    from mysticeti_tpu.config import StorageParameters

    nodes = 4
    committed = []
    hot, frames = _hot_frames(bank_signers, frames=5, one_shot=400)
    planes = []

    def real_crypto(authority, committee, metrics):
        return BatchedSignatureVerifier(
            committee, CpuSignatureVerifier(), max_delay_s=0.002,
            metrics=metrics)

    async def driver(harness):
        execution = harness.nodes[0].core.execution
        fold = execution.observe_commit

        def spy(height, blocks):
            committed.append((height, [
                bytes(st.transaction) for block in blocks
                for st in block.statements if isinstance(st, Share)]))
            return fold(height, blocks)

        execution.observe_commit = spy
        for a in range(nodes):
            node = harness.nodes[a]
            planes.append(IngressPlane(
                IngressParameters(admission=False, max_per_proposal=12)
            ).attach(core=node.core, block_verifier=node.block_verifier))
        for step in range(16):
            await asyncio.sleep(0.2)
            if step < len(frames):
                # The account talks to validator 0; the one-shot senders
                # of the frame are spread over all four.
                frame = frames[step]
                mine = [tx for tx in frame if tx in hot]
                theirs = [tx for tx in frame if tx not in hot]
                for a, plane in enumerate(planes):
                    share = theirs[a::nodes]
                    if a == 0:
                        share = share[:len(share) // 2] + mine + share[
                            len(share) // 2:]
                    result = await plane.submit_checked(f"conn-{a}", share)
                    assert result.shed == 0
            for a, plane in enumerate(planes):
                plane.tick()
                for tx in plane.drain(plane.max_per_proposal):
                    harness.inject(a, tx)

    params = Parameters(
        leader_timeout_s=1.0, execution=True, signed_transactions=True,
        genesis_allocation=bank_allocation,
        storage=StorageParameters(checkpoint_interval=0),
    )
    report, harness = run_chaos_sim(
        FaultPlan(seed=12), nodes, 8.0, str(tmp_path), parameters=params,
        with_metrics=True, verifier_factory=real_crypto, extra_fault=driver,
        committee=Committee.new_for_benchmarks(nodes),
    )
    fold = bank.Fold()
    fold.load_genesis(*X.read_genesis_allocation(bank_allocation))
    fold.log = []
    for height, payloads in committed:
        assert fold.commit(height, payloads) == harness.checker.state_root_at(
            0, height)
    by_hot = [verdict for payload, verdict in fold.log if payload in hot]
    assert len(by_hot) == HOT_OPS
    assert set(by_hot) <= set(bank.EXECUTED) and bank.ABORTED in by_hot
    assert bank.BAD_NONCE not in fold.verdicts
    assert [p for p, _ in fold.log if p in hot] == hot
    assert fold.accounts[bank_signers[0][1]][1] == HOT_OPS
    assert harness.nodes[0].core.execution.bad_nonce_total == 0
    assert planes[0].nonce_ahead_total > 0
    assert planes[0].lane_depth_max > 1
    assert report.state_root_chain


@pytest.mark.parametrize("n_accounts", [56, 255])
def test_a_request_with_repeated_signers_equals_the_oracle(n_accounts):
    """A quarter of the account signatures by four keys, so that the launch
    holds the same signer many times, a quarter of all corrupted (some of
    the repeats among them): every bit OpenSSL's, one kernel."""
    from mysticeti_tpu.ops import ed25519 as E

    signers = Committee.benchmark_signers(4)
    table = E.KeyTable([s.public_key.bytes for s in signers])
    rng = random.Random(4000 + n_accounts)
    keys = [bank.account(SEED, i) for i in range(n_accounts)]
    lanes = [rng.randrange(4) if rng.random() < 0.25
             else rng.randrange(4, n_accounts) for _ in range(n_accounts)]
    assert max(lanes.count(k) for k in range(4)) >= 2
    request = oracle.signed_request(
        rng, keys, lanes, rng.sample(range(n_accounts), n_accounts // 4))
    author = signers[0]
    digest = rng.randbytes(32)
    pks = [author.public_key.bytes] + request["public_keys"]
    digests = [digest] + request["digests"]
    sigs = [author.sign(digest)] + request["signatures"]
    before = {(d["kernel"], d["bucket"]): d["count"]
              for d in E.dispatch_counts()}
    got = E.dispatch_batch_table(table, pks, digests, sigs).result()
    assert [bool(b) for b in got] == [True] + request["expected"]
    assert 0 < sum(request["expected"]) < n_accounts
    launched = {k: d["count"] - before.get(k, 0) for d in E.dispatch_counts()
                for k in [(d["kernel"], d["bucket"])]}
    assert {k: n for k, n in launched.items() if n} == {("blob", 256): 1}
