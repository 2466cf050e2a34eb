"""Whole-node smoke tests over real localhost TCP (validator.rs:355-596 tier).

These run on the REAL asyncio loop (not the simulator): they exercise actual
sockets, frames, reconnects and the wal-sync thread.
"""
import asyncio
import os
import socket

import pytest

from mysticeti_tpu.cli import benchmark_genesis
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Identifier, Parameters, PrivateConfig
from mysticeti_tpu.validator import Validator


def _free_ports(n):
    socks = []
    ports = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def _setup(tmp_path, n):
    ports = _free_ports(2 * n)
    identifiers = [
        Identifier("127.0.0.1", ports[2 * i], ports[2 * i + 1]) for i in range(n)
    ]
    parameters = Parameters(identifiers=identifiers, leader_timeout_s=0.5)
    signers = Committee.benchmark_signers(n)
    from mysticeti_tpu.committee import Authority

    committee = Committee([Authority(1, s.public_key) for s in signers])
    privates = [
        PrivateConfig.new_in_dir(i, str(tmp_path / f"v{i}")) for i in range(n)
    ]
    return committee, parameters, signers, privates


async def _start_all(committee, parameters, signers, privates, n, verifier="accept"):
    return [
        await Validator.start_benchmarking(
            i,
            committee,
            parameters,
            privates[i],
            signer=signers[i],
            tps=20,
            serve_metrics_endpoint=(i == 0),
            verifier=verifier,
        )
        for i in range(n)
    ]


async def _wait_commits(validators, minimum, timeout_s):
    async def poll():
        while True:
            if all(len(v.committed_leaders()) >= minimum for v in validators):
                return
            await asyncio.sleep(0.2)

    await asyncio.wait_for(poll(), timeout=timeout_s)


@pytest.mark.parametrize(
    "kind, backend, aggregate",
    [
        ("accept", None, False),
        ("cpu", "CpuSignatureVerifier", False),
        ("tpu", "FallbackSignatureVerifier", False),
        ("tpu-only", "TpuSignatureVerifier", False),
        ("cpu-agg", "CpuSignatureVerifier", True),
        ("tpu-agg", "FallbackSignatureVerifier", True),
    ],
)
def test_make_verifier_kinds(monkeypatch, kind, backend, aggregate):
    """Every --verifier choice constructs and comes ready (regression: the
    wiring once referenced an unimported class and only failed at node
    boot).  ``tpu`` puts the circuit breaker in front of the accelerator,
    ``tpu-only`` is the accelerator alone; the "-agg" kinds turn on
    COLLECTOR-level aggregation (the flush window pools blocks from every
    peer connection, which is where quorum-capable batches form)."""
    from mysticeti_tpu import block_validator as bv
    from mysticeti_tpu.validator import _make_verifier

    # Warmup threads would trace/compile the kernel; wiring is what's tested.
    monkeypatch.setattr(bv.TpuSignatureVerifier, "warmup", lambda self: None)
    monkeypatch.delenv("MYSTICETI_VERIFIER_SOCKET", raising=False)
    # The collector's window and the pipeline's depth are no longer read
    # from the environment: a process started with the old names set runs
    # as if they were not.
    monkeypatch.setenv("MYSTICETI_VERIFY_WINDOW_MS", "50")
    monkeypatch.setenv("MYSTICETI_VERIFY_PIPELINE_DEPTH", "1")
    committee = Committee.new_for_benchmarks(4)

    v = _make_verifier(kind, committee)
    assert v.ready.wait(10) and v.warmup_error is None
    if backend is None:
        assert isinstance(v, bv.AcceptAllBlockVerifier)
        return
    assert isinstance(v, bv.BatchedSignatureVerifier)
    assert v.aggregate is aggregate
    assert v.max_delay_s == 0.005
    assert v.pipeline.depth() >= v.pipeline.MIN_DEPTH
    assert type(v.verifier) is getattr(bv, backend)
    wraps_breaker = kind.startswith("tpu") and kind != "tpu-only"
    assert hasattr(v.verifier, "breaker_open") is wraps_breaker
    if wraps_breaker:
        assert isinstance(v.verifier.tpu, bv.TpuSignatureVerifier)
        assert isinstance(v.verifier.cpu, bv.CpuSignatureVerifier)
        assert not v.verifier.breaker_open
        assert v.health_state()["breaker_open"] is False


def test_make_verifier_refuses_unknown_kind():
    from mysticeti_tpu.validator import _make_verifier

    with pytest.raises(ValueError):
        _make_verifier("gpu", Committee.new_for_benchmarks(4))


def test_tpu_flavor_accelerator_then_oracle_behind_the_breaker(monkeypatch):
    """What ``_make_verifier("tpu")`` deploys: a 1-signature batch goes to
    the accelerator; when the accelerator raises a transport error the
    oracle answers that batch, ``verifier_fallback_total`` moves by one and
    the breaker is open; a protocol error propagates and trips nothing."""
    from mysticeti_tpu import block_validator as bv
    from mysticeti_tpu.crypto import blake2b_256
    from mysticeti_tpu.metrics import Metrics
    from mysticeti_tpu.validator import _make_verifier
    from mysticeti_tpu.verify_pipeline import DeferredDispatch

    outcome = {"raises": None}
    accelerator_calls = []

    def accelerator(self, public_keys, digests, signatures):
        accelerator_calls.append(len(signatures))
        if outcome["raises"] is not None:
            raise outcome["raises"]
        return DeferredDispatch(
            bv.CpuSignatureVerifier().verify_signatures,
            public_keys, digests, signatures,
        )

    monkeypatch.setattr(bv.TpuSignatureVerifier, "warmup", lambda self: None)
    monkeypatch.setattr(
        bv.TpuSignatureVerifier, "verify_signatures_async", accelerator
    )
    monkeypatch.setattr(
        bv.TpuSignatureVerifier, "padded_batch", lambda self, n: 256
    )
    monkeypatch.delenv("MYSTICETI_VERIFIER_SOCKET", raising=False)
    metrics = Metrics()
    collector = _make_verifier(
        "tpu", Committee.new_for_benchmarks(4), metrics=metrics
    )
    assert collector.ready.wait(10)
    backend = collector.verifier
    signer = Committee.benchmark_signers(4)[0]
    digest = blake2b_256(b"one signature")
    batch = ([signer.public_key.bytes], [digest], [signer.sign(digest)])
    fallbacks = metrics.verifier_fallback_total._value.get

    assert backend.verify_signatures(*batch) == [True]
    assert accelerator_calls == [1] and fallbacks() == 0.0
    assert backend.backend_label == "hybrid-tpu"
    assert backend.dispatch_padded == 256

    outcome["raises"] = bv.VerifierProtocolError("committee mismatch")
    with pytest.raises(bv.VerifierProtocolError):
        backend.verify_signatures(*batch)
    assert not backend.breaker_open and fallbacks() == 0.0

    outcome["raises"] = ConnectionError("verifier service is down")
    assert backend.verify_signatures(*batch) == [True]  # the oracle's answer
    assert accelerator_calls == [1, 1, 1]
    assert backend.breaker_open and fallbacks() == 1.0
    assert backend.backend_label == "hybrid-cpu"
    assert collector.health_state()["breaker_open"] is True


def test_validator_commit(tmp_path):
    """4 validators over localhost TCP commit leaders (validator_commit)."""

    async def main():
        committee, parameters, signers, privates = _setup(tmp_path, 4)
        validators = await _start_all(committee, parameters, signers, privates, 4)
        try:
            await _wait_commits(validators, minimum=2, timeout_s=60)
        finally:
            for v in validators:
                await v.stop()

    asyncio.run(main())


def test_validator_sync_late_boot(tmp_path):
    """A late-booting node catches up through subscribe/sync (validator_sync)."""

    async def main():
        committee, parameters, signers, privates = _setup(tmp_path, 4)
        validators = await _start_all(committee, parameters, signers, privates, 3)
        try:
            await _wait_commits(validators, minimum=2, timeout_s=60)
            late = await Validator.start_benchmarking(
                3, committee, parameters, privates[3], signer=signers[3],
                tps=20, serve_metrics_endpoint=False,
            )
            validators.append(late)
            await _wait_commits([late], minimum=1, timeout_s=60)
        finally:
            for v in validators:
                await v.stop()

    asyncio.run(main())


def test_validator_crash_faults(tmp_path):
    """3 of 4 validators keep committing (validator_crash_faults)."""

    async def main():
        committee, parameters, signers, privates = _setup(tmp_path, 4)
        validators = await _start_all(committee, parameters, signers, privates, 3)
        try:
            await _wait_commits(validators, minimum=2, timeout_s=90)
        finally:
            for v in validators:
                await v.stop()

    asyncio.run(main())


def test_validator_metrics_endpoint(tmp_path):
    """The /metrics endpoint serves the benchmark-defining series."""

    async def main():
        committee, parameters, signers, privates = _setup(tmp_path, 4)
        validators = await _start_all(committee, parameters, signers, privates, 4)
        try:
            await _wait_commits(validators, minimum=1, timeout_s=60)
            host, port = parameters.metrics_address(0)
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            data = await asyncio.wait_for(reader.read(-1), timeout=10)
            writer.close()
            assert b"committed_leaders_total" in data
            assert b"benchmark_duration" in data
        finally:
            for v in validators:
                await v.stop()

    asyncio.run(main())


def test_benchmark_genesis_roundtrip(tmp_path):
    wd = str(tmp_path / "genesis")
    benchmark_genesis(["10.0.0.1", "10.0.0.2", "10.0.0.3"], wd)
    committee = Committee.load(os.path.join(wd, "committee.yaml"))
    parameters = Parameters.load(os.path.join(wd, "parameters.yaml"))
    assert len(committee) == 3
    assert len(parameters.identifiers) == 3
    assert parameters.identifiers[1].hostname == "10.0.0.2"
    assert os.path.exists(os.path.join(wd, "validator-0", "seed"))


def test_validator_shutdown_and_start(tmp_path):
    """Stop the whole committee, restart on the SAME ports with the SAME
    WALs, and commits must resume past the pre-restart point — catches port
    reuse and WAL-reopen-under-assembly bugs (validator_shutdown_and_start,
    validator.rs:~500-596)."""

    async def main():
        committee, parameters, signers, privates = _setup(tmp_path, 4)
        validators = await _start_all(committee, parameters, signers, privates, 4)
        try:
            await _wait_commits(validators, minimum=2, timeout_s=60)
        finally:
            for v in validators:
                await v.stop()
        before = min(len(v.committed_leaders()) for v in validators)
        assert before >= 2

        # Ports linger in TIME_WAIT; the server binds with SO_REUSEADDR, but
        # give the loop a beat to tear the old sockets down.
        await asyncio.sleep(0.5)

        restarted = await _start_all(committee, parameters, signers, privates, 4)
        try:
            # Recovery replays the WAL: committed history is intact and the
            # committee makes NEW progress beyond it.
            await _wait_commits(restarted, minimum=before + 2, timeout_s=60)
        finally:
            for v in restarted:
                await v.stop()

    asyncio.run(main())


def test_production_validators_commit_submitted_txs(tmp_path):
    """Production tier (validator.rs:165-212): SimpleBlockHandler ingestion,
    SimpleCommitObserver -> CommitConsumer delivery, submit-ack callbacks.
    The benchmarking smoke tests cover the fast-path node; this covers the
    application-facing assembly that nothing else drives."""
    n = 4
    committee, parameters, signers, privates = _setup(tmp_path, n)

    async def main():
        started = [
            await Validator.start_production(
                i,
                committee,
                parameters,
                privates[i],
                signer=signers[i],
                verifier="cpu",
            )
            for i in range(n)
        ]
        validators = [v for v, _, _ in started]
        handlers = [h for _, h, _ in started]
        consumers = [c for _, _, c in started]
        try:
            acked = []
            payloads = [f"tx-{i}".encode() for i in range(20)]
            for i, p in enumerate(payloads):
                handlers[i % n].submit(p, done=lambda p=p: acked.append(p))

            async def collect(consumer, want, timeout_s=60.0):
                got = set()
                loop = asyncio.get_event_loop()
                deadline = loop.time() + timeout_s
                while len(got) < len(want):
                    remaining = deadline - loop.time()
                    assert remaining > 0, f"only {len(got)}/{len(want)} delivered"
                    sub_dag = await asyncio.wait_for(
                        consumer.queue.get(), timeout=remaining
                    )
                    for block in sub_dag.blocks:
                        for _, tx in block.shared_transactions():
                            if tx in want:
                                got.add(tx)
                return got

            want = set(payloads)
            got = await collect(consumers[0], want)
            assert got == want
            # every node's consumer sees the same transactions
            got1 = await collect(consumers[1], want)
            assert got1 == want
            # submit-acks fired once each tx was drained into a proposal
            assert set(acked) == want
        finally:
            for v in validators:
                await v.stop()

    asyncio.run(main())


def test_production_replay_above_last_sent_height(tmp_path):
    """SimpleCommitObserver recovery (commit_observer.rs:232-260): a restarted
    node re-sends exactly the committed sub-dags above the consumer's
    last_sent_height."""
    n = 4
    committee, parameters, signers, privates = _setup(tmp_path, n)

    async def main():
        from mysticeti_tpu.validator import CommitConsumer

        started = [
            await Validator.start_production(
                i, committee, parameters, privates[i],
                signer=signers[i], verifier="accept",
            )
            for i in range(n)
        ]
        validators = [v for v, _, _ in started]
        consumers = [c for _, _, c in started]
        try:
            # run until some sub-dags are committed and delivered
            heights = []
            while len(heights) < 5:
                sub_dag = await asyncio.wait_for(consumers[0].queue.get(), 30.0)
                heights.append(sub_dag.height)
        finally:
            for v in validators:
                await v.stop()

        assert heights == sorted(heights)
        # Restart node 0 with a consumer that has seen up to heights[1]:
        # recovery must re-send heights above it, in order, before new ones.
        resumed = CommitConsumer(last_sent_height=heights[1])
        v0, _, consumer = await Validator.start_production(
            0, committee, parameters, privates[0],
            signer=signers[0], commit_consumer=resumed, verifier="accept",
        )
        try:
            replayed = []
            while len(replayed) < len(heights) - 2:
                sub_dag = await asyncio.wait_for(consumer.queue.get(), 30.0)
                replayed.append(sub_dag.height)
            assert replayed[: len(heights) - 2] == heights[2:]
        finally:
            await v0.stop()

    asyncio.run(main())
