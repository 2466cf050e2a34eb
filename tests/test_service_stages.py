"""The stage clock inside the verifier service (spans.StageClock): every
request's milliseconds by the program's own stages, always on, on the CPU.

Driven over the real unix socket with raw pipelined frames against a host
oracle, a backend that sleeps, and — for the profiler's view — the real
JAX backend on the CPU platform.
"""
import array
import asyncio
import gc
import json
import os
import socket
import struct
import threading
import time

import pytest

from mysticeti_tpu import crypto, spans
from mysticeti_tpu.block_validator import CpuSignatureVerifier, SignatureVerifier
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.verifier_service import (
    T_HELLO,
    T_HELLO_OK,
    T_RESULT,
    T_VERIFY,
    ServiceCounts,
    VerifierServer,
    report_path,
)

SIGNERS = [crypto.Signer.from_seed(i.to_bytes(32, "little")) for i in range(4)]
KEYS = [s.public_key.bytes for s in SIGNERS]
# A request's eight stages, header read to reply written.
PER_REQUEST = spans.SERVICE_STAGES[:8]


def _records(n, marker=0):
    """``n`` (key index, digest, signature) wire records, all valid; the
    first digest byte of the first record is ``marker``-dependent so that a
    backend can tell requests apart."""
    out = []
    for i in range(n):
        digest = crypto.blake2b_256(b"m%d-%d" % (marker, i))
        out.append(struct.pack("<H", i % 4) + digest
                   + SIGNERS[i % 4].sign(digest))
    return b"".join(out)


def _verify_frame(req_id, n, body):
    return struct.pack("<IBII", 8 + len(body), T_VERIFY, req_id, n) + body


def _read_frame(sock):
    head = b""
    while len(head) < 5:
        chunk = sock.recv(5 - len(head))
        assert chunk, "service closed the connection"
        head += chunk
    length, type_ = struct.unpack("<IB", head)
    payload = b""
    while len(payload) < length:
        payload += sock.recv(length - len(payload))
    return type_, payload


def _connect(path):
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(30.0)
    sock.connect(path)
    sock.sendall(struct.pack("<IBH", 2 + 32 * len(KEYS), T_HELLO, len(KEYS))
                 + b"".join(KEYS))
    type_, _ = _read_frame(sock)
    assert type_ == T_HELLO_OK
    return sock


def _pipelined(path, frames):
    """Send every frame of ``frames`` on one connection before reading any
    reply; returns the verdict bytes in order."""
    sock = _connect(path)
    try:
        sock.sendall(b"".join(frames))
        out = []
        for _ in frames:
            type_, payload = _read_frame(sock)
            assert type_ == T_RESULT
            out.append(payload[4:])
        return out
    finally:
        sock.close()


async def _serve(tmp_path, backend, fn, metrics=None, tracer=None,
                 every_request=True):
    """``every_request``: clock each request through its stages; off, one
    in ``spans.SAMPLE_ONE_IN`` as the service does, whoever listens."""
    server = VerifierServer(str(tmp_path / "v.sock"), committee_keys=KEYS,
                            backend=backend, metrics=metrics)
    assert server.stages.sample_one_in == spans.SAMPLE_ONE_IN == 32
    if every_request:
        server.stages.sample_one_in = 1
    server.stages.tracer = tracer
    await server.start()
    try:
        return await fn(server)
    finally:
        await server.stop()


def _totals(server):
    """The clock's totals, and what the service's own counts say was
    answered, clocked or not."""
    return dict(server.stages.totals(), answered=server.counts.requests)


def _service_clock(**kwargs):
    """A clock as the service makes one, and the counts it stamps."""
    counts = ServiceCounts()
    clock = spans.StageClock(
        spans.SERVICE_STAGES, stamps=ServiceCounts.STAMPS,
        read_stamps=counts.read, lag_stage="service_loop_lag",
        gc_stage="service_gc", **kwargs)
    return clock, counts


def _ring_sums(report):
    """{stage: [count, wall_s, cpu_s, max_wall_s]} and requests /
    signatures over every second of a report's ring."""
    sums, requests, signatures = {}, 0, 0
    for entry in report["seconds"].values():
        requests += entry.get("requests", 0)
        signatures += entry.get("signatures", 0)
        for stage, cell in entry.items():
            if isinstance(cell, list):
                into = sums.setdefault(stage, [0, 0.0, 0.0, 0.0])
                for column in (0, 1, 2):
                    into[column] += cell[column]
                into[3] = max(into[3], cell[3])
    return sums, requests, signatures


class SleepingBackend(SignatureVerifier):
    """Accepts everything, after the longest sleep that a digest of the
    call selects (a call may hold several requests), else ``default``."""

    def __init__(self, sleep_by_digest=None, default=0.0):
        self.sleep_by_digest = sleep_by_digest or {}
        self.default = default
        self.calls = -2  # of the launches: the service calibrates with two

    def verify_signatures(self, public_keys, digests, signatures):
        self.calls += 1
        time.sleep(max(
            (self.sleep_by_digest.get(bytes(d), self.default)
             for d in digests), default=0.0))
        return [True] * len(signatures)


def _first_digest(marker):
    """The digest ``_records(n, marker)`` puts first."""
    return crypto.blake2b_256(b"m%d-0" % marker)


class ReportingBackend(CpuSignatureVerifier):
    """A host oracle that leaves a device report, as the JAX backend does
    (host oracles leave none, and then the service writes no file)."""

    def device_report(self):
        return {"platform": "cpu", "device_kind": "stub", "device_count": 1}


def test_every_stage_counts_every_request_and_their_walls_tile_it(tmp_path):
    """Three pipelined connections against the host oracle: each of a
    request's eight stages is booked exactly once a request, and per
    request they add up to (reply written - header read) within a tenth."""
    tracer = spans.SpanTracer()
    per_conn, n_conns = 6, 3

    async def scenario(server):
        frames = [_verify_frame(i + 1, 5, _records(5, i))
                  for i in range(per_conn)]
        replies = await asyncio.gather(*(
            asyncio.to_thread(_pipelined, server.socket_path, frames)
            for _ in range(n_conns)
        ))
        for conn in replies:
            assert conn == [bytes([1] * 5)] * per_conn
        return server.stages.export(), _totals(server)

    report, totals = asyncio.run(
        _serve(tmp_path, CpuSignatureVerifier(), scenario, tracer=tracer))
    sent = per_conn * n_conns
    sums, requests, signatures = _ring_sums(report)
    assert (requests, signatures) == (sent, 5 * sent)
    for stage in PER_REQUEST:
        assert sums[stage][0] == sent, stage
        assert totals[stage]["count"] == sent, stage
        assert sum(totals[stage]["buckets"]) == sent, stage
    # A host oracle packs and launches nothing and works in the fetch.
    assert sums["service_pack"][1] == sums["service_launch"][1] == 0.0
    assert sums["service_fetch"][1] > 0.0
    assert totals["answered"] == sent
    for stage in PER_REQUEST:
        # CPU <= wall, but for the reads of the two clocks not being one
        # instant (no sample's CPU is held to its wall).
        assert sums[stage][2] <= sums[stage][1] + sent * 5e-5, stage
        if stage in spans.WAITING_STAGES:
            assert sums[stage][2] == 0.0, stage
    # Per request, from its spans: keyed "<connection>#<req_id>".
    by_request = {}
    for event in tracer.chrome_trace()["traceEvents"]:
        if event.get("ph") == "X":
            by_request.setdefault(event["args"]["block"], []).append(event)
    assert len(by_request) == sent
    for label, events in by_request.items():
        assert {e["name"] for e in events} == {
            s for s in PER_REQUEST
            if s not in ("service_pack", "service_launch")}, label
        first = min(e["ts"] for e in events)
        last = max(e["ts"] + e["dur"] for e in events)
        tiled = sum(e["dur"] for e in events)
        assert abs(tiled - (last - first)) <= 0.1 * (last - first) + 50, (
            label, tiled, last - first)  # microseconds


def test_pool_wait_is_the_wait_for_a_launch_slot(tmp_path):
    """A request waits in service_pool_wait from its hand-over until a
    launch takes it: not at all while a dispatcher thread sleeps, and for
    the launches in its way once every slot is busy - and then whatever
    queued meanwhile shares the next launch."""
    slots = VerifierServer.DISPATCHERS
    nap = 0.4

    def run(late_conns, name):
        backend = SleepingBackend(default=nap)

        async def scenario(server):
            (await asyncio.to_thread(_connect, server.socket_path)).close()
            one = [_verify_frame(1, 1, _records(1))]
            four = [_verify_frame(i + 1, 1, _records(1, i)) for i in range(4)]
            clients = []
            for _ in range(slots):  # one request a slot: each goes alone
                clients.append(asyncio.ensure_future(asyncio.to_thread(
                    _pipelined, server.socket_path, one)))
                await asyncio.sleep(0.05)
            clients += [  # every slot is busy for another 0.3 s
                asyncio.ensure_future(asyncio.to_thread(
                    _pipelined, server.socket_path, four))
                for _ in range(late_conns)]
            await asyncio.gather(*clients)
            return _ring_sums(server.stages.export())[0]

        path = tmp_path / name
        path.mkdir()
        return asyncio.run(_serve(path, backend, scenario)), backend.calls

    few, few_calls = run(0, "few")
    many, many_calls = run(10, "many")
    assert few_calls == slots
    assert few["service_pool_wait"][3] < 0.05
    assert many["service_pool_wait"][0] == slots + 40
    assert many["service_pool_wait"][3] > nap / 4
    assert many["service_pool_wait"][1] > 40 * nap / 8
    # Forty requests queued behind the busy slots: the first slot to come
    # free took all that were there by then (40 signatures are well under
    # a launch's cap), the stragglers rode the next one or two.
    assert slots + 1 <= many_calls <= slots + 3
    # The sleep itself is the fetch's, whole for every request of a launch.
    assert few["service_fetch"][1] >= slots * nap
    assert many["service_fetch"][1] >= (slots + 40) * nap


def test_reply_wait_is_the_line_behind_a_slow_request(tmp_path):
    """Replies leave in request order: a fast request behind a slow one of
    its connection waits for it, one on another connection does not.  The
    fast one is sent once the slow one's launch has left, so that it rides
    a launch of its own."""
    tracer = spans.SpanTracer()
    slow = 0.4

    def behind(path):
        sock = _connect(path)
        try:
            sock.sendall(_verify_frame(1, 2, _records(2, 1)))  # sleeps
            time.sleep(0.1)
            sock.sendall(_verify_frame(2, 1, _records(1, 2)))  # fast
            for req_id in (1, 2):  # in request order
                type_, payload = _read_frame(sock)
                assert type_ == T_RESULT
                assert struct.unpack("<I", payload[:4])[0] == req_id
        finally:
            sock.close()

    async def scenario(server):
        line = asyncio.to_thread(behind, server.socket_path)
        await asyncio.sleep(0.05)
        alone = asyncio.to_thread(_pipelined, server.socket_path, [
            _verify_frame(7, 1, _records(1, 3)),  # fast, its own connection
        ])
        await asyncio.gather(line, alone)

    asyncio.run(_serve(tmp_path, SleepingBackend({_first_digest(1): slow}),
                       scenario, tracer=tracer))
    waits = {}
    for event in tracer.chrome_trace()["traceEvents"]:
        if event.get("name") == "service_reply_wait":
            waits[event["args"]["block"].split("#")[1]] = event["dur"] / 1e6
    assert waits["2"] > slow - 0.2      # head of line behind request 1
    assert waits["1"] < 0.05            # the slow one itself: written at once
    assert waits["7"] < 0.05            # another connection: no line


def test_a_collection_shows_in_service_gc(tmp_path):
    async def scenario(server):
        gc.callbacks.append(server.stages.gc_callback)
        try:
            gc.collect()
        finally:
            gc.callbacks.remove(server.stages.gc_callback)
        return server.stages.export(), _totals(server)

    report, totals = asyncio.run(
        _serve(tmp_path, CpuSignatureVerifier(), scenario))
    assert totals["service_gc"]["count"] >= 1
    assert totals["service_gc"]["wall_s"] > 0.0
    assert totals["service_gc"]["cpu_s"] == totals["service_gc"]["wall_s"]
    assert report["gc_generations"]["2"][0] >= 1
    assert _ring_sums(report)[0]["service_gc"][0] >= 1


def test_the_report_ring_sums_to_the_scraped_series(tmp_path):
    """With a backend that leaves a device report the service writes
    ``stages`` into it at stop; over its seconds the ring holds what
    ``/metrics`` shows as the histogram's _sum and _count, and the CPU
    counter."""
    from prometheus_client import generate_latest

    from benchmark import harness

    metrics = Metrics()
    sock = tmp_path / "v.sock"

    async def scenario(server):
        server._warm_seconds = 0.0  # an injected backend is born warm
        frames = [_verify_frame(i + 1, 3, _records(3, i)) for i in range(8)]
        await asyncio.to_thread(_pipelined, server.socket_path, frames)

    asyncio.run(_serve(tmp_path, ReportingBackend(), scenario,
                       metrics=metrics))
    with open(report_path(str(sock))) as f:
        stages = json.load(f)["stages"]
    assert stages["clock"] == "time.monotonic"
    assert stages["columns"] == ["count", "wall_s", "cpu_s", "max_wall_s"]
    assert stages["sample_one_in"] == 1
    sums, requests, signatures = _ring_sums(stages)
    assert (requests, signatures) == (8, 24)
    # What a second's stamp adds: the CPU the process, the threads that
    # book (the loop and the pool) and the loop alone used in it.
    stamped = [second for second in stages["seconds"].values()
               if "requests" in second]
    assert stamped
    process = sum(second["process_cpu_s"] for second in stamped)
    threads = sum(second["threads_cpu_s"] for second in stamped)
    loop = sum(second["loop_cpu_s"] for second in stamped)
    # (The loop's own clock counts in ``threads`` from its first sample on,
    # in ``loop`` from the clock's making: no order between the two here.)
    assert process >= threads - 1e-3 and threads > 0.0 and loop > 0.0
    series = harness.parse_metrics(generate_latest(metrics.registry).decode())
    for stage in PER_REQUEST:
        count = harness.series_sum(
            series, "verifier_service_stage_seconds_count", stage=stage)
        total = harness.series_sum(
            series, "verifier_service_stage_seconds_sum", stage=stage)
        assert count == sums[stage][0] == 8, stage
        assert total == pytest.approx(sums[stage][1], rel=1e-9, abs=1e-12)
    for stage in ("service_decode", "service_unpack", "service_reply_build"):
        cpu = harness.series_sum(
            series, "verifier_service_stage_cpu_seconds_total", stage=stage)
        assert cpu == pytest.approx(sums[stage][2], rel=1e-9, abs=1e-12)
        assert cpu > 0.0
    # Waiting stages have no CPU series.
    assert not [s for s in series
                if s[0] == "verifier_service_stage_cpu_seconds_total"
                and s[1].get("stage") == "service_fetch"]
    # The reads that held a request and the writes that held a reply.
    for direction, stamp in (("read", "reads"), ("write", "writes")):
        calls = harness.series_sum(
            series, "verifier_service_io_calls_total", direction=direction)
        assert calls == sum(second[stamp] for second in stamped)
        assert 1 <= calls <= 8


def test_a_host_oracle_leaves_no_report(tmp_path):
    async def scenario(server):
        await asyncio.to_thread(
            _pipelined, server.socket_path,
            [_verify_frame(1, 1, _records(1))])

    asyncio.run(_serve(tmp_path, CpuSignatureVerifier(), scenario))
    assert not os.path.exists(report_path(str(tmp_path / "v.sock")))


def test_sixteen_threads_lose_no_sample():
    clock = spans.StageClock(spans.SERVICE_STAGES, ring_seconds=600)
    per_thread, n_threads = 5000, 16
    start = threading.Barrier(n_threads)

    def hammer(k):
        start.wait()
        for i in range(per_thread):
            clock.book(spans.SERVICE_STAGES[(k + i) % 8],
                       1000.0 + (i % 3), 0.001, 0.0005)

    threads = [threading.Thread(target=hammer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = per_thread * n_threads
    totals = clock.totals()
    assert sum(row["count"] for row in totals.values()) == total
    assert sum(sum(row["buckets"]) for row in totals.values()) == total
    sums = _ring_sums(clock.export())[0]
    assert sum(cell[0] for cell in sums.values()) == total
    assert sum(cell[1] for cell in sums.values()) == pytest.approx(
        0.001 * total)
    assert sum(cell[2] for cell in sums.values()) == pytest.approx(
        0.0005 * total)


def test_a_seconds_stamp_holds_what_was_answered_and_the_cpu_used():
    """Once a whole second the clock reads what its owner answered and the
    CPU clocks - the process's, every adopted thread's (from outside that
    thread) and its own: a second's entry holds the growth to the next
    stamp.  A thread that burns shows, the stamping thread that sleeps
    does not, and no reading is held to another."""
    clock, counts = _service_clock(ring_seconds=600)
    stop = threading.Event()
    adopted = threading.Event()

    def burn():
        clock.adopt_thread()
        adopted.set()
        while not stop.is_set():
            sum(range(2000))

    burner = threading.Thread(target=burn)
    burner.start()
    adopted.wait()
    base = int(time.monotonic()) + 10  # seconds of its own, later than now
    counts.requests, counts.signatures = 5, 9
    clock.stamp(base + 0.0)
    time.sleep(0.4)
    counts.requests, counts.signatures = 12, 30
    clock.stamp(base + 1.2)
    counts.requests = 99
    clock.stamp(base + 1.7)  # the same second: not read again
    stop.set()
    burner.join()
    counts.requests, counts.signatures = 20, 31
    seconds = clock.export()["seconds"]  # the burner has ended: its last
    first, second = seconds[str(base)], seconds[str(base + 1)]
    assert (first["requests"], first["signatures"]) == (7, 21)
    assert (second["requests"], second["signatures"]) == (8, 1)
    assert first["threads_cpu_s"] > 0.2
    assert first["process_cpu_s"] >= first["threads_cpu_s"] - 0.02
    assert first["loop_cpu_s"] < 0.1
    assert first["threads_cpu_s"] >= first["loop_cpu_s"]
    assert "service_decode" not in first  # no stage was booked


def test_the_dispatcher_threads_are_adopted_at_birth(tmp_path):
    """Every dispatcher thread's CPU counts from its start on, whether or
    not a clocked request ever rides one of its launches (one request in
    32 is clocked): each adopts itself before its first launch."""
    async def scenario(server):
        frames = [_verify_frame(i + 1, 1, _records(1, i)) for i in range(4)]
        await asyncio.gather(*(
            asyncio.to_thread(_pipelined, server.socket_path, frames)
            for _ in range(3)))
        return (len(server.stages._thread_clocks),
                [thread.is_alive() for thread in server._dispatchers])

    clocks, alive = asyncio.run(_serve(
        tmp_path, SleepingBackend(default=0.05), scenario,
        every_request=False))
    assert alive == [True] * VerifierServer.DISPATCHERS
    assert clocks == len(alive) + 1  # and the loop's own


def test_transfer_bytes_from_many_threads_lose_nothing(monkeypatch):
    """Each thread sums what it moved and puts it into the registry itself:
    no sum is shared, so none is lost."""
    from mysticeti_tpu.ops import ed25519 as E

    metrics = Metrics()
    monkeypatch.setattr(E, "_attr_metrics", metrics)
    monkeypatch.setattr(E, "_TRANSFER_FLUSH_S", 0.005)
    per_thread, n_threads = 4000, 8
    start = threading.Barrier(n_threads)

    def hammer():
        start.wait()
        for i in range(per_thread):
            E._note_transfer("to_device" if i % 2 else "from_device", 3)
        time.sleep(0.01)
        E._note_transfer("to_device", 1)  # moves what is left

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    counter = metrics.mysticeti_device_transfer_bytes_total
    assert counter.labels("to_device")._value.get() == n_threads * (
        3 * per_thread // 2 + 1)
    assert counter.labels("from_device")._value.get() == n_threads * (
        3 * per_thread // 2)


def test_only_a_backend_compile_counts_as_a_compile(monkeypatch):
    """JAX reports every trace of every jitted function under
    ``/jax/core/compile/`` too (32,000 for one ladder): the compile series,
    which count in the service since it wires them, count programs."""
    from mysticeti_tpu.ops import ed25519 as E

    metrics = Metrics()
    monkeypatch.setattr(E, "_attr_metrics", metrics)
    monkeypatch.setitem(E.COMPILE_STATS, "backend_compile_s", 0.0)
    for _ in range(50):
        E._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.01)
    E._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 4.0)
    E._on_duration("/jax/core/compile/backend_compile_duration", 0.25)
    assert metrics.mysticeti_jax_compiles_total._value.get() == 1
    assert metrics.mysticeti_jax_compile_seconds_total._value.get() == 0.25
    assert E.COMPILE_STATS["backend_compile_s"] == 0.25


def test_the_ring_forgets_after_its_seconds():
    clock = spans.StageClock(("service_decode",), ring_seconds=4)
    for second in range(10):
        clock.book("service_decode", second + 0.5, 0.25)
    clock.book("service_decode", 3.5, 0.25)  # long overwritten: dropped
    report = clock.export()
    assert [second for second, entry in report["seconds"].items()
            if "service_decode" in entry] == ["6", "7", "8", "9"]
    assert clock.totals()["service_decode"]["count"] == 11


def test_no_object_outlives_its_request(tmp_path):
    """A thread's ring is one preallocated array, and 10,000 requests leave
    the interpreter with no more live objects than it had after warm-up."""

    class Null(SignatureVerifier):
        def verify_signatures(self, public_keys, digests, signatures):
            return [True] * len(signatures)

    async def scenario(server):
        books = server.stages._books()  # a thread's own, made once
        assert isinstance(books.ring, array.array)
        assert isinstance(books.totals, array.array)
        assert server.stages._books() is books
        frames = [_verify_frame(i + 1, 2, _records(2)) for i in range(8)]
        sock = await asyncio.to_thread(_connect, server.socket_path)
        blob = b"".join(frames)

        def rounds(n):
            for _ in range(n):
                sock.sendall(blob)
                for _ in frames:
                    assert _read_frame(sock)[0] == T_RESULT

        await asyncio.to_thread(rounds, 100)  # warm-up: 800 requests
        gc.collect()
        before = len(gc.get_objects())
        await asyncio.to_thread(rounds, 1250)  # 10,000 requests
        gc.collect()
        after = len(gc.get_objects())
        sock.close()
        return before, after, _totals(server)

    before, after, totals = asyncio.run(
        _serve(tmp_path, Null(), scenario, every_request=False))
    assert totals["answered"] == 10_800
    assert totals["service_reply_wait"]["count"] == -(-10_800 // 32)
    assert after - before < 200, (before, after)


def test_one_request_in_thirty_two_is_clocked_and_all_are_counted(tmp_path):
    """As the service runs, with a tracer or without: the first request and
    every thirty-second after it are clocked whole, header read to reply
    written, and only those leave spans; what was answered is counted for
    every request, and the scraped CPU counter is the clocked requests'
    times 32."""
    from prometheus_client import generate_latest

    from benchmark import harness

    metrics = Metrics()

    tracer = spans.SpanTracer()

    async def scenario(server):
        frames = [_verify_frame(i + 1, 2, _records(2, i)) for i in range(8)]
        for _ in range(10):  # 80 requests, in the order they were sent
            await asyncio.to_thread(_pipelined, server.socket_path, frames)
        return server.stages.export(), _totals(server)

    report, totals = asyncio.run(_serve(
        tmp_path, CpuSignatureVerifier(), scenario, metrics=metrics,
        tracer=tracer, every_request=False))
    assert len({e["args"]["block"]
                for e in tracer.chrome_trace()["traceEvents"]
                if e.get("ph") == "X"}) == 3
    sums, requests, signatures = _ring_sums(report)
    assert report["sample_one_in"] == 32
    assert (requests, signatures, totals["answered"]) == (80, 160, 80)
    for stage in PER_REQUEST:  # requests 0, 32 and 64
        assert sums[stage][0] == totals[stage]["count"] == 3, stage
    series = harness.parse_metrics(generate_latest(metrics.registry).decode())
    cpu = harness.series_sum(
        series, "verifier_service_stage_cpu_seconds_total",
        stage="service_unpack")
    assert cpu == pytest.approx(sums["service_unpack"][2] * 32)
    assert harness.series_sum(
        series, "verifier_service_stage_seconds_count",
        stage="service_unpack") == 3


def test_request_stage_outside_a_launch_does_nothing():
    spans.request_stage("service_pack")  # no frame on this thread
    clock = spans.StageClock(spans.SERVICE_STAGES)
    clock.begin_launch([(("c0", 1), time.monotonic())])
    spans.request_stage("service_pack")
    spans.request_stage("service_pack")  # the same stage: one occurrence
    spans.request_stage("service_launch")
    clock.end_launch(1)
    spans.request_stage("service_fetch")  # the launch is over
    totals = clock.totals()
    for stage in spans.REQUEST_STAGES:
        assert totals[stage]["count"] == 1, stage
    assert totals["service_decode"]["count"] == 0
    assert totals["service_fetch"]["wall_s"] == 0.0


def test_a_fetch_is_said_once_to_who_listens_on_the_thread_clocked_or_not():
    """``request_fetch`` tells the thread's listener once, with no clocked
    request on the launch too, and never another thread's; with a frame it
    also names ``service_fetch``; with nobody listening it is
    ``request_stage("service_fetch")``."""
    heard = []
    spans.request_fetch()  # nobody listens, no frame: nothing
    spans.on_fetch(lambda: heard.append("first"))
    other = threading.Thread(target=spans.request_fetch)
    other.start()
    other.join(5)
    assert heard == []  # another thread's launch is not this one's
    spans.request_fetch()
    spans.request_fetch()  # the launch said it already
    assert heard == ["first"]
    spans.on_fetch(lambda: heard.append("never"))
    spans.on_fetch(None)  # the launch ended without saying
    spans.request_fetch()
    assert heard == ["first"]
    clock = spans.StageClock(spans.SERVICE_STAGES)
    clock.begin_launch([(("c0", 1), time.monotonic())])
    spans.request_stage("service_launch")
    spans.on_fetch(lambda: heard.append("second"))
    spans.request_fetch()
    time.sleep(0.002)
    clock.end_launch(1)
    assert heard == ["first", "second"]
    assert clock.totals()["service_fetch"]["wall_s"] >= 0.002


def _burn(seconds):
    """Use the calling thread's CPU for ``seconds`` of its own clock."""
    until = time.thread_time() + seconds
    while time.thread_time() < until:
        sum(range(500))


@pytest.mark.parametrize("riders", [1, 2, 5])
def test_a_clocked_request_books_its_launch_wall_whole_and_cpu_shared(riders):
    """Two clocked requests ride a launch of ``riders``: each books every
    REQUEST_STAGES stage exactly once - its own wait for the launch, then
    the launch's stages with their wall whole and their CPU divided by the
    requests the launch carried, so that CPU a clocked request times the
    request rate is still cores."""
    tracer = spans.SpanTracer()
    clock = spans.StageClock(spans.SERVICE_STAGES, ring_seconds=8,
                             tracer=tracer)
    t0 = time.monotonic()
    members = [(("c0", 1), t0 - 0.30), (("c1", 9), t0 - 0.10)]
    clock.begin_launch(members)
    # The wall between two boundaries, as this thread saw it: on a busy
    # host a burn of 0.02 s of CPU takes what the scheduler gives it.
    marks = []
    spans.request_stage("service_unpack")
    marks.append(time.monotonic())
    _burn(0.02)
    spans.request_stage("service_pack")
    marks.append(time.monotonic())
    _burn(0.04)
    spans.request_stage("service_fetch")
    marks.append(time.monotonic())
    time.sleep(0.05)
    spans.request_stage("service_reply_build")
    marks.append(time.monotonic())
    done = clock.end_launch(riders)
    unpack, pack, fetch = (b - a for a, b in zip(marks, marks[1:]))
    assert unpack >= 0.02 and pack >= 0.04 and fetch >= 0.05
    totals = clock.totals()
    for stage in spans.REQUEST_STAGES:
        assert totals[stage]["count"] == 2, stage
    # Each waited from ITS hand-over until the launch took both.
    assert totals["service_pool_wait"]["wall_s"] == pytest.approx(
        0.40, abs=0.02)
    assert totals["service_pool_wait"]["cpu_s"] == 0.0
    # Wall whole: twice the launch's, whatever it carried.
    assert totals["service_unpack"]["wall_s"] == pytest.approx(
        2 * unpack, abs=0.005)
    assert totals["service_pack"]["wall_s"] == pytest.approx(
        2 * pack, abs=0.005)
    assert totals["service_fetch"]["wall_s"] == pytest.approx(
        2 * fetch, abs=0.005)
    assert totals["service_launch"]["wall_s"] == 0.0  # never entered
    # CPU divided by the riders, clocked or not.
    assert totals["service_unpack"]["cpu_s"] == pytest.approx(
        2 * 0.02 / riders, rel=0.35)
    assert totals["service_pack"]["cpu_s"] == pytest.approx(
        2 * 0.04 / riders, rel=0.35)
    assert totals["service_fetch"]["cpu_s"] == 0.0  # a wait
    assert done >= t0 + 0.11
    # Both requests have the launch's spans under their own labels, and
    # their stages tile (hand-over -> replies built).
    by_request = {}
    for event in tracer.chrome_trace()["traceEvents"]:
        if event.get("ph") == "X":
            by_request.setdefault(event["args"]["block"], []).append(event)
    assert sorted(by_request) == ["c0#1", "c1#9"]
    for label, events in by_request.items():
        assert sorted(e["name"] for e in events) == sorted(
            s for s in spans.REQUEST_STAGES if s != "service_launch"), label
        first = min(e["ts"] for e in events)
        last = max(e["ts"] + e["dur"] for e in events)
        assert sum(e["dur"] for e in events) == pytest.approx(
            last - first, rel=0.01)
    # The next launch of this thread starts from nothing.
    clock.begin_launch([(("c0", 2), time.monotonic())])
    clock.end_launch(1)
    again = clock.totals()
    assert again["service_pack"]["count"] == 3
    assert again["service_pack"]["wall_s"] == totals["service_pack"]["wall_s"]


def test_a_seconds_stamp_counts_the_launches():
    """``launches`` rides the ring's stamp beside ``requests`` and
    ``signatures``: a whole number a second, the growth to the next."""
    clock, counts = _service_clock(ring_seconds=600)
    assert clock.stamp_names[:3] == ("requests", "signatures", "launches")
    base = int(time.monotonic()) + 10
    counts.requests, counts.signatures, counts.launches = 40, 320, 4
    clock.stamp(base + 0.0)
    counts.requests, counts.signatures, counts.launches = 100, 800, 9
    clock.stamp(base + 1.0)
    counts.launches = 10
    seconds = clock.export()["seconds"]
    assert seconds[str(base)]["launches"] == 5
    assert seconds[str(base + 1)]["launches"] == 1
    assert isinstance(seconds[str(base)]["launches"], int)


def test_a_seconds_stamp_counts_the_reads_and_the_writes():
    """``reads`` and ``writes`` ride the ring's stamp too: with
    ``requests`` they say how many frames a socket read and how many
    replies a write carried in that second."""
    clock, counts = _service_clock(ring_seconds=600)
    assert {"reads", "writes"} < set(clock.stamp_names)
    base = int(time.monotonic()) + 10
    counts.requests, counts.reads, counts.writes = 40, 30, 10
    clock.stamp(base + 0.0)
    counts.requests, counts.reads, counts.writes = 100, 50, 22
    clock.stamp(base + 1.0)
    counts.reads = 51
    seconds = clock.export()["seconds"]
    first, second = seconds[str(base)], seconds[str(base + 1)]
    assert (first["requests"], first["reads"], first["writes"]) == (60, 20, 12)
    assert (second["reads"], second["writes"]) == (1, 0)
    assert isinstance(first["reads"], int) and isinstance(first["writes"], int)


def test_one_launch_that_answers_a_connection_writes_to_it_once(tmp_path):
    """Over the socket: ten requests of one connection, pipelined; each of
    the first three wakes a launch slot and goes alone, the seven behind
    them ride one launch and their replies one write - so the ring reads
    ``requests / writes`` > 1, and ``requests / reads`` > 1 where a read
    held several frames.  Every request still books ``service_decode`` and
    ``service_reply_wait`` once."""
    backend = SleepingBackend(default=0.1)

    async def scenario(server):
        server.PIPELINE_DEPTH = 16  # (of this server alone)
        frames = [_verify_frame(i + 1, 2, _records(2, i)) for i in range(10)]
        await asyncio.to_thread(_pipelined, server.socket_path, frames)
        return server.stages.export(), server.counts.launches

    report, launches = asyncio.run(_serve(tmp_path, backend, scenario))
    sums, requests, _ = _ring_sums(report)
    seconds = report["seconds"].values()
    reads = sum(entry.get("reads", 0) for entry in seconds)
    writes = sum(entry.get("writes", 0) for entry in seconds)
    assert requests == 10 and launches == backend.calls
    assert 1 <= writes <= launches < requests
    assert 1 <= reads < requests
    assert sums["service_decode"][0] == sums["service_reply_wait"][0] == 10


def test_the_service_counts_launches_and_shares_cpu(tmp_path):
    """Over the socket: forty requests behind two busy slots ride a few
    launches; the ring's seconds carry how many, every clocked request
    booked every stage once, and the scraped histogram of requests a
    launch sums to the requests."""
    from prometheus_client import generate_latest

    from benchmark import harness

    metrics = Metrics()
    backend = SleepingBackend(default=0.05)

    async def scenario(server):
        frames = [_verify_frame(i + 1, 2, _records(2, i)) for i in range(4)]
        await asyncio.gather(*(
            asyncio.to_thread(_pipelined, server.socket_path, frames)
            for _ in range(10)))
        return server.stages.export(), server.counts.launches

    report, launches = asyncio.run(
        _serve(tmp_path, backend, scenario, metrics=metrics))
    sums, requests, signatures = _ring_sums(report)
    assert (requests, signatures) == (40, 80)
    assert launches == backend.calls < 40
    assert sum(entry.get("launches", 0)
               for entry in report["seconds"].values()) == launches
    for stage in PER_REQUEST:
        assert sums[stage][0] == 40, stage
    series = harness.parse_metrics(generate_latest(metrics.registry).decode())
    assert harness.series_sum(
        series, "verifier_service_coalesced_requests_count") == launches
    assert harness.series_sum(
        series, "verifier_service_coalesced_requests_sum") == 40
    assert harness.series_sum(
        series, "verify_dispatch_batch_size_sum") == 80
    assert harness.series_sum(
        series, "verify_dispatch_batch_size_count") == launches


def test_the_profiler_sees_flat_pack_and_launch(tmp_path):
    """With JAX (on the CPU) a profiler session around one clocked dispatch
    of the real backend holds host events named service_pack and
    service_launch, and no stage's annotation lies inside another's.  A
    clocked request is annotated whether or not a profile is taken, and a
    profile changes nothing about which requests are clocked."""
    import jax
    from jax.profiler import ProfileData

    from mysticeti_tpu.ops import ed25519 as E

    table = E.KeyTable(KEYS)
    digests = [crypto.blake2b_256(b"d%d" % i) for i in range(3)]
    sigs = [SIGNERS[i].sign(digests[i]) for i in range(3)]
    pks = KEYS[:3]
    assert list(E.verify_batch_table(table, pks, digests, sigs)) == [True] * 3
    assert spans._annotation("service_pack") is not None
    clock = spans.StageClock(spans.SERVICE_STAGES, sample_one_in=3)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    assert [clock.sampled() for _ in range(3)] == [True, False, False]
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert [clock.sampled() for _ in range(3)] == [True, False, False]
        clock.begin_launch([(("c0", 1), time.monotonic())])
        spans.request_stage("service_unpack")
        handle = E.dispatch_batch_table(table, pks, digests, sigs)
        assert list(handle.result()) == [True] * 3  # names the fetch itself
        spans.request_stage("service_reply_build")
        clock.end_launch(1)
        with spans.stage("service_decode", clock):
            pass
        # A request that is not clocked leaves no event.
        assert list(E.verify_batch_table(table, pks, digests, sigs)) == [
            True] * 3
    finally:
        jax.profiler.stop_trace()
    files = [os.path.join(root, name)
             for root, _, names in os.walk(tmp_path)
             for name in names if name.endswith(".xplane.pb")]
    assert files
    stages = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("service_"):
                    stages.append((line.name, event.start_ns,
                                   event.start_ns + event.duration_ns,
                                   event.name))
    assert sorted(s[3] for s in stages) == [
        "service_decode", "service_launch", "service_pack",
        "service_reply_build", "service_unpack",
    ]  # one request's, once each; service_fetch is a wait: the runtime marks it
    for line, start, end, name in stages:
        for other_line, o_start, o_end, other in stages:
            if (line, start, end, name) == (other_line, o_start, o_end, other):
                continue
            if line == other_line:
                assert end <= o_start or o_end <= start, (name, other)
    totals = clock.totals()
    assert totals["service_pack"]["wall_s"] > 0.0
    assert totals["service_launch"]["wall_s"] > 0.0
    assert totals["service_pack"]["cpu_s"] > 0.0
