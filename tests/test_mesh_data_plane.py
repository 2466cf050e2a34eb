"""Broadcast-once mesh data plane (r10): encode-once fan-out, scatter-gather
write coalescing, Ping/Pong priority, and zero-copy receive.

The plane is endpoint-local by contract — every optimization must leave the
wire byte-identical to the pre-r10 encoder.  The golden corpus pins that for
all 12 message tags; the census tests pin the N-subscribers → 1-encode
economics; the socket tests drive the real ``TcpNetwork._run_peer`` loops
over a live localhost pair.
"""
import asyncio
import os

import pytest

from mysticeti_tpu.committee import Committee
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.network import (
    BlockNotFound,
    Blocks,
    Connection,
    EncodedFrame,
    Ping,
    Pong,
    RequestBlocks,
    RequestBlocksResponse,
    RequestSnapshot,
    RequestSnapshotStream,
    SnapshotResponse,
    SubscribeOthersFrom,
    SubscribeOwnFrom,
    TcpNetwork,
    TimestampedBlocks,
    _FrameReceiver,
    _read_frame,
    decode_message,
    encode_message,
    frame_payload,
)
from mysticeti_tpu.types import BlockReference, Share, StatementBlock

from helpers import DagBlockWriter, build_dag


# --- golden corpus: byte-identity across every message tag -----------------

_REF = BlockReference(3, 7, bytes(range(32)))
_REF2 = BlockReference(1, 9, bytes(range(100, 132)))

# (message, expected frame payload hex) — the hex was produced by the
# pre-broadcast-once encoder; the encoder (and therefore the wire) must
# never drift, whatever the send path does locally.
GOLDEN_CORPUS = [
    (SubscribeOwnFrom(5), "010500000000000000"),
    (
        Blocks((b"block-one", b"block-two-bytes")),
        "020200000009000000626c6f636b2d6f6e650f000000626c6f636b2d74776f2d"
        "6279746573",
    ),
    (
        RequestBlocks((_REF, _REF2)),
        "030200000003000000000000000700000000000000000102030405060708090a"
        "0b0c0d0e0f101112131415161718191a1b1c1d1e1f0100000000000000090000"
        "00000000006465666768696a6b6c6d6e6f707172737475767778797a7b7c7d7e"
        "7f80818283",
    ),
    (
        RequestBlocksResponse((b"resp-block",)),
        "04010000000a000000726573702d626c6f636b",
    ),
    (
        BlockNotFound((_REF,)),
        "050100000003000000000000000700000000000000000102030405060708090a"
        "0b0c0d0e0f101112131415161718191a1b1c1d1e1f",
    ),
    (Ping(123456789), "0615cd5b0700000000"),
    (Pong(987654321), "07b168de3a00000000"),
    (SubscribeOthersFrom(2, 11), "0802000000000000000b00000000000000"),
    (RequestSnapshot(42), "092a00000000000000"),
    (SnapshotResponse(b"manifest-bytes"), "0a0e0000006d616e69666573742d6279746573"),
    (RequestSnapshotStream(17), "0b1100000000000000"),
    (
        TimestampedBlocks(
            (b"stamped-block",), sent_monotonic_ns=111, sent_wall_ns=222
        ),
        "0c6f00000000000000de00000000000000010000000d0000007374616d706564"
        "2d626c6f636b",
    ),
]


def test_golden_corpus_all_tags_byte_identical():
    """Every message tag 1-12: encode_message, the EncodedFrame cache path,
    and frame_payload all emit the pinned pre-r10 bytes, and the frame
    decodes back — from bytes AND from a memoryview (zero-copy mode)."""
    seen_tags = set()
    for msg, expected_hex in GOLDEN_CORPUS:
        expected = bytes.fromhex(expected_hex)
        assert encode_message(msg) == expected, type(msg).__name__
        assert EncodedFrame(msg).payload == expected
        assert frame_payload(EncodedFrame(msg)) == expected
        assert frame_payload(msg) == expected
        seen_tags.add(expected[0])
        # Roundtrip, both input modes.
        assert decode_message(expected) == msg
        view_decoded = decode_message(memoryview(bytearray(expected)))
        assert view_decoded == msg
    assert seen_tags == set(range(1, 13))


def test_encoded_frame_is_lazy_and_caches():
    """The sim delivers EncodedFrame objects without ever serializing; the
    TCP write path encodes once and reuses the bytes."""
    msg = Blocks((b"payload",))
    frame = EncodedFrame(msg)
    assert frame._payload is None  # nothing encoded yet
    first = frame.payload
    assert first == encode_message(msg)
    assert frame.payload is first  # cached, not re-encoded


def test_decode_message_views_are_zero_copy_until_materialized():
    """Block payloads decoded from a memoryview are sub-views of the frame
    buffer; StatementBlock.from_bytes materializes exactly one bytes that
    survives buffer reuse."""
    committee = Committee.new_test([1] * 4)
    signers = Committee.benchmark_signers(4)
    genesis = [StatementBlock.new_genesis(a).reference for a in range(4)]
    block = StatementBlock.build(
        0, 1, genesis, [Share(b"tx" * 50)], signer=signers[0]
    )
    frame = bytearray(encode_message(Blocks((block.to_bytes(),))))
    msg = decode_message(memoryview(frame))
    assert type(msg.blocks[0]) is memoryview
    decoded = StatementBlock.from_bytes(msg.blocks[0])
    del msg  # release the view before clobbering
    frame[:] = b"\x00" * len(frame)  # simulate buffer reuse
    assert decoded.to_bytes() == block.to_bytes()
    decoded.verify(committee)  # digest/signature contract intact


# --- Ping/Pong priority lane ----------------------------------------------


def test_ping_jumps_saturated_send_queue():
    async def main():
        conn = Connection(peer=2)
        while conn.try_send(Blocks((b"bulk",))):
            pass  # saturate the bounded queue
        assert conn.sender.full()
        # Must neither block nor drop, and must come out FIRST.
        await asyncio.wait_for(conn.send(Ping(7)), timeout=0.5)
        assert isinstance(conn.sender.get_nowait(), Ping)
        # Pong rides the same lane (the echo side of the probe).
        await asyncio.wait_for(conn.send(Pong(8)), timeout=0.5)
        assert isinstance(conn.sender.get_nowait(), Pong)

    asyncio.run(main())


def test_urgent_lane_is_capped_against_ping_floods():
    """The priority lane ignores the bulk bound but has its OWN cap: a
    peer flooding Pings (each answered with a front-queued Pong) cannot
    grow the send queue without limit while refusing to read."""
    from mysticeti_tpu.network import _SendQueue

    async def main():
        metrics = Metrics()
        conn = Connection(peer=7, metrics=metrics)
        accepted = 0
        for i in range(1000):
            if conn.try_send(Pong(i)):
                accepted += 1
        assert accepted == _SendQueue.URGENT_CAP
        assert conn.sender.qsize() == _SendQueue.URGENT_CAP
        dropped = metrics.connection_send_drops_total.labels("7")._value.get()
        assert dropped == 1000 - _SendQueue.URGENT_CAP
        # Draining the lane frees it again (the counter tracks pops).
        for _ in range(_SendQueue.URGENT_CAP):
            assert isinstance(conn.sender.get_nowait(), Pong)
        assert conn.try_send(Ping(0))
        # await-send drops over-cap probes instead of queueing them.
        await conn.send(Pong(1))
        while True:
            try:
                conn.sender.get_nowait()
            except asyncio.QueueEmpty:
                break

    asyncio.run(main())


def test_try_send_drops_are_counted():
    async def main():
        metrics = Metrics()
        conn = Connection(peer=5, metrics=metrics)
        while conn.try_send(Blocks((b"bulk",))):
            pass  # the exiting (False) call counts the first drop
        counter = metrics.connection_send_drops_total.labels("5")
        base = counter._value.get()
        assert base >= 1
        assert not conn.try_send(Blocks((b"dropped",)))
        assert not conn.try_send(Blocks((b"dropped",)))
        assert counter._value.get() == base + 2
        # Urgent frames never count as drops — they jump the bound.
        assert conn.try_send(Ping(1))
        assert counter._value.get() == base + 2

    asyncio.run(main())


# --- encode-once fan-out census -------------------------------------------


class _Notify:
    """Minimal stand-in for net_sync.Notify (subscribe/notify/generation)."""

    def __init__(self):
        self._event = asyncio.Event()
        self.generation = 0

    def subscribe(self):
        return self._event

    def notify(self):
        self.generation += 1
        event, self._event = self._event, asyncio.Event()
        event.set()


def test_encode_reuse_census(tmp_path):
    """N subscribers at one cursor: 1 build, N-1 reuses, identical frame
    object on every queue; a new block (generation bump) forces a rebuild."""
    from mysticeti_tpu.synchronizer import BlockDisseminator, FrameCache

    committee = Committee.new_test([1] * 4)
    writer = DagBlockWriter(committee, str(tmp_path))
    build_dag(committee, writer, None, 3)  # rounds 1-3 for every authority

    async def main():
        metrics = Metrics()
        cache = FrameCache(metrics)
        notify = _Notify()
        n_subs = 5
        conns = [Connection(peer=i + 1) for i in range(n_subs)]
        dissems = [
            BlockDisseminator(
                c, writer.block_store, notify, metrics=metrics,
                frame_cache=cache,
            )
            for c in conns
        ]
        for d in dissems:
            d.subscribe_own_from(0)
        frames = []
        for c in conns:
            # Every subscriber ships a frame covering rounds 1-3.
            frames.append(await asyncio.wait_for(c.sender.get(), timeout=2.0))
        for d in dissems:
            d.stop()
        assert all(f is frames[0] for f in frames)
        assert isinstance(frames[0], EncodedFrame)
        # All five queues carried the IDENTICAL immutable frame object.
        assert cache.builds == 1, cache.builds
        assert cache.reuses == n_subs - 1, cache.reuses
        reuse_series = metrics.dissemination_encode_reuse_total
        assert reuse_series._value.get() == n_subs - 1
        # A store change bumps the generation: the next frame is rebuilt,
        # never served stale from the cache.
        build_dag(
            committee, writer,
            [b.reference for b in writer.block_store.get_blocks_by_round(3)],
            4,
        )
        notify.notify()
        d2 = BlockDisseminator(
            Connection(peer=9), writer.block_store, notify, metrics=metrics,
            frame_cache=cache,
        )
        frame2, cursor2, count2 = d2._push_frame("own", None, 3)
        assert cursor2 == 4 and count2 == 1
        assert cache.builds == 2

    asyncio.run(main())


def test_frame_cache_identity_across_subscribers(tmp_path):
    """The cache returns the same EncodedFrame object (not equal copies) so
    a 3.12+ transport can hold one buffer N times without N serializations."""
    from mysticeti_tpu.synchronizer import BlockDisseminator, FrameCache

    committee = Committee.new_test([1] * 4)
    writer = DagBlockWriter(committee, str(tmp_path))
    build_dag(committee, writer, None, 2)

    async def main():
        cache = FrameCache()
        notify = _Notify()
        mk = lambda: BlockDisseminator(
            Connection(peer=1), writer.block_store, notify,
            frame_cache=cache,
        )
        a = mk()._push_frame("own", None, 0)
        b = mk()._push_frame("own", None, 0)
        assert a[0] is b[0]
        # Different cursors are different frames.
        c = mk()._push_frame("own", None, 1)
        assert c[0] is not a[0] and c[1] == 2
        # Helper streams have their own key space.
        h = mk()._push_frame("others", 2, 0)
        assert h[0] is not a[0] and h[2] > 0

    asyncio.run(main())


def test_frame_cache_bounded():
    from mysticeti_tpu.synchronizer import FrameCache

    cache = FrameCache()
    for i in range(3 * FrameCache.CAPACITY):
        cache.put(("own", None, i, 100, False, 0), (object(), i, 1))
    assert len(cache._frame_entries) == FrameCache.CAPACITY


# --- live socket pair: coalescing, priority, zero-copy receive -------------


async def _socket_pair():
    """(client reader, client writer, server reader, server writer) over a
    real localhost TCP connection."""
    loop = asyncio.get_event_loop()
    accepted = loop.create_future()

    async def on_conn(reader, writer):
        accepted.set_result((reader, writer))

    server = await asyncio.start_server(on_conn, host="127.0.0.1", port=0)
    port = server.sockets[0].getsockname()[1]
    c_reader, c_writer = await asyncio.open_connection("127.0.0.1", port)
    s_reader, s_writer = await accepted
    return server, c_reader, c_writer, s_reader, s_writer


async def _read_raw_frame(reader):
    header = await reader.readexactly(4)
    return await reader.readexactly(int.from_bytes(header, "little"))


def test_write_coalescing_ships_ping_first_and_byte_identical():
    """Drive the real _run_peer write loop: a saturated bulk backlog plus a
    Ping must reach the wire ping-first, every frame byte-identical to the
    plain encoder, with the coalescing/wire-bytes counters advancing."""

    async def main():
        metrics = Metrics()
        net = TcpNetwork(0, [("127.0.0.1", 0), ("127.0.0.1", 0)], metrics)
        server, c_reader, c_writer, s_reader, s_writer = await _socket_pair()
        peer_task = asyncio.ensure_future(net._run_peer(1, s_reader, s_writer))
        conn = await net.connections.get()
        bulk = [Blocks((bytes([i]) * 200,)) for i in range(50)]
        # Enqueue without yielding: the write loop sees one batch.
        for m in bulk:
            assert conn.try_send(m)
        await conn.send(Ping(1234))  # priority lane, still same batch
        frames = []
        while len([f for f in frames if f[0] != 6]) < 50 or not any(
            f == encode_message(Ping(1234)) for f in frames
        ):
            frames.append(await asyncio.wait_for(_read_raw_frame(c_reader), 5))
        # Drain any straggling startup ping so the byte census is exact.
        try:
            while True:
                frames.append(
                    await asyncio.wait_for(_read_raw_frame(c_reader), 0.2)
                )
        except asyncio.TimeoutError:
            pass
        # The probe ping precedes EVERY bulk frame (the peer task's own
        # startup ping may ride ahead — also urgent, also fine).
        ping_at = frames.index(encode_message(Ping(1234)))
        first_bulk = min(i for i, f in enumerate(frames) if f[0] != 6)
        assert ping_at < first_bulk, (ping_at, first_bulk)
        # Bulk order preserved, every frame byte-identical to the encoder.
        assert [f for f in frames if f[0] != 6] == [
            encode_message(m) for m in bulk
        ]
        total = sum(len(f) + 4 for f in frames)
        sent = metrics.mesh_wire_bytes_total.labels("sent")._value.get()
        assert sent == total
        # At least the 50-frame batch coalesced (scheduling may split the
        # first wakeup off, never below this floor).
        assert metrics.mesh_frames_coalesced_total._value.get() >= 49
        peer_task.cancel()
        c_writer.close()
        server.close()

    asyncio.run(main())


def test_zero_copy_receive_through_run_peer():
    """The receiving _run_peer parses frames via the BufferedProtocol path:
    block payloads surface as memoryviews, survive deep pipelining (buffer
    reuse is refcount-guarded), and Pings are answered on the priority lane."""

    async def main():
        metrics = Metrics()
        net = TcpNetwork(0, [("127.0.0.1", 0), ("127.0.0.1", 0)], metrics)
        server, c_reader, c_writer, s_reader, s_writer = await _socket_pair()
        peer_task = asyncio.ensure_future(net._run_peer(1, s_reader, s_writer))
        conn = await net.connections.get()

        payloads = [os.urandom(300) for _ in range(40)]
        parts = []
        for p in payloads:
            enc = encode_message(Blocks((p,)))
            parts += [len(enc).to_bytes(4, "little"), enc]
        parts += [
            len(encode_message(Ping(77))).to_bytes(4, "little"),
            encode_message(Ping(77)),
        ]
        c_writer.writelines(parts)
        await c_writer.drain()

        msgs = []
        for _ in range(40):
            msgs.append(await asyncio.wait_for(conn.recv(), 5))
        # Zero-copy mode delivered views (proves the BufferedProtocol path
        # attached, not the readexactly fallback)...
        assert all(type(m.blocks[0]) is memoryview for m in msgs)
        # ...and every payload is intact even though 40 frames crossed one
        # reusable buffer while earlier views were still alive.
        assert [bytes(m.blocks[0]) for m in msgs] == payloads
        received = metrics.mesh_wire_bytes_total.labels("received")._value.get()
        assert received == sum(len(p) for p in parts)
        # The Ping was consumed by the read loop and echoed as a Pong
        # (the peer task's own startup Ping may arrive first — skip it).
        while True:
            frame = await asyncio.wait_for(_read_raw_frame(c_reader), 5)
            if frame[0] != 6:
                break
        assert decode_message(frame) == Pong(77)
        peer_task.cancel()
        c_writer.close()
        server.close()

    asyncio.run(main())


def test_receive_buffer_shrinks_after_jumbo_frame():
    """A multi-MB frame grows the per-connection assembly buffer; once the
    backlog clears it swaps back to MIN_BUF instead of pinning the jumbo
    allocation for the life of the connection."""

    async def main():
        server, c_reader, c_writer, s_reader, s_writer = await _socket_pair()
        recv = _FrameReceiver.attach(s_reader, s_writer)
        assert recv is not None
        jumbo = Blocks((os.urandom(3_000_000),))
        enc = encode_message(jumbo)
        c_writer.writelines([len(enc).to_bytes(4, "little"), enc])
        await c_writer.drain()
        got = decode_message(await asyncio.wait_for(recv.read_frame(), 10))
        assert bytes(got.blocks[0]) == jumbo.blocks[0]
        del got
        assert len(recv._buf) == _FrameReceiver.MIN_BUF
        # The link keeps working on the fresh buffer.
        small = Blocks((b"x" * 100,))
        enc = encode_message(small)
        c_writer.writelines([len(enc).to_bytes(4, "little"), enc])
        await c_writer.drain()
        assert decode_message(await asyncio.wait_for(recv.read_frame(), 5)) == small
        c_writer.close()
        server.close()

    asyncio.run(main())


class _UnswitchableWriter:
    """A stream writer whose transport cannot change protocol (a mock
    stream, a wrapper): all ``_FrameReceiver.attach`` looks at."""

    transport = None


def test_unswitchable_transport_falls_back_to_stream_frames():
    """Where the transport cannot be switched ``attach`` returns None and
    leaves the StreamReader whole: ``_read_frame`` then yields, frame for
    frame, what a switched connection's receiver yields."""

    async def main():
        sent = [encode_message(msg) for msg, _hex in GOLDEN_CORPUS]
        wire = [part for enc in sent
                for part in (len(enc).to_bytes(4, "little"), enc)]
        server, c_reader, c_writer, s_reader, s_writer = await _socket_pair()
        assert _FrameReceiver.attach(s_reader, _UnswitchableWriter()) is None
        c_writer.writelines(wire)
        await c_writer.drain()
        streamed = [
            await asyncio.wait_for(_read_frame(s_reader), 5) for _ in sent
        ]
        s_writer.close()
        c_writer.close()
        server.close()

        server, c_reader, c_writer, s_reader, s_writer = await _socket_pair()
        recv = _FrameReceiver.attach(s_reader, s_writer)
        assert recv is not None
        c_writer.writelines(wire)
        await c_writer.drain()
        switched = [
            bytes(await asyncio.wait_for(recv.read_frame(), 5)) for _ in sent
        ]
        c_writer.close()
        server.close()
        assert streamed == switched == sent

    asyncio.run(main())


# --- ingest batching audit -------------------------------------------------


def test_ingest_whole_frame_batching(tmp_path):
    """A frame of K blocks crosses the core owner exactly twice: one
    processed() dedup command for the whole batch, one add_blocks() for the
    accepted batch — never a per-block hop."""
    from mysticeti_tpu.runtime.simulated import run_simulation

    committee = Committee.new_test([1] * 4)
    signers = Committee.benchmark_signers(4)

    async def scenario():
        from mysticeti_tpu.block_handler import TestBlockHandler
        from mysticeti_tpu.block_store import BlockStore
        from mysticeti_tpu.commit_observer import TestCommitObserver
        from mysticeti_tpu.config import Parameters
        from mysticeti_tpu.core import Core, CoreOptions
        from mysticeti_tpu.net_sync import NetworkSyncer
        from mysticeti_tpu.wal import walf

        wal_writer, wal_reader = walf(os.path.join(str(tmp_path), "wal-0"))
        recovered, observer_recovered = BlockStore.open(
            0, wal_reader, wal_writer, committee
        )
        handler = TestBlockHandler(
            last_transaction=0, committee=committee, authority=0
        )
        core = Core(
            block_handler=handler, authority=0, committee=committee,
            parameters=Parameters(), recovered=recovered,
            wal_writer=wal_writer, options=CoreOptions.test(),
            signer=signers[0],
        )
        observer = TestCommitObserver(
            core.block_store, committee, recovered_state=observer_recovered
        )

        class _Net:
            connections: asyncio.Queue = asyncio.Queue()

            async def stop(self):
                pass

        node = NetworkSyncer(core, observer, _Net())
        calls = {"processed": [], "add_blocks": []}
        real_processed = node.dispatcher.processed
        real_add = node.dispatcher.add_blocks

        async def processed(refs):
            calls["processed"].append(len(refs))
            return await real_processed(refs)

        async def add_blocks(blocks, connected):
            calls["add_blocks"].append(len(blocks))
            return await real_add(blocks, connected)

        node.dispatcher.processed = processed
        node.dispatcher.add_blocks = add_blocks
        await node.start()
        conn = Connection(peer=1)
        await _Net.connections.put(conn)
        await asyncio.sleep(0.1)

        genesis = [
            StatementBlock.new_genesis(a, committee.epoch).reference
            for a in range(4)
        ]
        blocks = [
            StatementBlock.build(
                a, 1, genesis, [Share(b"t%d" % a)], signer=signers[a],
                epoch=committee.epoch,
            )
            for a in (1, 2, 3)
        ]
        base_processed = len(calls["processed"])
        base_add = len(calls["add_blocks"])
        await conn.receiver.put(
            Blocks(tuple(b.to_bytes() for b in blocks))
        )
        await asyncio.sleep(1.0)
        assert calls["processed"][base_processed:] == [3]
        assert calls["add_blocks"][base_add:] == [3]
        await node.stop()

    run_simulation(scenario(), seed=42)
