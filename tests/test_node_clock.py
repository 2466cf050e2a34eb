"""A validator's one stage clock (spans.StageClock over spans.NODE_STAGES,
made by validator.py): the ring by the second, the stages booked where the
host's time can hide, what it stamps once a second, the two events, and the
document ``Validator.stop`` leaves and ``/debug/flight-recorder`` serves.
The service's clock shares the class: its export is held to what the tree
before this clock wrote for the same bookings."""
import asyncio
import gc
import json
import os
import socket
import time

import pytest

from mysticeti_tpu import spans
from mysticeti_tpu.committee import Authority, Committee
from mysticeti_tpu.config import Identifier, Parameters, PrivateConfig
from mysticeti_tpu.core_task import CoreTaskDispatcher
from mysticeti_tpu.execution import ExecutionState
from mysticeti_tpu.finality import FinalityTracker
from mysticeti_tpu.flight_recorder import FlightRecorder
from mysticeti_tpu.hostattr import HostMonitor
from mysticeti_tpu.ingress import IngressParameters, IngressPlane
from mysticeti_tpu.metrics import Metrics, serve_metrics
from mysticeti_tpu.validator import Validator
from mysticeti_tpu.verifier_service import ServiceCounts
from mysticeti_tpu.wal import WalWriter

N = 4


def _node_clock(ring=8):
    return spans.StageClock(
        spans.NODE_STAGES, ring_seconds=ring, stamps=spans.NODE_STAMPS,
        read_stamps=lambda: (0,) * len(spans.NODE_STAMPS),
        lag_stage="loop_lag", gc_stage="gc")


def _booked(clock, stage):
    """[count, wall_s, cpu_s, max_wall_s] of ``stage`` over the ring."""
    out = [0, 0.0, 0.0, 0.0]
    for entry in clock.export()["seconds"].values():
        cell = entry.get(stage)
        if cell:
            out = [out[0] + cell[0], out[1] + cell[1], out[2] + cell[2],
                   max(out[3], cell[3])]
    return out


# -- a live fleet of four: the real wiring --------------------------------------


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


async def _get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    data = await asyncio.wait_for(reader.read(-1), timeout=10)
    writer.close()
    return data.split(b"\r\n\r\n", 1)[1]


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Four validators on real sockets with the host oracle behind the
    collector, run until a checkpoint has been written, scraped, asked for
    the live document and stopped: {"live": validator 0's document served
    live, "left": the four documents left on disk, "dirs": where}."""
    root = tmp_path_factory.mktemp("fleet")
    ports = _free_ports(2 * N)
    parameters = Parameters(
        identifiers=[Identifier("127.0.0.1", ports[2 * i], ports[2 * i + 1])
                     for i in range(N)],
        leader_timeout_s=0.5)
    parameters.storage.checkpoint_interval = 64
    signers = Committee.benchmark_signers(N)
    committee = Committee([Authority(1, s.public_key) for s in signers])
    privates = [PrivateConfig.new_in_dir(i, str(root / f"v{i}"))
                for i in range(N)]

    async def main():
        validators = [
            await Validator.start_benchmarking(
                i, committee, parameters, privates[i], signer=signers[i],
                tps=200, serve_metrics_endpoint=(i == 0), verifier="cpu")
            for i in range(N)
        ]
        try:
            async def checkpointed():
                while not all(v.core.storage.checkpoints_written
                              for v in validators):
                    await asyncio.sleep(0.2)
            await asyncio.wait_for(checkpointed(), timeout=90)
            await asyncio.sleep(1.2)  # a whole second after it, stamped
            _, port = parameters.metrics_address(0)
            scrape = await _get(port, "/metrics")
            live = json.loads(await _get(port, "/debug/flight-recorder"))
        finally:
            for v in validators:
                await v.stop()
        return scrape.decode(), live

    scrape, live = asyncio.run(main())
    left = []
    for private in privates:
        with open(os.path.join(private.storage_path,
                               "flight-recorder.json")) as f:
            left.append(json.load(f))
    return {"scrape": scrape, "live": live, "left": left,
            "dirs": [p.storage_path for p in privates]}


FLEET_STAGES = (
    "receive", "verify", "dag_add", "leader_wait", "core_command",
    "loop_lag", "gc", "executor_wait", "wal_write", "wal_sync", "checkpoint",
    "scrape", "phase_admission", "phase_proposal", "phase_commit",
)


def _sum(document, stage):
    cells = [entry[stage] for entry in document["stages"]["seconds"].values()
             if stage in entry]
    return [sum(c[0] for c in cells), sum(c[1] for c in cells),
            sum(c[2] for c in cells), max((c[3] for c in cells), default=0)]


@pytest.mark.parametrize("stage", FLEET_STAGES)
def test_a_live_validator_books_the_stage_where_it_is_taken(fleet, stage):
    """Every stage of the node's clock that a plain deployment reaches has
    rows in the document validator 0 left (``scrape`` only there: it alone
    served an endpoint), with a wall, a longest sample no longer than the
    sum, and a CPU where the stage reads one."""
    count, wall, cpu, longest = _sum(fleet["left"][0], stage)
    assert count >= 1, stage
    assert 0.0 < longest <= wall
    if stage in ("core_command", "wal_write", "gc"):
        assert cpu > 0.0
    if stage in ("loop_lag", "executor_wait", "leader_wait", "scrape"):
        assert cpu == 0.0


def test_the_document_is_left_at_shutdown_and_served_live(fleet):
    """``Validator.stop`` writes ``flight-recorder.json`` beside the WAL
    without being asked, with the clock's ring as ``"stages"`` on the
    events' own clock; ``GET /debug/flight-recorder`` serves the same
    document while the validator lives."""
    for directory, document in zip(fleet["dirs"], fleet["left"]):
        assert os.path.isdir(os.path.join(directory, "wal"))
        assert not os.path.exists(
            os.path.join(directory, "flight-recorder.json.tmp"))
        ring = document["stages"]
        assert ring["clock"] == "time.monotonic"
        assert ring["columns"] == list(spans.StageClock.COLUMNS)
        seconds = sorted(int(s) for s in ring["seconds"])
        assert len(seconds) >= 3
        # The events are stamped on the ring's clock: each falls in the
        # seconds the ring covers (or the one before its first stamp).
        for event in document["events"]:
            assert seconds[0] - 1 <= int(event["t"]) <= seconds[-1], event
        assert {e["kind"] for e in document["events"]} >= {
            "commit", "checkpoint"}
        assert document["dumps"] == []  # the dump that wrote it is its first
        assert document["capacity"] == 16384 and document["dropped"] == 0
    live = fleet["live"]
    assert live["authority"] == 0
    assert set(live["stages"]["seconds"]) <= set(
        fleet["left"][0]["stages"]["seconds"])
    assert live["stages"]["columns"] == fleet["left"][0]["stages"]["columns"]


def test_a_second_holds_what_the_validator_counted_in_it(fleet):
    """The stamp: the threshold clock's rounds, leaders committed, own
    proposals, blocks received and transactions admitted grow second by
    second; a healthy fleet sheds nothing and times out only while it
    boots (0.5 s here); ``verify_requests`` counts the verifier service's
    requests, of which a host oracle has none."""
    ring = fleet["left"][0]["stages"]["seconds"]
    stamped = [entry for entry in ring.values() if "rounds" in entry]
    assert len(stamped) >= 3
    for name in spans.NODE_STAMPS + spans.StageClock.CPU_STAMPS:
        assert all(name in entry for entry in stamped), name
    totals = {name: sum(entry[name] for entry in stamped)
              for name in spans.NODE_STAMPS}
    assert totals["rounds"] >= 64 and totals["leaders"] >= 64
    assert totals["proposals"] >= 64
    # The highest round held keeps pace with the validator's own clock.
    assert abs(totals["frontier_round"] - totals["rounds"]) <= 2
    assert totals["blocks_received"] >= 3 * 64
    assert totals["tx_admitted"] > 0
    assert totals["shed"] == totals["shed_lane_cap"] == 0
    timeouts = sum(1 for e in fleet["left"][0]["events"]
                   if e["kind"] == "leader-timeout")
    assert totals["leader_timeouts"] <= timeouts + 1  # and the genesis kick
    assert totals["verify_requests"] == 0
    assert all(isinstance(entry["rounds"], int) for entry in stamped)
    assert sum(entry["process_cpu_s"] for entry in stamped) > 0.0


def test_the_scrape_renders_the_one_clock_and_two_series_fewer(fleet):
    """``block_stage_seconds{stage}`` renders the validator's one clock: the
    stages that booked, none that did not; the histogram the ``loop_lag``
    stage replaced and the last-blocking-call gauge are gone, the p99 gauge
    the health plane reads is not."""
    text = fleet["scrape"]
    rendered = {line.split('stage="')[1].split('"')[0]
                for line in text.splitlines()
                if line.startswith("block_stage_seconds_count")}
    assert set(FLEET_STAGES) - {"scrape"} <= rendered <= set(spans.NODE_STAGES)
    assert "admit_verify" not in rendered and "mesh_hold" not in rendered
    assert "mysticeti_loop_lag_seconds" not in text
    assert "mysticeti_blocking_call_last_ms" not in text
    assert "mysticeti_loop_lag_p99_seconds" in text
    metrics = Metrics()
    assert not hasattr(metrics, "mysticeti_loop_lag_seconds")
    assert not hasattr(metrics, "mysticeti_blocking_call_last_ms")


def test_fleetmon_shows_the_newest_seconds_of_a_live_document(fleet):
    """``tools/fleetmon.py`` fetches ``/debug/flight-recorder`` already: its
    summary of a node now holds the newest ten seconds' ``rounds`` and the
    host stage with the longest sample."""
    from tools.fleetmon import HOST_STAGES, last_seconds, recorder_summary

    rows = last_seconds(fleet["left"][0], last=10)
    ring = fleet["left"][0]["stages"]["seconds"]
    assert [row["second"] for row in rows] == sorted(map(int, ring))[-10:]
    assert all(row["worst_stage"] in HOST_STAGES for row in rows)
    assert all(row["worst_ms"] > 0 for row in rows)
    assert sum(row["rounds"] or 0 for row in rows) > 0
    summary = recorder_summary({"0": fleet["live"], "1": None})
    assert summary["1"] is None
    assert summary["0"]["last_seconds"] == last_seconds(fleet["live"])
    assert last_seconds({"events": []}) == []  # a node without the ring


# -- each call site alone -------------------------------------------------------


def _drive_core_command(clock, tmp_path):
    dispatcher = CoreTaskDispatcher(object(), stages=clock)

    def burn():
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.02:
            sum(range(1000))

    async def drive():
        dispatcher.start()
        await dispatcher._call(burn)
        dispatcher.stop()

    asyncio.run(drive())


def _drive_wal(clock, tmp_path):
    path = str(tmp_path / "wal")
    fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
    writer = WalWriter(fd, 0, path, async_writes=True)
    writer.stages = clock
    for i in range(64):
        writer.write(1, bytes(4096))
    writer.flush()
    syncer = writer.syncer()
    syncer.sync()
    syncer.close()
    writer.close()


def _drive_exec_fold(clock, tmp_path):
    state = ExecutionState()
    state.stages = clock
    assert state.observe_commit(1, []) is not None
    assert state.observe_commit(1, []) is None  # replayed: not folded again


def _drive_executor(clock, tmp_path):
    async def drive():
        loop = asyncio.get_running_loop()
        assert await spans.in_default_executor(
            loop, clock, lambda a, b: a + b, 2, 3) == 5
        assert await spans.in_default_executor(loop, None, len, "ab") == 2

    asyncio.run(drive())


def _drive_scrape(clock, tmp_path):
    async def drive():
        recorder = FlightRecorder(authority=0, stages=clock)
        server = await serve_metrics(
            Metrics(), "127.0.0.1", 0, flight_recorder=recorder,
            stages=clock)
        port = server.sockets[0].getsockname()[1]
        assert b"# HELP" in await _get(port, "/metrics")
        document = json.loads(await _get(port, "/debug/flight-recorder"))
        assert document["stages"]["clock"] == "time.monotonic"
        server.close()

    asyncio.run(drive())


def _drive_loop_lag(clock, tmp_path):
    async def drive():
        monitor = HostMonitor(stages=clock)
        monitor.loop_lag.interval_s = 0.02
        monitor.start()
        await asyncio.sleep(0.05)
        time.sleep(0.06)  # holds the loop: the next tick is that late
        await asyncio.sleep(0.05)
        monitor.stop()

    asyncio.run(drive())


def _drive_gc(clock, tmp_path):
    gc.callbacks.append(clock.gc_callback)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(clock.gc_callback)


def _drive_phases(clock, tmp_path):
    now = time.monotonic()
    tracker = FinalityTracker(sample_every=1, stages=clock)
    tracker.on_submit(b"k" * 32, now - 0.5, now - 0.4)
    tracker.on_proposal(b"k" * 32, now - 0.3)
    tracker.on_commit(b"k" * 32, now - 0.1, now)


SITES = {
    "core_command": (_drive_core_command, 1, 0.02, True),
    "wal_write": (_drive_wal, 1, 0.0, True),
    "wal_sync": (_drive_wal, 1, 0.0, False),
    "exec_fold": (_drive_exec_fold, 1, 0.0, None),
    "executor_wait": (_drive_executor, 1, 0.0, False),
    "scrape": (_drive_scrape, 2, 0.0, False),
    "loop_lag": (_drive_loop_lag, 2, 0.05, False),
    "gc": (_drive_gc, 1, 0.0, True),
    "phase_admission": (_drive_phases, 1, 0.1, False),
    "phase_proposal": (_drive_phases, 1, 0.1, False),
    "phase_commit": (_drive_phases, 1, 0.2, False),
}


@pytest.mark.parametrize("stage", sorted(SITES))
def test_the_call_site_books_its_stage_into_the_ring(stage, tmp_path):
    """The real dispatcher, WAL writer and syncer, execution fold,
    executor wrapper, metrics endpoint, loop probe, collector hook and
    finality tracker each book their stage into a ringed clock: at least
    ``samples`` of them, no shorter than ``wall`` together, with CPU beside
    the wall where the stage reads it and none where it waits."""
    drive, samples, wall, has_cpu = SITES[stage]
    clock = _node_clock()
    drive(clock, tmp_path)
    count, total, cpu, longest = _booked(clock, stage)
    assert count >= samples
    assert total >= wall - 1e-6 and 0.0 <= longest <= total
    if has_cpu is True:
        assert cpu > 0.0
    elif has_cpu is False:
        assert cpu == 0.0
    row = clock.totals()[stage]
    assert row["count"] == count and sum(row["buckets"]) == count
    booked = {name for name, r in clock.totals().items() if r["count"]}
    assert stage in booked


def test_every_stage_of_the_node_clock_is_a_registered_stage_name():
    """The `span-names` lint reads ``spans.STAGES`` (a literal tuple, parsed
    from the file): every stage of the node's clock is in it and the
    service's stages keep their place at its end."""
    import ast

    from mysticeti_tpu.analysis.checker import collect_span_stages

    with open(spans.__file__) as f:
        registered = collect_span_stages(ast.parse(f.read()))
    assert registered == spans.STAGES
    assert set(spans.NODE_STAGES) <= set(registered)
    assert len(set(spans.NODE_STAGES)) == len(spans.NODE_STAGES) == 19
    assert spans.STAGES[-len(spans.SERVICE_STAGES):] == spans.SERVICE_STAGES
    assert not set(spans.NODE_STAGES) & set(spans.SERVICE_STAGES)


@pytest.mark.parametrize("stage", [
    "core_command", "loop_lag", "gc", "executor_wait", "wal_write",
    "wal_sync", "checkpoint", "exec_fold", "scrape", "phase_admission",
    "phase_proposal", "phase_commit"])
def test_the_span_names_lint_knows_the_stage_and_flags_its_typo(stage):
    import textwrap

    from mysticeti_tpu.analysis import analyze_source

    def lint(name):
        return analyze_source(textwrap.dedent(f"""
            def site(stages, end, wall):
                stages.book("{name}", end, wall)
            """), "mysticeti_tpu/example.py", span_stages=spans.STAGES)

    assert lint(stage) == []
    assert [f.rule for f in lint(stage + "s")] == ["span-names"]


# -- the service's clock is the same class --------------------------------------

BASE = 4_000_000_000  # seconds of its own, far later than now

# What the tree before PR 39 exported for ``_service_bookings`` (its clock
# kept the counts itself), less each second's three CPU readings.
EXPORTED_BEFORE = '{"clock": "time.monotonic", "columns": ["count", "wall_s", "cpu_s", "max_wall_s"], "sample_one_in": 32, "gc_generations": {"0": [0, 0.0], "1": [0, 0.0], "2": [0, 0.0]}, "seconds": {"4000000000": {"service_decode": [2, 0.032, 0.015, 0.031], "service_pool_wait": [1, 0.022, 0.0105, 0.022], "service_unpack": [1, 0.013000000000000001, 0.006, 0.013000000000000001], "service_pack": [2, 0.038000000000000006, 0.018000000000000002, 0.034], "service_launch": [1, 0.025, 0.012, 0.025], "service_fetch": [1, 0.016, 0.0075, 0.016], "service_reply_build": [2, 0.044, 0.021, 0.037], "service_reply_wait": [1, 0.028, 0.0135, 0.028], "service_gc": [1, 0.019, 0.009000000000000001, 0.019], "service_loop_lag": [2, 0.05, 0.024, 0.04], "requests": 7, "signatures": 60, "launches": 3, "left_alone": 0, "left_full": 2, "left_drained": 1, "left_expired": 0, "reads": 5, "writes": 5}, "4000000001": {"service_decode": [1, 0.011, 0.005, 0.011], "service_pool_wait": [2, 0.034, 0.016, 0.032], "service_unpack": [1, 0.023, 0.011, 0.023], "service_pack": [1, 0.014, 0.006500000000000001, 0.014], "service_launch": [2, 0.04, 0.019000000000000003, 0.035], "service_fetch": [1, 0.026000000000000002, 0.0125, 0.026000000000000002], "service_reply_build": [1, 0.017, 0.008, 0.017], "service_reply_wait": [2, 0.046, 0.022, 0.038], "service_gc": [1, 0.029, 0.014, 0.029], "service_loop_lag": [1, 0.02, 0.0095, 0.02], "requests": 18, "signatures": 160, "launches": 4, "left_alone": 1, "left_full": 1, "left_drained": 1, "left_expired": 1, "reads": 11, "writes": 7}, "4000000002": {"service_decode": [1, 0.021, 0.01, 0.021], "service_pool_wait": [1, 0.012, 0.0055, 0.012], "service_unpack": [2, 0.036000000000000004, 0.017, 0.033], "service_pack": [1, 0.024, 0.0115, 0.024], "service_launch": [1, 0.015, 0.007, 0.015], "service_fetch": [2, 0.042, 0.02, 0.036000000000000004], "service_reply_build": [1, 0.027, 0.013000000000000001, 0.027], "service_reply_wait": [1, 0.018000000000000002, 0.0085, 0.018000000000000002], "service_gc": [2, 0.048, 0.023, 0.039], "service_loop_lag": [1, 0.03, 0.0145, 0.03], "requests": 1, "signatures": 0, "launches": 0, "left_alone": 0, "left_full": 0, "left_drained": 0, "left_expired": 0, "reads": 0, "writes": 0}}}'


def _service_bookings(clock, counts):
    names = spans.SERVICE_STAGES
    for i in range(40):
        clock.book(names[i % len(names)], BASE + (i % 3) + 0.25,
                   0.001 * (i + 1), 0.0005 * i)
    counts.requests, counts.signatures, counts.launches = 5, 40, 2
    counts.left[:] = [1, 0, 1, 0, 0]
    counts.reads, counts.writes = 4, 3
    clock.stamp(BASE + 0.0)
    counts.requests, counts.signatures, counts.launches = 12, 100, 5
    counts.left[:] = [1, 2, 2, 0, 2]
    counts.reads, counts.writes = 9, 8
    clock.stamp(BASE + 1.5)
    counts.requests, counts.signatures, counts.launches = 30, 260, 9
    counts.left[:] = [2, 3, 3, 1, 5]
    counts.reads, counts.writes = 20, 15
    clock.stamp(BASE + 2.1)
    counts.requests = 31


def test_the_services_export_is_what_it_was_for_the_same_bookings():
    """Byte for byte, key order and all: the stages' rows, the stamps
    under their names (twelve of the service's, three CPU clocks), the
    collections by generation."""
    counts = ServiceCounts()
    clock = spans.StageClock(
        spans.SERVICE_STAGES, ring_seconds=600, sample_one_in=32,
        stamps=ServiceCounts.STAMPS, read_stamps=counts.read,
        lag_stage="service_loop_lag", gc_stage="service_gc")
    assert clock.stamp_names == (
        "requests", "signatures", "launches", "left_alone", "left_full",
        "left_drained", "left_expired", "left_overlapped", "direct",
        "keyed_tried", "reads", "writes", "process_cpu_s", "threads_cpu_s",
        "loop_cpu_s")
    _service_bookings(clock, counts)
    report = clock.export()
    seconds, overlapped = {}, []
    for second in (BASE, BASE + 1, BASE + 2):
        entry = dict(report["seconds"][str(second)])
        assert list(entry)[-3:] == list(spans.StageClock.CPU_STAMPS)
        for name in spans.StageClock.CPU_STAMPS:
            assert isinstance(entry.pop(name), float)
        # Since PR 46 two stamps of the backend's (no backend here: zero).
        assert list(entry)[-4:-2] == ["direct", "keyed_tried"]
        assert entry.pop("direct") == entry.pop("keyed_tried") == 0
        # Since PR 47 a fifth reason a launch left, after the four.
        assert list(entry)[-3] == "left_overlapped"
        overlapped.append(entry.pop("left_overlapped"))
        seconds[str(second)] = entry
    again = json.dumps({**{k: v for k, v in report.items()
                           if k != "seconds"}, "seconds": seconds})
    assert again == EXPORTED_BEFORE
    assert overlapped == [2, 3, 0]  # the growth from stamp to stamp


# -- the two events -------------------------------------------------------------


class _Recorder:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append(dict(fields, kind=kind))


def test_a_second_that_shed_leaves_one_event_never_one_an_operation():
    """``shed``: the first refusal of a whole second and the second's
    count by reason, written when a later second sheds, at the next tick or
    at stop - where the plane has the ringed clock; without one (the
    simulator) no such event."""
    now = [100.25]
    recorder = _Recorder()
    plane = IngressPlane(IngressParameters(), recorder=recorder,
                         clock=lambda: now[0], stages=_node_clock())
    for _ in range(50):
        plane._count_sheds("c1", {"lane_cap": 2, "admission": 1}, 10)
    now[0] = 100.9
    plane._count_sheds("c2", {"lane_cap": 5}, 10)
    assert recorder.events == []  # the second is not over
    now[0] = 102.1
    plane._count_sheds("c3", {"duplicate": 1}, 10)
    assert recorder.events == [{
        "kind": "shed", "second": 100, "first_t": 100.25,
        "first_client": "c1", "first_reason": "admission",
        "by_reason": {"admission": 50, "lane_cap": 105}}]
    plane.stop()
    assert recorder.events[1] == {
        "kind": "shed", "second": 102, "first_t": 102.1,
        "first_client": "c3", "first_reason": "duplicate",
        "by_reason": {"duplicate": 1}}
    assert plane.shed_total() == 156 and plane.shed_for("lane_cap") == 105
    bare = IngressPlane(IngressParameters(), recorder=recorder,
                        clock=lambda: now[0],
                        stages=spans.StageClock(spans.NODE_STAGES))
    bare._count_sheds("c1", {"lane_cap": 2}, 10)
    bare.stop()
    assert len(recorder.events) == 2


def test_a_round_the_threshold_clock_sat_in_leaves_a_slow_round_event(
        monkeypatch):
    """``slow-round``: over half a second from one advance of the
    threshold clock to the next - the round, the seconds, this validator's
    own wait at the proposal gate and what ended it."""
    from mysticeti_tpu import syncer as syncer_module
    from mysticeti_tpu.syncer import Syncer, SyncerSignals

    now = [50.0]
    monkeypatch.setattr(syncer_module.spans, "runtime_now", lambda: now[0])

    class Core:
        round = 1
        proposed = 0
        ready = True
        reconfig = None

        def current_round(self):
            return self.round

        def add_blocks(self, blocks):
            self.round += 1
            return []

        def ready_new_block(self, period, connected):
            return self.ready

        def try_new_block(self):
            self.proposed = self.round
            return object()

        def last_proposed(self):
            return self.proposed

        def epoch_closed(self):
            return True  # no commits in this test

    recorder = _Recorder()
    core = Core()
    clock = _node_clock()
    syncer = Syncer(core, 3, SyncerSignals(), object(), stages=clock,
                    recorder=recorder)
    syncer.add_blocks([], None)            # round 2 at 50.0, proposed at once
    now[0] = 50.1
    core.ready = False
    syncer.add_blocks([], None)            # round 3: the gate stays shut
    now[0] = 50.9
    assert syncer.force_new_block(3, None)  # the leader timeout opens it
    assert recorder.events == []
    now[0] = 51.0
    core.ready = True
    syncer.add_blocks([], None)            # round 4, 0.9 s after round 3
    assert recorder.events == [{
        "kind": "slow-round", "round": 3, "seconds": 0.9, "wait_s": 0.8,
        "ended": "timeout"}]
    assert (syncer.proposals, syncer.leader_timeouts) == (3, 1)
    assert clock.totals()["leader_wait"]["count"] == 3
    now[0] = 51.7
    syncer.try_new_block(None)             # a closed connection: no round yet
    syncer.add_blocks([], None)            # round 5, 0.7 s after round 4
    assert recorder.events[1]["ended"] == "leader"
    assert recorder.events[1]["round"] == 4
