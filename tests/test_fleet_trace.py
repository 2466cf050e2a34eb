"""Fleet causal trace plane: wire timestamp extension (tag 12), cross-node
journey merge + skew estimation (tools/fleet_trace.py), the always-on flight
recorder with its dump triggers, and the shared truncated-trace extraction.

Acceptance pins (ISSUE 9): a seeded 10-node sim produces a byte-identical
merged fleet trace AND byte-identical flight-recorder dumps across same-seed
runs; the skew estimator recovers injected per-node clock offsets within
tolerance; a chaos safety failure writes recorder dumps for every live node;
the timestamp extension is version-skew safe in both directions.
"""
import asyncio
import json
import os
import sys

import pytest

from mysticeti_tpu import spans
from mysticeti_tpu.block_handler import TestBlockHandler
from mysticeti_tpu.block_store import BlockStore
from mysticeti_tpu.commit_observer import TestCommitObserver
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Parameters
from mysticeti_tpu.core import Core, CoreOptions
from mysticeti_tpu.flight_recorder import FlightRecorder
from mysticeti_tpu.metrics import Metrics, serve_metrics
from mysticeti_tpu.net_sync import NetworkSyncer
from mysticeti_tpu.network import (
    Blocks,
    SerdeError,
    TimestampedBlocks,
    decode_message,
    encode_message,
)
from mysticeti_tpu.runtime.simulated import run_simulation
from mysticeti_tpu.simulated_network import SimulatedNetwork
from mysticeti_tpu.spans import PIPELINE_STAGES, format_ref
from mysticeti_tpu.wal import walf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

pytestmark = pytest.mark.tracing


# -- wire format: tag 12, version-skew safe both directions -------------------


def test_timestamped_blocks_roundtrip():
    msg = TimestampedBlocks(
        (b"abc", b"defg"), sent_monotonic_ns=123456789, sent_wall_ns=987654321
    )
    decoded = decode_message(encode_message(msg))
    assert isinstance(decoded, TimestampedBlocks)
    assert isinstance(decoded, Blocks)  # every receive path handles it
    assert decoded.blocks == (b"abc", b"defg")
    assert decoded.sent_monotonic_ns == 123456789
    assert decoded.sent_wall_ns == 987654321


def test_plain_blocks_unchanged_on_wire():
    """New receiver <- old sender: tag 2 frames decode exactly as before."""
    decoded = decode_message(encode_message(Blocks((b"xy",))))
    assert type(decoded) is Blocks
    assert decoded.blocks == (b"xy",)


def test_old_receiver_resets_on_unknown_tag():
    """Old receiver <- new sender: a pre-r9 decoder has no tag 12 branch, so
    the frame MUST reject (connection reset, per wire-format §7) — same
    contract every unknown tag already obeys."""
    frame = bytearray(encode_message(
        TimestampedBlocks((b"z",), sent_monotonic_ns=1, sent_wall_ns=2)
    ))
    assert frame[0] == 12
    # Emulate the pre-r9 tag table: any tag beyond it rejects.
    frame[0] = 200
    with pytest.raises(SerdeError):
        decode_message(bytes(frame))


def test_wall_jump_detection():
    """The monotonic stamp's purpose: consecutive frames whose wall delta
    disagrees with the monotonic delta mean the sender's wall clock STEPPED
    — the receiver drops that frame's transit sample."""
    from mysticeti_tpu.net_sync import WALL_JUMP_TOLERANCE_US
    from mysticeti_tpu.network import wall_jump_us

    t0 = (1_000_000_000, 5_000_000_000)
    # Both clocks advanced 1s: consistent, well inside tolerance.
    steady = (t0[0] + 1_000_000_000, t0[1] + 1_000_000_000)
    assert wall_jump_us(t0, steady) == 0
    # 30ms of NTP slew over the gap: tolerated.
    slew = (t0[0] + 1_000_000_000, t0[1] + 1_030_000_000)
    assert wall_jump_us(t0, slew) <= WALL_JUMP_TOLERANCE_US
    # A 2s wall STEP (backwards or forwards) while monotonic moved 1s.
    jumped = (t0[0] + 1_000_000_000, t0[1] + 3_000_000_000)
    assert wall_jump_us(t0, jumped) > WALL_JUMP_TOLERANCE_US
    jumped_back = (t0[0] + 1_000_000_000, t0[1] - 1_000_000_000)
    assert wall_jump_us(t0, jumped_back) > WALL_JUMP_TOLERANCE_US


def test_disseminator_stamps_only_when_knob_on():
    from mysticeti_tpu.config import SynchronizerParameters
    from mysticeti_tpu.synchronizer import BlockDisseminator

    def make(knob):
        return BlockDisseminator(
            connection=None, block_store=None, block_ready=None,
            parameters=SynchronizerParameters(timestamp_frames=knob),
        )

    off = make(False)._blocks_message((b"b",))
    assert type(off) is Blocks
    on = make(True)._blocks_message((b"b",))
    assert isinstance(on, TimestampedBlocks)
    assert on.sent_wall_ns > 0 and on.sent_monotonic_ns > 0


# -- the deterministic 10-node sim: merge + dump byte-identity ---------------


class _SimNodeNetwork:
    def __init__(self, queue):
        self.connections = queue

    async def stop(self):
        pass


def _build_node(committee, signers, authority, tmp_dir, sim_net, parameters,
                recorder=None, stages=None):
    wal_writer, wal_reader = walf(os.path.join(tmp_dir, f"wal-{authority}"))
    recovered, observer_recovered = BlockStore.open(
        authority, wal_reader, wal_writer, committee
    )
    handler = TestBlockHandler(
        last_transaction=authority * 1_000_000,
        committee=committee,
        authority=authority,
    )
    core = Core(
        block_handler=handler,
        authority=authority,
        committee=committee,
        parameters=parameters,
        recovered=recovered,
        wal_writer=wal_writer,
        options=CoreOptions.test(),
        signer=signers[authority],
    )
    observer = TestCommitObserver(
        core.block_store, committee, recovered_state=observer_recovered
    )
    if recorder is not None:
        observer.recorder = recorder
    return NetworkSyncer(
        core,
        observer,
        _SimNodeNetwork(sim_net.node_connections[authority]),
        parameters=parameters,
        recorder=recorder,
        stages=stages,
    )


async def _run_traced_fleet(n, tmp_dir, virtual_seconds, recorders,
                            clocks=None):
    committee = Committee.new_test([1] * n)
    signers = Committee.benchmark_signers(n)
    parameters = Parameters(leader_timeout_s=1.0)
    parameters.synchronizer.timestamp_frames = True
    sim_net = SimulatedNetwork(n)
    if clocks is not None:
        # Made on the virtual loop, as validator.py makes a validator's.
        clocks.extend(_validators_clock(tmp_dir, a) for a in range(n))
        for recorder, clock in zip(recorders, clocks):
            recorder.stages = clock
    nodes = [
        _build_node(committee, signers, a, tmp_dir, sim_net, parameters,
                    recorder=recorders[a],
                    stages=clocks[a] if clocks is not None else None)
        for a in range(n)
    ]
    for node in nodes:
        await node.start()
    await sim_net.connect_all()
    await asyncio.sleep(virtual_seconds)
    for node in nodes:
        await node.stop()
    sim_net.close()
    # Recorder dumps must be taken ON the virtual loop (a production dump
    # runs where the incident is; and is_simulated() gates the wall stamp).
    dumps = [recorders[a].snapshot_bytes() for a in range(n)]
    return nodes, dumps


def _validators_clock(tmp_dir, authority):
    """The stage clock ``Validator._make_clock`` makes, where it is called."""
    from mysticeti_tpu.config import PrivateConfig
    from mysticeti_tpu.metrics import Metrics
    from mysticeti_tpu.validator import Validator

    validator = Validator()
    validator.metrics = Metrics()
    clock = validator._make_clock(PrivateConfig.new_in_dir(
        authority, os.path.join(tmp_dir, f"v{authority}")))
    assert validator._host_clock() is None  # nothing that measures the host
    return clock


# sha256 over the four dumps of ``_run_traced_fleet(4, ..., 6.0)`` at seed 23
# as the tree before the validator's ring (PR 37) wrote them.  A PR that
# adds an event to simulated runs moves it, and says so here.
SIM_DUMPS_BEFORE_THE_RING = (
    "2b816902c58ce1566f5ce6a4a1290690ee74635fc26da61c4b3e50883596a2e8")


def test_a_simulated_validator_has_no_ring_and_dumps_what_it_dumped(tmp_path):
    """Under the simulator the validator's clock has no ring, so the
    recorder's document has no ``"stages"`` and the ring's events
    (``slow-round``, ``shed``) are not recorded: a seeded fleet whose nodes
    were handed that clock dumps, byte for byte, what one that was handed
    none dumps, and what the tree before the ring dumped."""
    import hashlib

    def dumps_of(name, clocks):
        (tmp_path / name).mkdir()
        recorders = [FlightRecorder(authority=a) for a in range(4)]
        _, dumps = run_simulation(
            _run_traced_fleet(4, str(tmp_path / name), 6.0, recorders,
                              clocks=clocks), seed=23)
        return dumps

    clocks = []
    clocked, bare = dumps_of("clocked", clocks), dumps_of("bare", None)
    assert len(clocks) == 4
    assert all(clock.ring_seconds == 0 for clock in clocks)
    assert all(clock.stages == spans.NODE_STAGES for clock in clocks)
    # The clock still books what runs on the virtual clock.
    assert clocks[0].totals()["leader_wait"]["count"] > 10
    assert clocks[0].totals()["core_command"]["count"] == 0
    assert clocked == bare
    for dump in clocked:
        doc = json.loads(dump)
        assert "stages" not in doc
        assert not {e["kind"] for e in doc["events"]} & {"slow-round", "shed"}
    assert (hashlib.sha256(b"\n".join(clocked)).hexdigest()
            == SIM_DUMPS_BEFORE_THE_RING)


def _traced_fleet_run(tmp_dir, seed, n=10):
    recorders = [FlightRecorder(authority=a) for a in range(n)]
    tracer = spans.start_from_env()
    assert tracer is not None
    try:
        nodes, dumps = run_simulation(
            _run_traced_fleet(n, tmp_dir, 8.0, recorders), seed=seed
        )
    finally:
        spans.stop_from_env()
    committed = [
        list(node.syncer.commit_observer.committed_leaders) for node in nodes
    ]
    path = os.environ["MYSTICETI_TRACE"].replace("%p", str(os.getpid()))
    with open(path, "rb") as f:
        return f.read(), committed, dumps, path


def test_ten_node_merge_and_dumps_byte_identical(tmp_path, monkeypatch):
    """(a) of the acceptance tests: same seed => byte-identical raw trace,
    byte-identical MERGED fleet trace, and byte-identical flight-recorder
    dumps on every node; plus the journeys are genuinely stitched (author's
    propose + per-peer transit + every pipeline stage)."""
    from tools.fleet_trace import merge

    monkeypatch.setenv("MYSTICETI_TRACE", str(tmp_path / "trace-%p.json"))
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    raw_a, committed, dumps_a, path = _traced_fleet_run(
        str(tmp_path / "a"), seed=23
    )
    trace_a = tmp_path / "run-a.json"
    trace_a.write_bytes(raw_a)
    raw_b, _, dumps_b, _ = _traced_fleet_run(str(tmp_path / "b"), seed=23)

    assert raw_a == raw_b
    assert dumps_a == dumps_b
    # The dumps hold real event streams (commits + connection churn).
    doc = json.loads(dumps_a[0])
    kinds = {e["kind"] for e in doc["events"]}
    assert "commit" in kinds and "peer-connect" in kinds
    assert doc["dropped"] == 0 and doc["recorded"] == len(doc["events"])
    assert "generated_unix" not in doc  # sim dumps carry no wall clock

    merged_a = merge([str(trace_a)])
    merged_b = merge([path])
    canon = lambda d: json.dumps(d, sort_keys=True).encode()  # noqa: E731
    assert canon({**merged_a, "inputs": []}) == canon({**merged_b, "inputs": []})

    # Stitching: every committed mid-sequence leader's journey names its
    # author, carries per-peer transit, and crosses every pipeline stage.
    sequences = [seq for seq in committed if seq]
    assert sequences and all(len(s) >= 20 for s in sequences)
    leader = sequences[0][len(sequences[0]) // 2]
    label = format_ref(leader)
    journey = next(j for j in merged_a["journeys"] if j["block"] == label)
    assert journey["author"] == leader.authority
    assert journey["fully_stitched"] and journey["propose_anchored"]
    assert journey["transit_ms"], journey
    seen_stages = set()
    for node_stages in journey["nodes"].values():
        seen_stages.update(node_stages)
    assert set(PIPELINE_STAGES) <= seen_stages
    assert merged_a["fully_stitched"] >= 20
    assert merged_a["transit_observations"] > 0
    # The skew table is embedded and names every authority.
    assert set(merged_a["skew"]["offsets_us"]) == {str(a) for a in range(10)}


# -- skew estimator: injected offsets recovered ------------------------------


def _synthetic_trace(path, authority, peers, offsets_us, base_delay_us=50_000):
    """One node's trace containing only transit spans: raw transit from
    peer p = base delay + jitter + (own offset - p's offset); one
    zero-jitter frame per link pins the minimum."""
    events = [
        {"args": {"name": f"A{authority}"}, "name": "thread_name",
         "ph": "M", "pid": 1, "tid": authority},
    ]
    jitters = (0, 1_500, 3_000, 700)
    for p in peers:
        for i, jitter in enumerate(jitters):
            raw = base_delay_us + jitter + (
                offsets_us[authority] - offsets_us[p]
            )
            events.append(
                {
                    "args": {
                        "block": f"A{p}R{i + 1}#aabbccdd",
                        "src": p,
                        "raw_us": raw,
                    },
                    "cat": "pipeline",
                    "dur": max(0, raw),
                    "name": "transit",
                    "ph": "X",
                    "pid": 1,
                    "tid": authority,
                    "ts": 1_000_000 * (i + 1),
                }
            )
    doc = {
        "displayTimeUnit": "ms",
        "otherData": {"clock_runtime_us": 0, "clock_wall_us": 0},
        "traceEvents": events,
    }
    with open(path, "w") as f:
        json.dump(doc, f)


def test_skew_estimator_recovers_injected_offsets(tmp_path):
    """(b): per-node clock offsets injected into synthetic transit data are
    recovered within tolerance by min-transit alignment."""
    from tools.fleet_trace import merge

    offsets_us = {0: 0, 1: 40_000, 2: -25_000}  # +40ms, -25ms vs node 0
    paths = []
    for a in range(3):
        peers = [p for p in range(3) if p != a]
        path = str(tmp_path / f"trace-{a}.json")
        _synthetic_trace(path, a, peers, offsets_us)
        paths.append(path)
    doc = merge(paths)
    skew = doc["skew"]["offsets_us"]
    for a, injected in offsets_us.items():
        assert abs(skew[str(a)] - injected) <= 2_000, (skew, injected)
    # Corrected link latency lands back on the true delay floor (50ms).
    for link, row in doc["skew"]["links"].items():
        assert abs(row["latency_min_ms"] - 50.0) <= 2.0, (link, row)


# -- shared stage extraction: truncated multi-node trace ---------------------


def _pipeline_trace_doc():
    events = []
    for a in (0, 1):
        events.append({"args": {"name": f"A{a}"}, "name": "thread_name",
                       "ph": "M", "pid": 1, "tid": a})
    for label, author in (("A0R5#11112222", 0), ("A1R6#33334444", 1)):
        events.append({"args": {"block": label}, "cat": "pipeline", "dur": 10,
                       "name": "propose", "ph": "X", "pid": 1, "tid": author,
                       "ts": 1000})
        for a in (0, 1):
            for i, stage in enumerate(PIPELINE_STAGES):
                if a == author and stage in ("receive", "verify", "dag_add"):
                    continue
                events.append({
                    "args": {"block": label}, "cat": "pipeline",
                    "dur": 100 * (i + 1), "name": stage, "ph": "X",
                    "pid": 1, "tid": a, "ts": 2000 + 1000 * i,
                })
    return {"displayTimeUnit": "ms",
            "otherData": {"clock_runtime_us": 0, "clock_wall_us": 0},
            "traceEvents": events}


def test_truncated_trace_same_boundaries_in_both_tools(tmp_path):
    """Regression (satellite 3): trace_report --critical-path and the fleet
    merge share ONE salvage + stage-extraction path, so a multi-node trace
    truncated mid-flush yields the SAME committed leaders and the same
    per-leader stage sets in both tools."""
    from tools.fleet_trace import merge
    from tools.trace_report import attribute_critical_paths, load_events, load_spans

    full = json.dumps(_pipeline_trace_doc())
    # Tear inside the last event object: both tools must salvage the same
    # complete prefix.
    torn = full[: full.rfind('{"args"') + 40]
    path = tmp_path / "torn.json"
    path.write_text(torn)

    events, note = load_events(str(path))
    assert "truncated" in note
    report_chains = {
        (rec["leader"], rec["track"][1]): set(rec["stages"])
        for rec in attribute_critical_paths(load_spans(events))
    }
    merged = merge([str(path)])
    merge_chains = {}
    for j in merged["journeys"]:
        for a, stages in j["nodes"].items():
            merge_chains[(j["block"], int(a))] = {
                s for s in stages if s in PIPELINE_STAGES
            }
    assert set(report_chains) == set(merge_chains)
    for key, stages in report_chains.items():
        assert merge_chains[key] == stages, key
    # And the torn tail really cost something vs the intact file.
    intact = tmp_path / "full.json"
    intact.write_text(full)
    assert len(merge([str(intact)])["journeys"]) >= len(merged["journeys"])


# -- flight recorder unit + dump triggers ------------------------------------


def test_flight_recorder_ring_bounds_and_dump(tmp_path):
    rec = FlightRecorder(authority=3, capacity=4)
    for i in range(7):
        rec.record("evt", i=i)
    assert rec.recorded == 7 and rec.dropped == 3
    events = rec.events()
    assert len(events) == 4 and events[-1]["i"] == 6
    assert rec.events(last=2)[0]["i"] == 5

    path = str(tmp_path / "fr.json")
    written = rec.dump("shutdown", path=path)
    assert written == path and not os.path.exists(path + ".tmp")
    doc = json.loads(open(path).read())
    assert doc["authority"] == 3 and len(doc["events"]) == 4
    assert rec.dumps[0]["trigger"] == "shutdown"
    assert rec.dumps[0]["file"] == "fr.json"  # basenames only (determinism)


def test_flight_recorder_alert_dump_is_debounced(tmp_path):
    path = str(tmp_path / "fr.json")
    rec = FlightRecorder(authority=0, dump_path=path, alert_debounce_s=1e9)
    rec.on_alert("round-stall", None, "receive", 12.0, "stalled")
    assert os.path.exists(path + ".alert")
    os.unlink(path + ".alert")
    rec.on_alert("round-stall", None, "receive", 13.0, "still stalled")
    assert not os.path.exists(path + ".alert")  # inside the debounce window
    # Both alerts are in the ring regardless.
    assert sum(1 for e in rec.events() if e["kind"] == "slo-alert") == 2


def test_debug_flight_recorder_route(tmp_path):
    async def scenario():
        metrics = Metrics()
        rec = FlightRecorder(authority=7, metrics=metrics)
        rec.record("probe", detail="hello")
        server = await serve_metrics(
            metrics, "127.0.0.1", 0, flight_recorder=rec
        )
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /debug/flight-recorder HTTP/1.1\r\nHost: t\r\n\r\n")
        await writer.drain()
        payload = await reader.read()
        writer.close()
        server.close()
        await server.wait_closed()
        return payload

    payload = asyncio.run(scenario())
    head, body = payload.split(b"\r\n\r\n", 1)
    assert b"200 OK" in head and b"application/json" in head
    doc = json.loads(body)
    assert doc["authority"] == 7
    assert doc["events"][0]["kind"] == "probe"


# -- chaos integration: safety failure dumps every live node -----------------


def test_chaos_safety_failure_dumps_every_live_node(tmp_path):
    """(c): the moment the SafetyChecker fails, run_chaos_sim writes a valid
    flight-recorder dump for every live node before re-raising."""
    from mysticeti_tpu.chaos import FaultPlan, SafetyViolation, run_chaos_sim
    from mysticeti_tpu.types import BlockReference

    async def poison(harness):
        await asyncio.sleep(2.0)
        fake = BlockReference(9, 99, b"\xff" * 32)
        # A forged anchor at height 1 for authority 0: global prefix
        # consistency must fail at the end-of-run audit.
        harness.checker._anchors.setdefault(0, {})[1] = fake

    with pytest.raises(SafetyViolation):
        run_chaos_sim(
            FaultPlan(seed=5), 4, 6.0, str(tmp_path), extra_fault=poison
        )
    for a in range(4):
        path = tmp_path / f"flight-recorder-{a}.json"
        assert path.exists(), f"no dump for live node {a}"
        doc = json.loads(path.read_text())
        assert doc["authority"] == a
        assert any(e["kind"] == "commit" for e in doc["events"])


def test_chaos_recorder_dumps_byte_identical_same_seed(tmp_path):
    from mysticeti_tpu.chaos import CrashFault, FaultPlan, run_chaos_sim

    plan = FaultPlan(
        seed=11, crashes=[CrashFault(node=2, at_s=3.0, downtime_s=1.5)]
    )
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    report_a, _ = run_chaos_sim(plan, 4, 8.0, str(tmp_path / "a"))
    report_b, _ = run_chaos_sim(plan, 4, 8.0, str(tmp_path / "b"))
    assert report_a.recorder_dumps == report_b.recorder_dumps
    assert set(report_a.recorder_dumps) == {0, 1, 2, 3}
    crashed = json.loads(report_a.recorder_dumps[2])
    kinds = [e["kind"] for e in crashed["events"]]
    assert "crash" in kinds and "restart" in kinds


# -- fleetmon: flight-recorder embed + --dump-on-red -------------------------


def test_fleetmon_dump_on_red(tmp_path):
    import argparse

    from tools.fleetmon import run as fleetmon_run

    async def scenario():
        metrics = Metrics()
        rec = FlightRecorder(authority=0, metrics=metrics)
        rec.record("commit", height=4)
        server = await serve_metrics(
            metrics, "127.0.0.1", 0, flight_recorder=rec
        )
        port = server.sockets[0].getsockname()[1]
        args = argparse.Namespace(
            targets=[f"127.0.0.1:{port}", "127.0.0.1:1"],  # node 1 is dead
            fleet_dir=None, interval=0.1, duration=0.0, once=True,
            out=str(tmp_path / "fleetmon.json"), min_participation=0.0,
            max_ticks=10, no_dashboard=True, dump_on_red=True,
            max_loop_lag=0.0,
        )
        rc = await fleetmon_run(args)
        server.close()
        await server.wait_closed()
        return rc

    rc = asyncio.run(scenario())
    assert rc == 3  # the dead node fails the readiness gate
    artifact = json.loads((tmp_path / "fleetmon.json").read_text())
    # Flight-recorder summary embedded: last events for the live node,
    # None for the unreachable one.
    summary = artifact["flight_recorder"]
    assert summary["0"]["last_events"][-1]["kind"] == "commit"
    assert summary["1"] is None
    # --dump-on-red wrote the live node's full ring next to the artifact.
    dump = tmp_path / "fleetmon.json.flight-0.json"
    assert dump.exists()
    assert artifact["flight_recorder_dumps"] == [dump.name]
    doc = json.loads(dump.read_text())
    assert doc["events"][0]["kind"] == "commit"
