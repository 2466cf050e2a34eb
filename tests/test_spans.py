"""Block-lifecycle span tracing: tracer unit semantics, the 10-node
deterministic-sim acceptance path (MYSTICETI_TRACE -> valid, reproducible
Chrome trace-event JSON + trace_report breakdown), and the verifier-path
telemetry scrape via the /metrics endpoint."""
import asyncio
import json
import os
import sys

from mysticeti_tpu import spans
from mysticeti_tpu.block_handler import TestBlockHandler
from mysticeti_tpu.block_store import BlockStore
from mysticeti_tpu.commit_observer import TestCommitObserver
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Parameters
from mysticeti_tpu.core import Core, CoreOptions
from mysticeti_tpu.net_sync import NetworkSyncer
from mysticeti_tpu.runtime.simulated import run_simulation
from mysticeti_tpu.simulated_network import SimulatedNetwork
from mysticeti_tpu.spans import PIPELINE_STAGES, SpanTracer, format_ref
from mysticeti_tpu.types import BlockReference
from mysticeti_tpu.wal import walf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _ref(authority=3, round_=7, tag=b"\x01"):
    return BlockReference(authority, round_, tag.ljust(32, b"\x00"))


# -- tracer unit semantics ----------------------------------------------------

def test_begin_end_records_completed_span():
    tracer = SpanTracer()
    ref = _ref()
    tracer.begin_span("dag_add", ref, authority=0, t=1.0)
    # A duplicate begin must NOT shrink the measured wait.
    tracer.begin_span("dag_add", ref, authority=0, t=2.0)
    tracer.end_span("dag_add", ref, authority=0, t=3.5)
    # Unmatched end: silently ignored.
    tracer.end_span("dag_add", _ref(tag=b"\x02"), authority=0, t=4.0)
    events = tracer.chrome_trace()["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 1
    assert xs[0]["name"] == "dag_add"
    assert xs[0]["ts"] == 1_000_000 and xs[0]["dur"] == 2_500_000
    assert xs[0]["args"]["block"] == format_ref(ref)


def test_tracks_are_split_by_authority_and_named():
    tracer = SpanTracer()
    ref = _ref()
    tracer.record_span("receive", ref, 0.0, t1=0.5, authority=2)
    tracer.record_span("receive", ref, 0.0, t1=0.5, authority=5)
    trace = tracer.chrome_trace()
    names = {
        e["tid"]: e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert names[2] == "A2" and names[5] == "A5"
    assert {e["tid"] for e in trace["traceEvents"] if e["ph"] == "X"} == {2, 5}


def test_write_is_atomic_and_valid_json(tmp_path):
    tracer = SpanTracer()
    tracer.record_span("commit", _ref(), 1.0, t1=2.0, authority=1)
    path = str(tmp_path / "trace.json")
    tracer.write(path)
    assert not os.path.exists(path + ".tmp")
    data = json.loads(open(path).read())
    assert any(e["ph"] == "X" for e in data["traceEvents"])


def test_event_cap_drops_instead_of_growing():
    tracer = SpanTracer()
    tracer.MAX_EVENTS = 3
    for i in range(5):
        tracer.record_span("commit", _ref(tag=bytes([i + 1])), 0.0, t1=1.0,
                           authority=0)
    assert len([e for e in tracer.chrome_trace()["traceEvents"]
                if e["ph"] == "X"]) == 3
    assert tracer.dropped == 2


# -- the always-on stage clock ------------------------------------------------


def test_the_service_stages_are_registered_stage_names():
    """Every name the stage clock books is in the central registry (the
    ``span-names`` lint parses it), and the sets that sort them agree."""
    assert spans.SERVICE_STAGES == (
        "service_decode", "service_pool_wait", "service_unpack",
        "service_pack", "service_launch", "service_fetch",
        "service_reply_build", "service_reply_wait", "service_gc",
        "service_loop_lag",
    )
    assert set(spans.SERVICE_STAGES) <= set(spans.STAGES)
    assert set(spans.REQUEST_STAGES) < set(spans.SERVICE_STAGES)
    assert spans.WAITING_STAGES < set(spans.SERVICE_STAGES)
    assert set(spans.BLOCK_PATH_STAGES) <= set(PIPELINE_STAGES)
    assert set(spans.NODE_STAGES) <= set(spans.STAGES)
    # A service request's span is labelled by (connection, req_id).
    assert format_ref(("c3", 17)) == "c3#17"


def test_a_stage_that_stands_alone_books_itself_and_its_span():
    tracer = SpanTracer()
    clock = spans.StageClock(spans.SERVICE_STAGES, ring_seconds=8,
                             tracer=tracer)
    with spans.stage("service_decode", clock) as decode:
        decode.since -= 0.5  # it began in a wait, half a second ago
        decode.ref = ("c0", 9)
    row = clock.totals()["service_decode"]
    assert row["count"] == 1 and 0.5 <= row["wall_s"] < 0.6
    assert row["cpu_s"] < 0.1  # the wait is wall, not CPU
    (event,) = [e for e in tracer.chrome_trace()["traceEvents"]
                if e["ph"] == "X"]
    assert (event["name"], event["args"]["block"]) == ("service_decode", "c0#9")
    (second,) = clock.export()["seconds"].values()
    assert second["service_decode"][0] == 1
    assert second["service_decode"][3] == row["wall_s"]


def test_block_path_stages_are_always_on_and_virtual_under_the_sim(tmp_path):
    """Without any tracer a validator books receive / verify / dag_add per
    received batch, and leader_wait per proposal, into
    ``block_stage_seconds``; under the simulator the
    clock is virtual, so two same-seed runs scrape identical text — and
    none of the node clock's stages that measure the host is booked
    there (a stage that booked nothing is not rendered)."""
    from prometheus_client import generate_latest

    from mysticeti_tpu.metrics import Metrics

    def scrape(name):
        (tmp_path / name).mkdir()
        metrics = [Metrics() for _ in range(4)]
        run_simulation(
            _run_nodes(4, str(tmp_path / name), 3.0, metrics=metrics), seed=5)
        return [
            line for line in
            generate_latest(metrics[0].registry).decode().splitlines()
            if line.startswith("block_stage_seconds")
        ]

    first, second = scrape("a"), scrape("b")
    assert first == second
    counts = {line.split('stage="')[1].split('"')[0]: float(line.split()[-1])
              for line in first if line.startswith("block_stage_seconds_count")}
    assert set(counts) == set(spans.BLOCK_PATH_STAGES) | {"leader_wait"}
    assert set(counts) < set(spans.NODE_STAGES)
    assert all(count > 10 for count in counts.values()), counts
    assert spans.active() is None


# -- the 10-node deterministic-sim acceptance path ---------------------------

class _SimNodeNetwork:
    def __init__(self, queue):
        self.connections = queue

    async def stop(self):
        pass


def _build_node(committee, signers, authority, tmp_dir, sim_net, parameters,
                metrics=None):
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
    return NetworkSyncer(
        core,
        observer,
        _SimNodeNetwork(sim_net.node_connections[authority]),
        parameters=parameters,
        metrics=metrics,
    )


async def _run_nodes(n, tmp_dir, virtual_seconds, metrics=None):
    committee = Committee.new_test([1] * n)
    signers = Committee.benchmark_signers(n)
    parameters = Parameters(leader_timeout_s=1.0)
    sim_net = SimulatedNetwork(n)
    nodes = [
        _build_node(committee, signers, a, tmp_dir, sim_net, parameters,
                    metrics=metrics[a] if metrics else None)
        for a in range(n)
    ]
    for node in nodes:
        await node.start()
    await sim_net.connect_all()
    await asyncio.sleep(virtual_seconds)
    for node in nodes:
        await node.stop()
    sim_net.close()
    return nodes


def _traced_sim_run(tmp_dir, seed):
    """One traced 10-node sim: returns (trace bytes, committed leader refs)."""
    tracer = spans.start_from_env()
    assert tracer is not None
    try:
        nodes = run_simulation(_run_nodes(10, tmp_dir, 8.0), seed=seed)
    finally:
        spans.stop_from_env()
    committed = [
        list(node.syncer.commit_observer.committed_leaders) for node in nodes
    ]
    with open(os.environ["MYSTICETI_TRACE"].replace("%p", str(os.getpid())),
              "rb") as f:
        return f.read(), committed


def test_ten_node_sim_trace_report_and_verifier_metrics(tmp_path, monkeypatch, capsys):
    """The acceptance path in one test: a 10-node deterministic sim under
    MYSTICETI_TRACE yields a valid Chrome trace (all pipeline stages present
    for a committed block, per-track monotone virtual timestamps,
    byte-identical across two same-seed runs), trace_report prints the
    per-stage breakdown from it, and the verifier batch-size/padding/route
    series are scrapeable over the /metrics HTTP endpoint."""
    trace_path = tmp_path / "trace-%p.json"
    monkeypatch.setenv("MYSTICETI_TRACE", str(trace_path))

    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    raw_a, committed = _traced_sim_run(str(tmp_path / "a"), seed=17)
    raw_b, _ = _traced_sim_run(str(tmp_path / "b"), seed=17)

    # Determinism: virtual-clocked spans of a seeded sim are byte-identical.
    assert raw_a == raw_b

    data = json.loads(raw_a)
    events = data["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "sim produced no spans"

    # Every pipeline stage is present for one mid-sequence committed leader.
    sequences = [seq for seq in committed if seq]
    assert sequences and all(len(s) >= 20 for s in sequences), [
        len(s) for s in committed
    ]
    leader = sequences[0][len(sequences[0]) // 2]
    label = format_ref(leader)
    stages_for_leader = {e["name"] for e in xs if e["args"]["block"] == label}
    assert set(PIPELINE_STAGES) <= stages_for_leader, (
        label, sorted(stages_for_leader)
    )

    # Virtual timestamps: non-negative, monotone per authority track.
    last_ts = {}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["ts"] >= last_ts.get(e["tid"], 0), e
        last_ts[e["tid"]] = e["ts"]
    # One named track per simulated authority.
    track_names = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {f"A{i}" for i in range(10)} <= track_names

    # trace_report prints a per-stage latency breakdown from the file.
    from tools.trace_report import main as report_main

    path = str(trace_path).replace("%p", str(os.getpid()))
    assert report_main([path]) == 0
    out = capsys.readouterr().out
    for stage in PIPELINE_STAGES:
        assert stage in out, out
    assert "p50_ms" in out and "p99_ms" in out

    # Verifier-path telemetry reaches the /metrics endpoint (real asyncio:
    # the collector + breaker + HTTP server need threads/sockets the
    # simulator forbids).
    scrape = asyncio.run(_verifier_metrics_scrape())
    assert "verify_dispatch_batch_size" in scrape
    assert 'verify_padding_wasted_total{backend="hybrid-tpu"}' in scrape
    assert "verifier_fallback_total 0.0" in scrape
    assert "verify_batch_size" in scrape


async def _verifier_metrics_scrape() -> str:
    from mysticeti_tpu.block_validator import (
        BatchedSignatureVerifier,
        CpuSignatureVerifier,
        FallbackSignatureVerifier,
    )
    from mysticeti_tpu.metrics import Metrics, serve_metrics
    from mysticeti_tpu.types import Share, StatementBlock

    metrics = Metrics()
    committee = Committee.new_for_benchmarks(4)
    signers = Committee.benchmark_signers(4)

    class FakeTpu(CpuSignatureVerifier):
        """CPU oracle pretending to be a bucket-padded accelerator."""

        def padded_batch(self, n):
            return 256 if n <= 256 else n

    # Breaker closed: the block batch goes to the "accelerator".
    hybrid = FallbackSignatureVerifier(
        tpu=FakeTpu(), cpu=CpuSignatureVerifier(), metrics=metrics,
    )
    collector = BatchedSignatureVerifier(committee, hybrid, metrics=metrics)
    genesis = [StatementBlock.new_genesis(i) for i in range(4)]
    prev = [g.reference for g in genesis]
    blocks = [
        StatementBlock.build(a, 1, prev, [Share(bytes([a]))], signer=signers[a])
        for a in range(1, 4)
    ]
    oks = await collector.verify_blocks(blocks)
    assert all(oks)
    server = await serve_metrics(metrics, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
    await writer.drain()
    payload = await reader.read()
    writer.close()
    server.close()
    await server.wait_closed()
    return payload.decode()
