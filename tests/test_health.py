"""Fleet health plane: probe/watchdog unit semantics, commit critical-path
attribution from the span stream, cluster aggregation, the deterministic
chaos-sim acceptance path (seeded partition + crash-restart -> SLO alerts
naming the stalled authority and stage, byte-identical health timeline
across same-seed runs), and the trace_report robustness satellites."""
import asyncio
import json
import os
import sys

import pytest

from mysticeti_tpu.health import (
    Alert,
    CriticalPathAnalyzer,
    FleetHealthMonitor,
    HealthProbe,
    SLOThresholds,
    cluster_snapshot,
    cluster_snapshot_from_texts,
    node_health_from_series,
)
from mysticeti_tpu.metrics import Metrics, serve_metrics
from mysticeti_tpu.spans import SpanTracer
from mysticeti_tpu.types import BlockReference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# -- stubs --------------------------------------------------------------------


class _FakeWal:
    def __init__(self):
        self.backlog = False

    def pending(self):
        return self.backlog


class _FakeStore:
    def __init__(self, last_seen):
        self.last_seen = last_seen

    def last_seen_by_authority(self, a):
        return self.last_seen.get(a, 0)


class _FakeCore:
    def __init__(self, authority=0, n=4):
        self.authority = authority
        self.round = 0
        self.wal_writer = _FakeWal()
        self.block_store = _FakeStore({a: 0 for a in range(n)})

    def current_round(self):
        return self.round


class _FakeObserver:
    class _Interp:
        last_height = 0

    def __init__(self):
        self.commit_interpreter = self._Interp()


def _probe(slo=None, n=4, metrics=None):
    clock = {"t": 0.0}
    probe = HealthProbe(
        0, n, metrics=metrics, slo=slo or SLOThresholds(),
        clock=lambda: clock["t"],
    )
    core = _FakeCore(0, n)
    observer = _FakeObserver()
    probe.attach(core=core, net_syncer=None, commit_observer=observer)
    return probe, core, observer, clock


# -- probe / watchdog units ---------------------------------------------------


def test_slo_thresholds_json_round_trip():
    slo = SLOThresholds(
        min_commit_rate=1.5, max_round_stall_s=7.0, max_commit_stall_s=9.0,
        max_authority_lag_rounds=12, max_breaker_open_fraction=0.25,
        min_participation=0.8,
    )
    assert SLOThresholds.from_dict(json.loads(json.dumps(slo.to_dict()))) == slo


def test_probe_rates_and_frontier():
    probe, core, observer, clock = _probe()
    s0 = probe.sample()
    assert s0["round"] == 0 and s0["status"] == "ok"
    # One second later: 4 rounds and 2 commits happened; peer 2 lags.
    clock["t"] = 1.0
    core.round = 4
    observer.commit_interpreter.last_height = 2
    core.block_store.last_seen = {1: 4, 2: 1, 3: 6}
    s1 = probe.sample()
    assert s1["round_advance_rate"] > 0
    assert s1["commit_rate"] > 0
    assert s1["authority_lag_rounds"] == {"1": 0, "2": 3, "3": 0}
    assert s1["frontier_skew_rounds"] == 2  # peer 3 is at round 6, we at 4
    assert s1["round_stall_s"] == 0.0


def test_watchdog_round_stall_fires_once_and_clears():
    probe, core, observer, clock = _probe(
        slo=SLOThresholds(max_round_stall_s=5.0)
    )
    probe.sample()
    clock["t"] = 6.0
    s = probe.sample()
    assert s["status"] == "degraded"
    assert [a.kind for a in probe.alerts] == ["round-stall"]
    assert probe.alerts[0].stage == "receive"
    assert probe.alerts[0].observer == 0
    # Still stalled: NO duplicate alert (transition semantics).
    clock["t"] = 8.0
    probe.sample()
    assert len(probe.alerts) == 1
    # Round advances: the alert clears, a later stall re-fires.
    clock["t"] = 9.0
    core.round = 3
    assert probe.sample()["status"] == "ok"
    clock["t"] = 20.0
    probe.sample()
    assert [a.kind for a in probe.alerts] == ["round-stall", "round-stall"]


def test_watchdog_commit_rate_floor_does_not_collide_with_stall():
    """min_commit_rate uses its own alert kind: sharing commit-stall's
    firing key would let the healthy stall check clear it every tick and
    the rate alert re-fire per sample (per-tick spam)."""
    probe, core, observer, clock = _probe(
        slo=SLOThresholds(
            max_round_stall_s=0.0, max_commit_stall_s=100.0,
            min_commit_rate=5.0,
        )
    )
    probe.sample()
    for t in (1.0, 2.0, 3.0):
        clock["t"] = t
        core.round += 1  # rounds move; commits crawl below the floor
        observer.commit_interpreter.last_height += 1
        probe.sample()
    kinds = [a.kind for a in probe.alerts]
    assert kinds == ["commit-rate"], kinds  # fired exactly once
    assert probe.alerts[0].stage == "commit"


def test_watchdog_authority_lag_names_the_straggler():
    probe, core, observer, clock = _probe(
        slo=SLOThresholds(max_round_stall_s=0.0, max_authority_lag_rounds=5)
    )
    core.round = 10
    core.block_store.last_seen = {1: 10, 2: 2, 3: 9}
    probe.sample()
    assert len(probe.alerts) == 1
    alert = probe.alerts[0]
    assert alert.kind == "authority-lag" and alert.authority == 2
    assert alert.stage == "receive"
    assert "authority 2" in alert.detail


def test_probe_gauges_and_alert_counter():
    metrics = Metrics()
    probe, core, observer, clock = _probe(
        slo=SLOThresholds(max_round_stall_s=1.0), metrics=metrics
    )
    core.round = 7
    core.block_store.last_seen = {1: 7, 2: 5, 3: 7}
    probe.sample()
    clock["t"] = 2.0
    probe.sample()  # round stalled for 2 s -> alert + degraded gauge
    text = metrics.expose().decode()
    assert "mysticeti_health_round_advance_rate" in text
    assert 'mysticeti_health_authority_lag_rounds{authority="2"} 2.0' in text
    assert "mysticeti_health_status 0.0" in text
    assert (
        'mysticeti_health_slo_alerts_total{authority="",kind="round-stall"'
        ',stage="receive"} 1.0' in text
    )


def test_diagnosis_and_health_route():
    probe, core, observer, clock = _probe(
        slo=SLOThresholds(max_round_stall_s=1.0)
    )
    probe.sample()
    doc = probe.diagnosis()
    assert doc["status"] == "ok" and doc["authority"] == 0
    assert doc["signals"]["round"] == 0

    async def scrape(path):
        metrics = Metrics()
        server = await serve_metrics(metrics, "127.0.0.1", 0, health_probe=probe)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        await writer.drain()
        payload = await reader.read()
        writer.close()
        server.close()
        await server.wait_closed()
        head, _, body = payload.partition(b"\r\n\r\n")
        return head.split(b"\r\n")[0].decode(), body

    status, body = asyncio.run(scrape("/health"))
    assert "200" in status
    assert json.loads(body)["status"] == "ok"
    # Degrade: the route turns 503 — a readiness gate, not just a document.
    clock["t"] = 5.0
    probe.sample()
    status, body = asyncio.run(scrape("/health"))
    assert "503" in status
    assert json.loads(body)["status"] == "degraded"


def _tripping_collector(metrics=None):
    """A collector over the ``tpu`` flavor's breaker whose accelerator is
    down: the first batch trips it and the oracle answers."""
    from mysticeti_tpu.block_validator import (
        BatchedSignatureVerifier,
        FallbackSignatureVerifier,
        SignatureVerifier,
    )
    from mysticeti_tpu.committee import Committee

    class Down(SignatureVerifier):
        def verify_signatures(self, public_keys, digests, signatures):
            raise ConnectionError("verifier service is down")

    class Accepts(SignatureVerifier):
        def verify_signatures(self, public_keys, digests, signatures):
            return [True] * len(signatures)

    fallback = FallbackSignatureVerifier(
        tpu=Down(), cpu=Accepts(), metrics=metrics
    )
    fallback._breaker_clock = lambda: 0.0  # frozen: never re-probes
    collector = BatchedSignatureVerifier(
        Committee.new_for_benchmarks(4), fallback, metrics=metrics
    )
    batch = ([b"k" * 32], [b"d" * 32], [b"s" * 64])
    return collector, lambda: fallback.verify_signatures(*batch)


def test_health_state_carries_the_breaker_and_no_pin():
    collector, trip = _tripping_collector()
    state = collector.health_state()
    assert sorted(state) == [
        "backend", "breaker_open", "pipeline_depth", "pipeline_inflight",
    ]
    assert state["breaker_open"] is False
    assert trip() == [True]  # the oracle answered the batch that tripped it
    state = collector.health_state()
    assert state["breaker_open"] is True
    assert state["backend"] == "hybrid-cpu"


def test_health_document_and_gauge_follow_the_breaker():
    from mysticeti_tpu.flight_recorder import FlightRecorder

    metrics = Metrics()
    collector, trip = _tripping_collector(metrics)
    recorder = FlightRecorder(0)
    clock = {"t": 0.0}
    probe = HealthProbe(
        0, 4, metrics=metrics, clock=lambda: clock["t"], recorder=recorder
    )
    probe.attach(
        core=_FakeCore(0, 4), commit_observer=_FakeObserver(),
        block_verifier=collector,
    )
    probe.sample()
    verifier = probe.diagnosis()["signals"]["verifier"]
    assert verifier["breaker_open"] is False
    assert not [key for key in verifier if "pin" in key]
    assert "mysticeti_health_verifier_breaker_open 0.0" in (
        metrics.expose().decode()
    )
    trip()
    clock["t"] = 1.0
    probe.sample()
    assert probe.diagnosis()["signals"]["verifier"]["breaker_open"] is True
    text = metrics.expose().decode()
    assert "mysticeti_health_verifier_breaker_open 1.0" in text
    assert "verifier_fallback_total 1.0" in text
    assert "pinned" not in text
    kinds = [event["kind"] for event in recorder.events()]
    assert kinds.count("breaker") == 1 and "pin" not in kinds


# -- commit critical-path attribution ----------------------------------------


def _ref(authority, round_, tag):
    return BlockReference(authority, round_, bytes([tag]).ljust(32, b"\x00"))


def test_critical_path_attribution_from_span_stream():
    metrics = Metrics()
    tracer = SpanTracer()
    analyzer = CriticalPathAnalyzer(metrics=metrics, authority=0)
    tracer.add_sink(analyzer.on_span)
    leader = _ref(3, 7, 1)
    # The pipeline chain as the instrumentation records it, receive
    # dominating (authority 3 was slow reaching us).
    tracer.record_span("receive", leader, 0.0, t1=2.0, authority=0)
    tracer.record_span("verify", leader, 2.0, t1=2.1, authority=0)
    tracer.record_span("dag_add", leader, 2.1, t1=2.2, authority=0)
    tracer.record_span("proposal_wait", leader, 2.2, t1=2.5, authority=0)
    tracer.record_span("finalize", leader, 2.5, t1=2.6, authority=0)
    # Another node's track must not pollute this analyzer.
    tracer.record_span("receive", leader, 0.0, t1=9.0, authority=1)
    assert analyzer.leaders_attributed == 0  # no commit span yet
    tracer.record_span("commit", leader, 2.5, t1=2.55, authority=0)
    assert analyzer.leaders_attributed == 1
    top = analyzer.top_blocking()
    assert top[0]["stage"] == "receive" and top[0]["authority"] == 3
    assert top[0]["leaders"] == 1 and top[0]["blocked_s"] == pytest.approx(2.0)
    text = metrics.expose().decode()
    assert 'commit_critical_path_seconds_count{stage="receive"} 1.0' in text
    assert 'commit_critical_path_seconds_count{stage="commit"} 1.0' in text
    # Non-pipeline stages never enter the attribution index.
    tracer.record_span("verify_dispatch", _ref(1, 1, 2), 0.0, t1=1.0, authority=0)
    assert _ref(1, 1, 2) not in analyzer._stages


# -- cluster aggregation ------------------------------------------------------


def _node_text(round_, commit_round, committed, lags, alerts=0):
    lines = [
        f"threshold_clock_round {round_}",
        f"commit_round {commit_round}",
        "mysticeti_health_commit_rate 2.5",
        "mysticeti_health_round_advance_rate 4.0",
        "mysticeti_health_status 1",
    ]
    for a, count in committed.items():
        lines.append(
            f'committed_leaders_total{{authority="{a}",status="committed"}} '
            f"{count}"
        )
    for a, lag in lags.items():
        lines.append(
            f'mysticeti_health_authority_lag_rounds{{authority="{a}"}} {lag}'
        )
    if alerts:
        lines.append(
            'mysticeti_health_slo_alerts_total{kind="round-stall",'
            f'authority="",stage="receive"}} {alerts}'
        )
    return "\n".join(lines) + "\n"


def test_cluster_snapshot_participation_skew_stragglers():
    texts = {
        "0": _node_text(20, 18, {0: 5, 1: 4, 2: 6}, {"1": 0, "2": 1, "3": 9}),
        "1": _node_text(20, 12, {0: 5, 1: 4, 2: 6}, {"0": 0, "2": 2, "3": 11}),
        "2": None,  # unreachable this tick
    }
    snap = cluster_snapshot_from_texts(
        texts, 4, slo=SLOThresholds(min_participation=0.9)
    )
    assert snap["unreachable"] == ["2"]
    assert snap["quorum_participation"] == 0.75  # 3 of 4 authorities committed
    assert snap["commit_skew_rounds"] == 6
    assert snap["straggler_score"]["3"] == 11  # worst view wins
    assert snap["status"] == "degraded"
    assert any(r.startswith("unreachable") for r in snap["degraded_reasons"])
    assert "participation" in snap["degraded_reasons"]


def test_cluster_snapshot_green_path():
    texts = {
        str(i): _node_text(20, 18, {a: 3 for a in range(4)}, {})
        for i in range(4)
    }
    snap = cluster_snapshot_from_texts(texts, 4)
    assert snap["status"] == "ok" and snap["degraded_reasons"] == []
    assert snap["quorum_participation"] == 1.0
    assert snap["commit_rate_by_node"]["0"] == 2.5


def test_node_health_counts_alerts():
    view = node_health_from_series(
        [
            ("mysticeti_health_slo_alerts_total",
             {"kind": "authority-lag", "authority": "2"}, 3.0),
            ("mysticeti_health_slo_alerts_total",
             {"kind": "round-stall", "authority": ""}, 1.0),
        ]
    )
    assert view["slo_alerts"] == {"authority-lag": 3.0, "round-stall": 1.0}
    snap = cluster_snapshot({"0": view}, 4)
    assert snap["slo_alert_totals"] == {"authority-lag": 3.0, "round-stall": 1.0}
    # Cumulative alert history alone must NOT mark the fleet degraded: the
    # node recovered (status gauge is back to ok), so the snapshot is green
    # while the totals preserve the history for the artifact reader.
    assert snap["status"] == "ok" and snap["degraded_reasons"] == []


# -- the deterministic chaos acceptance path ---------------------------------


def _chaos_scenario(tmp_dir):
    from mysticeti_tpu.chaos import (
        CrashFault,
        FaultPlan,
        PartitionFault,
        run_chaos_sim,
    )

    plan = FaultPlan(
        seed=7,
        partitions=[
            PartitionFault(
                start_s=4.0, end_s=14.0, group_a=(2,),
                group_b=(0, 1, 3, 4, 5, 6), symmetric=True,
            )
        ],
        crashes=[CrashFault(node=5, at_s=6.0, downtime_s=6.0)],
    )
    slo = SLOThresholds(
        max_round_stall_s=5.0, max_commit_stall_s=6.0,
        max_authority_lag_rounds=8,
    )
    return run_chaos_sim(plan, 7, 20.0, tmp_dir, slo=slo, with_metrics=True)


def test_chaos_sim_alerts_name_stalled_authority_and_stage(tmp_path):
    """The acceptance scenario: a seeded blackhole partition of authority 2
    plus a crash-restart of authority 5.  The SLO watchdog must NAME both —
    every healthy observer raises authority-lag alerts against 2 (stage
    receive) during the partition and against 5 during its downtime — and
    the health timeline must be byte-identical across two same-seed runs."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    report, harness = _chaos_scenario(str(tmp_path / "a"))

    assert report.health_timeline, "health plane produced no samples"
    lag_alerts = [
        a for a in report.slo_alerts if a["kind"] == "authority-lag"
    ]
    named = {a["authority"] for a in lag_alerts}
    assert 2 in named, report.slo_alerts  # the partitioned authority
    assert 5 in named, report.slo_alerts  # the crashed authority
    assert all(a["stage"] == "receive" for a in lag_alerts)
    # The partitioned node saw its OWN pipeline stall too (round + commit).
    own = {
        (a["kind"], a["observer"])
        for a in report.slo_alerts
        if a["authority"] is None
    }
    assert ("round-stall", 2) in own
    assert ("commit-stall", 2) in own
    # Observers are the healthy nodes; the victim never indicts itself as a
    # peer-lag straggler.
    assert all(
        a["observer"] != a["authority"] for a in lag_alerts
    )
    # Down node recorded as down in the timeline during its outage.
    mid = [
        e for e in report.health_timeline if 7.0 <= e["t"] <= 11.0
    ]
    assert mid and all(e["nodes"]["5"].get("down") for e in mid)
    # Alerts are counted on the per-node metrics too.
    text = harness.metrics[0].expose().decode()
    assert 'mysticeti_health_slo_alerts_total{authority="2"' in text

    # Determinism: same plan, same seed -> byte-identical timeline + alerts.
    report_b, _ = _chaos_scenario(str(tmp_path / "b"))
    assert report.health_timeline_bytes == report_b.health_timeline_bytes
    assert report.slo_alerts == report_b.slo_alerts


def test_fleet_monitor_report_and_down_nodes():
    probes = {}
    clock = {"t": 0.0}
    for a in range(3):
        p = HealthProbe(
            a, 3, slo=SLOThresholds(max_authority_lag_rounds=5),
            clock=lambda: clock["t"],
        )
        core = _FakeCore(a, 3)
        core.round = 10
        core.block_store.last_seen = {b: 10 for b in range(3)}
        p.attach(core=core, commit_observer=_FakeObserver())
        probes[a] = p
    probes[2].detach()  # node 2 is down
    monitor = FleetHealthMonitor(probes.get, 3, interval_s=1.0)
    entry = monitor.tick()
    assert entry["nodes"]["2"] == {"down": True}
    assert "wal_backlog" not in entry["nodes"]["0"]  # volatile key stripped
    report = monitor.fleet_report()
    assert report["down"] == ["2"]
    assert report["status"] == "degraded"


# -- trace_report: critical path + robustness satellites ----------------------


def _write_trace(path, tracer):
    tracer.write(path)
    return path


def test_trace_report_critical_path_mode(tmp_path, capsys):
    from tools.trace_report import main as report_main

    tracer = SpanTracer()
    leader = _ref(3, 7, 1)
    tracer.record_span("receive", leader, 0.0, t1=2.0, authority=0)
    tracer.record_span("verify", leader, 2.0, t1=2.1, authority=0)
    tracer.record_span("dag_add", leader, 2.1, t1=2.2, authority=0)
    tracer.record_span("proposal_wait", leader, 2.2, t1=2.5, authority=0)
    tracer.record_span("commit", leader, 2.5, t1=2.55, authority=0)
    tracer.record_span("finalize", leader, 2.5, t1=2.6, authority=0)
    path = _write_trace(str(tmp_path / "t.json"), tracer)
    assert report_main([path, "--critical-path"]) == 0
    out = capsys.readouterr().out
    assert "1 committed leader observation" in out
    assert "receive" in out and "3" in out  # blocking stage + authority


def test_trace_report_critical_path_no_commits_notes_and_exits_zero(
    tmp_path, capsys
):
    from tools.trace_report import main as report_main

    tracer = SpanTracer()
    tracer.record_span("receive", _ref(1, 1, 1), 0.0, t1=1.0, authority=0)
    path = _write_trace(str(tmp_path / "t.json"), tracer)
    assert report_main([path, "--critical-path"]) == 0
    assert "no committed leaders" in capsys.readouterr().out


def test_trace_report_tolerates_truncated_tail(tmp_path, capsys):
    from tools.trace_report import main as report_main

    tracer = SpanTracer()
    for i in range(5):
        tracer.record_span("commit", _ref(0, i + 1, i + 1), float(i),
                           t1=float(i) + 0.5, authority=0)
    path = str(tmp_path / "t.json")
    tracer.write(path)
    whole = open(path).read()
    # Tear the file mid-event (a SIGKILL landing mid-flush of the .tmp, or
    # a reader racing the writer).
    with open(path, "w") as f:
        f.write(whole[: int(len(whole) * 0.7)])
    assert report_main([path]) == 0
    captured = capsys.readouterr()
    assert "salvaged" in captured.err
    assert "commit" in captured.out


def test_trace_report_empty_trace_exits_zero(tmp_path, capsys):
    from tools.trace_report import main as report_main

    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": []}, f)
    assert report_main([path]) == 0
    assert "no spans" in capsys.readouterr().out


def test_trace_report_missing_file_is_an_error(tmp_path, capsys):
    from tools.trace_report import main as report_main

    assert report_main([str(tmp_path / "absent.json")]) == 2


# -- orderly-shutdown telemetry flush ----------------------------------------


def test_flush_active_writes_span_tail(tmp_path):
    from mysticeti_tpu import spans

    path = str(tmp_path / "tail.json")
    tracer = SpanTracer(flush_path=path, flush_every_s=3600.0)
    spans._active = tracer
    try:
        tracer.record_span("commit", _ref(0, 1, 1), 0.0, t1=1.0, authority=0)
        assert not os.path.exists(path)  # periodic flusher never ran
        spans.flush_active()
        data = json.loads(open(path).read())
        assert any(e.get("ph") == "X" for e in data["traceEvents"])
    finally:
        spans._active = None


def test_metric_reporter_final_sweep_publishes_tail_window():
    from mysticeti_tpu.metrics import MetricReporter

    metrics = Metrics()
    metrics.transaction_committed_latency.observe(0.25)
    reporter = MetricReporter(metrics, interval_s=3600.0)
    reporter.stop(final=True)  # never started: stop must still publish
    text = metrics.expose().decode()
    assert (
        'histogram_pct{name="transaction_committed_latency",pct="50"} 0.25'
        in text
    )
