"""Host attribution plane: subsystem-registry totality over the package,
attribution precedence units, byte-identical deterministic reports from a
seeded synthetic census, the published series, the blocking-call detector
(planted synchronous sleep on the core owner -> SLO alert + flight-recorder
event), the loop-lag probe, and the clean seeded 10-node sim with the host
SLOs armed producing ZERO false positives."""
import asyncio
import json
import os
import random
import sys
import time

import pytest

from mysticeti_tpu import profiling
from mysticeti_tpu.core_task import CoreTaskDispatcher
from mysticeti_tpu.flight_recorder import FlightRecorder
from mysticeti_tpu.health import HealthProbe, SLOThresholds
from mysticeti_tpu.hostattr import HostMonitor, LoopLagProbe
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.orchestrator.measurement import iter_series
from mysticeti_tpu.profiling import (
    FRAME_SUBSYSTEMS,
    SUBSYSTEMS,
    SubsystemAccountant,
    attribute,
    thread_class_of,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

pytestmark = pytest.mark.perf

PKG = os.path.join(REPO, "mysticeti_tpu")


# -- the declarative registry -------------------------------------------------


def test_subsystem_mapping_totality():
    """Every module in the package must resolve through SUBSYSTEMS — a new
    module cannot silently land its CPU time in "other" (the span-names
    lint idiom applied to the attribution registry)."""
    missing = []
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            module = fn[:-3]
            if module not in SUBSYSTEMS:
                missing.append(os.path.join(os.path.relpath(dirpath, REPO), fn))
    assert not missing, (
        f"modules without a SUBSYSTEMS row (add them in profiling.py): "
        f"{sorted(missing)}"
    )


def test_attribute_precedence():
    # Parked leaf -> idle, regardless of what is above it.
    assert attribute(
        [("selectors", "select", False), ("core_task", "_run", True)]
    ) == "event-loop-idle"
    # GC override beats the module map anywhere in the stack: a wal append
    # inside retire_below is GC cost.
    assert attribute([
        ("wal", "append", True),
        ("storage", "retire_below", True),
        ("core_task", "_run", True),
    ]) == "gc"
    # Leaf-most in-package frame decides; third-party frames are charged to
    # whichever package module called into them.
    assert attribute([
        ("numpy_core", "dot", False),
        ("serde", "parse_block", True),
        ("net_sync", "handle", True),
    ]) == "mesh-parse"
    assert attribute([("wal", "fsync", True)]) == "wal"
    # Nothing recognizable -> other.
    assert attribute([("mystery", "f", False)]) == "other"
    assert attribute([]) == "other"


def test_thread_classes():
    assert thread_class_of("MainThread") == "loop"
    assert thread_class_of("ThreadPoolExecutor-0_0") == "verifier"
    assert thread_class_of("wal-writer") == "wal"
    assert thread_class_of("dataplane-offload_0") == "offload"
    assert thread_class_of("mysterious") == "aux"


# -- the accountant -----------------------------------------------------------


def _seeded_census(seed, ticks=200):
    """A reproducible synthetic census: the determinism seam's test load."""
    rng = random.Random(seed)
    modules = sorted(SUBSYSTEMS)
    censuses = []
    for _ in range(ticks):
        samples = []
        for tc in ("loop", "verifier", "wal"):
            if rng.random() < 0.3:
                samples.append((tc, [("selectors", "select", False)]))
            else:
                samples.append(
                    (tc, [(rng.choice(modules), "work", True)])
                )
        censuses.append(samples)
    return censuses


def test_report_deterministic_from_seeded_census():
    reports = []
    for _ in range(2):
        acct = SubsystemAccountant()
        for census in _seeded_census(42):
            acct.ingest_census(census, 1.0 / 99.0)
        reports.append(acct.report_bytes())
    assert reports[0] == reports[1]
    doc = json.loads(reports[0])
    assert doc["census_ticks"] == 200
    # Every census module resolved through the registry: fully attributed.
    assert doc["attributed_ratio"] == 1.0
    assert 0.0 < doc["gil_convoy_ratio"] <= 1.0
    assert doc["subsystem_seconds"]


def test_accountant_math_and_convoy():
    acct = SubsystemAccountant()
    idle = [("selectors", "select", False)]
    busy = [("wal", "append", True)]
    # Tick 1: one busy thread (no convoy); tick 2: two busy (convoy).
    acct.ingest_census([("loop", busy), ("wal", idle)], 0.01)
    acct.ingest_census([("loop", busy), ("wal", busy)], 0.01)
    doc = acct.report()
    assert doc["census_ticks"] == 2 and doc["convoy_ticks"] == 1
    assert doc["gil_convoy_ratio"] == 0.5
    assert doc["cpu_seconds"]["wal/loop"] == pytest.approx(0.02)
    assert doc["cpu_seconds"]["wal/wal"] == pytest.approx(0.01)
    assert doc["subsystem_seconds"]["event-loop-idle"] == pytest.approx(0.01)
    # "other" time drags the attributed ratio down.
    acct.ingest_census([("loop", [("mystery", "f", False)])], 0.01)
    doc = acct.report()
    assert doc["attributed_ratio"] == pytest.approx(0.75)


def test_publish_exports_series():
    metrics = Metrics()
    acct = SubsystemAccountant()
    acct.bind(metrics, leaders_fn=lambda: 100)
    acct.ingest_census([("loop", [("wal", "append", True)])], 0.5)
    acct.publish()
    acct.publish()  # idempotent: deltas, not re-adds
    series = {
        (name, labels.get("subsystem"), labels.get("thread_class")): value
        for name, labels, value in iter_series(metrics.expose().decode())
    }
    assert series[("mysticeti_cpu_seconds_total", "wal", "loop")] == (
        pytest.approx(0.5)
    )
    # 0.5 s over 100 leaders = 5000 us/leader.
    assert series[("mysticeti_cpu_us_per_leader", "wal", None)] == (
        pytest.approx(5000.0)
    )


# -- folded-file salvage + flame diff (satellites) ---------------------------


def test_load_folded_salvages_tmp(tmp_path):
    # A node SIGKILL'd before its first complete flush leaves only the tmp
    # file; load_folded must fall back to it instead of dying.
    path = str(tmp_path / "prof.folded")
    with open(path + ".tmp", "w") as f:
        f.write("a;b 10\nc;d 3\ntorn-line-without-count")
    lines = profiling.load_folded(path)
    assert "a;b 10" in lines
    svg = profiling.flamegraph_svg(lines)  # torn line skipped, not fatal
    assert svg.startswith("<svg") and "a" in svg
    with pytest.raises(FileNotFoundError):
        profiling.load_folded(str(tmp_path / "absent.folded"))


def test_render_diff(tmp_path):
    base = tmp_path / "base.folded"
    new = tmp_path / "new.folded"
    base.write_text("main;wal:append 80\nmain;serde:parse 20\n")
    new.write_text("main;wal:append 20\nmain;serde:parse 80\n")
    out = profiling.render_diff(str(base), str(new))
    svg = open(out).read()
    assert svg.startswith("<svg")
    # Both directions of the delta palette present: serde grew (red side),
    # wal shrank (blue side).
    assert "+60.0 pts" in svg and "-60.0 pts" in svg


def test_mkflamegraph_diff_cli(tmp_path):
    import subprocess

    base = tmp_path / "base.folded"
    new = tmp_path / "new.folded"
    base.write_text("main;a 1\n")
    new.write_text("main;a 2\n")
    out = tmp_path / "diff.svg"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mkflamegraph.py"),
         "--diff", str(base), str(new), str(out)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    assert out.exists() and out.read_text().startswith("<svg")


# -- the blocking-call detector (acceptance) ---------------------------------


class _FakeWal:
    def pending(self):
        return False


class _FakeStore:
    def last_seen_by_authority(self, a):
        return 0


class _FakeCore:
    authority = 0
    wal_writer = _FakeWal()
    block_store = _FakeStore()

    def current_round(self):
        return 0


class _FakeObserver:
    class _Interp:
        last_height = 0

    commit_interpreter = _Interp()


def test_blocking_call_detector_catches_planted_sleep():
    """The planted >=50 ms synchronous hold on the core owner must surface
    as a blocking-call SLO alert AND a flight-recorder event; fast commands
    must not trip it (the zero-false-positive half rides the sim test)."""
    metrics = Metrics()
    recorder = FlightRecorder(authority=0)
    monitor = HostMonitor(
        metrics=metrics, recorder=recorder, blocking_threshold_ms=50.0
    )
    dispatcher = CoreTaskDispatcher(object())
    dispatcher.blocking_monitor = monitor

    def planted_sleep():
        time.sleep(0.06)  # the bug the dynamic lint twin exists to catch
        return "done"

    def fast():
        return "ok"

    async def drive():
        dispatcher.start()
        for _ in range(5):
            assert await dispatcher._call(fast) == "ok"
        assert await dispatcher._call(planted_sleep) == "done"
        dispatcher.stop()

    asyncio.run(drive())
    assert monitor.blocking_total == 1  # the sleep, not the fast commands
    events = [
        e for e in recorder.events() if e["kind"] == "blocking-call"
    ]
    assert len(events) == 1
    assert events[0]["site"] == "core:planted_sleep"
    assert events[0]["ms"] >= 50.0

    probe = HealthProbe(
        0, 4, metrics=metrics,
        slo=SLOThresholds(max_blocking_call_ms=50.0),
        clock=lambda: 0.0,
    )
    probe.attach(
        core=_FakeCore(), commit_observer=_FakeObserver(),
        host_monitor=monitor,
    )
    snapshot = probe.sample()
    kinds = [a["kind"] for a in snapshot.get("alerts", [])]
    assert kinds == ["blocking-call"]
    assert snapshot["host"]["last_blocking"]["site"] == "core:planted_sleep"
    # The drain re-arms the alert: a clean next sample clears it.
    s2 = probe.sample()
    assert not s2.get("alerts")


def test_loop_lag_probe_measures_a_blocked_loop():
    async def drive():
        probe = LoopLagProbe(interval_s=0.01).start()
        await asyncio.sleep(0.05)
        time.sleep(0.08)  # hold the loop: the next callback fires late
        await asyncio.sleep(0.03)
        probe.stop()
        return probe

    probe = asyncio.run(drive())
    assert probe.sample_count() >= 3
    assert probe.percentile(99) >= 0.05  # saw the 80 ms hold


def test_loop_lag_probe_tells_a_listener_every_sample():
    """The verifier service books the probe's samples as its
    ``service_loop_lag`` stage through ``on_lag``."""
    from mysticeti_tpu import spans

    clock = spans.StageClock(spans.SERVICE_STAGES, ring_seconds=8)

    async def drive():
        probe = LoopLagProbe(
            interval_s=0.01,
            on_lag=lambda lag: clock.book(
                "service_loop_lag", time.monotonic(), lag),
        ).start()
        await asyncio.sleep(0.03)
        time.sleep(0.08)
        await asyncio.sleep(0.03)
        probe.stop()
        return probe

    probe = asyncio.run(drive())
    row = clock.totals()["service_loop_lag"]
    assert row["count"] == probe.sample_count() >= 3
    assert row["wall_s"] >= 0.05 and row["cpu_s"] == 0.0


def test_host_monitor_state_shape():
    monitor = HostMonitor(blocking_threshold_ms=50.0)
    state = monitor.state()
    assert state["loop_lag_samples"] == 0
    assert state["blocking_calls"] == 0
    assert state["last_blocking"] is None
    assert state["blocking_threshold_ms"] == 50.0
    assert monitor.drain_worst_blocking_ms() == 0.0
    # Sub-threshold command: not a blocking call.
    monitor.note_command("core:fast", 0.001)
    assert monitor.blocking_total == 0


# -- zero false positives under the clean seeded sim -------------------------


def test_clean_sim_no_host_false_positives(tmp_path):
    """A clean seeded 10-node sim with the host SLOs armed and the full
    wiring attached (HostMonitor on every probe AND every dispatcher) must
    raise ZERO loop-lag/blocking-call alerts: under virtual time the probe
    and the dispatcher measurement stay off by design, so host wall-clock
    hiccups cannot leak into the deterministic timeline."""
    from test_net_sync_sim import build_node

    from mysticeti_tpu.committee import Committee
    from mysticeti_tpu.config import Parameters
    from mysticeti_tpu.runtime.simulated import run_simulation
    from mysticeti_tpu.simulated_network import SimulatedNetwork

    n = 10
    alerts = []

    async def drive():
        committee = Committee.new_test([1] * n)
        signers = Committee.benchmark_signers(n)
        parameters = Parameters(leader_timeout_s=1.0)
        sim_net = SimulatedNetwork(n)
        nodes = [
            build_node(committee, signers, a, str(tmp_path), sim_net,
                       parameters)
            for a in range(n)
        ]
        slo = SLOThresholds(max_loop_lag_s=0.25, max_blocking_call_ms=50.0)
        probes = []
        for a, node in enumerate(nodes):
            monitor = HostMonitor(blocking_threshold_ms=50.0).start()
            node.dispatcher.blocking_monitor = monitor
            probe = HealthProbe(a, n, slo=slo).attach(
                core=node.core,
                commit_observer=node.syncer.commit_observer,
                host_monitor=monitor,
            )
            probes.append(probe)
        for node in nodes:
            await node.start()
        await sim_net.connect_all()
        for _ in range(20):
            await asyncio.sleep(1.0)
            for probe in probes:
                snapshot = probe.sample()
                alerts.extend(snapshot.get("alerts", []))
                # The sim's host block must be all-zero (determinism).
                host = snapshot["host"]
                assert host["loop_lag_samples"] == 0
                assert host["blocking_calls"] == 0
        for node in nodes:
            await node.stop()
        sim_net.close()
        return nodes

    nodes = run_simulation(drive(), seed=7)
    committed = [len(list(n_.syncer.commit_observer.committed_leaders))
                 for n_ in nodes]
    assert all(c > 0 for c in committed), committed  # the sim did real work
    host_kinds = [a["kind"] for a in alerts
                  if a["kind"] in ("loop-lag", "blocking-call")]
    assert host_kinds == [], host_kinds
