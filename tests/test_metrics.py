"""PreciseHistogram window semantics (stat.rs:8-100 drain-per-sweep parity).

Round-4 verdict: the old buffer stopped appending at ``max_samples`` and the
reporter never cleared, so at fleet load the published p50/p90/p99 described
the first ~2.5 s of the run forever.  These tests pin the fix: reservoir
sampling within a window + drain on report.
"""
import asyncio
import json

from mysticeti_tpu.metrics import Metrics, PreciseHistogram, serve_metrics


def test_percentiles_track_shifted_distribution_after_200k():
    h = PreciseHistogram(max_samples=10_000)
    # Window 1: 200k observations around 1.0 — far beyond the buffer cap.
    for i in range(200_000):
        h.observe(1.0 + (i % 100) / 1000.0)
    pcts = h.pcts((50, 90, 99))
    assert 1.0 <= pcts[50] <= 1.1
    h.clear()
    # Window 2: the distribution shifts to ~5.0.  A frozen buffer would keep
    # reporting ~1.0; the drained reservoir must follow the shift.
    for i in range(200_000):
        h.observe(5.0 + (i % 100) / 1000.0)
    pcts = h.pcts((50, 90, 99))
    assert 5.0 <= pcts[50] <= 5.1
    assert 5.0 <= pcts[99] <= 5.1
    # Cumulative average still spans both windows.
    assert 2.9 < h.avg() < 3.2
    assert h.count == 400_000


def test_reservoir_is_representative_within_one_window():
    h = PreciseHistogram(max_samples=1_000)
    # One window whose character changes after the buffer fills: 100k warmup
    # samples at 10.0 then 100k steady-state at 1.0.  Appending-only capture
    # would report p50=10 (pure warmup); a uniform reservoir over the window
    # reports the ~50/50 mixture.
    for _ in range(100_000):
        h.observe(10.0)
    for _ in range(100_000):
        h.observe(1.0)
    mixed = sum(1 for s in h.samples if s == 1.0) / len(h.samples)
    assert 0.35 < mixed < 0.65
    assert len(h.samples) == 1_000


def test_report_precise_drains_and_keeps_last_value_on_quiet_window():
    m = Metrics()
    for _ in range(100):
        m.transaction_committed_latency.observe(2.0)
    m.report_precise()
    g = m._pct_gauge.labels("transaction_committed_latency", "50")
    assert g._value.get() == 2.0
    assert m.transaction_committed_latency.samples == []
    # Quiet window: no new samples — the gauge keeps its last published value.
    m.report_precise()
    assert g._value.get() == 2.0
    # Next busy window at a different level: the gauge follows.
    for _ in range(100):
        m.transaction_committed_latency.observe(7.0)
    m.report_precise()
    assert g._value.get() == 7.0


REFERENCE_SERIES_MAP = [
    # (reference metrics.rs:36-87 field, our scrape name)
    ("benchmark_duration", "benchmark_duration"),
    ("latency_s", "latency_s"),
    ("latency_squared_s", "latency_squared_s"),
    ("committed_leaders_total", "committed_leaders_total"),
    ("leader_timeout_total", "leader_timeout_total"),
    ("inter_block_latency_s", "inter_block_latency_s"),
    ("block_store_unloaded_blocks", "block_store_unloaded_blocks"),
    ("block_store_loaded_blocks", "block_store_loaded_blocks"),
    ("block_store_entries", "block_store_entries"),
    ("block_store_cleanup_util", "utilization_timer"),  # proc label
    ("wal_mappings", "wal_mappings"),
    ("core_lock_util", "utilization_timer"),  # proc="core:*"
    ("core_lock_enqueued", "core_lock_enqueued"),
    ("core_lock_dequeued", "core_lock_dequeued"),
    ("block_handler_pending_certificates", "block_handler_pending_certificates"),
    ("block_handler_cleanup_util", "utilization_timer"),
    ("commit_handler_pending_certificates", "commit_handler_pending_certificates"),
    ("missing_blocks", "missing_blocks_total"),
    ("blocks_suspended", "blocks_suspended"),
    ("block_sync_requests_sent", "block_sync_requests_sent"),
    ("block_sync_requests_received", "block_sync_requests_received"),
    ("transaction_certified_latency", "histogram_pct"),  # name label
    ("certificate_committed_latency", "histogram_pct"),
    ("transaction_committed_latency", "histogram_pct"),
    ("proposed_block_size_bytes", "histogram_pct"),
    ("proposed_block_transaction_count", "histogram_pct"),
    ("proposed_block_vote_count", "histogram_pct"),
    ("connection_latency_sender", "connection_latency"),
    ("connected_nodes", "connected_nodes"),
    ("utilization_timer", "utilization_timer"),
    ("threshold_clock_round", "threshold_clock_round"),
    ("commit_round", "commit_round"),
    ("blocks_per_commit_count", "histogram_pct"),
    ("sub_dags_per_commit_count", "histogram_pct"),
    ("block_commit_latency", "histogram_pct"),
    ("block_receive_latency", "block_receive_latency"),
    ("add_block_latency", "add_block_latency"),
    ("quorum_receive_latency", "histogram_pct"),
    ("ready_new_block", "ready_new_block"),
    # Beyond the reference: the TPU verifier series + wal size.
    ("-", "verified_signatures_total"),
    ("-", "verify_batch_size"),
    ("-", "wal_size_bytes"),
]


def test_scrape_contains_full_reference_inventory():
    """Every series in the reference's Metrics struct (metrics.rs:36-87) has
    a scrapeable counterpart here — the series-for-series map is also
    recorded in PARITY.md."""
    m = Metrics()
    # Label-less series appear in an empty scrape; labeled/vec series appear
    # once touched — touch one child each so the scrape carries them all.
    m.latency_s.labels("shared")
    m.latency_squared_s.labels("shared")
    m.committed_leaders_total.labels("0", "committed")
    m.inter_block_latency_s.labels("shared")
    m.block_sync_requests_sent.labels("1")
    m.block_sync_requests_received.labels("1")
    m.connection_latency.labels("1")
    m.block_receive_latency.labels("0")
    m.add_block_latency.labels("0")
    m.utilization_timer_us.labels("core:add_blocks")
    m.ready_new_block.labels("leader")
    m.verified_signatures_total.labels("cpu", "accepted")
    m.quorum_receive_latency.observe(0.1)
    for name in (
        "transaction_certified_latency", "certificate_committed_latency",
        "transaction_committed_latency", "proposed_block_size_bytes",
        "proposed_block_transaction_count", "proposed_block_vote_count",
        "blocks_per_commit_count", "sub_dags_per_commit_count",
        "block_commit_latency",
    ):
        m._precise[name].observe(1.0)
    m.report_precise()
    scrape = m.expose().decode()
    for ref_field, ours in REFERENCE_SERIES_MAP:
        assert ours in scrape, f"{ref_field} -> {ours} missing from scrape"
    # The precise channels ride histogram_pct{name=...}: check each label.
    for name in sorted(m._precise):
        assert f'name="{name}"' in scrape, name
    # The verifier hot-path inventory (batch shape, padding, fallback,
    # wire, service queue) — labeled series need one touched child to appear.
    m.verify_padding_wasted_total.labels("cpu")
    m.verify_wire_bytes_total.labels("sent")
    m.verifier_service_inflight.labels("c0")
    scrape = m.expose().decode()
    for series in (
        "verify_dispatch_batch_size",
        "verify_padding_wasted_total",
        "verifier_fallback_total",
        "verify_wire_bytes_total",
        "verifier_service_queue_depth",
        "verifier_service_inflight",
    ):
        assert series in scrape, series


def test_healthz_route_alongside_metrics():
    """The metrics endpoint answers /healthz with 200 + uptime and keeps
    serving the prometheus scrape on /metrics."""

    async def scenario():
        metrics = Metrics()
        server = await serve_metrics(metrics, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]

        async def get(path):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
            await writer.drain()
            data = await reader.read()
            writer.close()
            return data

        health = await get("/healthz")
        scrape = await get("/metrics")
        server.close()
        await server.wait_closed()
        return health, scrape

    health, scrape = asyncio.run(scenario())
    head, _, body = health.partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"application/json" in head
    payload = json.loads(body)
    assert payload["status"] == "ok"
    assert payload["uptime_s"] >= 0.0
    assert b"200 OK" in scrape
    assert b"benchmark_duration" in scrape
