"""Driver: the fleet of ``gateway_open_loop`` as it was deployed, one
validator a region (configuration ``paper10wan``).

Everything ``gateway_open_loop`` does, which this file imports and leaves
byte for byte what the accepted cells run: the same ``Fleet``,
``OpenLoopClient``, probe and ``check_fleet``.  What differs is data: the
configuration's ``parameters.link_delay_ms`` (the N x N table of one-way
delays, merged into ``parameters.yaml`` like every other parameter) puts
the program's delay line on every validator-to-validator link.  Beside
``check_fleet`` this driver holds the run to the configuration's
``guarantees.delay`` (``check_delay``), every comparison exact:

* transactions notified sooner after the client wrote them than the
  reference's ``finality_floor_s`` of their gateway: 0 (the client's own
  clock and ``reference/wan.py``; nothing of the program is read);
* directed links not counted through the delay line, or whose
  ``mesh_link_delay_seconds`` is not the table's: 0 of N x (N - 1) - every
  link's ``mesh_delayed_frames_total`` is above 0 and, summed over a
  node's peers, equals that node's ``mesh_hold`` samples (one a frame
  written: the line keeps both books);
* links whose ``connection_latency`` (the mesh's own Ping/Pong round trip),
  mean since boot, is under the table's RTT, or that have no sample: 0
  (``PING_INTERVAL_S`` is 30 s, so a link has one or two samples a run:
  read at the end over the whole run, not as a window delta);
* ``connection_send_drops_total`` over the run: 0.

Configuration file, beyond ``gateway_open_loop``'s keys: ``node_main``
(optional), another wrapper of the node's entry point in place of ``python
-m mysticeti_tpu`` - the control in ``benchmark/tests/``.
"""
from __future__ import annotations

import asyncio
import os
import random
import statistics
import sys
from typing import Dict, List

from benchmark import harness, wan_readers
from benchmark.drivers import gateway_open_loop as base
# The open loop itself, with the client made by the caller (which reads
# every connection's notifications afterwards): the signed driver's, as it
# stands; it uses nothing of signed transfers.
from benchmark.drivers.gateway_signed_open_loop import _drive
from benchmark.harness import BenchError, log
from benchmark.reference import wan

# What benchmark/sweep.py drives a fleet driver through.
OpenLoopClient = base.OpenLoopClient
sleep_until = base.sleep_until


class Fleet(base.Fleet):
    def spawn_node(self, i: int, name=None) -> None:
        main = self.config.get("node_main")
        if not main:
            return super().spawn_node(i, name)
        env = dict(os.environ)
        env.update({k: str(v) for k, v in self.config["node_env"].items()})
        env["TRANSACTION_SIZE"] = str(self.config["transaction_bytes"])
        env["MYSTICETI_VERIFIER_SOCKET"] = self.run.socket
        self.run.spawn(
            name or f"node-{i}",
            [sys.executable, os.path.join(harness.ROOT, main), "run",
             "--authority", str(i),
             "--committee-path", os.path.join(self.dir, "committee.yaml"),
             "--parameters-path", os.path.join(self.dir, "parameters.yaml"),
             "--private-config-path",
             os.path.join(self.dir, f"validator-{i}"),
             "--verifier", self.config["verifier"]],
            env=env,
        )


def link_table_ms(config: dict) -> List[List[float]]:
    table = config["parameters"].get("link_delay_ms")
    n = int(config["validators"])
    if not table or len(table) != n or any(len(row) != n for row in table):
        raise BenchError("the configuration states no N x N "
                         "parameters.link_delay_ms")
    return table


def notified_after_s(client: OpenLoopClient) -> List[List[float]]:
    """Per gateway, seconds from when the client wrote a transaction's
    frame to when its commit notification was read, for every transaction
    of the whole run that was notified."""
    return [[received - client.ticks[tick]["sent"][conn.index]
             for tick, received in conn.notified]
            for conn in client.connections]


def check_delay(run: harness.Run, fleet: Fleet, final: list,
                client: OpenLoopClient, table: List[List[float]]) -> None:
    """``guarantees.delay``, as far as a run can show it."""
    n = fleet.n
    floors = wan.finality_floors_s(table)
    elapsed = notified_after_s(client)
    sooner = sum(1 for i in range(n) for x in elapsed[i] if x < floors[i])
    silent = [i for i in range(n) if not elapsed[i]]
    margins = [min(elapsed[i]) - floors[i] for i in range(n) if elapsed[i]]
    log("soonest notification over the floor, by gateway, ms: "
        f"{[round(1e3 * m, 1) for m in margins]}")
    run.check("transactions notified sooner than their gateway's "
              "finality_floor_s", sooner, 0, sooner == 0 and not silent)

    uncounted: Dict[str, str] = {}
    slow: Dict[str, str] = {}
    drops = 0
    for a, series in enumerate(final):
        series = series or []
        drops += int(harness.series_sum(series, "connection_send_drops_total"))
        through = 0
        for b in range(n):
            if a == b:
                continue
            link, peer = f"{a}->{b}", str(b)
            frames = harness.series_sum(series, "mesh_delayed_frames_total",
                                        peer=peer)
            through += frames
            said = [v for name, labels, v in series
                    if name == "mesh_link_delay_seconds"
                    and labels.get("peer") == peer]
            if frames <= 0:
                uncounted[link] = "no frame through the line"
            elif said != [table[a][b] / 1e3]:
                uncounted[link] = f"delay {said} s, table {table[a][b]} ms"
            samples = harness.series_sum(series, "connection_latency_count",
                                         peer=peer)
            total = harness.series_sum(series, "connection_latency_sum",
                                       peer=peer)
            rtt = (table[a][b] + table[b][a]) / 1e3
            if samples < 1:
                slow[link] = "no sample"
            elif total / samples < rtt:
                slow[link] = f"{total / samples:.4f} s under {rtt} s"
        held = harness.series_sum(series, "block_stage_seconds_count",
                                  stage="mesh_hold")
        if held != through:
            uncounted[f"{a}->*"] = (f"{int(through)} frames through the "
                                    f"lines, {int(held)} mesh_hold samples")
    for what in (uncounted, slow):
        if what:
            log(f"links at fault: {dict(list(what.items())[:8])}")
    links = n * (n - 1)
    run.check(f"links of {links} not counted through the delay line or not "
              "at the table's delay", len(uncounted), 0, not uncounted)
    run.check(f"links of {links} whose mesh RTT (connection_latency, mean "
              "since boot) is under the table's", len(slow), 0, not slow)
    run.check("mesh frames dropped at a full send queue "
              "(connection_send_drops_total)", drops, 0, drops == 0)


def log_send_queues(run: harness.Run) -> None:
    """What a link's bounded send queue (1,024 frames) has to hold under
    the delay: frames a second over the window x the link's delay."""
    links = wan_readers.link_frames(run)
    if links:
        rates = [frames / run.seconds for frames, _ in links]
        held = [frames / run.seconds * delay_s for frames, delay_s in links]
        log(f"frames a second a link: median {statistics.median(rates):.1f}, "
            f"most {max(rates):.1f}; frames a link's send queue holds "
            f"(rate x delay): median {statistics.median(held):.2f}, most "
            f"{max(held):.2f} of 1,024")


def finality_by_gateway(run: harness.Run, client: OpenLoopClient,
                        ) -> List[List[float]]:
    """Due -> notified, as the end-to-end median counts it, of the window's
    transactions notified by the drain's end, by gateway."""
    window = client.ticks_due(*run.window)
    notify_by = run.window[1] + float(run.cell["traffic"]["drain_s"])
    return [[received - client.ticks[tick]["due"]
             for tick, received in conn.notified
             if tick in window and received <= notify_by]
            for conn in client.connections]


def drive(run: harness.Run) -> dict:
    from mysticeti_tpu.config import Parameters

    if "link_delay_ms" not in Parameters.__dataclass_fields__:
        # At once, before anything boots (a program from before PR 32).
        raise BenchError("this program has no injected link delay "
                         "(Parameters.link_delay_ms)")
    table = link_table_ms(run.cell["config"])
    fleet = Fleet(run)
    keys = fleet.genesis()
    fleet.assert_ports_free()
    run.start_service(keys)
    spec = run.cell["config"]["probe"]
    rng = random.Random(run.seed ^ 0x9E3779B9)
    probes = [base.make_probe(rng, fleet.signing_keys(), spec)
              for _ in range(2)]
    run.snapshot("probe_start")
    base.run_probe(run, probes[0], int(spec["in_flight"]),
                   "on the idle service")
    run.snapshot("probe_end")
    client = OpenLoopClient(
        fleet.ports("gateway"), run.seed,
        int(run.cell["config"]["transaction_bytes"]),
        float(run.cell["traffic"]["tick_s"]))
    fleet.boot()
    log(f"{fleet.n} validators booted, one a region: "
        f"{run.cell['config'].get('regions')}")
    try:
        outcome = asyncio.run(_drive(run, fleet, probes[1], client))
    finally:
        run.observed["unexpected_exits"] = run.unexpected_exits()
        mapped = {n: harness.maps_jax(p.pid)
                  for n, p in run.children.items() if p.poll() is None}
        final = fleet.scrape()
        fleet.stop()
        run.stop_service()
    latencies = outcome["latencies"]
    record = {k: outcome[k] for k in
              ("submitted", "shed", "unanswered", "late_notified",
               "lost_after_ack", "late_s", "latencies")}
    run.observed["client"] = record
    floors = wan.finality_floors_s(table)
    run.observed["wan"] = {
        "floors_s": floors,
        "p50_by_gateway_s": [statistics.median(v) if v else None
                             for v in finality_by_gateway(run, client)],
    }
    if outcome["nodes_start"] and outcome["nodes_end"]:
        run.observed["nodes"] = {"start": outcome["nodes_start"],
                                 "end": outcome["nodes_end"]}
    log_send_queues(run)
    log("mean finality by the second it was due in: "
        f"{outcome['latency_avg_by_second_s']}")
    log("finality floor / measured p50 by gateway, s: " + ", ".join(
        f"{floor:.4f} / " + ("none" if p50 is None else f"{p50:.4f}")
        for floor, p50 in zip(floors,
                              run.observed["wan"]["p50_by_gateway_s"])))
    log(f"window: {record['submitted']} due, {len(latencies)} notified by "
        f"the drain's end, {record['late_notified']} later, "
        f"{record['shed']} shed, {record['unanswered']} unanswered, "
        f"{record['lost_after_ack']} acknowledged and never notified")
    base.check_fleet(run, fleet, final, mapped)
    check_delay(run, fleet, final, client, table)
    probed = base.kernels_used(run.snapshots["probe_end"],
                               run.snapshots["probe_start"])
    unprobed = sorted(base.kernels_used(run.snapshots["window_end"],
                                        run.snapshots["window_start"])
                      - probed)
    run.check("kernels the window ran and the probe did not", unprobed, [],
              bool(probed) and not unprobed)
    run.check("acknowledged transactions never notified",
              record["lost_after_ack"], 0, record["lost_after_ack"] == 0)
    beyond_p95 = len(latencies) - int(0.95 * len(latencies))
    run.check("finality samples beyond the 95th percentile", beyond_p95,
              ">= 10", beyond_p95 >= 10)
    end_to_end = {}
    if latencies:
        end_to_end = {
            "committed_tx_s": len(latencies) / run.seconds,
            "finality_p50_s": statistics.median(latencies),
        }
        log(f"finality over {len(latencies)} samples: p50 "
            f"{end_to_end['finality_p50_s']:.4f}s p95 "
            f"{harness.quantile(latencies, 0.95):.4f}s")
    return {"attempted": record["submitted"],
            "failed": record["submitted"] - len(latencies),
            "end_to_end": end_to_end}
