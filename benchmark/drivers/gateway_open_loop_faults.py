"""Driver: the fleet of ``gateway_open_loop`` under permanent crash faults
(configuration ``paper10f3``).

Everything ``gateway_open_loop`` does, which this file imports and leaves
byte for byte what the accepted cells run: the same ``OpenLoopClient``,
probe and open loop.  What differs is the configuration's ``faults``: all
``validators`` boot, connect to all and run one ``leader_timeout_s`` without
a leader timeout (their start-up is behind them: ``wait_connected``);
``kill_at_s_into_warmup`` after the schedule begins the driver SIGKILLs
``faults.validators`` together and never boots them again (the reference
orchestrator's ``FaultsType::Permanent``); the client speaks to the live
gateways only (a load generator sits beside each validator in that
deployment, so the dead validators' clients are gone with them).  The driver owns what it starts:
when ``drive`` returns or raises, the killed validators are reaped, the
live ones and the service stopped, and none of the configuration's ports
is bound (``tests/test_rehearsal_faults.py`` holds it).

``check_fleet`` is ``gateway_open_loop``'s, over the live validators;
``check_faults`` holds the run to the configuration's ``safety``,
``liveness`` and ``faults`` guarantees, every comparison exact:

* heights where a live WAL's committed leader or sub-DAG differs from what
  the plain reference (``reference/commit_rule.py``: ``decide`` +
  ``linearize``) gives on the DAG read back from that same WAL: 0, over
  every height the WAL holds, with at least ``compared_heights_min``
  heights that every live WAL holds;
* blocks of a dead validator, in any live WAL, at a round above the highest
  any live validator held from it ``settle_s`` (2 s) after the kill: 0; and
  slots it leads above that round decided ``commit``, by the reference or
  in a WAL: 0;
* ``leader_timeout_total`` on the live validators, growth over the window
  (scraped at its edges): 0; what fires outside it is logged;
* live validators whose ``connected_nodes`` is not (live - 1) at the
  window's edges: 0 (``settle_s`` after the kill and at the end: logged);
* dead validators whose metrics port still answers then or at the end, or
  whose exit code is not SIGKILL's: 0.

Configuration file, beyond ``gateway_open_loop``'s keys: ``faults``
{``kind``, ``validators``, ``kill_at_s_into_warmup``, ``signal``},
``compared_heights_min``
and ``node_main`` (optional: another wrapper of the node's entry point, the
control in ``benchmark/tests/``).

``sweep.py`` drives this module through ``Fleet``, ``OpenLoopClient`` and
``sleep_until``: ``Fleet.boot()`` there kills the faulty validators once
all have connected, before the first rung, and ``Fleet.ports("gateway")``
gives the live gateways.
"""
from __future__ import annotations

import asyncio
import json
import os
import random
import signal
import statistics
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from benchmark import harness
from benchmark.drivers import gateway_open_loop as base
from benchmark.drivers import gateway_open_loop_wan as node_main
# The open loop itself, with the client made by the caller: the signed
# driver's, as it stands; it uses nothing of signed transfers.
from benchmark.drivers.gateway_signed_open_loop import _drive
from benchmark.harness import BenchError, log
from benchmark.reference import commit_rule

# What benchmark/sweep.py drives a fleet driver through.
OpenLoopClient = base.OpenLoopClient
sleep_until = base.sleep_until

SETTLE_S = 2.0  # after the kill: the dead validators' last rounds are read
WATCH_S, WATCH_STEP_S = 4.0, 0.5  # the transient after the kill, logged
SIGNALS = {"SIGKILL": signal.SIGKILL}


class Fleet(node_main.Fleet):
    """The fleet, ``faults.validators`` of it to be killed; ``node_main``
    as the wide-area driver's fleet has it."""

    def __init__(self, run: harness.Run) -> None:
        super().__init__(run)
        faults = self.config["faults"]
        if faults.get("kind") != "permanent":
            raise BenchError("this driver injects permanent faults only")
        self.dead: List[int] = [int(v) for v in faults["validators"]]
        self.live: List[int] = [i for i in range(self.n) if i not in self.dead]
        self.signal = SIGNALS[faults["signal"]]
        self.kill_after_s = float(faults["kill_at_s_into_warmup"])
        self.killed_at: Optional[float] = None
        self.killed: Dict[str, int] = {}  # exit codes, apart from the run's
        self.settled: Optional[dict] = None
        self.after_kill: List[tuple] = []  # (s after the kill, scrapes)
        self._wals: Dict[int, dict] = {}  # wal_dag, read once a validator
        self.edges: Dict[str, list] = {}  # scrapes at the window's edges

    def ports(self, kind: str) -> List[int]:
        ports = super().ports(kind)
        if kind == "gateway":
            return [ports[i] for i in self.live]
        return ports

    def boot(self, kill_when_connected: bool = True) -> None:
        super().boot()
        self.wait_connected()
        if kill_when_connected:  # a sweep: the faults are there throughout
            self.kill()
            time.sleep(SETTLE_S)
            self.settle()

    def connected(self) -> List[Optional[tuple]]:
        """(connections, threshold clock round, leader timeouts since boot)
        of every validator."""
        return [None if series is None else
                (harness.series_sum(series, "connected_nodes"),
                 harness.series_sum(series, "threshold_clock_round"),
                 harness.series_sum(series, "leader_timeout_total"))
                for series in self.scrape()]

    def wait_connected(self, timeout_s: float = 120.0) -> None:
        """Until every validator counts a connection to every other and the
        fleet has left its start-up behind.  The proposal gate opens at no
        round below ``wave_length`` (``Core.ready_new_block``, as the
        reference's), so the first rounds are proposed by leader timeouts,
        which are no fault's, and validators that booted seconds apart fire
        them seconds apart: a kill that lands among them finds the dead at
        round 2 and nothing of theirs to settle.  Left behind means one
        whole ``leader_timeout_s`` in which every validator stayed
        connected to all, none fired a timeout and every clock moved."""
        deadline = time.monotonic() + timeout_s
        past = int(self.parameters["wave_length"])
        calm_s = float(self.parameters["leader_timeout_s"])
        calm: Optional[tuple] = None  # (since, rounds then, timeouts)
        while True:
            counts = self.connected()
            now = time.monotonic()
            if all(c is not None and c[0] == self.n - 1 and c[1] >= past
                   for c in counts):
                rounds = [c[1] for c in counts]
                timeouts = [c[2] for c in counts]
                if calm is None or calm[2] != timeouts:
                    calm = (now, rounds, timeouts)
                elif now - calm[0] >= calm_s and all(
                        r > r0 for r, r0 in zip(rounds, calm[1])):
                    return
            else:
                calm = None
            if self.run.unexpected_exits():
                raise BenchError("a validator died at boot: "
                                 f"{self.run.unexpected_exits()}")
            if time.monotonic() > deadline:
                raise BenchError(f"the fleet never connected: {counts}")
            time.sleep(0.2)

    def kill(self) -> None:
        """All of ``dead`` at once, each reaped; from here on they are this
        fleet's to account for (``killed``), not ``run.children``."""
        self.killed_at = time.monotonic()
        procs = {f"node-{i}": self.run.children[f"node-{i}"]
                 for i in self.dead}
        for proc in procs.values():
            proc.send_signal(self.signal)
        for name, proc in procs.items():
            proc.wait()
            self.killed[name] = proc.returncode
            del self.run.children[name]
        log(f"killed validators {self.dead} "
            f"({time.monotonic() - self.killed_at:.3f}s): {self.killed}")

    def wal_files(self, authority: int) -> List[str]:
        from mysticeti_tpu.storage import MANIFEST_NAME

        path = os.path.join(self.dir, f"validator-{authority}", "wal")
        if os.path.isdir(path):
            with open(os.path.join(path, MANIFEST_NAME)) as f:
                return [os.path.join(path, s["name"])
                        for s in json.load(f).get("segments", [])]
        return [path] if os.path.exists(path) else []

    def settle(self, scrapes: Optional[list] = None) -> None:
        """``SETTLE_S`` after the kill: what every live validator's WAL
        holds by now (bytes a file), its scrape, and whether the dead still
        answer."""
        scrapes = scrapes or self.scrape()
        self.settled = {
            "wal_bytes": {i: {f: os.path.getsize(f)
                              for f in self.wal_files(i)} for i in self.live},
            "scrapes": scrapes,
            "answering": [i for i in self.dead if scrapes[i] is not None],
        }

    async def kill_under_load(self, client: OpenLoopClient) -> None:
        """``kill_after_s`` after the schedule's first tick is due."""
        while not client.ticks:
            await asyncio.sleep(0.01)
        begin = client.ticks[0]["due"]
        await sleep_until(begin + self.kill_after_s)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.kill)
        # The transient, for the log alone: the live validators' rounds,
        # timeouts and parked blocks every half second for two leader
        # timeouts after the kill (the window opens later still).
        for step in range(1, round(WATCH_S / WATCH_STEP_S) + 1):
            await sleep_until(self.killed_at + step * WATCH_STEP_S)
            scrapes = await loop.run_in_executor(None, self.scrape)
            self.after_kill.append((step * WATCH_STEP_S, scrapes))
            if step == round(SETTLE_S / WATCH_STEP_S):
                await loop.run_in_executor(None, self.settle, scrapes)
        if not self.run.trace:
            # A traced run scrapes every validator at the window's edges
            # (``run.observed["nodes"]``); an untraced one scrapes them here,
            # for ``check_faults`` alone.
            for edge in ("start", "end"):
                while self.run.window is None:
                    await asyncio.sleep(0.01)
                await sleep_until(self.run.window[edge == "end"])
                self.edges[edge] = await loop.run_in_executor(
                    None, self.scrape)

    def stop(self) -> None:
        names = [f"node-{i}" for i in self.live
                 if f"node-{i}" in self.run.children]
        procs = [self.run.children.pop(n) for n in names]
        with ThreadPoolExecutor(max(1, len(procs))) as pool:
            codes = list(pool.map(harness.stop_process, procs))
        self.run.exit_codes.update(zip(names, codes))
        for i in self.dead:  # a run that ended before the kill
            proc = self.run.children.pop(f"node-{i}", None)
            if proc is not None:
                self.killed[f"node-{i}"] = harness.stop_process(proc)

    def wal_dag(self, authority: int) -> dict:
        if authority not in self._wals:
            self._wals[authority] = self._read_wal(authority)
        return self._wals[authority]

    def _read_wal(self, authority: int) -> dict:
        """One validator's WAL read back with the program's own reader:
        the DAG as the reference's plain records (genesis among them),
        ``{height: (leader key, [sub-DAG keys])}`` of its commit entries,
        and per dead author the highest round among its blocks that lay in
        the files ``settle`` measured (-1: none)."""
        from mysticeti_tpu.block_store import (
            WAL_ENTRY_BLOCK,
            WAL_ENTRY_COMMIT,
            WAL_ENTRY_OWN_BLOCK,
            CommitData,
            OwnBlockData,
        )
        from mysticeti_tpu.serde import Reader
        from mysticeti_tpu.types import StatementBlock
        from mysticeti_tpu.wal import WalReader

        def ref_key(ref) -> tuple:
            return (ref.authority, ref.round, ref.digest)

        held = (self.settled or {}).get("wal_bytes", {}).get(authority, {})
        dag: Dict[tuple, commit_rule.Block] = {}
        commits: Dict[int, tuple] = {}
        held_round = {d: -1 for d in self.dead}
        for a in range(self.n):
            genesis = StatementBlock.new_genesis(a)
            dag[ref_key(genesis.reference)] = commit_rule.Block(
                a, 0, genesis.reference.digest, [])
        for file in self.wal_files(authority):
            early = held.get(file, 0)
            reader = WalReader(file)
            try:
                for pos, tag, payload in reader.iter_until():
                    if tag == WAL_ENTRY_COMMIT:
                        r = Reader(payload)
                        for _ in range(r.u32()):
                            commit = CommitData.decode(r)
                            commits[commit.height] = (
                                ref_key(commit.leader),
                                [ref_key(x) for x in commit.sub_dag])
                        continue
                    if tag == WAL_ENTRY_BLOCK:
                        block = StatementBlock.from_bytes(payload)
                    elif tag == WAL_ENTRY_OWN_BLOCK:
                        block = OwnBlockData.from_bytes(payload).block
                    else:
                        continue
                    ref = block.reference
                    dag[ref_key(ref)] = commit_rule.Block(
                        ref.authority, ref.round, ref.digest,
                        [ref_key(x) for x in block.includes])
                    if ref.authority in held_round and pos < early:
                        held_round[ref.authority] = max(
                            held_round[ref.authority], ref.round)
            finally:
                reader.close()
        return {"dag": list(dag.values()), "commits": commits,
                "held_round": held_round}


class _Live:
    """The live validators, as ``base.check_fleet`` reads a fleet."""

    def __init__(self, fleet: Fleet) -> None:
        self.fleet = fleet
        self.n = len(fleet.live)

    def wal_leaders(self, k: int) -> Dict[int, tuple]:
        commits = self.fleet.wal_dag(self.fleet.live[k])["commits"]
        return {height: leader for height, (leader, _) in commits.items()}


def check_fleet(run: harness.Run, fleet: Fleet, final: list,
                mapped: Dict[str, bool]) -> None:
    """``gateway_open_loop.check_fleet``, each check over the live
    validators (``run.exit_codes`` holds theirs and the service's); the
    killed validators' exit codes apart."""
    base.check_fleet(run, _Live(fleet), [final[i] for i in fleet.live],
                     mapped)
    want = {f"node-{i}": -int(fleet.signal) for i in fleet.dead}
    run.check("exit codes of the killed validators", fleet.killed, want,
              fleet.killed == want)


def compare_with_reference(fleet: Fleet, wal: dict) -> dict:
    """One WAL against ``commit_rule`` on its own DAG: the heights that
    differ, and the reference's decided slots."""
    decided = commit_rule.decide(wal["dag"], fleet.n)
    sequence = commit_rule.linearize(wal["dag"], decided)
    differing = [h for h in sorted(wal["commits"])
                 if h > len(sequence) or wal["commits"][h] != sequence[h - 1]]
    return {"decided": decided, "differing": differing}


def _transient(fleet: Fleet, at: float, scrapes: list) -> list:
    """One half-second step after the kill, summed over the live
    validators: where their threshold clocks stand and what has fired or
    is parked since boot."""
    def read(name: str) -> List[int]:
        return [int(harness.series_sum(scrapes[i] or [], name))
                for i in fleet.live]

    rounds = read("threshold_clock_round")
    return [at, f"{min(rounds)}-{max(rounds)}",
            sum(read("leader_timeout_total")), sum(read("blocks_suspended"))]


def check_faults(run: harness.Run, fleet: Fleet, final: list) -> None:
    """The configuration's ``safety``, ``liveness`` and ``faults``, as far
    as a run can show them."""
    config = run.cell["config"]
    settled = fleet.settled or {"scrapes": [None] * fleet.n,
                                "answering": list(fleet.dead)}
    started = time.monotonic()
    wals = {i: fleet.wal_dag(i) for i in fleet.live}
    compared = {i: compare_with_reference(fleet, wal)
                for i, wal in wals.items()}
    log(f"{len(wals)} WALs read back and decided by the reference in "
        f"{time.monotonic() - started:.1f}s: blocks "
        f"{[len(w['dag']) for w in wals.values()]}, heights "
        f"{[len(w['commits']) for w in wals.values()]}, slots decided "
        f"{[len(c['decided']) for c in compared.values()]}")
    differing = {f"node-{i}": c["differing"][:4]
                 for i, c in compared.items() if c["differing"]}
    count = sum(len(c["differing"]) for c in compared.values())
    run.check("commit heights where a live WAL's leader or sub-DAG differs "
              "from reference/commit_rule.py on that WAL's own DAG",
              count, 0, count == 0 and not differing)
    if differing:
        log(f"heights that differ: {differing}")
    shared = min(len(w["commits"]) for w in wals.values())
    least = int(config["compared_heights_min"])
    run.check("commit heights every live WAL holds, compared with the "
              "reference", shared, f">= {least}", shared >= least)

    # The dead validators' last rounds, and nothing of theirs above them.
    last = {d: max(w["held_round"][d] for w in wals.values())
            for d in fleet.dead}
    beyond, committed = [], []
    for i, wal in wals.items():
        beyond += [(i, b.author, b.round) for b in wal["dag"]
                   if b.author in last and b.round > last[b.author]]
        committed += [(i, key[0], key[1])
                      for key, _ in wal["commits"].values()
                      if key[0] in last and key[1] > last[key[0]]]
        committed += [(i, s.leader, s.round)
                      for s in compared[i]["decided"]
                      if s.leader in last and s.round > last[s.leader]
                      and s.outcome == commit_rule.COMMIT]
    log(f"highest round held of each dead validator {SETTLE_S} s after the "
        f"kill: {last}")
    run.check("blocks of a dead validator above the round held of it "
              f"{SETTLE_S} s after the kill, in any live WAL",
              len(beyond), 0, not beyond and min(last.values()) >= 1)
    run.check("slots a dead validator leads above that round decided commit",
              len(committed), 0, not committed)
    if beyond or committed:
        log(f"of the dead: blocks {beyond[:8]}, commits {committed[:8]}")

    # What the kill left to fetch: references asked of the live validators
    # and blocks parked on a missing parent, by the settle scrape and after.
    fetched = {
        name: [int(sum(harness.series_sum(scrapes[i] or [], name)
                       for i in fleet.live))
               for scrapes in (settled["scrapes"], final)]
        for name in ("block_sync_requests_received", "blocks_suspended",
                     "missing_blocks_total")}
    log(f"fetches over the live validators, [by {SETTLE_S} s after the "
        f"kill, by the run's end]: {fetched}")
    # Not steady with exactly 2f + 1 alive (PERF.md section 7): a range
    # one vote short of certified stays pending, and the aggregator's
    # snapshot written at a commit grows with it.
    pending = [int(harness.series_sum(
        final[i] or [], "block_handler_pending_certificates"))
        for i in fleet.live]
    wal_mb = [round(sum(os.path.getsize(f) for f in fleet.wal_files(i)) / 1e6)
              for i in fleet.live]
    log("at the run's end, by live validator: pending certificates "
        f"{pending}, WAL MB {wal_mb}")
    log("after the kill [s, rounds least-most, leader timeouts, blocks "
        f"parked]: {[_transient(fleet, *step) for step in fleet.after_kill]}")

    # Liveness: no timeout in the window, every live validator connected
    # to every other.  (Outside the window the harness itself disturbs the
    # fleet - the probe beside the load, the load's end, a trace collected:
    # what fires there is logged.)
    nodes = run.observed.get("nodes") or fleet.edges
    unscraped = [None] * fleet.n
    settle, start, end = (settled["scrapes"], nodes.get("start") or unscraped,
                          nodes.get("end") or unscraped)

    def timeouts_grown(earlier: list, later: list) -> float:
        return sum(
            harness.series_sum(later[i], "leader_timeout_total")
            - harness.series_sum(earlier[i], "leader_timeout_total")
            if earlier[i] is not None and later[i] is not None
            else float("nan") for i in fleet.live)

    grown = timeouts_grown(start, end)
    log("leader timeouts over the live validators: "
        f"{timeouts_grown(settle, start)} from {SETTLE_S} s after the kill "
        f"to the window, {grown} in the window, {timeouts_grown(end, final)} "
        "from the window to the run's end")
    run.check("leader_timeout_total growth over the window, summed over "
              "the live validators", grown, 0, grown == 0)
    want = len(fleet.live) - 1

    def apart(scrapes: list) -> list:
        return [i for i in fleet.live if scrapes[i] is None
                or harness.series_sum(scrapes[i], "connected_nodes") != want]

    log(f"live validators whose connected_nodes is not {want}: "
        f"{apart(settle)} {SETTLE_S} s after the kill, {apart(final)} at "
        "the run's end")
    at_edges = sorted(set(apart(start)) | set(apart(end)))
    run.check(f"live validators whose connected_nodes is not {want} at the "
              "window's edges", at_edges, [], not at_edges)
    answering = sorted(set(settled["answering"])
                       | {i for i in fleet.dead if final[i] is not None})
    run.check("dead validators whose metrics port still answers",
              answering, [], not answering)


async def _drive_and_kill(run: harness.Run, fleet: Fleet, probe: List[dict],
                          client: OpenLoopClient) -> dict:
    killer = asyncio.ensure_future(fleet.kill_under_load(client))
    try:
        outcome = await _drive(run, fleet, probe, client)
    finally:
        if not killer.done():
            killer.cancel()
        await asyncio.gather(killer, return_exceptions=True)
    if not killer.cancelled() and killer.exception() is not None:
        raise killer.exception()
    return outcome


def drive(run: harness.Run) -> dict:
    from mysticeti_tpu import spans

    if "leader_wait" not in spans.STAGES:
        # At once, before anything boots (a program from before PR 36: a
        # dead leader's every slot costs it the leader timeout).
        raise BenchError("this program does not take a closed connection "
                         "out of the proposal gate (spans.STAGES has no "
                         "leader_wait)")
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
    final: list = [None] * fleet.n
    try:
        fleet.boot(kill_when_connected=False)
        log(f"{fleet.n} validators booted, connected and past their start-up; "
            f"{fleet.dead} die {fleet.kill_after_s} s into the load")
        outcome = asyncio.run(_drive_and_kill(run, fleet, probes[1], client))
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
    run.observed["faults"] = {"live": fleet.live, "dead": fleet.dead}
    if outcome["nodes_start"] and outcome["nodes_end"]:
        run.observed["nodes"] = {"start": outcome["nodes_start"],
                                 "end": outcome["nodes_end"]}
    log("mean finality by the second it was due in: "
        f"{outcome['latency_avg_by_second_s']}")
    log(f"window: {record['submitted']} due, {len(latencies)} notified by "
        f"the drain's end, {record['late_notified']} later, "
        f"{record['shed']} shed, {record['unanswered']} unanswered, "
        f"{record['lost_after_ack']} acknowledged and never notified")
    check_fleet(run, fleet, final, mapped)
    check_faults(run, fleet, final)
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
