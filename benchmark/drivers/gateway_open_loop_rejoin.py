"""Driver: the fleet of ``gateway_open_loop`` with one validator crashed and
booted again on its WAL (configuration ``paper10cr``).

Everything ``gateway_open_loop_faults`` does, which this file imports and
leaves byte for byte what the accepted cell runs, up to the kill: all
``validators`` boot, connect to all and run one ``leader_timeout_s`` without
a leader timeout; ``faults.kill_at_s_into_warmup`` after the schedule
begins the driver SIGKILLs ``faults.validators``; the client speaks to the
other gateways only.  Then the reference orchestrator's
``FaultsType::CrashRecovery``: the killed validator's WAL is copied while
it is down, and ``faults.restart_at_s_into_window`` after the window opens
the driver starts it again with the same command, key and directory
(``Fleet.restart``).  From then to the drain's end the returned validator
and ``CURVE_OTHERS`` of the others are scraped each ``CURVE_STEP_S``
(committed height, threshold-clock round, blocks verified: the rejoin's curve, logged whole, which the cell's readers
read).  The driver owns what it starts, as the faults driver does.

``check_fleet`` is the faults driver's over the validators that never died
(their counters did not start again at zero); ``check_rejoin`` holds the
run to the configuration's ``safety``, ``recovery`` and ``liveness``, every
comparison exact:

* safety - heights where any of the N WALs' committed leader or sub-DAG
  differs from ``reference/commit_rule.py`` on that WAL's own DAG: 0;
  heights the returned validator's WAL shares with validator 0's and names
  otherwise: 0; (author, round) pairs that have two digests anywhere in the
  N WALs: 0 (the returned validator signed no second block for a round it
  had proposed before the kill);
* recovery - what the restarted process reports of its boot (the
  ``wal_recovery{what}`` gauges: blocks, highest own round, last committed
  height, bytes cut as torn) equals ``reference/recovery.py`` on the copy;
  commits written in the copy that its own DAG does not support: 0; files
  of its WAL after the run that do not begin with the copy's sound bytes:
  0; recovered boots (``crash_recovery_total``) and ``wal_replay`` samples:
  1 each;
* liveness - ``leader_timeout_total`` growth over the window, summed over
  the validators that never died: 0 (as ``check_faults`` holds it; how many
  of them were booked to the returned validator's slots,
  ``mysticeti_health_leader_timeout_total{authority}``, is logged; the rate
  is read by ``leader_timeouts_s.cr``); validators whose ``connected_nodes``
  is not N - 1 at the window's end (N - 2 at its start, on those up): 0;
  the returned validator's committed height at the drain's end less its
  height at boot: at least ``compared_heights_min``; whether it is *in
  step* by then (within ``in_step_commits`` of the others' median) is
  measured (``recover_s``), not required.

Configuration file, beyond the faults driver's keys: ``faults`` {``kind``:
``crash_recovery``, ``restart_at_s_into_window``}, ``in_step_commits``.

``sweep.py`` drives this module through ``Fleet``, ``OpenLoopClient`` and
``sleep_until``: ``Fleet.boot()`` there kills the faulty validator once all
have connected and it stays down through every rung.
"""
from __future__ import annotations

import asyncio
import os
import random
import shutil
import statistics
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from benchmark import harness
from benchmark.drivers import gateway_open_loop as base
from benchmark.drivers import gateway_open_loop_faults as faults
from benchmark.drivers import gateway_open_loop_wan as node_main
from benchmark.drivers.gateway_signed_open_loop import _drive
from benchmark.harness import BenchError, log
from benchmark.reference import recovery

# What benchmark/sweep.py drives a fleet driver through.
OpenLoopClient = base.OpenLoopClient
sleep_until = base.sleep_until

CURVE_STEP_S = 0.5
CURVE_OTHERS = 2  # validators that never died on the curve, beside the returned
REPORTED = ("blocks", "own_round", "commit_height", "torn_bytes")


def point_of(series: Optional[list]) -> Optional[dict]:
    """What one scrape says of where a validator stands."""
    if series is None:
        return None
    return {
        "height": harness.series_sum(series, "committed_height"),
        "round": harness.series_sum(series, "threshold_clock_round"),
        # Blocks received and verified since the process began (a block
        # is one signature here).
        "blocks": harness.series_sum(series, "verified_signatures_total",
                                     outcome="accepted"),
    }


def requests_of(series: Optional[list]) -> Optional[dict]:
    """What a validator has sent the verifier service since it began:
    requests by signatures held (``verify_dispatch_batch_size``, cumulative
    by ``le``), and how many it has in flight of how many it may."""
    if series is None:
        return None
    by_le = {labels["le"]: value for name, labels, value in series
             if name == "verify_dispatch_batch_size_bucket"}
    return {
        "by_le": by_le,
        "requests": harness.series_sum(
            series, "verify_dispatch_batch_size_count"),
        "signatures": harness.series_sum(
            series, "verify_dispatch_batch_size_sum"),
        "inflight": harness.series_sum(series, "verify_pipeline_inflight"),
        "depth": harness.series_sum(series, "verify_pipeline_depth"),
    }


class Fleet(faults.Fleet):
    """The faults driver's fleet, its ``dead`` to come back."""

    def __init__(self, run: harness.Run) -> None:
        # ``faults.Fleet.__init__`` takes permanent faults only; what it
        # sets up is set up here, for the other kind.
        node_main.Fleet.__init__(self, run)
        spec = self.config["faults"]
        if spec.get("kind") != "crash_recovery":
            raise BenchError("this driver injects crash-recovery faults only")
        self.dead: List[int] = [int(v) for v in spec["validators"]]
        self.live: List[int] = [i for i in range(self.n) if i not in self.dead]
        self.signal = faults.SIGNALS[spec["signal"]]
        self.kill_after_s = float(spec["kill_at_s_into_warmup"])
        self.restart_after_s = float(spec["restart_at_s_into_window"])
        self.killed_at: Optional[float] = None
        self.killed: Dict[str, int] = {}
        self.settled: Optional[dict] = None
        self.after_kill: List[tuple] = []
        self._wals: Dict[int, dict] = {}
        self.edges: Dict[str, list] = {}
        # The rejoin: the copies of the dead validators' WALs, when they
        # were started again, their second exit codes, and the curve.
        self.copies: Dict[int, str] = {}
        self.restarted_at: Optional[float] = None
        self.returned_codes: Dict[str, int] = {}
        self.curve: List[tuple] = []  # (s after the restart, [point_of])
        self.boot_scrape: Dict[int, list] = {}
        # What the returned validators asked of the service, step by step.
        self.asked: List[tuple] = []  # (s after the restart, [requests_of])

    def copy_wals(self) -> None:
        """The dead validators' WALs as the kill left them."""
        for i in self.dead:
            source = os.path.join(self.dir, f"validator-{i}", "wal")
            self.copies[i] = os.path.join(self.run.workdir, f"wal-copy-{i}")
            if os.path.isdir(source):
                shutil.copytree(source, self.copies[i])
            else:
                shutil.copy(source, self.copies[i])
        log(f"WALs of {self.dead} copied: "
            f"{[self._tree_bytes(p) for p in self.copies.values()]} bytes")

    @staticmethod
    def _tree_bytes(path: str) -> int:
        if os.path.isfile(path):
            return os.path.getsize(path)
        return sum(os.path.getsize(os.path.join(path, name))
                   for name in os.listdir(path))

    def restart(self) -> None:
        """The same command, key and directory, as an operator's
        supervisor would; from here on they are ``run.children`` again."""
        self.restarted_at = time.monotonic()
        for i in self.dead:
            self.spawn_node(i)
        log(f"validators {self.dead} started again, "
            f"{self.restarted_at - self.killed_at:.3f}s after the kill")

    async def rejoin(self, until_after_s: float) -> None:
        """The copy while they are down, the restart at its instant, then
        the curve to ``until_after_s`` after the window's end."""
        loop = asyncio.get_running_loop()
        while len(self.killed) < len(self.dead) or self.run.window is None:
            await asyncio.sleep(0.05)  # reaped: nothing writes the WAL now
        await loop.run_in_executor(None, self.copy_wals)
        await sleep_until(self.run.window[0] + self.restart_after_s)
        await loop.run_in_executor(None, self.restart)
        step = 0
        end = self.run.window[1] + until_after_s
        while self.restarted_at + step * CURVE_STEP_S <= end:
            await sleep_until(self.restarted_at + step * CURVE_STEP_S)
            scrapes = await loop.run_in_executor(None, self.scrape_curve)
            at = time.monotonic() - self.restarted_at
            self.curve.append((at, [point_of(s) for s in scrapes]))
            self.asked.append((at, [requests_of(scrapes[i])
                                    for i in self.dead]))
            for i in self.dead:
                # Its first answer: the boot's report, before it grew.
                if scrapes[i] is not None and i not in self.boot_scrape:
                    self.boot_scrape[i] = scrapes[i]
                    log(f"validator {i} answers {at:.2f}s after its restart")
            step += 1

    def scrape_curve(self) -> List[Optional[list]]:
        """The returned validators and ``CURVE_OTHERS`` of those that never
        died, side by side; None for the rest.  A scrape holds a
        validator's event loop for half a round (``scrape`` in its ring:
        ~15 ms), and one that is half a round late when it leads may skip
        its own slot, which costs every validator the leader timeout: the
        curve does not need all of them twice a second."""
        who = self.dead + self.live[:CURVE_OTHERS]
        ports = self.ports("metrics")
        with ThreadPoolExecutor(len(who)) as pool:
            texts = dict(zip(who, pool.map(
                harness.http_get, [ports[i] for i in who])))
        return [harness.parse_metrics(texts[i]) if texts.get(i) else None
                for i in range(self.n)]

    def stop(self) -> None:
        names = [f"node-{i}" for i in range(self.n)
                 if f"node-{i}" in self.run.children]
        procs = [self.run.children.pop(n) for n in names]

        def stop(proc) -> tuple:
            began = time.monotonic()
            return harness.stop_process(proc), time.monotonic() - began

        with ThreadPoolExecutor(max(1, len(procs))) as pool:
            stopped = dict(zip(names, pool.map(stop, procs)))
        codes = {name: code for name, (code, _) in stopped.items()}
        log("seconds each validator took to stop: "
            f"{ {n: round(s, 1) for n, (_, s) in stopped.items()} }")
        for i in self.dead:
            code = codes.pop(f"node-{i}", None)
            if code is None:
                continue
            # Killed once already: this is the returned process's code.
            # Never killed (a run that ended early): its only one.
            into = self.returned_codes if f"node-{i}" in self.killed \
                else self.killed
            into[f"node-{i}"] = code
        self.run.exit_codes.update(codes)


# -- the checks -----------------------------------------------------------------


def in_step_at(fleet: Fleet, config: dict) -> Optional[float]:
    """Seconds from the restart to the first point of the curve from which
    the returned validator's committed height stays within
    ``in_step_commits`` of the others' median to the curve's end; None
    where it never does (or the curve is empty)."""
    within = float(config["in_step_commits"])
    (back,) = fleet.dead
    since = None
    for at, points in fleet.curve:
        others = [points[i]["height"] for i in fleet.live
                  if points[i] is not None]
        ok = (points[back] is not None and others
              and statistics.median(others) - points[back]["height"]
              <= within)
        if ok and since is None:
            since = at
        elif not ok:
            since = None
    return since


def _log_curve(fleet: Fleet) -> None:
    (back,) = fleet.dead
    log("the rejoin by the half second [s after the restart, the others' "
        "median committed height and round, the returned validator's "
        "height, round, blocks accepted]:")
    for at, points in fleet.curve:
        others = [points[i] for i in fleet.live if points[i] is not None]
        mine = points[back]
        log(f"  {at:6.2f} "
            f"{statistics.median(p['height'] for p in others):8.0f} "
            f"{statistics.median(p['round'] for p in others):7.0f}   "
            + ("down" if mine is None else
               f"{mine['height']:8.0f} {mine['round']:7.0f} "
               f"{mine['blocks']:8.0f}"))


def _log_requests(fleet: Fleet, step: Optional[float]) -> None:
    """What the returned validator asked of the service while it caught
    up (to in step; to the curve's end where never), and after: the
    observation ``traffic/catchup.json`` was once set from, re-taken."""
    answered = [(at, asked[0]) for at, asked in fleet.asked
                if asked[0] is not None]
    if not answered:
        return
    until = answered[-1][0] if step is None else step
    during = [row for row in answered if row[0] <= until] or answered[:1]

    def between(first: dict, last: dict) -> str:
        edges = sorted(last["by_le"], key=float)
        grown = [last["by_le"][le] - first["by_le"].get(le, 0.0)
                 for le in edges]
        each = [int(n - below) for n, below in zip(grown, [0.0] + grown)]
        requests = last["requests"] - first["requests"]
        return (f"{int(requests)} requests holding "
                f"{int(last['signatures'] - first['signatures'])} "
                f"signatures, by signatures a request (le: requests) "
                f"{ {le: n for le, n in zip(edges, each) if n} }")

    zero = {"by_le": {}, "requests": 0.0, "signatures": 0.0}
    log(f"the returned validator's requests to the service, boot to "
        f"{until:.2f} s after the restart: {between(zero, during[-1][1])}; "
        f"in flight {sorted(r['inflight'] for _, r in during)} of depth "
        f"{sorted({r['depth'] for _, r in during})}")
    if answered[-1][0] > until:
        log(f"and from then to {answered[-1][0]:.2f} s: "
            f"{between(during[-1][1], answered[-1][1])}")


def check_rejoin(run: harness.Run, fleet: Fleet, final: list) -> None:
    config = run.cell["config"]
    (back,) = fleet.dead
    everyone = list(range(fleet.n))
    started = time.monotonic()
    wals = {i: fleet.wal_dag(i) for i in everyone}
    compared = {i: faults.compare_with_reference(fleet, wal)
                for i, wal in wals.items()}
    log(f"{len(wals)} WALs read back and decided by the reference in "
        f"{time.monotonic() - started:.1f}s: blocks "
        f"{[len(w['dag']) for w in wals.values()]}, heights "
        f"{[len(w['commits']) for w in wals.values()]}")

    # Safety.
    differing = {f"node-{i}": c["differing"][:4]
                 for i, c in compared.items() if c["differing"]}
    count = sum(len(c["differing"]) for c in compared.values())
    run.check("commit heights where a WAL's leader or sub-DAG differs from "
              "reference/commit_rule.py on that WAL's own DAG, over all "
              f"{fleet.n}", count, 0, count == 0)
    if differing:
        log(f"heights that differ: {differing}")
    shared = min(len(wals[i]["commits"]) for i in fleet.live)
    least = int(config["compared_heights_min"])
    run.check("commit heights every WAL of a validator that never died "
              "holds, compared with the reference", shared, f">= {least}",
              shared >= least)
    mine, other = wals[back]["commits"], wals[fleet.live[0]]["commits"]
    apart = [h for h in mine if h in other and mine[h] != other[h]]
    run.check("heights the returned validator's WAL shares with validator "
              f"{fleet.live[0]}'s and names otherwise", len(apart), 0,
              not apart and len(set(mine) & set(other)) >= least)
    digests: Dict[tuple, set] = {}
    for wal in wals.values():
        for block in wal["dag"]:
            digests.setdefault((block.author, block.round), set()).add(
                block.digest)
    twice = sorted(slot for slot, found in digests.items() if len(found) > 1)
    run.check(f"(author, round) pairs with two digests in the {fleet.n} WALs",
              len(twice), 0, not twice)
    if twice:
        log(f"signed twice: {twice[:8]}")

    # Recovery: the boot's report against the plain reader of the copy.
    want = recovery.report(fleet.copies[back], fleet.n)
    boot = fleet.boot_scrape.get(back) or []
    got = {what: int(harness.series_sum(boot, "wal_recovery", what=what))
           for what in REPORTED} if boot else {}
    run.check("what the restarted validator reports of its recovery "
              "(wal_recovery: blocks, own round, commit height, torn bytes)",
              got, {what: want[what] for what in REPORTED},
              got == {what: want[what] for what in REPORTED}
              and want["adopted"] == 0)
    run.check("commits written in the copied WAL that its own DAG does not "
              "support, by the reference", len(want["unsupported"]), 0,
              not want["unsupported"] and want["commit_height"] > 0)
    changed = []
    wal = os.path.join(fleet.dir, f"validator-{back}", "wal")
    for file, cut in want["cuts"].items():
        after = (wal if os.path.isfile(fleet.copies[back])
                 else os.path.join(wal, os.path.basename(file)))
        with open(file, "rb") as f:
            before = f.read(cut)
        try:
            with open(after, "rb") as f:
                same = f.read(cut) == before
        except OSError:
            same = cut == 0  # cut whole, as unreachable: it may be gone
        if not same:
            changed.append(os.path.basename(file))
    run.check("files of the returned validator's WAL that do not begin "
              "with the copy's sound bytes", changed, [], not changed)
    end = final[back] or []
    boots = {
        "crash_recovery_total": int(harness.series_sum(
            end, "crash_recovery_total")),
        "wal_replay samples": int(harness.series_sum(
            end, "block_stage_seconds_count", stage="wal_replay")),
    }
    run.check("recovered boots of the returned validator", boots,
              {k: 1 for k in boots}, all(v == 1 for v in boots.values()))
    log(f"recovery: reference {({w: want[w] for w in REPORTED})}, "
        f"{want['entries']} records; the boot replayed "
        f"{int(harness.series_sum(boot, 'wal_recovery', what='replayed_entries'))}"
        f" records after checkpoint height "
        f"{int(harness.series_sum(boot, 'wal_recovery', what='checkpoint_height'))}"
        f", wal_replay "
        f"{harness.series_sum(end, 'block_stage_seconds_sum', stage='wal_replay'):.3f}s")

    # Liveness.
    nodes = run.observed.get("nodes") or fleet.edges
    unscraped = [None] * fleet.n
    start = nodes.get("start") or unscraped
    closing = nodes.get("end") or unscraped

    def timeouts_grown(earlier: list, later: list) -> float:
        return sum(
            harness.series_sum(later[i], "leader_timeout_total")
            - harness.series_sum(earlier[i], "leader_timeout_total")
            if earlier[i] is not None and later[i] is not None
            else float("nan") for i in fleet.live)

    settle = (fleet.settled or {"scrapes": unscraped})["scrapes"]
    grown = timeouts_grown(start, closing)
    log("leader timeouts over the validators that never died, whoever led "
        f"the slot: {timeouts_grown(settle, start)} from {faults.SETTLE_S} s "
        f"after the kill to the window, {grown} in the window, "
        f"{timeouts_grown(closing, final)} from the window to the run's end")
    # Of them, those booked to the returned validator's slots
    # (``mysticeti_health_leader_timeout_total{authority}``, the leader of
    # the round that was abandoned): what a gate that waits for a validator
    # still catching up costs, and what the no-gate control must show.
    for_back = sum(
        harness.series_sum(closing[i], "mysticeti_health_leader_timeout_total",
                           authority=str(back))
        - harness.series_sum(start[i], "mysticeti_health_leader_timeout_total",
                             authority=str(back))
        if start[i] is not None and closing[i] is not None else float("nan")
        for i in fleet.live)
    log(f"of those in the window, for slots the returned validator led: "
        f"{for_back}")
    run.check("leader_timeout_total growth over the window, summed over "
              "the validators that never died", grown, 0, grown == 0)

    def apart_from(scrapes: list, want_n: int, who: List[int]) -> list:
        return [i for i in who if scrapes[i] is None or harness.series_sum(
            scrapes[i], "connected_nodes") != want_n]

    at_start = apart_from(start, fleet.n - 2, fleet.live)
    at_end = apart_from(closing, fleet.n - 1, everyone)
    run.check(f"validators whose connected_nodes is not {fleet.n - 2} at "
              f"the window's start (the {len(fleet.live)} up) or "
              f"{fleet.n - 1} at its end (all)", [at_start, at_end],
              [[], []], not at_start and not at_end)
    answering = (fleet.settled or {"answering": fleet.dead})["answering"]
    run.check("killed validators whose metrics port still answered "
              f"{faults.SETTLE_S} s after the kill", answering, [],
              not answering)
    want_codes = {f"node-{i}": -int(fleet.signal) for i in fleet.dead}
    bad = {n: c for n, c in fleet.returned_codes.items() if c not in (0, -15)}
    run.check("exit codes of the returned validator: the kill's, then 0 / "
              "SIGTERM", [fleet.killed, fleet.returned_codes],
              [want_codes, "0 or -15"],
              fleet.killed == want_codes and not bad
              and set(fleet.returned_codes) == set(want_codes))
    drained = [points[back] for at, points in fleet.curve
               if points[back] is not None]
    climbed = (drained[-1]["height"] - want["commit_height"]
               if drained else float("nan"))
    run.check("commits the returned validator made between its boot and "
              "the drain's end", climbed, f">= {least}", climbed >= least)
    off_chip = {
        labels.get("backend"): value for name, labels, value in end
        if name.removesuffix("_total") == "verified_signatures"
        and labels.get("backend") != "tpu-remote" and value}
    rejected = int(harness.series_sum(end, "verified_signatures_total",
                                      outcome="rejected"))
    fetched = int(harness.series_sum(end, "verified_signatures_total",
                                     backend="tpu-remote"))
    run.check("signatures the returned validator verified off the chip "
              "path, or rejected", [off_chip, rejected], [{}, 0],
              not off_chip and rejected == 0 and fetched > 0)

    # Measured, not required.
    _log_curve(fleet)
    step = in_step_at(fleet, config)
    _log_requests(fleet, step)
    others = [closing[i] for i in fleet.live if closing[i] is not None]
    lag = (statistics.median(
        harness.series_sum(s, "committed_height") for s in others)
        - harness.series_sum(closing[back] or [], "committed_height")
        if others and closing[back] is not None else None)
    log(f"in step (within {config['in_step_commits']} commits) "
        f"{'never' if step is None else f'{step:.2f} s after the restart'}; "
        f"{lag} commits behind at the window's end")
    run.observed["rejoin"] = {
        "back": back, "restarted_at": fleet.restarted_at,
        "curve": fleet.curve, "recover_s": step, "lag_commits": lag,
    }


# -- the run ----------------------------------------------------------------------


async def _drive_and_rejoin(run: harness.Run, fleet: Fleet,
                            probe: List[dict], client: OpenLoopClient,
                            ) -> dict:
    drain_s = float(run.cell["traffic"]["drain_s"])
    killer = asyncio.ensure_future(fleet.kill_under_load(client))
    rejoiner = asyncio.ensure_future(fleet.rejoin(drain_s))
    try:
        outcome = await _drive(run, fleet, probe, client)
    finally:
        for task in (killer, rejoiner):
            if not task.done():
                task.cancel()
        await asyncio.gather(killer, rejoiner, return_exceptions=True)
    for task in (killer, rejoiner):
        if not task.cancelled() and task.exception() is not None:
            raise task.exception()
    return outcome


def drive(run: harness.Run) -> dict:
    from mysticeti_tpu import spans

    if "wal_replay" not in spans.STAGES:
        # At once, before anything boots (a program from before PR 45: a
        # validator that is back and behind costs the others the leader
        # timeout in every slot it leads, and its boot reports nothing).
        raise BenchError("this program does not report a WAL recovery nor "
                         "keep a returned validator out of the proposal "
                         "gate (spans.STAGES has no wal_replay)")
    fleet = Fleet(run)
    if len(fleet.dead) != 1:
        raise BenchError("this driver brings one validator back")
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
        log(f"{fleet.n} validators booted, connected and past their "
            f"start-up; {fleet.dead} dies {fleet.kill_after_s} s into the "
            f"load and is started again {fleet.restart_after_s} s into the "
            "window")
        outcome = asyncio.run(_drive_and_rejoin(run, fleet, probes[1], client))
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
    # Finality of what was due while the returned validator caught up:
    # ``finality_p50_s.rejoin`` (one list a connection, tick by tick).
    window = client.ticks_due(*run.window)
    run.observed["due_and_finality"] = [
        (client.ticks[tick]["due"], received - client.ticks[tick]["due"])
        for conn in client.connections for tick, received in conn.notified
        if tick in window]
    log("mean finality by the second it was due in: "
        f"{outcome['latency_avg_by_second_s']}")
    log(f"window: {record['submitted']} due, {len(latencies)} notified by "
        f"the drain's end, {record['late_notified']} later, "
        f"{record['shed']} shed, {record['unanswered']} unanswered, "
        f"{record['lost_after_ack']} acknowledged and never notified")
    faults.check_fleet(run, fleet, final, mapped)
    check_rejoin(run, fleet, final)
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
