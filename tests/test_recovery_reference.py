"""``benchmark/reference/recovery.py`` against the system: what a boot on a
WAL reports of its recovery (``storage.open_store`` -> ``BlockStore.open``
-> ``Core``: blocks in the store, the highest own round, the last
committed height, bytes cut as a torn tail) equals what the plain reader
finds in the same bytes, on the logs of seeded simulated fleets of 4, 7 and
10 authorities - one a single file, two segmented with checkpoints - cut
at seeded offsets: not at all, on a record boundary, inside a header,
inside a payload, by a flipped byte, and anywhere."""
import asyncio
import os
import random
import shutil

import pytest

from benchmark.reference import recovery
from mysticeti_tpu.block_handler import TestBlockHandler
from mysticeti_tpu.chaos import ChaosSimHarness
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.config import Parameters, StorageParameters
from mysticeti_tpu.core import Core, CoreOptions
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.runtime.simulated import run_simulation
from mysticeti_tpu.storage import active_wal_file, open_store

CUTS = ("whole", "boundary", "header", "payload", "flipped", "anywhere-a",
        "anywhere-b")
# n -> the storage under the fleet: the legacy single file, and segments
# small enough that the log rolls and checkpoints are written in the run.
STORAGE = {
    4: StorageParameters(segment_bytes=0),
    7: StorageParameters(segment_bytes=96 * 1024, checkpoint_interval=16,
                         gc_depth=0),
    10: StorageParameters(segment_bytes=192 * 1024, checkpoint_interval=24,
                          gc_depth=0),
}
_LOGS = {}


def _parameters(n: int) -> Parameters:
    return Parameters(leader_timeout_s=1.0, storage=STORAGE[n])


def _fleet_log(n: int, tmp_root) -> str:
    """The log one validator of a seeded fleet of ``n`` left behind when it
    was stopped under way (closed in order: every append is in the file)."""
    if n not in _LOGS:
        directory = str(tmp_root / f"fleet-{n}")
        os.makedirs(directory)
        victim = n - 1

        async def scenario():
            # The benchmark's committee: the election the reference has.
            harness = ChaosSimHarness(
                n, directory, _parameters(n),
                committee=Committee.new_for_benchmarks(n))
            await harness.start()
            await asyncio.sleep(6.0)
            await harness.crash(victim)
            await harness.stop()

        run_simulation(scenario(), seed=4500 + n)
        _LOGS[n] = os.path.join(directory, f"wal-{victim}")
    return _LOGS[n]


@pytest.fixture(scope="module")
def logs(tmp_path_factory):
    return tmp_path_factory.mktemp("recovery-logs")


def _cut(path: str, how: str, rng: random.Random) -> None:
    """Cut the file appends land in, as a crash would have left it."""
    target = active_wal_file(path)
    with open(target, "rb") as f:
        data = f.read()
    found, end = recovery.records(data)
    assert end == len(data) and len(found) >= 2  # a segment may be young
    position, _tag, payload = found[rng.randrange(max(1, len(found) // 2),
                                                  len(found))]
    if how == "whole":
        return
    if how == "flipped":
        at = position + recovery.HEADER.size + rng.randrange(len(payload))
        data = data[:at] + bytes([data[at] ^ 0x10]) + data[at + 1:]
    else:
        size = {
            "boundary": position,
            "header": position + rng.randrange(1, recovery.HEADER.size),
            "payload": position + recovery.HEADER.size
            + rng.randrange(1, max(2, len(payload))),
        }.get(how, rng.randrange(len(data) // 2, len(data)))
        data = data[:size]
    with open(target, "wb") as f:
        f.write(data)


@pytest.mark.parametrize("how", CUTS)
@pytest.mark.parametrize("n", sorted(STORAGE))
def test_a_boot_reports_what_the_plain_reader_finds_in_the_log(
        n, how, logs, tmp_path):
    source = _fleet_log(n, logs)
    path = str(tmp_path / "wal")
    if os.path.isdir(source):
        shutil.copytree(source, path)
    else:
        shutil.copy(source, path)
    _cut(path, how, random.Random(f"{n}-{how}"))
    want = recovery.report(path, n)
    assert want["adopted"] == 0 and want["unsupported"] == []
    assert want["blocks"] > 8 * n and want["own_round"] > 8
    assert want["commit_height"] > 4
    if how in ("header", "payload", "flipped"):
        assert want["torn_bytes"] > 0
    if how in ("whole", "boundary"):
        assert want["torn_bytes"] == 0

    authority = n - 1
    committee = Committee.new_for_benchmarks(n)
    metrics = Metrics()
    recovered, _observer, wal_writer, lifecycle = open_store(
        authority, path, committee, _parameters(n), metrics)
    got = {"blocks": recovered.recovered_blocks,
           "own_round": recovered.last_own_block.block.round(),
           "commit_height": recovered.commit_height,
           "torn_bytes": recovered.torn_bytes}
    assert got == {k: want[k] for k in got}
    assert recovered.replayed_entries > 0 and recovered.replay_wall_s > 0
    if STORAGE[n].checkpoint_interval and STORAGE[n].segment_bytes:
        # The boot began at a checkpoint and still counts the whole log.
        assert recovered.checkpoint_height > 0
        assert recovered.replayed_entries < want["entries"]
    # The torn tail is gone from the file before the first new append.
    assert {f: os.path.getsize(f) for f in want["cuts"]
            if os.path.exists(f)} == {
        f: size for f, size in want["cuts"].items() if os.path.exists(f)}
    # Core opens on it and proposes nothing at or below what it had signed.
    core = Core(
        block_handler=TestBlockHandler(
            last_transaction=authority * 1_000_000, committee=committee,
            authority=authority),
        authority=authority, committee=committee,
        parameters=_parameters(n), recovered=recovered,
        wal_writer=wal_writer, options=CoreOptions.test(),
        signer=Committee.benchmark_signers(n)[authority], metrics=metrics,
        storage=lifecycle)
    assert core.last_proposed() == want["own_round"]
    assert metrics.crash_recovery_total._value.get() == 1
    block = core.try_new_block()
    assert block is None or block.round() > want["own_round"]
    wal_writer.close()
    core.block_store.close()
