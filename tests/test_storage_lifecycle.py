"""Storage lifecycle plane (storage.py): segmented WAL + manifest atomicity,
commit-anchored checkpoints with fallback, DAG garbage collection, and
snapshot catch-up — unit coverage plus deterministic sims on the
virtual-time loop (crash-during-roll, crash-during-checkpoint, torn
manifest, bounded disk, O(recent) bootstrap)."""
import json
import os

import pytest

from mysticeti_tpu.chaos import CrashFault, FaultPlan, run_chaos_sim
from mysticeti_tpu.config import Parameters, StorageParameters
from mysticeti_tpu.storage import (
    MANIFEST_NAME,
    SegmentedWalWriter,
    active_wal_file,
    checkpoint_files,
    open_store,
    open_wal,
)
from mysticeti_tpu.wal import HEADER_SIZE, WalError, walf

pytestmark = pytest.mark.storage


def _params(**storage_kwargs):
    defaults = dict(segment_bytes=16 * 1024, checkpoint_interval=5, gc_depth=20)
    defaults.update(storage_kwargs)
    return Parameters(
        leader_timeout_s=1.0, storage=StorageParameters(**defaults)
    )


# ---------------------------------------------------------------------------
# Segmented WAL units


def test_roll_read_iter_and_reopen(tmp_path):
    params = StorageParameters(segment_bytes=2048)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    positions = [w.writev(1, (bytes([i % 250]) * 100,)) for i in range(50)]
    assert w.segment_count() > 1  # it actually rolled
    # Positions stay a single contiguous u64 space across segments.
    for i, p in enumerate(positions):
        tag, payload = r.read(p)
        assert (tag, bytes(payload)) == (1, bytes([i % 250]) * 100)
    assert [e[0] for e in r.iter_until()] == positions
    # Replay-from-position (the checkpoint seam) crosses segment boundaries.
    assert [e[0] for e in r.iter_from(positions[30])] == positions[30:]
    w.close()
    r.close()

    w2, r2 = open_wal(path, params)
    assert [e[0] for e in r2.iter_until()] == positions
    assert w2.write(2, b"post-reopen") == positions[-1] + HEADER_SIZE + 100
    w2.close()
    r2.close()


def test_entries_never_straddle_segments(tmp_path):
    params = StorageParameters(segment_bytes=1024)
    w, r = open_wal(str(tmp_path / "wal"), params)
    for i in range(20):
        w.write(1, b"x" * 300)
    w.flush()
    for name, base, size, _mr in w.segments_snapshot():
        # Every segment starts at an entry boundary: a standalone reader on
        # the bare file replays it fully.
        reader_positions = []
        from mysticeti_tpu.wal import WalReader

        reader = WalReader(os.path.join(str(tmp_path / "wal"), name))
        consumed = 0
        for pos, _tag, payload in reader.iter_until():
            consumed = pos + HEADER_SIZE + len(payload)
        reader.close()
        assert consumed == size, name
    w.close()
    r.close()


def test_single_file_migration(tmp_path):
    path = str(tmp_path / "wal")
    w, r = walf(path)
    p = w.write(7, b"legacy-entry")
    w.sync()
    w.close()
    r.close()
    assert os.path.isfile(path)
    w2, r2 = open_wal(path, StorageParameters(segment_bytes=4096))
    assert os.path.isdir(path)  # migrated in place
    assert r2.read(p) == (7, b"legacy-entry")
    w2.close()
    r2.close()


def test_torn_active_tail_truncated_on_reopen(tmp_path):
    params = StorageParameters(segment_bytes=4096)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    good = w.write(1, b"good")
    w.write(2, b"to-be-torn" * 10)
    w.sync()
    w.close()
    r.close()
    active = active_wal_file(path)
    with open(active, "r+b") as f:
        f.truncate(os.path.getsize(active) - 8)

    w2, r2 = open_wal(path, params)
    replayed = list(r2.iter_from(0, w2.position()))
    assert [(t, bytes(d)) for _, t, d in replayed] == [(1, b"good")]
    # The recovery contract: truncate at the tear, then appends resume there.
    w2.truncate_to(good + HEADER_SIZE + 4)
    p3 = w2.write(3, b"after")
    assert p3 == good + HEADER_SIZE + 4
    assert [t for _, t, _ in r2.iter_until()] == [1, 3]
    w2.close()
    r2.close()


def test_tear_in_sealed_segment_drops_later_segments(tmp_path):
    params = StorageParameters(segment_bytes=1024)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    for i in range(12):
        w.write(1, bytes([i]) * 300)
    w.sync()
    segments = w.segments_snapshot()
    assert len(segments) >= 3
    w.close()
    r.close()
    # Tear INSIDE the second segment (sealed): everything after it is
    # unreachable on replay and must be dropped.
    victim = os.path.join(path, segments[1][0])
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) - 5)

    w2, r2 = open_wal(path, params)
    entries = list(r2.iter_from(0, w2.position()))
    end = entries[-1][0] + HEADER_SIZE + len(entries[-1][2])
    assert end < w2.position()  # replay stops at the tear
    w2.truncate_to(end)
    # The torn segment became the active one; later segments are gone.
    assert w2.position() == end
    assert w2.segments_snapshot()[-1][0] == segments[1][0]
    assert not os.path.exists(os.path.join(path, segments[2][0]))
    p = w2.write(9, b"resumed")
    assert p == end
    assert [t for _, t, _ in r2.iter_from(p)] == [9]
    w2.close()
    r2.close()


def test_crash_during_roll_orphan_segment_recovered(tmp_path):
    params = StorageParameters(segment_bytes=2048)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    positions = [w.write(1, b"z" * 150) for _ in range(10)]
    names = [s[0] for s in w.segments_snapshot()]
    w.close()
    r.close()
    # Crash window: the next segment file was created but the manifest
    # rewrite never happened.
    orphan = os.path.join(path, f"wal.{len(names):06d}")
    open(orphan, "wb").close()
    w2, r2 = open_wal(path, params)
    assert not os.path.exists(orphan) or os.path.getsize(orphan) == 0
    assert [e[0] for e in r2.iter_until()] == positions
    w2.write(1, b"continues")
    w2.close()
    r2.close()


def test_torn_manifest_tmp_is_ignored(tmp_path):
    params = StorageParameters(segment_bytes=2048)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    positions = [w.write(1, b"m" * 100) for _ in range(5)]
    w.close()
    r.close()
    # A crash mid-manifest-rewrite leaves a half-written tmp; the rename
    # never happened so the real manifest is intact.
    with open(os.path.join(path, MANIFEST_NAME + ".tmp"), "w") as f:
        f.write('{"version": 1, "segments": [{"nam')
    w2, r2 = open_wal(path, params)
    assert [e[0] for e in r2.iter_until()] == positions
    assert not os.path.exists(os.path.join(path, MANIFEST_NAME + ".tmp"))
    w2.close()
    r2.close()


def test_corrupt_manifest_is_loud(tmp_path):
    params = StorageParameters(segment_bytes=2048)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    w.write(1, b"x")
    w.close()
    r.close()
    with open(os.path.join(path, MANIFEST_NAME), "w") as f:
        f.write("{broken json")
    with pytest.raises(WalError, match="manifest"):
        open_wal(path, params)


def test_wal_size_bytes_counts_live_segments_only(tmp_path):
    params = StorageParameters(segment_bytes=1024)
    w, r = open_wal(str(tmp_path / "wal"), params)
    for i in range(1, 13):
        p = w.write(1, bytes([i]) * 300)
        w.note_round(i, p)
    w.flush()
    total = w.position()
    assert w.size_bytes() == total
    reclaimed, removed = w.retire_below(6, keep_from_position=total)
    assert removed > 0 and reclaimed > 0
    # The gauge source now reports live bytes, not lifetime bytes written.
    assert w.size_bytes() == total - reclaimed
    assert w.position() == total  # logical append position is untouched
    w.close()
    r.close()


def test_retire_below_is_prefix_only(tmp_path):
    """A sealed segment still holding live rounds STOPS garbage collection:
    deleting a later low-round segment past it would punch a hole in the
    base space, which recovery would misread as a mid-log tear."""
    params = StorageParameters(segment_bytes=1024)
    w, r = open_wal(str(tmp_path / "wal"), params)
    positions = []
    for i in range(12):
        positions.append(w.write(1, bytes([i]) * 300))
    w.flush()
    segs = w.segments_snapshot()
    assert len(segs) >= 4
    # First segment keeps a LIVE round; the second holds only retired ones.
    w.note_round(100, positions[0])
    w.note_round(1, segs[1][1])
    reclaimed, removed = w.retire_below(50, keep_from_position=w.position())
    assert (reclaimed, removed) == (0, 0)  # blocked by the live prefix
    # Bases stay contiguous, so a reopen sees no phantom tear.
    snapshot = w.segments_snapshot()
    for prev, cur in zip(snapshot, snapshot[1:]):
        assert cur[1] == prev[1] + prev[2]
    w.close()
    r.close()
    w2, r2 = open_wal(str(tmp_path / "wal"), params)
    assert [e[0] for e in r2.iter_until()] == positions
    w2.close()
    r2.close()


def test_gc_crash_between_manifest_and_unlink_recovers(tmp_path):
    """Crash-safety order of segment GC: the manifest drops the victims
    BEFORE their files are unlinked, so a crash in between leaves orphan
    files (deleted on recovery) — never a manifest naming missing files."""
    params = StorageParameters(segment_bytes=1024)
    path = str(tmp_path / "wal")
    w, r = open_wal(path, params)
    positions = []
    for i in range(1, 13):
        p = w.write(1, bytes([i]) * 300)
        w.note_round(i, p)
        positions.append(p)
    w.sync()
    victim_names = [s[0] for s in w.segments_snapshot()[:2]]
    victim_bytes = {
        name: open(os.path.join(path, name), "rb").read()
        for name in victim_names
    }
    reclaimed, removed = w.retire_below(9, keep_from_position=w.position())
    assert removed >= 2
    survivors = [e[0] for e in r.iter_until()]
    w.close()
    r.close()
    # Simulate the crash window: the unlinked victims come BACK as orphans
    # (equivalently: the crash happened right after the manifest rewrite).
    for name, data in victim_bytes.items():
        with open(os.path.join(path, name), "wb") as f:
            f.write(data)
    w2, r2 = open_wal(path, params)
    assert [e[0] for e in r2.iter_until()] == survivors
    for name in victim_names:
        assert not os.path.exists(os.path.join(path, name))  # orphans purged
    w2.close()
    r2.close()


# ---------------------------------------------------------------------------
# Checkpoint + GC + recovery through the real node stack (deterministic sims)


@pytest.mark.chaos
def test_checkpoint_boot_replays_only_the_tail(tmp_path):
    """Disk bounded + O(recent) boot: segments below the GC floor are
    deleted while the fleet commits, and a crash-restart boots from the
    newest checkpoint, replaying a small fraction of lifetime WAL bytes."""
    plan = FaultPlan(seed=7, crashes=[CrashFault(node=2, at_s=20.0, downtime_s=2.0)])
    report, harness = run_chaos_sim(
        plan, 4, 30.0, str(tmp_path), parameters=_params(), with_metrics=True
    )
    # Liveness through GC + checkpointing.
    assert all(harness.committed_height(a) > 100 for a in range(4))
    for authority in range(4):
        node = harness.nodes[authority]
        lifecycle = node.core.storage
        wal_dir = os.path.join(str(tmp_path), f"wal-{authority}")
        # GC actually deleted segments: the address space no longer starts
        # at zero and the reclaimed counter moved.
        assert node.core.wal_writer.first_base() > 0
        metrics = harness.metrics[authority]
        assert metrics.wal_reclaimed_bytes_total._value.get() > 0
        assert metrics.checkpoint_last_commit_index._value.get() > 0
        # Live disk is bounded well below lifetime bytes written.
        assert node.core.wal_writer.size_bytes() < node.core.wal_writer.position()
        assert len(checkpoint_files(wal_dir)) == 2  # pruned to the keep set
    # The crashed node recovered FROM A CHECKPOINT: it replayed only the
    # post-checkpoint tail, a small fraction of the lifetime log.
    restarted = harness.nodes[2].core.storage
    assert restarted.recovered_checkpoint_height > 0
    assert restarted.replay_start > 0
    lifetime = harness.nodes[2].core.wal_writer.position()
    assert restarted.replayed_bytes < lifetime / 5, (
        restarted.replayed_bytes, lifetime,
    )
    assert harness.metrics[2].crash_recovery_total._value.get() == 1.0
    # And it kept committing after the restart.
    assert harness.committed_height(2) > report.crash_events[0]["committed_height"]


@pytest.mark.chaos
def test_same_seed_storage_chaos_is_byte_identical(tmp_path):
    """Crash-during-roll / crash-during-checkpoint land WHEREVER the seeded
    schedule puts them (16 KiB segments roll every ~1 s; checkpoints every 5
    commits): same-seed runs produce byte-identical fault schedules and
    logs, and every node always recovers to a committing state."""
    plan = FaultPlan(
        seed=23,
        crashes=[
            CrashFault(node=1, at_s=6.0, downtime_s=2.0),
            CrashFault(node=3, at_s=9.0, downtime_s=2.0, torn_tail_bytes=11),
        ],
    )
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    report, harness = run_chaos_sim(
        plan, 4, 18.0, str(tmp_path / "a"), parameters=_params(),
        with_metrics=True,
    )
    replay, _ = run_chaos_sim(
        plan, 4, 18.0, str(tmp_path / "b"), parameters=_params(),
        with_metrics=True,
    )
    assert report.schedule_bytes == replay.schedule_bytes
    assert report.fault_log_bytes == replay.fault_log_bytes
    assert report.sequences == replay.sequences
    for event in report.crash_events:
        node = event["node"]
        assert harness.metrics[node].crash_recovery_total._value.get() == 1.0
        assert harness.committed_height(node) > event["committed_height"]


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    plan = FaultPlan(seed=5)
    report, harness = run_chaos_sim(
        plan, 4, 20.0, str(tmp_path), parameters=_params(), with_metrics=True
    )
    wal_dir = os.path.join(str(tmp_path), "wal-1")
    newest, older = checkpoint_files(wal_dir)[:2]
    # Torn checkpoint (crash mid-write survived the atomic rename somehow /
    # disk corruption): flip bytes in the newest one.
    with open(newest, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff\xff")
    from mysticeti_tpu.committee import Committee

    committee = Committee.new_test([1, 1, 1, 1])
    recovered, _obs, wal_writer, lifecycle = open_store(
        1, wal_dir, committee, _params()
    )
    older_height = int(os.path.basename(older).split(".")[1])
    assert lifecycle.recovered_checkpoint_height == older_height
    assert recovered.commit_height >= older_height  # tail replay catches up
    wal_writer.close()
    recovered.block_store.close()

    # Both checkpoints corrupt + GC'd history = genuinely unreplayable: the
    # boot refuses loudly instead of silently starting from a hole.
    with open(older, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(WalError, match="checkpoint"):
        open_store(1, wal_dir, committee, _params())


# ---------------------------------------------------------------------------
# Snapshot catch-up


@pytest.mark.chaos
def test_snapshot_catchup_rejoins_and_commits_fleet_sequence(tmp_path):
    """A node that missed ~200 commit heights (its history GC'd fleet-wide,
    so block-by-block pull from round zero is impossible) rejoins via the
    snapshot stream, adopts the fleet's commit baseline, and commits the
    SAME leader sequence at every shared height.  (The >= 1000-round regime
    rides in tools/storage_probe.py.)"""
    params = _params(snapshot_catchup=True, catchup_threshold_commits=50)
    plan = FaultPlan(
        seed=13, crashes=[CrashFault(node=3, at_s=3.0, downtime_s=30.0)]
    )
    report, harness = run_chaos_sim(
        plan, 4, 45.0, str(tmp_path), parameters=params, with_metrics=True
    )
    node3 = harness.nodes[3]
    lifecycle = node3.core.storage
    crashed_at = report.crash_events[0]["committed_height"]
    assert lifecycle.snapshots_adopted == 1
    # It genuinely skipped history: resumed well past where it crashed...
    anchors3 = harness.checker._anchors[3]
    resumed = min(h for h in sorted(anchors3) if h > crashed_at)
    assert resumed > crashed_at + params.storage.catchup_threshold_commits // 2
    # ...rejoined the committing fleet...
    heights = [harness.committed_height(a) for a in range(4)]
    assert min(heights) > max(heights) - 10
    assert harness.committed_height(3) > resumed + 50
    # ...and the committed-leader sequence is prefix-consistent with every
    # healthy node at every shared height (incl. the adopted anchor).
    anchors0 = harness.checker._anchors[0]
    shared = set(anchors0) & set(anchors3)
    assert len(shared) > 100
    assert all(anchors0[h] == anchors3[h] for h in shared)
    # The serving side shipped the bounded post-floor window, not history.
    served = sum(
        harness.nodes[a].snapshot_blocks_served
        + sum(
            d.snapshot_blocks_sent
            for d in harness.nodes[a]._disseminators.values()
        )
        for a in range(3)
    )
    assert served > 0


def test_manifest_and_checkpoint_roundtrip_units(tmp_path):
    from mysticeti_tpu.storage import SnapshotManifest, fold_leader_digest
    from mysticeti_tpu.types import BlockReference

    ref = BlockReference(2, 41, b"\x07" * 32)
    digest = fold_leader_digest(b"\x00" * 32, ref)
    manifest = SnapshotManifest(
        commit_height=41,
        last_committed_leader=ref,
        gc_round=21,
        chain_digest=digest,
        committed_refs=[ref, BlockReference(0, 40, b"\x01" * 32)],
    )
    again = SnapshotManifest.from_bytes(manifest.to_bytes())
    assert again == manifest
    # The digest chain is order-sensitive: folding the other ref differs.
    assert fold_leader_digest(b"\x00" * 32, manifest.committed_refs[1]) != digest


def test_block_manager_floor_drops_and_releases(tmp_path):
    from mysticeti_tpu.block_manager import BlockManager
    from mysticeti_tpu.block_store import BlockStore, BlockWriter
    from mysticeti_tpu.committee import Committee
    from mysticeti_tpu.types import StatementBlock

    committee = Committee.new_test([1, 1, 1, 1])
    w, r = walf(str(tmp_path / "wal"))
    recovered, _ = BlockStore.open(0, r, w, committee)
    store = recovered.block_store
    manager = BlockManager(store, 4)
    writer = BlockWriter(w, store)
    genesis = [
        StatementBlock.new_genesis(a, committee.epoch)
        for a in committee.authority_indexes()
    ]
    # A block whose parents (round 9) we will never have.
    parents = [
        StatementBlock.build(a, 9, [g.reference for g in genesis], ())
        for a in committee.authority_indexes()
    ]
    orphan = StatementBlock.build(
        0, 10, [p.reference for p in parents], ()
    )
    processed, missing = manager.add_blocks([orphan], writer)
    assert not processed and missing  # parked, parents requested
    # Raising the floor to 10 settles the sub-floor parents and releases it.
    released, _missing2 = manager.set_gc_floor(10, writer)
    assert [b.reference for _pos, b in released] == [orphan.reference]
    assert all(not refs for refs in manager.missing)
    # Ancient blocks below the floor are dropped outright now...
    ancient, _ = manager.add_blocks(parents, writer)
    assert ancient == []
    # ...and read as settled at the dedup gate (never re-verified).
    assert manager.exists_or_pending(parents[0].reference)
    w.close()
    r.close()


def test_linearizer_floor_and_adoption():
    from mysticeti_tpu.consensus.linearizer import Linearizer
    from mysticeti_tpu.types import BlockReference

    lin = Linearizer(block_store=None)
    refs = [BlockReference(a, r, bytes([a]) * 32) for a in range(2) for r in (5, 30)]
    lin.committed.update(refs)
    lin.last_height = 3
    lin.set_gc_round(10)
    assert all(r.round >= 10 for r in lin.committed)
    adopt_refs = [BlockReference(1, 40, b"\x09" * 32)]
    lin.adopt_snapshot(90, adopt_refs, 25)
    assert lin.last_height == 90
    assert lin.gc_round == 25
    assert adopt_refs[0] in lin.committed


def test_storage_parameters_unification(tmp_path):
    # Legacy spellings migrate into the storage block...
    p = Parameters(enable_cleanup=False, store_retain_rounds=77)
    assert p.storage.enable_cleanup is False
    assert p.storage.retain_rounds == 77
    assert p.enable_cleanup is False and p.store_retain_rounds == 77
    # ...and the YAML round-trip keeps one canonical spelling.
    p2 = Parameters(storage=StorageParameters(gc_depth=123, snapshot_catchup=True))
    path = str(tmp_path / "parameters.yaml")
    p2.dump(path)
    raw = open(path).read()
    assert "gc_depth: 123" in raw and "enable_cleanup" not in raw.split("storage:")[0]
    p3 = Parameters.load(path)
    assert p3.storage.gc_depth == 123
    assert p3.storage.snapshot_catchup is True
    assert p3.store_retain_rounds == p3.storage.retain_rounds


def test_wal_inspect_tool(tmp_path):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    plan = FaultPlan(seed=3)
    run_chaos_sim(
        plan, 4, 14.0, str(tmp_path), parameters=_params(), with_metrics=True
    )
    wal_dir = os.path.join(str(tmp_path), "wal-0")

    def run(*args):
        return subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "wal_inspect.py"), *args],
            capture_output=True, text=True,
        )

    healthy = run(wal_dir, "--json")
    assert healthy.returncode == 0, healthy.stdout + healthy.stderr
    doc = json.loads(healthy.stdout)
    assert doc["layout"] == "segmented"
    assert doc["checkpoints"] and doc["checkpoints"][0]["valid"]
    assert doc["census"]["block"]["entries"] > 0
    # Tear a SEALED segment: unreplayable -> exit 2 with a diagnosis.
    segments = sorted(
        n for n in os.listdir(wal_dir) if n.startswith("wal.")
    )
    victim = os.path.join(wal_dir, segments[0])
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) - 6)
    torn = run(wal_dir)
    assert torn.returncode == 2
    assert "SEALED" in torn.stdout
    # GC'd history with every checkpoint corrupted -> exit 3.
    for ckpt in checkpoint_files(wal_dir):
        with open(ckpt, "r+b") as f:
            f.seek(8)
            f.write(b"\x00" * 8)
    broken = run(wal_dir)
    assert broken.returncode in (2, 3)
    assert "UNREPLAYABLE" in broken.stdout or "SEALED" in broken.stdout
