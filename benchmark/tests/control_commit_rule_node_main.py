"""A control of the crash-fault cell: validators whose committer skips
leaders the rule commits (the other, ``control_norepair_node_main.py``,
keeps a dead peer in the proposal gate).

``python -m mysticeti_tpu`` with ``UniversalCommitter.try_commit`` altered
where it returns: every decided slot of a round divisible by ten that the
rule decided ``commit`` comes out ``skip``, identically on every validator
(the round decides, so all of them flip the same slots).  The skipped
leader's block and its history are then sequenced with the next committed
leader's sub-DAG, so nothing is lost: the WALs agree with each other at
every height, every transaction is notified, no timeout fires, the dead
stay dead - every check ``paper10-steady`` has passes, and every counter
of the program looks sound.  That is the shortcut this deployment tempts: a
committer that is quicker to give a slot up than the rule allows, which
under faults looks like the faults' own skips.  Only the plain reference
(``benchmark/reference/commit_rule.py``) on the DAG each validator wrote
shows that those slots had their certificates.  The configuration's safety
guarantee is that every live validator's committed sequence equals the
reference's, so a run against this node must come out with ``correct``
false by that comparison alone; it adds no switch to the program.

The fault driver starts this file in place of ``python -m mysticeti_tpu``
where the configuration names it as ``node_main``: ``control_spec`` writes
such a copy of a cell's files,

    python3 benchmark/tests/control_commit_rule_node_main.py \\
        --control-spec BENCHMARK.json paper10f3-steady .bench_work/control
    python3 benchmark/run.py --spec .bench_work/control/spec.json \\
        --workload paper10f3-steady ...
"""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

EVERY = 10


def skip_every_tenth() -> None:
    from mysticeti_tpu.consensus import LeaderStatus
    from mysticeti_tpu.consensus.universal_committer import UniversalCommitter

    sound = UniversalCommitter.try_commit

    def try_commit(self, last_decided):
        return [
            LeaderStatus.skip(status.authority_round)
            if status.kind == LeaderStatus.COMMIT
            and status.round % EVERY == 0 else status
            for status in sound(self, last_decided)]

    UniversalCommitter.try_commit = try_commit


def control_spec(spec_path: str, workload: str, out_dir: str,
                 node_main: str = __file__) -> str:
    """A copy of one cell's entries and files under ``out_dir`` whose
    configuration names ``node_main`` (this file) as its ``node_main``; the
    path of the copy's spec."""
    with open(spec_path) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    source = os.path.join(ROOT, entry["file"])
    with open(source) as f:
        config = json.load(f)
    config["node_main"] = os.path.relpath(os.path.abspath(node_main), ROOT)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(os.path.join(out_dir, "configs"))
    os.makedirs(os.path.join(out_dir, "traffic"))
    target = os.path.join(out_dir, "configs", os.path.basename(source))
    with open(target, "w") as f:
        json.dump(config, f, indent=1)
    traffic = cell["traffic"] + ".json"
    shutil.copy(
        os.path.join(os.path.dirname(os.path.dirname(source)), "traffic",
                     traffic),
        os.path.join(out_dir, "traffic", traffic))
    entry["file"] = os.path.abspath(target)
    path = os.path.join(out_dir, "spec.json")
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return path


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    skip_every_tenth()
    from mysticeti_tpu.cli import main

    sys.exit(main())
