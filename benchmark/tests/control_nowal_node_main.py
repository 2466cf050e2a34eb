"""A control of the crash-recovery cell: a validator that, started again,
ignores the WAL it left and boots from genesis.

``python -m mysticeti_tpu`` with one thing before it: where the storage
directory already holds a WAL (this is a restart), the WAL is moved aside,
so the node opens an empty log, reports no recovery, and proposes from
round 1 again - second blocks for every round it had signed before the
kill, which its peers still hold.  The fleet goes on and commits; nothing a
client sees is wrong.  Under the configuration's fault the run must come
out with ``correct`` false by the recovery comparisons (the boot's report
against ``reference/recovery.py``, the WAL against its copy, the recovered
boots) and by the (author, round) pairs with two digests; it adds no
switch to the program.

    python3 benchmark/tests/control_nowal_node_main.py --control-spec \\
        BENCHMARK.json paper10cr-rejoin .bench_work/control
    python3 benchmark/run.py --spec .bench_work/control/spec.json \\
        --workload paper10cr-rejoin ...
"""
from __future__ import annotations

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_commit_rule_node_main as shared  # noqa: E402 (ROOT on the path)


def forget_the_wal(argv) -> None:
    storage = argv[argv.index("--private-config-path") + 1]
    wal = os.path.join(storage, "wal")
    if os.path.exists(wal):
        aside = wal + ".ignored"
        shutil.rmtree(aside, ignore_errors=True)
        os.replace(wal, aside)


def control_spec(spec_path: str, workload: str, out_dir: str) -> str:
    return shared.control_spec(spec_path, workload, out_dir, __file__)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    forget_the_wal(sys.argv)
    from mysticeti_tpu.cli import main

    sys.exit(main())
