"""A control of the SmallBank cell: validators whose WriteCheck forgets the
overdraft penalty.

``python -m mysticeti_tpu`` with ``ExecutionState._apply_smallbank`` altered
for one procedure: a check that savings and checking together do not cover
takes its amount off checking and not the source's one unit more.  All ten
validators do the same, so their executed roots agree with each other at
every height, every operation is notified, no nonce breaks, every counter
of the program looks sound.  Only the plain reference
(``benchmark/reference/smallbank.py``), which was written from the source
and not from the program, folds the committed sequence to other balances:
the run must come out with ``correct`` false by "heights where the executed
root differs from the reference's fold of the WAL" alone; it adds no switch
to the program.

    python3 benchmark/tests/control_nopenalty_node_main.py --control-spec \\
        BENCHMARK.json smallbank10-hotspot .bench_work/control
    python3 benchmark/run.py --spec .bench_work/control/spec.json \\
        --workload smallbank10-hotspot ...
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_commit_rule_node_main as shared  # noqa: E402 (ROOT on the path)


def forget_the_penalty() -> None:
    from mysticeti_tpu import execution as X

    sound = X.ExecutionState._apply_smallbank

    def apply(self, tx, entry, deltas):
        verdict = sound(self, tx, entry, deltas)
        checking, _, savings = entry
        if tx.op == X.OP_WRITE_CHECK and savings + checking < tx.amount:
            now, nonce, kept = self._exec_accounts[tx.account]
            deltas[tx.account] = self._exec_accounts[tx.account] = (
                now + 1, nonce, kept)
        return verdict

    X.ExecutionState._apply_smallbank = apply


def control_spec(spec_path: str, workload: str, out_dir: str) -> str:
    return shared.control_spec(spec_path, workload, out_dir, __file__)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    forget_the_penalty()
    from mysticeti_tpu.cli import main

    sys.exit(main())
