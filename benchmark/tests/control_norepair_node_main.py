"""A control of the crash-fault cell: validators that never take a peer out
of the proposal gate, as the program did before PR 36.

``python -m mysticeti_tpu`` with ``AuthoritySet.remove`` made to do nothing:
a peer once connected stays in ``connected_authorities`` for ever, so
``Core.ready_new_block`` goes on waiting for a dead validator in every slot
it leads, and each such slot costs every live validator the whole
``leader_timeout_s``.  Nothing is unsafe about it - the WALs agree, the
reference decides what the validators decided, the dead stay dead - and on
a healthy fleet nothing shows at all.  Under the configuration's faults the
run must come out with ``correct`` false by ``leader_timeout_total``'s
growth over the window and by the commit heights the live WALs hold (under
``compared_heights_min``), with finality in seconds; it adds no switch to
the program.

    python3 benchmark/tests/control_norepair_node_main.py --control-spec \\
        BENCHMARK.json paper10f3-steady .bench_work/control
    python3 benchmark/run.py --spec .bench_work/control/spec.json \\
        --workload paper10f3-steady ...
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_commit_rule_node_main as shared  # noqa: E402 (ROOT on the path)


def keep_the_dead_in_the_gate() -> None:
    from mysticeti_tpu.types import AuthoritySet

    AuthoritySet.remove = lambda self, authority: False


def control_spec(spec_path: str, workload: str, out_dir: str) -> str:
    return shared.control_spec(spec_path, workload, out_dir, __file__)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    keep_the_dead_in_the_gate()
    from mysticeti_tpu.cli import main

    sys.exit(main())
