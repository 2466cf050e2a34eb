"""A control of the crash-recovery cell: validators whose proposal gate
waits for every connected leader however far behind, as the program did
before PR 45.

``python -m mysticeti_tpu`` with ``Core.LEADER_HORIZON_ROUNDS`` put out of
reach: ``Core.ready_new_block`` then waits for the validator that is back
and hundreds of rounds behind in every slot it leads, and each such slot
costs every other validator the whole ``leader_timeout_s`` (its proposals
jump the rounds it replays, so the block waited for never comes).  Nothing
is unsafe about it - the WALs agree, the reference decides what the
validators decided, the recovery is what the copy holds - and on a fleet in
which nobody restarts nothing shows at all.  Under the configuration's
fault the run must come out with ``correct`` false by
``leader_timeout_total``'s growth over the window alone, with
``leader_timeouts_s.cr`` above 0; it adds no switch to the program.  The
same ``--control-spec`` as the other node controls.
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_commit_rule_node_main as shared  # noqa: E402 (ROOT on the path)


def wait_for_whoever_is_connected() -> None:
    from mysticeti_tpu.core import Core

    Core.LEADER_HORIZON_ROUNDS = 1 << 40


def control_spec(spec_path: str, workload: str, out_dir: str) -> str:
    return shared.control_spec(spec_path, workload, out_dir, __file__)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    wait_for_whoever_is_connected()
    from mysticeti_tpu.cli import main

    sys.exit(main())
