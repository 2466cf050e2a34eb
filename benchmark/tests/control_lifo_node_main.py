"""A control of the SmallBank cell: validators whose mempool drains an
account's lane newest first.

``python -m mysticeti_tpu`` with the queue of every ``acct:`` lane made a
stack: of two operations of one account that wait for a proposal together,
the later one is proposed first.  Nothing is unsafe about it - every
signature is checked, the WALs agree, the ten executed roots agree with
each other and with the reference's fold of the committed sequence - and
on ``transfers10``, whose lanes are never deeper than one, nothing shows at
all.  That is the shortcut this deployment tempts: a pool that forgets that
one account's operations are a sequence.  Under the cell's traffic the
later operation folds as ``bad_nonce`` and its account's whole tail behind
it, so the run must come out with ``correct`` false by the per-account
comparison of ``sequencing`` (operations of accounts never refused that
executed neither applied nor aborted; executed operations that are no
prefix of what was sent), with ``failed`` in the hundreds; it adds no
switch to the program.

    python3 benchmark/tests/control_lifo_node_main.py --control-spec \\
        BENCHMARK.json smallbank10-hotspot .bench_work/control
    python3 benchmark/run.py --spec .bench_work/control/spec.json \\
        --workload smallbank10-hotspot ...
"""
from __future__ import annotations

import os
import sys
from collections import deque

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control_commit_rule_node_main as shared  # noqa: E402 (ROOT on the path)


class NewestFirst(deque):
    def popleft(self):
        return self.pop()


def drain_accounts_newest_first() -> None:
    from mysticeti_tpu.ingress import Mempool

    sound = Mempool.submit

    def submit(self, client, transactions, priority=False, t_submit=None):
        out = sound(self, client, transactions, priority=priority,
                    t_submit=t_submit)
        if client.startswith("acct:"):
            with self._mempool_lock:
                lane = self._lanes.get((client, priority))
                if lane is not None and type(lane.queue) is deque:
                    lane.queue = NewestFirst(lane.queue)
        return out

    Mempool.submit = submit


def control_spec(spec_path: str, workload: str, out_dir: str) -> str:
    return shared.control_spec(spec_path, workload, out_dir, __file__)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    drain_accounts_newest_first()
    from mysticeti_tpu.cli import main

    sys.exit(main())
