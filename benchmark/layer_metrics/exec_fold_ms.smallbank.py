"""Mean fold of one commit through the execution state (``exec_fold``,
one sample a commit) over the window, median over validators, in ms, where
a commit writes the same accounts many times (execution); as
``exec_fold_ms.py``, whose list of cells is pinned."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_mean_ms(run, "exec_fold")
