"""Share of the execution transactions folded over the window whose signer
or counterparty an earlier transaction of the same commit had written: what
a parallel fold would have to serialise (execution)."""
from benchmark import smallbank_readers


def read(run):
    return smallbank_readers.window_share(
        run, "mysticeti_execution_conflicts_total",
        "mysticeti_execution_txs_total")
