"""Execution transactions one validator folded as ``bad_nonce`` a second
of the window: an account's sequence that broke (execution).  Every
validator folds the same sequence, so one is read."""
from benchmark import smallbank_readers


def read(run):
    return smallbank_readers.one_validator_rate(
        run, "mysticeti_execution_txs_total",
        "mysticeti_execution_ops_total", result="bad_nonce")
