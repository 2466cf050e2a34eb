"""Signatures the validators verified over the window, on blocks and on
operations, per kernel launch the service made in it, where a launch holds
the same signers several times (collector and pipeline); as
``sigs_per_dispatch.transfers.py``."""
from benchmark import transfer_readers


def read(run):
    return transfer_readers.signatures_per_launch(run)
