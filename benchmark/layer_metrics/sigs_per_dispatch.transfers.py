"""Signatures the validators verified over the window, on blocks and on
transactions, per kernel launch the service made in it: how full the
launches are when requests hold hundreds of signatures."""
from benchmark import transfer_readers


def read(run):
    return transfer_readers.signatures_per_launch(run)
