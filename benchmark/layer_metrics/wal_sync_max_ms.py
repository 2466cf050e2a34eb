"""Longest drain + fsync of a validator's WAL syncer thread in the window
(``wal_sync``), max over validators, in ms; 0.0 where the window holds
none (storage)."""
from benchmark import node_readers


def read(run):
    return node_readers.stage_max_ms(run, "wal_sync")
