"""Share of the signatures the validators verified over the window that
were on client transactions (at the gateways and on receipt) and not on
blocks: the reason this cell exists (collector and pipeline)."""
from benchmark import transfer_readers


def read(run):
    counted = transfer_readers.window_signatures(run)
    if not counted:
        return None
    return 100.0 * counted[0] / sum(counted)
