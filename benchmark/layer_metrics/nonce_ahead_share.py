"""Share of the execution transactions admitted over the window whose
nonce was AHEAD of their account's executed nonce at admission: operations
of one account in flight together (ingress)."""
from benchmark import smallbank_readers


def read(run):
    return smallbank_readers.window_share(
        run, "mysticeti_ingress_nonce_ahead_total",
        "mysticeti_ingress_admitted_total")
