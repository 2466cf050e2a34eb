"""Mean of block_stage_seconds{stage="admit_verify"} over the window,
median over nodes: a gateway frame's signatures parsed -> verdicts back
from the verifier service (ingress; wall)."""
from benchmark import stage_readers


def read(run):
    return stage_readers.block_stage_ms(run, "admit_verify")
