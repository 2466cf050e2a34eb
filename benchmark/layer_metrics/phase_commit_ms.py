"""Mean proposal -> leader-sequence commit decision over the window
(core.py, commit_observer.py)."""
from benchmark import readers


def read(run):
    return readers.phase_ms(run, "commit")
