"""The control: a verifier service that checks less.

``service_main.py`` with the backend altered where verdicts are produced:
only the first half of every batch goes to the device, the second half is
accepted unverified - the shortcut a later PR could be tempted by (skip
what "always verifies").  The configuration's guarantee is that every
accept/reject bit equals the oracle's, so a run against this service must
come out with ``correct`` false; it adds no switch to the program.

    python3 benchmark/run.py ... --service-main benchmark/tests/control_service_main.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import service_main  # noqa: E402


def check_less() -> None:
    from mysticeti_tpu.block_validator import TpuSignatureVerifier

    sound = TpuSignatureVerifier.verify_signatures

    def half_checked(self, public_keys, digests, signatures):
        n = len(signatures)
        k = (n + 1) // 2
        checked = sound(self, public_keys[:k], digests[:k], signatures[:k])
        return list(checked) + [True] * (n - k)

    TpuSignatureVerifier.verify_signatures = half_checked


if __name__ == "__main__":
    check_less()
    sys.exit(service_main.main())
