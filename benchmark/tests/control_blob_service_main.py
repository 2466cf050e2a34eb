"""The control of the signed-transfer cell: a verifier service that takes a
stranger's word.

``service_main.py`` with the backend altered where verdicts are produced:
a signature whose signer is not in the committee's table - a client
account's, on a transaction - is accepted unverified; the committee's own
signatures are verified as ever.  That is the shortcut this deployment
tempts: transaction signatures are over 95% of what the chip verifies.
The configuration's guarantee is that every accept/reject bit equals the
oracle's and that no corrupted transfer is ever acknowledged, so a run
against this service must come out with ``correct`` false; it adds no
switch to the program.

    python3 benchmark/run.py ... --service-main benchmark/tests/control_blob_service_main.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import service_main  # noqa: E402


def trust_strangers() -> None:
    from mysticeti_tpu.block_validator import TpuSignatureVerifier

    sound = TpuSignatureVerifier.verify_signatures

    def strangers_pass(self, public_keys, digests, signatures):
        verdicts = list(sound(self, public_keys, digests, signatures))
        if self._table is not None:
            known = self._table.indices_for(
                [bytes(pk) for pk in public_keys]) >= 0
            verdicts = [bool(ok) or not k for ok, k in zip(verdicts, known)]
        return verdicts

    TpuSignatureVerifier.verify_signatures = strangers_pass


if __name__ == "__main__":
    trust_strangers()
    sys.exit(service_main.main())
