"""The second control: the keyed-tile kernel alone accepts what it is given.

On the chip a request whose signatures are all by one signer is served by
the keyed-tile kernel (``ops/ed25519._dispatch_indexed_keyed``: one key a
256-lane tile), every other by the generic ladder.  Here exactly those
requests come back all accepted, unverified, and the generic ladder stays
sound: the fault the first control (``control_service_main.py``, above both
kernels) cannot tell from a sound service at the keyed kernel's own sizes.
The rule that picks the kernel is the program's own
(``group_blob_for_tiles`` at the chip's tile), so the same requests are hit
off the chip, where the XLA form serves everything.  A run against this
service must come out with ``correct`` false; it adds no switch to the
program.

    python3 benchmark/run.py ... --service-main benchmark/tests/control_keyed_service_main.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import service_main  # noqa: E402

CHIP_TILE = 256  # ops/ed25519_pallas.default_tile() on a TPU


def break_keyed() -> None:
    import jax.numpy as jnp

    from mysticeti_tpu.ops import ed25519 as E

    sound = E.dispatch_indexed_chunks

    def keyed_accepts_all(blob, table):
        handles = []
        for start, count, bucket in E.iter_buckets(blob.shape[0]):
            chunk = blob[start:start + count]
            if E.group_blob_for_tiles(chunk, len(table),
                                      min(CHIP_TILE, bucket), bucket) is None:
                handles += sound(chunk, table)
            else:
                E._note_kernel("keyed", bucket, E._backend())
                handles.append((count, jnp.ones(bucket, bool)))
        return handles

    E.dispatch_indexed_chunks = keyed_accepts_all


if __name__ == "__main__":
    break_keyed()
    sys.exit(service_main.main())
