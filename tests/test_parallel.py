"""Sharded verification over a virtual 8-device CPU mesh (multi-chip dry-run)."""
import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from mysticeti_tpu.crypto import Ed25519PrivateKey

import jax


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a multi-device mesh")
def test_sharded_verify_matches_single_device():
    from mysticeti_tpu.ops import ed25519 as E
    from mysticeti_tpu.parallel import make_mesh, sharded_verify_batch

    import random

    rng = random.Random(42)
    pks, msgs, sigs = [], [], []
    for i in range(16):
        key = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.randrange(256) for _ in range(32))
        )
        pk = key.public_key().public_bytes_raw()
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = key.sign(msg)
        if i % 4 == 3:
            sig = bytearray(sig)
            sig[7] ^= 0xFF
            sig = bytes(sig)
        pks.append(pk)
        msgs.append(msg)
        sigs.append(sig)

    mesh = make_mesh(8)
    ok, total = sharded_verify_batch(mesh, pks, msgs, sigs)
    single = E.verify_batch(pks, msgs, sigs)
    assert (ok == single).all()
    assert total == int(single.sum())
    assert total == 12  # 4 corrupted out of 16


def test_sharded_fused_verify_matches_oracle():
    """Fused raw-bytes sharded path on the virtual 8-device mesh: per-item
    bits match the oracle and the psum'd count is exact."""
    import random

    import numpy as np
    from mysticeti_tpu.crypto import Ed25519PrivateKey

    from mysticeti_tpu.parallel import make_mesh, sharded_verify_batch_fused

    rng = random.Random(21)
    pks, msgs, sigs, expect = [], [], [], []
    for i in range(13):  # odd size: exercises bucket padding across shards
        key = Ed25519PrivateKey.from_private_bytes(
            bytes(rng.randrange(256) for _ in range(32))
        )
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = key.sign(msg)
        ok = True
        if i % 5 == 3:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
            ok = False
        pks.append(key.public_key().public_bytes_raw())
        msgs.append(msg)
        sigs.append(sig)
        expect.append(ok)
    mesh = make_mesh(8)
    got, total = sharded_verify_batch_fused(mesh, pks, msgs, sigs)
    assert list(got) == expect
    assert total == sum(expect)


def test_sharded_indexed_verify_matches_oracle():
    """Committee-indexed sharded path (table replicated, blob sharded):
    bit-identical to the generic sharded path incl. unknown-key fallback."""
    import random

    from mysticeti_tpu.ops import ed25519 as E
    from mysticeti_tpu.parallel.mesh import (
        make_mesh,
        sharded_verify_batch_fused,
        sharded_verify_batch_indexed,
    )

    rng = random.Random(17)
    keys = [
        Ed25519PrivateKey.from_private_bytes(
            bytes(rng.randrange(256) for _ in range(32))
        )
        for _ in range(5)
    ]
    table = E.KeyTable([k.public_key().public_bytes_raw() for k in keys[:4]])
    pks, msgs, sigs = [], [], []
    for i in range(64):
        k = keys[i % 5]  # key 4 is unknown to the table
        m = bytes(rng.randrange(256) for _ in range(32))
        s = k.sign(m)
        if i % 6 == 0:
            s = bytes([s[0] ^ 1]) + s[1:]
        pks.append(k.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(s)
    mesh = make_mesh(8)
    ok_idx, total_idx = sharded_verify_batch_indexed(mesh, table, pks, msgs, sigs)
    ok_gen, total_gen = sharded_verify_batch_fused(mesh, pks, msgs, sigs)
    assert (ok_idx == ok_gen).all()
    assert total_idx == total_gen == int(ok_gen.sum())
    # The verifier service hands the same batch over as rows of uint8 arrays
    # (column slices of its wire records): the same verdicts.
    import numpy as np

    records = np.frombuffer(
        b"".join(pk + m + s for pk, m, s in zip(pks, msgs, sigs)), np.uint8
    ).reshape(len(sigs), 128)
    ok_rows, total_rows = sharded_verify_batch_indexed(
        mesh, table, records[:, :32], records[:, 32:64], records[:, 64:]
    )
    assert (ok_rows == ok_gen).all() and total_rows == total_gen
