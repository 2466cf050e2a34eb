"""Parity tests for the fused raw-bytes Ed25519 verification path.

The fused path (pack_bytes -> device SHA-512 + mod-L + parse + ladder) must
agree bit-for-bit with the CPU oracle (cryptography/OpenSSL) — the same
accept/reject contract the consensus layer depends on (BASELINE config #2).

Tier 1 (not marked ``kernel``): the blob entry point of the Pallas backend,
whose preparation runs inside the call, on tests/kernel_cases.py.
"""
import random

import numpy as np
import jax.numpy as jnp
import pytest

kernel = pytest.mark.kernel  # tier 2: compile-heavy

from mysticeti_tpu.crypto import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
    InvalidSignature,
)

from mysticeti_tpu.ops import ed25519 as E
from mysticeti_tpu.ops import scalar as S

import kernel_cases as KC


def _keypair(rng):
    key = Ed25519PrivateKey.from_private_bytes(
        bytes(rng.randrange(256) for _ in range(32))
    )
    return key, key.public_key().public_bytes_raw()


def _oracle(pk: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(pk).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def _fused(pks, msgs, sigs) -> np.ndarray:
    words, s_words, host_ok = E.pack_bytes(pks, msgs, sigs)
    return np.asarray(
        E.verify_fused_kernel(
            jnp.asarray(words), jnp.asarray(s_words), jnp.asarray(host_ok)
        )
    )


@kernel
def test_fused_accepts_valid_and_rejects_corrupted():
    rng = random.Random(10)
    pks, msgs, sigs, expect = [], [], [], []
    for i in range(24):
        key, pk = _keypair(rng)
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = key.sign(msg)
        if i % 4 == 1:  # corrupt signature R
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        elif i % 4 == 2:  # corrupt message
            msg = bytes([msg[0] ^ 1]) + msg[1:]
        elif i % 4 == 3:  # wrong key
            _, pk = _keypair(rng)
        pks.append(pk)
        msgs.append(msg)
        sigs.append(sig)
        expect.append(_oracle(pk, msg, sig))
    got = _fused(pks, msgs, sigs)
    assert list(got) == expect
    assert any(expect) and not all(expect)


@kernel
def test_fused_rejects_noncanonical_s():
    """s' = s + L is congruent mod L but non-canonical: RFC 8032 / OpenSSL
    reject it, and so must the kernel (malleability defense)."""
    rng = random.Random(11)
    key, pk = _keypair(rng)
    msg = bytes(32)
    sig = key.sign(msg)
    s = int.from_bytes(sig[32:], "little")
    forged = sig[:32] + (s + S.L).to_bytes(32, "little")
    assert not _oracle(pk, msg, forged)
    got = _fused([pk, pk], [msg, msg], [sig, forged])
    assert list(got) == [True, False]


@kernel
def test_fused_rejects_noncanonical_a_and_r():
    """Point encodings with y >= p must be rejected (A via the explicit
    canonicity check, R via the exact raw-limb compare)."""
    rng = random.Random(12)
    key, pk = _keypair(rng)
    msg = bytes(range(32))
    sig = key.sign(msg)
    # Non-canonical A: p + small y (valid curve ys: p+1 has x solution? just
    # require reject regardless — the oracle rejects any y >= p encoding).
    bad_a = (S.P + 3).to_bytes(32, "little")
    # Non-canonical R likewise.
    bad_r_sig = (S.P + 3).to_bytes(32, "little") + sig[32:]
    expect = [_oracle(pk, msg, sig), _oracle(bad_a, msg, sig), _oracle(pk, msg, bad_r_sig)]
    got = _fused([pk, bad_a, pk], [msg] * 3, [sig, sig, bad_r_sig])
    assert list(got) == expect == [True, False, False]


@kernel
def test_fused_matches_host_path():
    """Fused device packing must agree with the host pack_batch path on the
    same inputs (valid + invalid mix)."""
    rng = random.Random(13)
    pks, msgs, sigs = [], [], []
    for i in range(16):
        key, pk = _keypair(rng)
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = key.sign(msg)
        if i % 3 == 2:
            sig = sig[:32] + bytes([sig[32] ^ 255]) + sig[33:]
        pks.append(pk)
        msgs.append(msg)
        sigs.append(sig)
    fused = _fused(pks, msgs, sigs)
    host = np.asarray(E.verify_kernel(*[jnp.asarray(x) for x in E.pack_batch(pks, msgs, sigs)]))
    assert list(fused) == list(host)


@kernel
def test_verify_batch_end_to_end_padding_and_malformed():
    """verify_batch: odd sizes (bucket padding), malformed lengths masked."""
    rng = random.Random(14)
    pks, msgs, sigs, expect = [], [], [], []
    for i in range(37):
        key, pk = _keypair(rng)
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = key.sign(msg)
        ok = True
        if i == 5:
            sig = sig[:40]  # malformed length
            ok = False
        elif i == 11:
            pk = pk[:10]
            ok = False
        elif i == 20:
            sig = bytes(64)
            ok = False
        pks.append(pk)
        msgs.append(msg)
        sigs.append(sig)
        expect.append(ok)
    got = E.verify_batch(pks, msgs, sigs)
    assert list(got) == expect
    assert E.verify_batch([], [], []).shape == (0,)


@kernel
def test_verify_batch_nonfused_fallback():
    """Non-32-byte messages take the host-hash path and still verify."""
    rng = random.Random(15)
    pks, msgs, sigs = [], [], []
    for i in range(5):
        key, pk = _keypair(rng)
        msg = bytes(rng.randrange(256) for _ in range(7 + i * 13))
        pks.append(pk)
        msgs.append(msg)
        sigs.append(key.sign(msg))
    got = E.verify_batch(pks, msgs, sigs)
    assert list(got) == [True] * 5


@kernel
def test_fused_pallas_interpret_parity():
    """The Pallas fused wrapper agrees with the XLA fused kernel (interpret
    mode on CPU, tiny tile)."""
    from mysticeti_tpu.ops import ed25519_pallas as PK

    rng = random.Random(16)
    pks, msgs, sigs = [], [], []
    for i in range(8):
        key, pk = _keypair(rng)
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = key.sign(msg)
        if i % 2:
            msg = bytes([msg[0] ^ 1]) + msg[1:]
        pks.append(pk)
        msgs.append(msg)
        sigs.append(sig)
    words, s_words, host_ok = E.pack_bytes(pks, msgs, sigs)
    got = np.asarray(
        PK.verify_fused_pallas(
            jnp.asarray(words),
            jnp.asarray(s_words),
            jnp.asarray(host_ok),
            tile=8,
            interpret=True,
        )
    )
    want = np.asarray(
        E.verify_fused_kernel(
            jnp.asarray(words), jnp.asarray(s_words), jnp.asarray(host_ok)
        )
    )
    assert list(got) == list(want)
    assert any(want) and not all(want)


@pytest.fixture(scope="module")
def blob_verdicts():
    """The blob entry point (strangers' signatures: the key rides in the
    blob) over the cases, eight tiles a grid, beside the ``xla`` form."""
    from mysticeti_tpu.ops import ed25519_pallas as PK

    blob = KC.packed_blob()
    got = PK.verify_fused_blob_pallas(blob, tile=KC.TILE, interpret=True)
    return np.asarray(got), np.asarray(E.verify_fused_blob_kernel(blob))


@pytest.mark.parametrize("name", KC.NAMES)
def test_blob_entry_point_verdicts(name, blob_verdicts):
    got, xla = blob_verdicts
    KC.check_verdict(name, got[KC.LANE[name]], xla[KC.LANE[name]])
