"""The pure-Python RFC 8032 fallback oracle (mysticeti_tpu._ed25519_py).

These tests target the fallback module *directly* (not through crypto.py's
backend selection), so its strict accept/reject semantics stay covered in
tier-1 even on machines where the ``cryptography`` package is installed —
and especially on the tier-1 environment where the fallback IS the oracle
every other test leans on.
"""
import hashlib

import pytest

from mysticeti_tpu import _ed25519_py as F
from mysticeti_tpu import crypto


def test_rfc8032_selftest_vector():
    F.selftest()


def _keypair(seed: bytes):
    key = F.Ed25519PrivateKey.from_private_bytes(seed)
    return key, key.public_key()


def test_sign_verify_roundtrip_and_rejects():
    key, pub = _keypair(hashlib.blake2b(b"fallback-seed", digest_size=32).digest())
    msg = b"the quick brown fox"
    sig = key.sign(msg)
    pub.verify(sig, msg)  # accepts

    with pytest.raises(F.InvalidSignature):
        pub.verify(sig, msg + b"!")  # wrong message
    corrupted = bytearray(sig)
    corrupted[3] ^= 0x40
    with pytest.raises(F.InvalidSignature):
        pub.verify(bytes(corrupted), msg)  # corrupted R
    corrupted = bytearray(sig)
    corrupted[40] ^= 0x01
    with pytest.raises(F.InvalidSignature):
        pub.verify(bytes(corrupted), msg)  # corrupted S
    _, other = _keypair(bytes(32))
    with pytest.raises(F.InvalidSignature):
        other.verify(sig, msg)  # wrong key


def test_rejects_noncanonical_s():
    """s' = s + L is congruent mod L but non-canonical: RFC 8032 / OpenSSL
    reject it (malleability defense), and the oracle must agree with the
    kernels that are tested against it."""
    key, pub = _keypair(bytes(range(32)))
    msg = b"malleability"
    sig = key.sign(msg)
    s = int.from_bytes(sig[32:], "little")
    assert s < F.L
    forged = sig[:32] + (s + F.L).to_bytes(32, "little")
    with pytest.raises(F.InvalidSignature):
        pub.verify(forged, msg)


def test_rejects_noncanonical_point_encodings():
    key, pub = _keypair(b"\x11" * 32)
    msg = b"encodings"
    sig = key.sign(msg)
    # Non-canonical A: y >= p.
    bad_pk = F.Ed25519PublicKey.from_public_bytes(bytes([0xFF] * 31 + [0x7F]))
    with pytest.raises(F.InvalidSignature):
        bad_pk.verify(sig, msg)
    # Non-canonical R likewise.
    forged = bytes([0xFF] * 31 + [0x7F]) + sig[32:]
    with pytest.raises(F.InvalidSignature):
        pub.verify(forged, msg)


def test_crypto_surface_works_with_active_backend():
    """Whichever backend crypto.py selected, the Signer/PublicKey surface
    holds: deterministic seeds, digest-layered sign/verify, bool returns."""
    signer = crypto.Signer.from_seed(b"surface-test-seed")
    again = crypto.Signer.from_seed(b"surface-test-seed")
    assert signer.public_key == again.public_key
    digest = crypto.blake2b_256(b"payload")
    sig = signer.sign(digest)
    assert signer.public_key.verify(sig, digest) is True
    assert signer.public_key.verify(sig, crypto.blake2b_256(b"other")) is False
    assert isinstance(crypto.HAVE_CRYPTOGRAPHY, bool)


def test_oracles_accept_memoryviews():
    """The verifier service slices its request frames into memoryviews and
    hands them to the CPU oracle untouched; both oracles — the pure-Python
    one directly, and whichever backend crypto.py selected (``cryptography``
    loads keys from ``bytes`` only) — must take any bytes-like buffer."""
    from mysticeti_tpu.block_validator import CpuSignatureVerifier

    seed = hashlib.blake2b(b"memoryview-seed", digest_size=32).digest()
    key, pub = _keypair(seed)
    digest = crypto.blake2b_256(b"payload")
    sig = key.sign(digest)
    frame = memoryview(pub.public_bytes_raw() + digest + sig)
    pk_v, digest_v, sig_v = frame[:32], frame[32:64], frame[64:]

    F.Ed25519PublicKey.from_public_bytes(pk_v).verify(sig_v, digest_v)

    assert crypto.PublicKey(pk_v).verify(sig_v, digest_v) is True
    assert crypto.PublicKey(pk_v) == crypto.PublicKey(bytes(pk_v))
    flipped = bytearray(sig)
    flipped[1] ^= 1
    assert CpuSignatureVerifier().verify_signatures(
        [pk_v, pk_v], [digest_v, digest_v], [sig_v, memoryview(flipped)]
    ) == [True, False]
