"""The plain reference: Ed25519 as OpenSSL verifies it (RFC 8032), one
signature at a time, through the ``cryptography`` package.  It imports
nothing of the program and takes nothing the program made; keys, digests
and signatures all come from ``--seed``.

Also the seeded makers of what is verified: keys, signed 32-byte digests,
and corruptions of exactly one bit of R, s or the digest.
"""
from __future__ import annotations

import random
from typing import List, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)
from cryptography.hazmat.primitives.serialization import (
    Encoding,
    PublicFormat,
)


def verify(public_key: bytes, message: bytes, signature: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(bytes(public_key)).verify(
            bytes(signature), bytes(message))
    except (InvalidSignature, ValueError):
        return False
    return True


def verify_all(public_keys: Sequence[bytes], messages: Sequence[bytes],
               signatures: Sequence[bytes]) -> List[bool]:
    return [verify(pk, m, s)
            for pk, m, s in zip(public_keys, messages, signatures)]


def key_from_seed(seed32: bytes) -> Tuple[Ed25519PrivateKey, bytes]:
    private = Ed25519PrivateKey.from_private_bytes(seed32)
    return private, private.public_key().public_bytes(
        Encoding.Raw, PublicFormat.Raw)


def seeded_keys(rng: random.Random, n: int) -> List[tuple]:
    """``n`` (private key, public key bytes) pairs drawn from ``rng``."""
    return [key_from_seed(rng.randbytes(32)) for _ in range(n)]


def flip_one_bit(rng: random.Random, digest: bytes, signature: bytes,
                 ) -> Tuple[bytes, bytes]:
    """One bit of R (signature[:32]), s (signature[32:]) or the digest,
    each part as likely as the others."""
    part = rng.randrange(3)
    bit = 1 << rng.randrange(8)
    pos = rng.randrange(32)
    if part == 2:
        digest = digest[:pos] + bytes([digest[pos] ^ bit]) + digest[pos + 1:]
    else:
        pos += 32 * part
        signature = (signature[:pos] + bytes([signature[pos] ^ bit])
                     + signature[pos + 1:])
    return digest, signature


def signed_request(rng: random.Random, keys: List[tuple],
                   lane_signers: Sequence[int], corrupt: Sequence[int],
                   ) -> dict:
    """One signature a lane over a seeded 32-byte digest, lane ``i`` by
    ``keys[lane_signers[i]]``; the positions in ``corrupt`` get one flipped
    bit.  ``expected`` is the oracle's verdict on what is sent, computed
    here and not assumed from ``corrupt``."""
    bad = set(corrupt)
    pks, digests, sigs = [], [], []
    for i, signer in enumerate(lane_signers):
        private, public = keys[signer]
        digest = rng.randbytes(32)
        sig = private.sign(digest)
        if i in bad:
            digest, sig = flip_one_bit(rng, digest, sig)
        pks.append(public)
        digests.append(digest)
        sigs.append(sig)
    return {"public_keys": pks, "digests": digests, "signatures": sigs,
            "expected": verify_all(pks, digests, sigs)}


def signed_window(rng: random.Random, keys: List[tuple], signers: Sequence[int],
                  n: int, corrupt: Sequence[int]) -> dict:
    """``n`` signatures, each lane's signer drawn uniformly from ``signers``
    (indices into ``keys``)."""
    lanes = [signers[rng.randrange(len(signers))] for _ in range(n)]
    return signed_request(rng, keys, lanes, corrupt)
