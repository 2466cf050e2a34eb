"""Signatures that reach every branch of a launch's preparation — the
challenge hash, its reduction mod L, ``s < L``, ``A < p``, both sign bits,
``host_ok`` — and what each must come to by ``hashlib`` and Python integers.

Shared by the tier-1 cases of tests/test_ed25519_pallas.py (the preparation
alone, and the indexed entry point), tests/test_ed25519_fused.py (the blob
entry point) and tests/test_ed25519_keyed.py (the keyed one).  A case is
``(name, public key, message, signature, host_ok)``; a message is a 32-byte
digest, the only thing the fused kernels hash.

Forgeries need no secret: under the identity A = (0, 1) — a sound point of
order one — [s]B == R + [k]A holds for R = [s]B whatever k is, so
``(encode([s]B), s)`` verifies for any s the canonicity rules admit.  That
makes a rule visible in a verdict: s = L - 1 is accepted and s = L, whose
equation holds as well, is not; y = p + 1 names the same point as y = 1 and
is refused for its encoding alone.
"""
import hashlib
import random

import numpy as np

from mysticeti_tpu import _ed25519_py as PY
from mysticeti_tpu.crypto import Ed25519PrivateKey

P, L = PY.P, PY.L


def encode(y: int, sign: int) -> bytes:
    """A point encoding's 32 bytes, canonical or not."""
    assert 0 <= y < 1 << 255
    return (y | (sign << 255)).to_bytes(32, "little")


IDENTITY = encode(1, 0)
TILE = 8  # lanes a block under the interpreter; the cases fill whole tiles


def challenge(pk: bytes, msg: bytes, sig: bytes) -> int:
    """k = SHA-512(R || A || M) mod L."""
    digest = hashlib.sha512(sig[:32] + pk + msg).digest()
    return int.from_bytes(digest, "little") % L


def reference_verdict(pk: bytes, msg: bytes, sig: bytes, host_ok: bool) -> bool:
    """The kernels' rule in Python integers: A decodes (y < p, on the curve,
    no x = 0 with the sign set), s < L, and [s]B + [k](-A) encodes to R's
    very bytes (so a non-canonical R, which no encoder emits, is refused)."""
    a = PY._decompress(pk)
    s = int.from_bytes(sig[32:], "little")
    if not host_ok or a is None or s >= L:
        return False
    x, y, z, t = a
    res = PY._double_mul(s, challenge(pk, msg, sig), (P - x, y, z, P - t))
    return PY._compress(res) == sig[:32]


def _forged(s: int) -> bytes:
    """The signature (encode([s]B), s): sound under A = identity."""
    return PY._compress(PY._mul(s % L, PY._BASE)) + s.to_bytes(32, "little")


def _flip(data: bytes, at: int, bit: int = 0) -> bytes:
    out = bytearray(data)
    out[at] ^= 1 << bit
    return bytes(out)


def _off_curve_y() -> int:
    return next(y for y in range(2, 100) if PY._decompress(encode(y, 0)) is None)


def build_cases(seed: int = 44):
    rng = random.Random(seed)
    digest = lambda: bytes(rng.randrange(256) for _ in range(32))
    cases = []

    def add(name, pk, msg, sig, ok=True):
        cases.append((name, pk, msg, sig, ok))

    # Honest traffic by four signers — keys and R's of both signs among
    # them, asserted below — with one corruption each of R, M and s, and a
    # lane the host refused between sound ones.
    signers = [Ed25519PrivateKey.from_private_bytes(digest()) for _ in range(4)]
    pks = [k.public_key().public_bytes_raw() for k in signers]
    assert {pk[31] >> 7 for pk in pks} == {0, 1}
    for i in range(16):
        key, pk, msg = signers[i % 4], pks[i % 4], digest()
        sig, name, ok = key.sign(msg), f"honest-{i:02d}", True
        if i == 3:
            name, sig = name + "-R-corrupted", _flip(sig, 5, 3)
        elif i == 6:
            name, msg = name + "-M-corrupted", _flip(msg, 31, 7)
        elif i == 9:
            name, sig = name + "-s-corrupted", _flip(sig, 40)
        elif i == 12:
            name, ok = name + "-host-ok-0", False
        add(name, pk, msg, sig, ok)
    assert {c[3][31] >> 7 for c in cases} == {0, 1}
    for name, msg in (("zeros", bytes(32)), ("0xff", b"\xff" * 32)):
        add(f"message-all-{name}", pks[0], msg, signers[0].sign(msg))
        add(f"message-all-{name}-other-key", pks[1], msg, signers[0].sign(msg))

    # s at the edges of [0, L): under an honest key (the equation fails
    # anyway) and under the identity (it holds: only s < L decides).
    key, pk, msg = signers[0], pks[0], digest()
    sig = key.sign(msg)
    edges = [("0", 0), ("1", 1), ("L-1", L - 1), ("L", L), ("L+1", L + 1),
             ("2^252", 1 << 252), ("2^256-1", (1 << 256) - 1)]
    for name, s in edges:
        add(f"identity-A-forged-s={name}", IDENTITY, digest(), _forged(s))
    add("honest-R-with-s=L-1", pk, msg, sig[:32] + (L - 1).to_bytes(32, "little"))
    s_plus_l = int.from_bytes(sig[32:], "little") + L
    add("honest-s-plus-L", pk, msg, sig[:32] + s_plus_l.to_bytes(32, "little"))
    add("host-ok-0-identity", IDENTITY, digest(), _forged(5), False)

    # A's encoding: y = p - 1, p, p + 1 with the sign bit either way, y = 1
    # with the sign set (x = 0 cannot be odd), a y off the curve — each
    # under a forgery that would verify if A were taken for the identity.
    for name, y in (("p-1", P - 1), ("p", P), ("p+1", P + 1), ("1", 1)):
        for sign in (0, 1):
            if (name, sign) != ("1", 0):
                add(f"A-y={name}-sign={sign}", encode(y, sign), digest(),
                    _forged(rng.randrange(L)))
    # y = p - 1 with the sign clear IS a point, (0, -1) of order two: the
    # forgery verifies under it whenever k comes out even.
    while True:
        m, forged = digest(), _forged(rng.randrange(L))
        if challenge(encode(P - 1, 0), m, forged) % 2 == 0:
            break
    add("A-y=p-1-sign=0-k-even", encode(P - 1, 0), m, forged)
    add("A-off-curve", encode(_off_curve_y(), 0), digest(), _forged(7))
    add("A-off-curve-honest-sig", encode(_off_curve_y(), 1), msg, sig)

    # R's encoding: y + p names the same point and is not what an encoder
    # writes.  s = 0 gives R = (0, 1); y = p + 1 is its other name.
    zero = (0).to_bytes(32, "little")
    add("R-canonical-y=1", IDENTITY, digest(), encode(1, 0) + zero)
    add("R-non-canonical-y=p+1", IDENTITY, digest(), encode(P + 1, 0) + zero)
    add("R-y=1-sign-set", IDENTITY, digest(), encode(1, 1) + zero)

    # A random batch up to eight whole tiles: one lane in three corrupted
    # somewhere in its signature, its message or its key.
    while len(cases) < 8 * TILE:
        at = rng.randrange(4)
        msg = digest()
        parts = [pks[at], msg, signers[at].sign(msg)]
        name = f"random-{len(cases):02d}"
        if rng.randrange(3) == 0:
            which = rng.randrange(3)
            parts[which] = _flip(
                parts[which], rng.randrange(len(parts[which])), rng.randrange(8)
            )
            name += "-" + "AMS"[which] + "-corrupted"
        add(name, *parts)
    assert len({c[0] for c in cases}) == len(cases)
    return cases


CASES = build_cases()
NAMES = [c[0] for c in CASES]
PKS, MSGS, SIGS = ([c[i] for c in CASES] for i in (1, 2, 3))
HOST_OK = np.array([c[4] for c in CASES])
WANT = {c[0]: reference_verdict(*c[1:]) for c in CASES}
LANE = {name: i for i, name in enumerate(NAMES)}

# What the rules above must come to, whatever computes it.
assert WANT["identity-A-forged-s=L-1"] and WANT["identity-A-forged-s=0"]
assert not WANT["identity-A-forged-s=L"] and not WANT["identity-A-forged-s=L+1"]
assert not WANT["identity-A-forged-s=2^256-1"]
assert WANT["A-y=p-1-sign=0-k-even"] and not WANT["A-y=p-1-sign=1"]
assert not any(WANT[n] for n in NAMES if n.startswith("A-") and "p-1" not in n)
assert WANT["R-canonical-y=1"] and not WANT["R-non-canonical-y=p+1"]
assert not WANT["R-y=1-sign-set"] and not WANT["honest-12-host-ok-0"]
assert WANT["message-all-zeros"] and WANT["message-all-0xff"]
assert sum(WANT[n] for n in NAMES if n.startswith("honest-")) == 12
assert len(CASES) == 64

# Where the ``xla`` ladder (``ops.ed25519.verify_impl``) is itself wrong, and
# was before the preparation moved: the identity as A with s = 0 or 2^252
# leaves it an x that is not 0 mod p, so it rejects what OpenSSL and the
# Pallas kernels accept and accepts R = (y = 1, sign set).  ``prepare_fused``
# is right on these lanes; they are held to the oracle and Python integers
# (ROADMAP queue 1).
XLA_LADDER_WRONG = frozenset(
    {"identity-A-forged-s=0", "identity-A-forged-s=2^252", "R-canonical-y=1",
     "R-y=1-sign-set"}
)


# Where OpenSSL's decoder is laxer than the rule of this repo's kernels, the
# ``xla`` form and ``_ed25519_py`` alike, before this change as after it: it
# takes y >= p for y - p and x = 0 with the sign set for x = 0.
OPENSSL_LAXER = frozenset(
    {"A-y=p-1-sign=1", "A-y=p+1-sign=0", "A-y=p+1-sign=1", "A-y=1-sign=1"}
)


def openssl_verdict(name: str) -> bool:
    """The host oracle's verdict of a case (``host_ok`` is the host's own
    refusal: such a lane never reaches the oracle)."""
    from mysticeti_tpu import crypto

    _, pk, msg, sig, ok = CASES[LANE[name]]
    return ok and crypto.PublicKey(pk).verify(sig, msg)


def packed_blob() -> np.ndarray:
    """The cases as ``pack_blob`` lays them out (the key rides in the blob),
    the host's refusals in the host_ok column."""
    from mysticeti_tpu.ops import ed25519 as E

    blob = E.pack_blob(PKS, MSGS, SIGS)
    blob[:, 32] &= HOST_OK
    return blob


def indexed_blob():
    """The cases as ``pack_blob_indexed`` lays them out, and the key table
    their indices point into (every case's key, sound or not)."""
    from mysticeti_tpu.ops import ed25519 as E

    table = E.KeyTable(sorted(set(PKS)))
    blob = E.pack_blob_indexed(
        table.indices_for(PKS), MSGS, SIGS, host_ok=HOST_OK, num_keys=len(table)
    )
    return blob, table


def entry_points(lanes: int, tile: int, keys: int = 10) -> dict:
    """The three jitted entry points a launch reaches, each with the
    (shape, dtype) of its arguments at a bucket of ``lanes``."""
    from mysticeti_tpu.ops import ed25519_pallas as EP

    blob, table = ((lanes, 26), np.uint32), ((keys, 8), np.uint32)
    return {
        "blob": (EP._verify_fused_blob_pallas_jit, [((lanes, 33), np.uint32)]),
        "indexed": (EP._verify_fused_indexed_pallas_jit, [blob, table]),
        "keyed": (
            EP._verify_keyed_blob_jit,
            [
                blob,
                table,
                ((keys, 64, 3, EP.NLIMBS, 16), np.int32),  # the keys' combs
                ((lanes // tile,), np.int32),  # a key a tile
                ((lanes,), np.int32),  # positions
            ],
        ),
    }


def check_verdict(name: str, got, xla) -> None:
    """One lane of an entry point against Python integers, the host oracle
    and the ``xla`` form's verdict of the same lane."""
    assert bool(got) == WANT[name]
    if name not in OPENSSL_LAXER:
        assert bool(got) == openssl_verdict(name)
    else:
        assert openssl_verdict(name) and not got
    if name not in XLA_LADDER_WRONG:
        assert bool(got) == bool(xla)
    else:
        assert bool(got) != bool(xla)  # mended? take the name off the list


