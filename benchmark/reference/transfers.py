"""The plain reference of the signed-transfer deployment (`transfers10`).

Everything here is written out from `docs/execution.md` and imports nothing
of the program: the seeded maker of accounts and keys, the signed
envelope's bytes, transfers and their one-bit corruptions, the genesis
allocation's bytes, and a fold — a dict of balances and nonces and the root
chain — over a committed sequence.  Signatures are OpenSSL's
(`ed25519_oracle.py`).  The same transactions in the same order give the
same verdict a transaction and the same root at every height.

Unlike the program's fold, this one VERIFIES every signed transfer it is
given: a transfer whose signature OpenSSL rejects is the no-op
``bad_signature`` here, so a forged transfer that the system let into the
committed sequence shows as a root that differs from the validators'.
"""
from __future__ import annotations

import hashlib
import random
import struct
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.reference import ed25519_oracle as oracle

EXEC_MAGIC = b"\xffEXECTX\x01"
SIGNED_MAGIC = b"\xffSIGNTX\x01"
ALLOCATION_MAGIC = b"MYSTALLOC\x01"
OP_CREATE, OP_MINT, OP_TRANSFER = 0, 1, 2
MAX_ACCOUNT_KEY_LEN = 64
GENESIS_ROOT = bytes(32)

APPLIED = "applied"
ACCOUNT_EXISTS = "account_exists"
UNKNOWN_ACCOUNT = "unknown_account"
BAD_NONCE = "bad_nonce"
INSUFFICIENT_BALANCE = "insufficient_balance"
UNSIGNED = "unsigned"
BAD_SIGNATURE = "bad_signature"


# -- accounts ------------------------------------------------------------------


def account_seed(seed: int, index: int) -> bytes:
    """The private seed of account ``index`` of the allocation ``seed``."""
    return hashlib.blake2b(
        b"mysticeti-account" + struct.pack("<QQ", seed, index),
        digest_size=32).digest()


def account(seed: int, index: int) -> tuple:
    """(private key, 32-byte public key = the account's key)."""
    return oracle.key_from_seed(account_seed(seed, index))


def account_keys(span: Tuple[int, int, int]) -> bytes:
    """The public keys of accounts ``start..stop`` of ``seed``, back to
    back (one argument: it is what a process pool maps)."""
    seed, start, stop = span
    return b"".join(account(seed, i)[1] for i in range(start, stop))


def allocation_bytes(balance: int, keys: bytes) -> bytes:
    """A genesis allocation file: magic | u64 balance | u32 count | keys."""
    return (ALLOCATION_MAGIC + struct.pack("<QI", balance, len(keys) // 32)
            + keys)


def genesis_root(balance: int, keys: bytes) -> bytes:
    """Where the root chain starts when an allocation is loaded."""
    return hashlib.blake2b(
        GENESIS_ROOT + allocation_bytes(balance, keys), digest_size=32
    ).digest()


# -- the envelope ---------------------------------------------------------------


def encode_exec_tx(op: int, account_key: bytes, nonce: int, amount: int,
                   dest: bytes = b"") -> bytes:
    return (EXEC_MAGIC + struct.pack("<BI", op, len(account_key))
            + account_key + struct.pack("<QQI", nonce, amount, len(dest))
            + dest)


def signed_message(body: bytes) -> bytes:
    """What the account signs: BLAKE2b-256 of the body."""
    return hashlib.blake2b(body, digest_size=32).digest()


def encode_envelope(signature: bytes, body: bytes) -> bytes:
    return SIGNED_MAGIC + signature + body


def _decode_exec_tx(data: bytes, at: int, whole: bool) -> Optional[dict]:
    """The ExecTx at ``data[at:]`` (which must end with it if ``whole``),
    or None if it does not decode."""
    try:
        if data[at:at + 8] != EXEC_MAGIC:
            return None
        at += 8
        op, n = struct.unpack_from("<BI", data, at)
        at += 5
        key = data[at:at + n]
        if len(key) != n:
            return None
        at += n
        nonce, amount, m = struct.unpack_from("<QQI", data, at)
        at += 20
        dest = data[at:at + m]
        if len(dest) != m:
            return None
        at += m
    except struct.error:
        return None
    if whole and at != len(data):
        return None
    if op not in (OP_CREATE, OP_MINT, OP_TRANSFER):
        return None
    if not 1 <= len(key) <= MAX_ACCOUNT_KEY_LEN:
        return None
    if op == OP_TRANSFER:
        if not 1 <= len(dest) <= MAX_ACCOUNT_KEY_LEN:
            return None
    elif dest:
        return None
    return {"op": op, "account": key, "nonce": nonce, "amount": amount,
            "dest": dest}


def decode_envelope(payload: bytes) -> Optional[dict]:
    """A signed envelope's transaction with ``signature`` and ``message``
    (what was signed), or None: not the magic, or garbled — an opaque
    no-op either way."""
    if payload[:8] != SIGNED_MAGIC or len(payload) < 72:
        return None
    tx = _decode_exec_tx(payload, 72, whole=False)
    if tx is None or len(tx["account"]) != 32:
        return None
    tx["signature"] = payload[8:72]
    tx["message"] = signed_message(payload[72:])
    return tx


# -- transfers -------------------------------------------------------------------


def make_transfer(sender: tuple, nonce: int, amount: int, dest_key: bytes,
                  size: int, filler: bytes) -> bytes:
    """One signed transfer of exactly ``size`` bytes: the memo is
    ``filler`` cut to what is left."""
    private, public = sender
    tx = encode_exec_tx(OP_TRANSFER, public, nonce, amount, dest_key)
    memo = filler[:size - 72 - len(tx)]
    body = tx + memo
    envelope = encode_envelope(private.sign(signed_message(body)), body)
    if len(envelope) != size:
        raise ValueError(f"a transfer of {len(envelope)} bytes, not {size}")
    return envelope


def corrupt_signature(rng: random.Random, envelope: bytes) -> bytes:
    """One bit of the 64-byte signature flipped."""
    at = 8 + rng.randrange(64)
    return (envelope[:at] + bytes([envelope[at] ^ (1 << rng.randrange(8))])
            + envelope[at + 1:])


def sound(envelope: bytes) -> bool:
    """OpenSSL's verdict on a signed transfer's signature."""
    tx = decode_envelope(envelope)
    return tx is not None and oracle.verify(
        tx["account"], tx["message"], tx["signature"])


# -- the fold ----------------------------------------------------------------------


class Fold:
    """Balances, nonces and the root chain over a committed sequence, where
    signatures are required (``signed``) or not."""

    def __init__(self, signed: bool = True) -> None:
        self.signed = signed
        # OpenSSL's verdict on an envelope; a caller that has computed the
        # verdicts in bulk (the same function, over a pool) puts its table
        # here.
        self.sound = sound
        self.accounts: Dict[bytes, Tuple[int, int]] = {}
        self.root = GENESIS_ROOT
        self.height = 0
        self.roots: Dict[int, bytes] = {}
        self.verdicts: Dict[str, int] = {}

    def load_genesis(self, balance: int, keys: bytes) -> None:
        self.accounts = {keys[at:at + 32]: (balance, 0)
                         for at in range(0, len(keys), 32)}
        self.root = genesis_root(balance, keys)

    def verdict(self, payload: bytes, deltas: dict) -> Optional[str]:
        """Apply one committed payload; None for an opaque one."""
        if self.signed:
            tx = decode_envelope(payload)
            if tx is None:
                bare = _decode_exec_tx(payload, 0, whole=True)
                return UNSIGNED if bare is not None else None
            if not self.sound(payload):
                return BAD_SIGNATURE
        else:
            tx = _decode_exec_tx(payload, 0, whole=True)
            if tx is None:
                return None
        return self._apply(tx, deltas)

    def _apply(self, tx: dict, deltas: dict) -> str:
        accounts, key = self.accounts, tx["account"]
        if tx["op"] == OP_CREATE:
            if key in accounts:
                return ACCOUNT_EXISTS
            if tx["nonce"] != 0:
                return BAD_NONCE
            deltas[key] = accounts[key] = (tx["amount"], 1)
            return APPLIED
        if key not in accounts:
            return UNKNOWN_ACCOUNT
        balance, nonce = accounts[key]
        if tx["nonce"] != nonce:
            return BAD_NONCE
        if tx["op"] == OP_MINT:
            deltas[key] = accounts[key] = (balance + tx["amount"], nonce + 1)
            return APPLIED
        if tx["amount"] > balance:
            return INSUFFICIENT_BALANCE
        dest = tx["dest"]
        if dest == key:
            deltas[key] = accounts[key] = (balance, nonce + 1)
            return APPLIED
        dest_balance, dest_nonce = accounts.get(dest, (0, 0))
        deltas[key] = accounts[key] = (balance - tx["amount"], nonce + 1)
        deltas[dest] = accounts[dest] = (dest_balance + tx["amount"],
                                         dest_nonce)
        return APPLIED

    def commit(self, height: int, payloads: Iterable[bytes]) -> bytes:
        """Fold one committed sub-dag's payloads, in its linearized order;
        the root after it."""
        if height != self.height + 1:
            raise ValueError(f"commit {height} after {self.height}")
        deltas: Dict[bytes, Tuple[int, int]] = {}
        for payload in payloads:
            verdict = self.verdict(bytes(payload), deltas)
            if verdict is not None:
                self.verdicts[verdict] = self.verdicts.get(verdict, 0) + 1
        parts: List[bytes] = [self.root,
                              struct.pack("<QI", height, len(deltas))]
        for key in sorted(deltas):
            balance, nonce = deltas[key]
            parts.append(struct.pack("<I", len(key)) + key
                         + struct.pack("<QQ", balance, nonce))
        self.root = hashlib.blake2b(b"".join(parts), digest_size=32).digest()
        self.height = height
        self.roots[height] = self.root
        return self.root
