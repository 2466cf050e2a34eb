"""The plain reference of the SmallBank deployment (`smallbank10`).

Written out from `docs/execution.md` and from the benchmark's source
(Alomari, Cahill, Fekete, Roehm, "The Cost of Serializability on Platforms
That Use Snapshot Isolation", ICDE 2008, as shipped in H-Store / OLTPBench
and used by Blockbench), importing nothing of the program: the seeded maker
of accounts and keys (``transfers.py``'s derivation, copied), the
two-balance allocation's bytes, the signed envelope, the six procedures
over a plain dict, the root chain, and a fold that VERIFIES every signature
with OpenSSL (``ed25519_oracle.py``): a forged operation in the committed
sequence is the no-op ``bad_signature`` here and shows as a differing root.
It also makes the traffic (``schedule``), so that program and reference see
one stream.

Departures from the source, each on purpose:

* ``Balance`` is a write-free TRANSACTION: it rides consensus as a signed
  operation and consumes its signer's nonce, as Blockbench sends it; it
  returns nothing here but its verdict.
* Amounts are integer cents (the source's 5.00 / 1.30 / 20.20 are 500 /
  130 / 2020), balances too.
* ``TransactSavings`` takes an unsigned amount (the envelope's ``amount``
  is a u64): the source's procedure takes a signed one and aborts below
  zero, its generator only ever sends +20.20.
* An abort is an EXECUTED outcome (``aborted``): no balance moves and the
  signer's nonce is consumed, so that a wallet that signs ahead keeps its
  sequence.  Only ``SendPayment`` aborts (checking below the amount).
* No operation creates an account: an unknown N1 or N2 is the no-op
  ``unknown_account``.  The signer of an operation is its N1.
* ``WriteCheck`` may drive checking below zero (the source's overdraft
  penalty of 1 where savings + checking do not cover the check); so may an
  ``Amalgamate`` of such an account lower its N2.
"""
from __future__ import annotations

import hashlib
import random
import struct
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.reference import ed25519_oracle as oracle

EXEC_MAGIC = b"\xffEXECTX\x01"
SIGNED_MAGIC = b"\xffSIGNTX\x01"
ALLOCATION_MAGIC = b"MYSTALLOC\x01"  # one balance an account
ALLOCATION_MAGIC_TWO = b"MYSTALLOC\x02"  # checking and savings
OP_CREATE, OP_MINT, OP_TRANSFER = 0, 1, 2
(OP_BALANCE, OP_DEPOSIT_CHECKING, OP_TRANSACT_SAVINGS, OP_AMALGAMATE,
 OP_WRITE_CHECK, OP_SEND_PAYMENT) = range(3, 9)
# The source's names, as the configuration's ``mix`` and ``amounts`` use them.
PROCEDURES = {
    "Amalgamate": OP_AMALGAMATE, "Balance": OP_BALANCE,
    "DepositChecking": OP_DEPOSIT_CHECKING, "SendPayment": OP_SEND_PAYMENT,
    "TransactSavings": OP_TRANSACT_SAVINGS, "WriteCheck": OP_WRITE_CHECK,
}
WITH_DEST = (OP_TRANSFER, OP_AMALGAMATE, OP_SEND_PAYMENT)
MAX_ACCOUNT_KEY_LEN = 64
GENESIS_ROOT = bytes(32)
WIDE = 1 << 63

APPLIED = "applied"
ABORTED = "aborted"
ACCOUNT_EXISTS = "account_exists"
UNKNOWN_ACCOUNT = "unknown_account"
BAD_NONCE = "bad_nonce"
INSUFFICIENT_BALANCE = "insufficient_balance"
UNSIGNED = "unsigned"
BAD_SIGNATURE = "bad_signature"
EXECUTED = (APPLIED, ABORTED)


# -- accounts and the allocation ---------------------------------------------


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


def allocation_bytes(checking: int, keys: bytes, savings: int) -> bytes:
    """A genesis allocation file.  With savings: the second magic | u64
    checking | u64 savings | u32 count | keys; without, the one-balance
    file of ``transfers.py``: magic | u64 balance | u32 count | keys."""
    count = len(keys) // 32
    if savings == 0:
        return ALLOCATION_MAGIC + struct.pack("<QI", checking, count) + keys
    return (ALLOCATION_MAGIC_TWO + struct.pack("<QQI", checking, savings,
                                               count) + keys)


def genesis_root(checking: int, keys: bytes, savings: int) -> bytes:
    """Where the root chain starts when an allocation is loaded."""
    return hashlib.blake2b(
        GENESIS_ROOT + allocation_bytes(checking, keys, savings),
        digest_size=32).digest()


def account_entry(key: bytes, checking: int, nonce: int, savings: int) -> bytes:
    """One account in the root's input.  Without savings and with checking
    at or above zero: u32 len | key | u64 checking | u64 nonce.  Else the
    wide form: u32 len | key | i64 checking | u64 (nonce | 2**63) | u64
    savings."""
    head = struct.pack("<I", len(key)) + key
    if savings == 0 and checking >= 0:
        return head + struct.pack("<QQ", checking, nonce)
    return head + struct.pack("<qQQ", checking, nonce | WIDE, savings)


# -- the envelope -------------------------------------------------------------


def encode_exec_tx(op: int, account_key: bytes, nonce: int, amount: int,
                   dest: bytes = b"") -> bytes:
    return (EXEC_MAGIC + struct.pack("<BI", op, len(account_key))
            + account_key + struct.pack("<QQI", nonce, amount, len(dest))
            + dest)


def signed_message(body: bytes) -> bytes:
    """What the account signs: BLAKE2b-256 of the body."""
    return hashlib.blake2b(body, digest_size=32).digest()


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
    if not OP_CREATE <= op <= OP_SEND_PAYMENT:
        return None
    if not 1 <= len(key) <= MAX_ACCOUNT_KEY_LEN:
        return None
    if op in WITH_DEST:
        if not 1 <= len(dest) <= MAX_ACCOUNT_KEY_LEN:
            return None
    elif dest:
        return None
    return {"op": op, "account": key, "nonce": nonce, "amount": amount,
            "dest": dest}


def decode_envelope(payload: bytes) -> Optional[dict]:
    """A signed envelope's operation with ``signature`` and ``message``
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


def make_operation(signer: tuple, op: int, nonce: int, amount: int,
                   dest_key: bytes, size: int, filler: bytes) -> bytes:
    """One signed operation of exactly ``size`` bytes: the memo is
    ``filler`` cut to what is left."""
    private, public = signer
    tx = encode_exec_tx(op, public, nonce, amount, dest_key)
    body = tx + filler[:size - 72 - len(tx)]
    envelope = SIGNED_MAGIC + private.sign(signed_message(body)) + body
    if len(envelope) != size:
        raise ValueError(f"an operation of {len(envelope)} bytes, not {size}")
    return envelope


def corrupt_signature(rng: random.Random, envelope: bytes) -> bytes:
    """One bit of the 64-byte signature flipped."""
    at = 8 + rng.randrange(64)
    return (envelope[:at] + bytes([envelope[at] ^ (1 << rng.randrange(8))])
            + envelope[at + 1:])


def sound(envelope: bytes) -> bool:
    """OpenSSL's verdict on a signed operation's signature."""
    tx = decode_envelope(envelope)
    return tx is not None and oracle.verify(
        tx["account"], tx["message"], tx["signature"])


# -- the traffic --------------------------------------------------------------


def schedule(seed: int, count: int, accounts: int, hotspot_accounts: int,
             hotspot_share: float, mix: Dict[str, float],
             amounts: Dict[str, int]) -> List[tuple]:
    """``count`` operations from ``seed``: (op, N1, N2 or None, amount).
    The procedure is drawn by ``mix`` (weights by the source's names); each
    account choice falls in the hotspot (indices below
    ``hotspot_accounts``) with probability ``hotspot_share`` and is uniform
    over the rest otherwise; N2 is drawn until it differs from N1."""
    rng = random.Random(seed)
    names = sorted(mix)
    weights = [float(mix[name]) for name in names]
    cold = accounts - hotspot_accounts

    def choose() -> int:
        if rng.random() < hotspot_share:
            return rng.randrange(hotspot_accounts)
        return hotspot_accounts + rng.randrange(cold)

    out = []
    for name in rng.choices(names, weights, k=count):
        op = PROCEDURES[name]
        n1, n2 = choose(), None
        if op in WITH_DEST:
            n2 = choose()
            while n2 == n1:
                n2 = choose()
        out.append((op, n1, n2, int(amounts.get(name, 0))))
    return out


# -- the fold -----------------------------------------------------------------


class Fold:
    """Checking, savings, nonces and the root chain over a committed
    sequence of signed operations (``signed``: a bare operation is the
    no-op ``unsigned``)."""

    def __init__(self, signed: bool = True) -> None:
        self.signed = signed
        # OpenSSL's verdict on an envelope; a caller that has computed the
        # verdicts in bulk (the same function, over a pool) puts its table
        # here.
        self.sound = sound
        # key -> (checking, nonce, savings)
        self.accounts: Dict[bytes, Tuple[int, int, int]] = {}
        self.root = GENESIS_ROOT
        self.height = 0
        self.roots: Dict[int, bytes] = {}
        self.verdicts: Dict[str, int] = {}
        # Every verdict in the order given, (payload, verdict), for a
        # caller that holds operations to their own verdicts.
        self.log: Optional[List[tuple]] = None

    def load_genesis(self, checking: int, keys: bytes, savings: int) -> None:
        self.accounts = {keys[at:at + 32]: (checking, 0, savings)
                         for at in range(0, len(keys), 32)}
        self.root = genesis_root(checking, keys, savings)

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
        return self.apply(tx, deltas)

    def apply(self, tx: dict, deltas: dict) -> str:
        accounts, key, op = self.accounts, tx["account"], tx["op"]
        amount, dest = tx["amount"], tx["dest"]
        if op == OP_CREATE:
            if key in accounts:
                return ACCOUNT_EXISTS
            if tx["nonce"] != 0:
                return BAD_NONCE
            deltas[key] = accounts[key] = (amount, 1, 0)
            return APPLIED
        if key not in accounts:
            return UNKNOWN_ACCOUNT
        checking, nonce, savings = accounts[key]
        if tx["nonce"] != nonce:
            return BAD_NONCE
        if op == OP_TRANSFER:
            # The program's transfer: an overdraft consumes no nonce, and
            # an unknown destination is created.
            if amount > checking:
                return INSUFFICIENT_BALANCE
            deltas[key] = accounts[key] = (checking - amount, nonce + 1,
                                           savings)
            to = accounts.get(dest, (0, 0, 0))
            deltas[dest] = accounts[dest] = (to[0] + amount, to[1], to[2])
            return APPLIED
        if op in WITH_DEST and dest not in accounts:
            return UNKNOWN_ACCOUNT
        verdict, credit = APPLIED, 0
        if op in (OP_MINT, OP_DEPOSIT_CHECKING):
            checking += amount
        elif op == OP_TRANSACT_SAVINGS:
            savings += amount
        elif op == OP_AMALGAMATE:
            credit, checking, savings = savings + checking, 0, 0
        elif op == OP_WRITE_CHECK:
            if savings + checking < amount:
                checking -= amount + 1
            else:
                checking -= amount
        elif op == OP_SEND_PAYMENT:
            if checking < amount:
                verdict = ABORTED
            else:
                checking -= amount
                credit = amount
        deltas[key] = accounts[key] = (checking, nonce + 1, savings)
        if credit:
            to = accounts[dest]
            deltas[dest] = accounts[dest] = (to[0] + credit, to[1], to[2])
        return verdict

    def commit(self, height: int, payloads: Iterable[bytes]) -> bytes:
        """Fold one committed sub-dag's payloads, in its linearized order;
        the root after it."""
        if height != self.height + 1:
            raise ValueError(f"commit {height} after {self.height}")
        deltas: Dict[bytes, Tuple[int, int, int]] = {}
        for payload in payloads:
            payload = bytes(payload)
            verdict = self.verdict(payload, deltas)
            if verdict is not None:
                self.verdicts[verdict] = self.verdicts.get(verdict, 0) + 1
                if self.log is not None:
                    self.log.append((payload, verdict))
        parts: List[bytes] = [self.root,
                              struct.pack("<QI", height, len(deltas))]
        for key in sorted(deltas):
            parts.append(account_entry(key, *deltas[key]))
        self.root = hashlib.blake2b(b"".join(parts), digest_size=32).digest()
        self.height = height
        self.roots[height] = self.root
        return self.root
