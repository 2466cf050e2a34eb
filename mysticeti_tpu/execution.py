"""Deterministic execution plane: an account ledger folded over the commits.

The committed leader sequence is a total order every honest node derives
identically (the same property :mod:`.reconfig` anchors epoch changes on),
which makes it a replicated-state-machine log for free.  This module is the
CONSUMER half of ROADMAP item 3: a deterministic account/transfer runtime
folded over the linearized commits, whose per-commit **state root** becomes
a cross-node safety invariant and the object clients actually wait for
(execution-backed finality, the ACE-runtime shape from PAPERS.md).

* ``ExecTx`` — a typed transaction that rides the committed sequence as an
  ordinary ``Share`` payload prefixed with ``EXEC_MAGIC``: CREATE / MINT /
  TRANSFER over an account's one balance, and SmallBank's six procedures
  (Balance, DepositChecking, TransactSavings, Amalgamate, WriteCheck,
  SendPayment) over its two — the balance above is its **checking**, and
  a **savings** balance stands beside it.  Non-magic payloads (benchmark
  counters, stamped random bytes, reconfig changes) are opaque no-ops — the
  runtime coexists with every existing workload.
* ``ExecutionState`` — the per-node state machine owned by the consensus
  core: folds each committed sub-dag (linearized order, one commit at a
  time, the ``ReconfigState.observe_commit`` pattern) and emits a chained
  per-commit state root.
* **State root** — BLAKE2b-256 over ``prev_root ‖ height ‖ sorted account
  deltas`` (canonical serde encoding, accounts sorted by key).  Every
  commit advances the chain — a commit with no execution transactions
  still produces a new root — so two honest nodes can be compared at
  *every* shared height, and a fork anywhere poisons every later root.

Determinism rules (docs/execution.md):

* Inputs are exactly (previous state, commit height, Share payloads in
  sub-dag linearized order).  No clocks, no RNG, no per-node identity.
* Invalid transactions (bad nonce, overdraft, duplicate create, unknown
  account) are deterministic typed no-ops — every node rejects them with
  the same verdict, so duplicates and garbage cannot fork the chain.  They
  consume no nonce.
* A SmallBank procedure that its own rules abort (a SendPayment beyond the
  checking balance) is an EXECUTED outcome, ``aborted``: no balance moves,
  but the nonce is consumed and the account enters the commit's deltas and
  the root.  A wallet that signs several operations ahead keeps its
  sequence after one of them aborts; after a TRANSFER's
  ``insufficient_balance``, which consumes none, it would not.
* A payload carrying ``EXEC_MAGIC`` that fails to decode is an opaque
  no-op, exactly like :func:`.reconfig.parse_reconfig_tx` — a garbled
  transaction must not fork honest nodes on whether to error.

Concurrency: mutation is single-owner (the consensus core task calls
:meth:`ExecutionState.observe_commit`), but the ingress plane *probes*
account state from submission threads for pre-consensus admission
(a stale nonce or an unknown account is shed before consensus pays for the
transaction),
so the account table is guarded by ``_exec_lock`` (lint GUARDED_FIELDS).

Signed transactions (``Parameters.signed_transactions``, docs/execution.md):
an :class:`ExecTx` then rides inside a signed envelope — ``SIGNED_MAGIC ‖
signature ‖ body`` with ``body = ExecTx ‖ memo``, signed by the spending
account, whose key IS its Ed25519 public key — and a bare ``EXECTX`` folds
as the typed no-op ``unsigned``.  The signature is checked where a
transaction enters a validator (the gateway, ingress.py) and where a block
carrying it is received (block_validator.py), never here: the fold stays a
pure function of the committed sequence.  A **genesis allocation** funds
the accounts such a deployment starts with and enters the root chain.
"""
from __future__ import annotations

import hashlib
import os
import struct
import threading
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Tuple

from .serde import Reader, SerdeError, Writer
from .spans import booked
from .types import Share, StatementBlock

# Share-payload prefix marking an execution transaction.  Same shape as
# RECONFIG_MAGIC: 8 bytes, first byte 0xFF — unreachable for the 8-byte
# little-endian benchmark counters below 2**63.
EXEC_MAGIC = b"\xffEXECTX\x01"
# Share-payload prefix of a signed envelope: magic ‖ 64-byte Ed25519
# signature ‖ body, body = ExecTx encoding ‖ opaque memo.  The signer is
# the ExecTx's account (its 32-byte key is the public key) and the signed
# message is BLAKE2b-256(body): 32 bytes, so the verifier's fused digest
# kernels take it like a block digest.
SIGNED_MAGIC = b"\xffSIGNTX\x01"
SIGNATURE_LEN = 64
SIGNER_KEY_LEN = 32
# First bytes of a genesis allocation file (``write_genesis_allocation``):
# one balance an account, or (the second magic) a checking and a savings
# balance.  Allocations are told apart by their magic, nothing else.
ALLOCATION_MAGIC = b"MYSTALLOC\x01"
ALLOCATION_MAGIC_TWO = b"MYSTALLOC\x02"

OP_CREATE = 0  # create account with an initial (faucet) balance; nonce must be 0
OP_MINT = 1  # balance += amount on an existing account (nonce-gated)
OP_TRANSFER = 2  # move amount to dest (auto-created at 0); nonce-gated
# SmallBank (Alomari et al., ICDE 2008; docs/execution.md "SmallBank"):
# account = N1 = the signer, amount = V, dest = N2.  Every one is
# nonce-gated, creates no account, and consumes its nonce whenever it
# executes — applied or aborted.
OP_BALANCE = 3  # reads both balances, changes neither
OP_DEPOSIT_CHECKING = 4  # checking += V
OP_TRANSACT_SAVINGS = 5  # savings += V
OP_AMALGAMATE = 6  # checking[N2] += savings[N1] + checking[N1]; N1's become 0
OP_WRITE_CHECK = 7  # checking -= V, and 1 more where savings + checking < V
OP_SEND_PAYMENT = 8  # aborts where checking[N1] < V; else V from N1's to N2's

_OP_NAMES = {
    OP_CREATE: "create", OP_MINT: "mint", OP_TRANSFER: "transfer",
    OP_BALANCE: "balance", OP_DEPOSIT_CHECKING: "deposit_checking",
    OP_TRANSACT_SAVINGS: "transact_savings", OP_AMALGAMATE: "amalgamate",
    OP_WRITE_CHECK: "write_check", OP_SEND_PAYMENT: "send_payment",
}
# Operations that name a second account in ``dest``.
_OPS_WITH_DEST = frozenset({OP_TRANSFER, OP_AMALGAMATE, OP_SEND_PAYMENT})
_SMALLBANK_OPS = frozenset(range(OP_BALANCE, OP_SEND_PAYMENT + 1))

# Typed apply verdicts.  The *names* are the metrics label set
# (mysticeti_execution_txs_total{result}) and the ingress shed vocabulary —
# keep them stable.
APPLIED = "applied"
REJECT_EXISTS = "account_exists"
REJECT_UNKNOWN = "unknown_account"
REJECT_BAD_NONCE = "bad_nonce"
REJECT_OVERDRAFT = "insufficient_balance"
# A SmallBank procedure that its own rules aborted: executed, nonce consumed.
ABORTED = "aborted"
# Where signatures are required: a bare EXECTX in the committed sequence
# (the fold's verdict), and a transaction whose signature does not verify
# (the gateway's verdict; such a transaction never reaches the fold).
REJECT_UNSIGNED = "unsigned"
REJECT_BAD_SIGNATURE = "bad_signature"

MAX_ACCOUNT_KEY_LEN = 64

# Recent (height, root) pairs retained for the /debug document, the gateway
# resume reply, and the chaos state-root audit.  Bounded: old roots are
# recomputable from the WAL and irrelevant to live agreement checks.
ROOT_WINDOW = 1024

GENESIS_ROOT = b"\x00" * 32

# One account in the root's input and in the durable state: the canonical
# serde fields ``bytes key ‖ u64 balance ‖ u64 nonce`` in one pack.  An
# account with savings, or whose checking balance a WriteCheck drove below
# zero, takes the wide form: ``bytes key ‖ i64 checking ‖ u64 (nonce |
# 2**63) ‖ u64 savings`` — the nonce's top bit, which no nonce counted up
# from 0 reaches, says that the balance is signed and that savings follow.
_ACCOUNT_TAIL = struct.Struct("<QQ")
_ACCOUNT_TAIL_WIDE = struct.Struct("<qQQ")
_WIDE = 1 << 63
_U32 = struct.Struct("<I")


def _account_entry(key: bytes, balance: int, nonce: int,
                   savings: int = 0) -> bytes:
    if savings == 0 and balance >= 0:
        return _U32.pack(len(key)) + key + _ACCOUNT_TAIL.pack(balance, nonce)
    return _U32.pack(len(key)) + key + _ACCOUNT_TAIL_WIDE.pack(
        balance, nonce | _WIDE, savings)


def _read_account_entry(r: Reader) -> Tuple[bytes, Tuple[int, int, int]]:
    """(key, (balance, nonce, savings)) of one entry off ``r``."""
    key = bytes(r.bytes())
    balance, nonce = r.u64(), r.u64()
    if not nonce & _WIDE:
        return key, (balance, nonce, 0)
    if balance >= _WIDE:
        balance -= 1 << 64
    return key, (balance, nonce ^ _WIDE, r.u64())


@dataclass(frozen=True)
class ExecTx:
    """One typed execution transaction riding the committed sequence."""

    op: int
    account: bytes
    nonce: int = 0
    amount: int = 0
    dest: bytes = b""

    def __post_init__(self) -> None:
        if self.op not in _OP_NAMES:
            raise ValueError(f"unknown execution op {self.op}")
        if not self.account or len(self.account) > MAX_ACCOUNT_KEY_LEN:
            raise ValueError(
                f"account key must be 1..{MAX_ACCOUNT_KEY_LEN} bytes"
            )
        if self.op in _OPS_WITH_DEST:
            if not self.dest or len(self.dest) > MAX_ACCOUNT_KEY_LEN:
                raise ValueError(
                    f"{_OP_NAMES[self.op]} dest must be "
                    f"1..{MAX_ACCOUNT_KEY_LEN} bytes"
                )
        elif self.dest:
            raise ValueError(f"{_OP_NAMES[self.op]} takes no dest")
        if self.nonce < 0 or self.amount < 0:
            raise ValueError("nonce/amount must be non-negative")

    def to_bytes(self) -> bytes:
        w = Writer()
        w.fixed(EXEC_MAGIC)
        w.u8(self.op)
        w.bytes(self.account)
        w.u64(self.nonce)
        w.u64(self.amount)
        w.bytes(self.dest)
        return w.finish()

    @staticmethod
    def decode(r: Reader) -> "ExecTx":
        """One transaction off ``r``, which may hold more behind it (a
        signed envelope's memo)."""
        magic = r.fixed(len(EXEC_MAGIC))
        if magic != EXEC_MAGIC:
            raise SerdeError("not an execution transaction")
        op = r.u8()
        account = bytes(r.bytes())
        nonce = r.u64()
        amount = r.u64()
        dest = bytes(r.bytes())
        return ExecTx(op, account, nonce, amount, dest)

    @staticmethod
    def from_bytes(data: bytes) -> "ExecTx":
        r = Reader(data)
        tx = ExecTx.decode(r)
        r.expect_done()
        return tx

    def describe(self) -> str:
        extra = f", dest={self.dest.hex()}" if self.dest else ""
        return (
            f"{_OP_NAMES[self.op]}(account={self.account.hex()}, "
            f"nonce={self.nonce}, amount={self.amount}{extra})"
        )


def parse_exec_tx(payload: bytes) -> Optional[ExecTx]:
    """Decode a Share payload into an :class:`ExecTx`, or None for ordinary
    transactions.  A payload carrying the magic but failing to decode is
    treated as ordinary data (a garbled transaction must not fork honest
    nodes on whether to error — ignoring it is the deterministic choice)."""
    if not payload.startswith(EXEC_MAGIC):
        return None
    try:
        return ExecTx.from_bytes(payload)
    except (SerdeError, ValueError):
        return None


@dataclass(frozen=True)
class SignedTx:
    """A decoded signed envelope: the transaction, its signature, and the
    32-byte message the signature is over (None where the caller asked for
    no digest).  The signer's public key is ``tx.account``."""

    tx: ExecTx
    signature: bytes
    digest: Optional[bytes]


def signed_digest(body: bytes) -> bytes:
    """What the spending account signs: BLAKE2b-256 of the envelope's body
    (the ExecTx encoding and the memo behind it)."""
    return hashlib.blake2b(body, digest_size=32).digest()


def encode_signed_tx(tx: ExecTx, signature: bytes, memo: bytes = b"") -> bytes:
    """The envelope around ``tx``; ``signature`` is over
    ``signed_digest(tx.to_bytes() + memo)`` by the key ``tx.account``."""
    if len(signature) != SIGNATURE_LEN or len(tx.account) != SIGNER_KEY_LEN:
        raise ValueError("a signed transaction has a 64-byte signature and "
                         "a 32-byte account key")
    return SIGNED_MAGIC + signature + tx.to_bytes() + memo


def parse_signed_tx(payload: bytes, digest: bool = True) -> Optional[SignedTx]:
    """Decode a Share payload into a :class:`SignedTx`, or None for anything
    else.  A payload with the magic that does not decode — a truncated
    signature, a garbled transaction, an account key that is no 32-byte
    public key — is an opaque no-op by the rule ``EXEC_MAGIC`` follows:
    nothing is verified for it and nothing folds."""
    if not payload.startswith(SIGNED_MAGIC):
        return None
    at = len(SIGNED_MAGIC) + SIGNATURE_LEN
    try:
        tx = ExecTx.decode(Reader(payload, at))
    except (SerdeError, ValueError):
        return None
    if len(tx.account) != SIGNER_KEY_LEN:
        return None
    return SignedTx(
        tx,
        bytes(payload[len(SIGNED_MAGIC):at]),
        signed_digest(payload[at:]) if digest else None,
    )


# -- genesis allocation -------------------------------------------------------


def account_seed(seed: int, index: int) -> bytes:
    """The private seed of benchmark account ``index`` under ``seed``:
    as ``Committee.benchmark_signers`` derives the committee's keys from
    their index, a benchmark genesis derives its accounts'."""
    return hashlib.blake2b(
        b"mysticeti-account" + seed.to_bytes(8, "little")
        + index.to_bytes(8, "little"), digest_size=32,
    ).digest()


def _account_keys(span: Tuple[int, int, int]) -> bytes:
    from .crypto import Signer

    seed, start, stop = span
    return b"".join(
        Signer.from_seed(account_seed(seed, i)).public_key.bytes
        for i in range(start, stop)
    )


def _allocation_header(balance: int, count: int, savings: int = 0) -> bytes:
    if savings == 0:
        return Writer().fixed(ALLOCATION_MAGIC).u64(balance).u32(count).finish()
    return (Writer().fixed(ALLOCATION_MAGIC_TWO).u64(balance).u64(savings)
            .u32(count).finish())


def write_genesis_allocation(path: str, count: int, seed: int,
                             balance: int, savings: int = 0) -> None:
    """``count`` accounts, each funded with ``balance``: magic ‖ u64 balance
    ‖ u32 count ‖ count 32-byte keys, in index order; where every account
    also starts with ``savings``, the second magic ‖ u64 checking ‖ u64
    savings ‖ u32 count ‖ the keys.  A million key derivations take a core
    a minute, so large counts are spread over the host's cores."""
    ranges = [(seed, at, min(count, at + 8192))
              for at in range(0, count, 8192)]
    workers = min(len(ranges), os.cpu_count() or 1)
    if workers > 1:
        import multiprocessing

        with multiprocessing.get_context("fork").Pool(workers) as pool:
            chunks = pool.map(_account_keys, ranges)
    else:
        chunks = [_account_keys(span) for span in ranges]
    with open(path + ".tmp", "wb") as f:
        f.write(_allocation_header(balance, count, savings))
        for chunk in chunks:
            f.write(chunk)
    os.replace(path + ".tmp", path)


def read_genesis_allocation(path: str) -> tuple:
    """What ``ExecutionState.load_genesis`` takes, off an allocation file:
    (balance, the keys' bytes back to back), and the savings balance
    behind them where the file has two balances."""
    with open(path, "rb") as f:
        data = f.read()
    r = Reader(data)
    magic = r.fixed(len(ALLOCATION_MAGIC))
    if magic not in (ALLOCATION_MAGIC, ALLOCATION_MAGIC_TWO):
        raise SerdeError(f"{path} is no genesis allocation")
    balance = r.u64()
    savings = r.u64() if magic == ALLOCATION_MAGIC_TWO else None
    count = r.u32()
    keys = data[r.pos:]
    if len(keys) != SIGNER_KEY_LEN * count:
        raise SerdeError(
            f"{path} names {count} accounts and holds {len(keys)} key bytes")
    return (balance, keys) if savings is None else (balance, keys, savings)


@dataclass(frozen=True)
class ExecutionResult:
    """Outcome of folding one committed sub-dag."""

    height: int
    root: bytes
    applied: int
    rejected: int
    # typed verdict name -> count for this commit (APPLIED included)
    verdicts: Tuple[Tuple[str, int], ...] = ()


class ExecutionState:
    """Deterministic account/transfer state machine on the committed sequence.

    Single-owner mutation (the consensus core task calls
    :meth:`observe_commit` / :meth:`adopt` / :meth:`recover`); concurrent
    *probes* from ingress submission threads go through :meth:`probe` under
    the same lock.
    """

    def __init__(self, metrics=None, signed: bool = False) -> None:
        # Signatures required (Parameters.signed_transactions): the fold
        # takes transactions out of signed envelopes only.
        self.signed = signed
        self._exec_lock = threading.Lock()
        # account key -> (balance, nonce, savings); the balance is the
        # checking balance and may be negative (WriteCheck).  Guarded by
        # _exec_lock (lint GUARDED_FIELDS): the core task folds commits
        # while ingress submission threads probe balances for
        # pre-consensus admission.
        self._exec_accounts: Dict[bytes, Tuple[int, int, int]] = {}
        # The genesis allocation as loaded, (balance, keys, savings), if any.
        self._genesis: Optional[Tuple[int, bytes, int]] = None
        # Every account a commit has touched, with its encoded entry as of
        # its last commit, in the order they were first touched (the same
        # on every node, being the committed sequence's).  The durable
        # encoding is those entries back to back — over the allocation
        # every node loads at boot, where there is one — and costs a
        # checkpoint one join, not a sort of every account.
        self._exec_touched: Dict[bytes, bytes] = {}
        self.last_height = 0
        self.root = GENESIS_ROOT
        self.recent_roots: Deque[Tuple[int, bytes]] = deque(maxlen=ROOT_WINDOW)
        self.applied_total = 0
        self.rejected_total = 0
        # Transactions folded as ``bad_nonce`` since this process started
        # (spans.NODE_STAMPS ``exec_bad_nonce``): a sequence that broke.
        self.bad_nonce_total = 0
        self.metrics = metrics
        # The validator's stage clock (spans.StageClock; None = not
        # clocked): ``observe_commit`` books ``exec_fold``.
        self.stages = None

    # -- queries ---------------------------------------------------------

    def probe(self, account: bytes) -> Optional[Tuple[int, int]]:
        """(balance, nonce) snapshot, or None for an unknown account.
        Advisory by design: in-flight committed transactions may move the
        account before a submission folded against this snapshot lands."""
        entry = self.balances(account)
        return None if entry is None else entry[:2]

    def balances(self, account: bytes) -> Optional[Tuple[int, int, int]]:
        """(checking, nonce, savings) snapshot — what a committed Balance
        reads — or None for an unknown account."""
        with self._exec_lock:
            return self._exec_accounts.get(account)

    def account_count(self) -> int:
        with self._exec_lock:
            return len(self._exec_accounts)

    def load_genesis(self, balance: int, keys: bytes,
                     savings: int = 0) -> None:
        """Fund the genesis allocation's accounts (nonce 0) before height
        1.  The allocation enters the root chain: two validators that
        loaded different allocations disagree at the first root."""
        if self.last_height:
            raise ValueError("a genesis allocation is loaded before any "
                             "commit is folded")
        self._genesis = (balance, keys, savings)
        # The root chain starts from the allocation file's bytes.
        h = hashlib.blake2b(GENESIS_ROOT, digest_size=32)
        h.update(_allocation_header(
            balance, len(keys) // SIGNER_KEY_LEN, savings))
        h.update(keys)
        self.root = h.digest()
        self._fund_genesis()

    def _fund_genesis(self) -> None:
        balance, keys, savings = self._genesis
        # One shared entry: a million distinct tuples in each of ten
        # processes is host memory nothing needs.
        entry = (balance, 0, savings)
        accounts = {keys[at:at + SIGNER_KEY_LEN]: entry
                    for at in range(0, len(keys), SIGNER_KEY_LEN)}
        with self._exec_lock:
            self._exec_accounts = accounts
            self._exec_touched = {}

    def transaction_of(self, payload: bytes):
        """What the fold makes of a Share payload: an :class:`ExecTx` to
        apply, ``REJECT_UNSIGNED`` for a bare EXECTX where signatures are
        required, None for an opaque payload.  The ingress plane admits by
        the same reading."""
        if not self.signed:
            return parse_exec_tx(payload)
        signed = parse_signed_tx(payload, digest=False)
        if signed is not None:
            return signed.tx
        return REJECT_UNSIGNED if parse_exec_tx(payload) is not None else None

    def root_at(self, height: int) -> Optional[bytes]:
        """The chained root at ``height`` if still in the recent window."""
        for h, root in reversed(self.recent_roots):
            if h == height:
                return root
            if h < height:
                break
        return None

    def admission(self, tx: ExecTx) -> Tuple[Optional[str], bool]:
        """Pre-consensus admission check for the ingress plane: (a typed
        reject for a transaction that is *already* doomed against current
        state or None for a plausibly valid one, whether its nonce is
        AHEAD of the account's — earlier operations of the account still
        in flight).

        Deliberately weaker than :meth:`_apply`, because the snapshot is
        advisory: only what in-flight traffic cannot cure is shed before
        consensus pays for the transaction.

        * shed: a nonce BEHIND the account's (``bad_nonce``), an unknown
          signer or — for a SmallBank operation, which creates no account —
          an unknown ``dest`` (``unknown_account``), a CREATE of an account
          that exists (``account_exists``), and a TRANSFER at the
          account's current nonce for more than its current balance
          (``insufficient_balance``: nothing of the account is in flight
          ahead of it, so only another account's transfer could still fund
          it, and the fold would refuse it without consuming a nonce);
        * admitted: a nonce ahead, a CREATE of an account not yet there,
          and every SmallBank operation whatever the funds — an abort is
          an outcome the client is owed, and funds may arrive before the
          operation folds."""
        entry = self.balances(tx.account)
        if tx.op == OP_CREATE:
            return (REJECT_EXISTS if entry is not None else None), False
        if entry is None:
            return REJECT_UNKNOWN, False
        balance, nonce, _ = entry
        if tx.nonce < nonce:
            return REJECT_BAD_NONCE, False
        if tx.op == OP_TRANSFER:
            if tx.nonce == nonce and tx.amount > balance:
                return REJECT_OVERDRAFT, False
        elif tx.op in _OPS_WITH_DEST and self.balances(tx.dest) is None:
            return REJECT_UNKNOWN, False
        return None, tx.nonce > nonce

    def admission_verdict(self, tx: ExecTx) -> Optional[str]:
        """The reject of :meth:`admission`, or None."""
        return self.admission(tx)[0]

    # -- the fold --------------------------------------------------------

    def _apply(self, tx: ExecTx,
               deltas: Dict[bytes, Tuple[int, int, int]]) -> str:
        """Apply one transaction against the account table (lock held by
        the caller), recording touched accounts into ``deltas``."""
        accounts = self._exec_accounts
        if tx.op == OP_CREATE:
            if tx.account in accounts:
                return REJECT_EXISTS
            if tx.nonce != 0:
                return REJECT_BAD_NONCE
            accounts[tx.account] = (tx.amount, 1, 0)
            deltas[tx.account] = accounts[tx.account]
            return APPLIED
        entry = accounts.get(tx.account)
        if entry is None:
            return REJECT_UNKNOWN
        balance, nonce, savings = entry
        if tx.nonce != nonce:
            return REJECT_BAD_NONCE
        if tx.op in _SMALLBANK_OPS:
            return self._apply_smallbank(tx, entry, deltas)
        if tx.op == OP_MINT:
            accounts[tx.account] = (balance + tx.amount, nonce + 1, savings)
            deltas[tx.account] = accounts[tx.account]
            return APPLIED
        # OP_TRANSFER
        if tx.amount > balance:
            return REJECT_OVERDRAFT
        if tx.dest == tx.account:
            # Self-transfer: balance unchanged, nonce still consumed.
            accounts[tx.account] = (balance, nonce + 1, savings)
            deltas[tx.account] = accounts[tx.account]
            return APPLIED
        dest_balance, dest_nonce, dest_savings = accounts.get(
            tx.dest, (0, 0, 0))
        accounts[tx.account] = (balance - tx.amount, nonce + 1, savings)
        accounts[tx.dest] = (dest_balance + tx.amount, dest_nonce,
                             dest_savings)
        deltas[tx.account] = accounts[tx.account]
        deltas[tx.dest] = accounts[tx.dest]
        return APPLIED

    def _apply_smallbank(self, tx: ExecTx, entry: Tuple[int, int, int],
                         deltas: Dict[bytes, Tuple[int, int, int]]) -> str:
        """One SmallBank procedure of a known signer at its nonce: the
        nonce is consumed whatever the outcome, but for an unknown N2."""
        accounts = self._exec_accounts
        checking, nonce, savings = entry
        op, amount = tx.op, tx.amount
        if op in _OPS_WITH_DEST and tx.dest not in accounts:
            return REJECT_UNKNOWN
        verdict, credit = APPLIED, 0
        if op == OP_DEPOSIT_CHECKING:
            checking += amount
        elif op == OP_TRANSACT_SAVINGS:
            savings += amount
        elif op == OP_AMALGAMATE:
            credit, checking, savings = savings + checking, 0, 0
        elif op == OP_WRITE_CHECK:
            # The source's overdraft penalty: one more where the two
            # balances together do not cover the check.
            checking -= amount + 1 if savings + checking < amount else amount
        elif op == OP_SEND_PAYMENT:
            if checking < amount:
                verdict = ABORTED
            else:
                checking -= amount
                credit = amount
        # OP_BALANCE reads, and writes nothing but the nonce.
        deltas[tx.account] = accounts[tx.account] = (
            checking, nonce + 1, savings)
        if credit:
            # After the signer's own entry, so that N2 = N1 reads it.
            to_checking, to_nonce, to_savings = accounts[tx.dest]
            deltas[tx.dest] = accounts[tx.dest] = (
                to_checking + credit, to_nonce, to_savings)
        return verdict

    def observe_commit(
        self, height: int, blocks: List[StatementBlock]
    ) -> Optional[ExecutionResult]:
        """Fold one committed sub-dag (linearized block order) into the
        state and advance the root chain.  Returns None when the commit was
        already folded (crash replay re-delivers committed heights —
        exactly the ``ReconfigState.observe_commit`` skip).  Where the
        validator's stage clock is attached (``stages``) a fold is one
        ``exec_fold`` sample, wall and the thread's CPU."""
        if height <= self.last_height:
            return None
        with booked(self.stages, "exec_fold", cpu=True):
            return self._fold(height, blocks)

    def _fold(
        self, height: int, blocks: List[StatementBlock]
    ) -> ExecutionResult:
        verdicts: Dict[str, int] = {}
        ops: Dict[int, int] = {}
        conflicts = 0
        deltas: Dict[bytes, Tuple[int, int, int]] = {}
        with self._exec_lock:
            for block in blocks:
                for st in block.statements:
                    if not isinstance(st, Share):
                        continue
                    tx = self.transaction_of(bytes(st.transaction))
                    if tx is None:
                        continue
                    if tx is REJECT_UNSIGNED:
                        verdict = tx
                    else:
                        ops[tx.op] = ops.get(tx.op, 0) + 1
                        # Its signer or its counterparty was written
                        # earlier in this same commit.
                        if tx.account in deltas or tx.dest in deltas:
                            conflicts += 1
                        verdict = self._apply(tx, deltas)
                    verdicts[verdict] = verdicts.get(verdict, 0) + 1
            entries = {
                key: _account_entry(key, *deltas[key]) for key in deltas
            }
            self._exec_touched.update(entries)
        # Chained root: prev ‖ height ‖ sorted account deltas.  The digest
        # input is canonical serde bytes, so it is identical wherever the
        # same commit folds over the same predecessor state.
        h = hashlib.blake2b(digest_size=32)
        h.update(self.root)
        h.update(Writer().u64(height).u32(len(deltas)).finish())
        h.update(b"".join(entries[key] for key in sorted(entries)))
        self.root = h.digest()
        self.last_height = height
        self.recent_roots.append((height, self.root))
        # ``aborted`` is an executed outcome, counted with ``applied``.
        applied = verdicts.get(APPLIED, 0) + verdicts.get(ABORTED, 0)
        rejected = sum(verdicts.values()) - applied
        self.applied_total += applied
        self.rejected_total += rejected
        self.bad_nonce_total += verdicts.get(REJECT_BAD_NONCE, 0)
        if self.metrics is not None:
            for verdict, count in verdicts.items():
                self.metrics.mysticeti_execution_txs_total.labels(
                    verdict
                ).inc(count)
            for op, count in ops.items():
                self.metrics.mysticeti_execution_ops_total.labels(
                    _OP_NAMES[op]
                ).inc(count)
            if conflicts:
                self.metrics.mysticeti_execution_conflicts_total.inc(
                    conflicts)
            self.metrics.mysticeti_execution_height.set(height)
            self.metrics.mysticeti_execution_accounts.set(
                len(self._exec_accounts)
            )
        return ExecutionResult(
            height,
            self.root,
            applied,
            rejected,
            tuple(sorted(verdicts.items())),
        )

    # -- durability ------------------------------------------------------

    def to_bytes(self) -> bytes:
        """Canonical durable encoding (checkpoints / snapshot manifests):
        the accounts a commit has touched, in the order the committed
        sequence first touched them, so two nodes on the same root encode
        byte-identically.  Without a genesis allocation that is every
        account; over one, the rest is the allocation every node loads
        itself (sorting and encoding a million entries in every checkpoint
        stalled the core for over half a second each)."""
        w = Writer()
        w.u64(self.last_height)
        w.fixed(self.root)
        with self._exec_lock:
            entries = list(self._exec_touched.values())
        w.u32(len(entries))
        w.fixed(b"".join(entries))
        w.u64(self.applied_total)
        w.u64(self.rejected_total)
        return w.finish()

    def recover(self, data: bytes) -> None:
        """Adopt a persisted state wholesale (checkpoint recovery), onto
        the freshly funded genesis allocation where there is one.  The
        entries are taken in whatever order they were written."""
        if not data:
            return
        r = Reader(data)
        last_height = r.u64()
        root = r.fixed(32)
        accounts: Dict[bytes, Tuple[int, int, int]] = {}
        for _ in range(r.u32()):
            key, entry = _read_account_entry(r)
            accounts[key] = entry
        applied_total = r.u64()
        rejected_total = r.u64()
        r.expect_done()
        if self._genesis is not None:
            self._fund_genesis()
        with self._exec_lock:
            if self._genesis is None:
                self._exec_accounts = {}
            self._exec_accounts.update(accounts)
            self._exec_touched = {
                key: _account_entry(key, *entry)
                for key, entry in accounts.items()
            }
        self.last_height = last_height
        self.root = root
        self.applied_total = applied_total
        self.rejected_total = rejected_total
        self.recent_roots.clear()
        if last_height:
            self.recent_roots.append((last_height, root))

    def adopt(self, data: bytes) -> bool:
        """Snapshot catch-up: adopt a remote execution state iff it is
        AHEAD of ours (the :meth:`.reconfig.ReconfigState.adopt_chain`
        shape — a remote at or behind our height carries nothing we need
        and is ignored).  Trust model: the manifest rode the same
        quorum-anchored snapshot the commit baseline did; the adopted root
        is cross-checked against the fleet by the chaos state-root audit
        and re-verified implicitly by every later locally-folded commit."""
        if not data:
            return False
        r = Reader(data)
        remote_height = r.u64()
        if remote_height <= self.last_height:
            return False
        self.recover(data)
        return True

    def state(self) -> dict:
        """Live introspection document (/debug/consensus)."""
        return {
            "height": self.last_height,
            "root": self.root.hex(),
            "accounts": self.account_count(),
            "applied_total": self.applied_total,
            "rejected_total": self.rejected_total,
            "recent_roots": [
                {"height": h, "root": root.hex()}
                for h, root in list(self.recent_roots)[-16:]
            ],
        }
