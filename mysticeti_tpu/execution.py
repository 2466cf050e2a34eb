"""Deterministic execution plane: account/transfer state machine on commits.

The committed leader sequence is a total order every honest node derives
identically (the same property :mod:`.reconfig` anchors epoch changes on),
which makes it a replicated-state-machine log for free.  This module is the
CONSUMER half of ROADMAP item 3: a deterministic account/transfer runtime
folded over the linearized commits, whose per-commit **state root** becomes
a cross-node safety invariant and the object clients actually wait for
(execution-backed finality, the ACE-runtime shape from PAPERS.md).

* ``ExecTx`` — a typed CREATE/MINT/TRANSFER transaction that rides the
  committed sequence as an ordinary ``Share`` payload prefixed with
  ``EXEC_MAGIC``.  Non-magic payloads (benchmark counters, stamped random
  bytes, reconfig changes) are opaque no-ops — the runtime coexists with
  every existing workload.
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
  the same verdict, so duplicates and garbage cannot fork the chain.
* A payload carrying ``EXEC_MAGIC`` that fails to decode is an opaque
  no-op, exactly like :func:`.reconfig.parse_reconfig_tx` — a garbled
  transaction must not fork honest nodes on whether to error.

Concurrency: mutation is single-owner (the consensus core task calls
:meth:`ExecutionState.observe_commit`), but the ingress plane *probes*
account state from submission threads for pre-consensus admission
(bad-nonce / insufficient-balance shed before consensus pays for the tx),
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
# First bytes of a genesis allocation file (``write_genesis_allocation``).
ALLOCATION_MAGIC = b"MYSTALLOC\x01"

OP_CREATE = 0  # create account with an initial (faucet) balance; nonce must be 0
OP_MINT = 1  # balance += amount on an existing account (nonce-gated)
OP_TRANSFER = 2  # move amount to dest (auto-created at 0); nonce-gated

_OP_NAMES = {OP_CREATE: "create", OP_MINT: "mint", OP_TRANSFER: "transfer"}

# Typed apply verdicts.  The *names* are the metrics label set
# (mysticeti_execution_txs_total{result}) and the ingress shed vocabulary —
# keep them stable.
APPLIED = "applied"
REJECT_EXISTS = "account_exists"
REJECT_UNKNOWN = "unknown_account"
REJECT_BAD_NONCE = "bad_nonce"
REJECT_OVERDRAFT = "insufficient_balance"
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
# serde fields ``bytes key ‖ u64 balance ‖ u64 nonce`` in one pack.
_ACCOUNT_TAIL = struct.Struct("<QQ")
_U32 = struct.Struct("<I")


def _account_entry(key: bytes, balance: int, nonce: int) -> bytes:
    return _U32.pack(len(key)) + key + _ACCOUNT_TAIL.pack(balance, nonce)


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
        if self.op == OP_TRANSFER:
            if not self.dest or len(self.dest) > MAX_ACCOUNT_KEY_LEN:
                raise ValueError(
                    f"transfer dest must be 1..{MAX_ACCOUNT_KEY_LEN} bytes"
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


def _allocation_header(balance: int, count: int) -> bytes:
    return Writer().fixed(ALLOCATION_MAGIC).u64(balance).u32(count).finish()


def write_genesis_allocation(path: str, count: int, seed: int,
                             balance: int) -> None:
    """``count`` accounts, each funded with ``balance``: magic ‖ u64 balance
    ‖ u32 count ‖ count 32-byte keys, in index order.  A million key
    derivations take a core a minute, so large counts are spread over the
    host's cores."""
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
        f.write(_allocation_header(balance, count))
        for chunk in chunks:
            f.write(chunk)
    os.replace(path + ".tmp", path)


def read_genesis_allocation(path: str) -> Tuple[int, bytes]:
    """(balance, the keys' bytes back to back) of an allocation file."""
    with open(path, "rb") as f:
        data = f.read()
    r = Reader(data)
    if r.fixed(len(ALLOCATION_MAGIC)) != ALLOCATION_MAGIC:
        raise SerdeError(f"{path} is no genesis allocation")
    balance = r.u64()
    count = r.u32()
    keys = data[r.pos:]
    if len(keys) != SIGNER_KEY_LEN * count:
        raise SerdeError(
            f"{path} names {count} accounts and holds {len(keys)} key bytes")
    return balance, keys


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
        # account key -> (balance, nonce).  Guarded by _exec_lock (lint
        # GUARDED_FIELDS): the core task folds commits while ingress
        # submission threads probe balances for pre-consensus admission.
        self._exec_accounts: Dict[bytes, Tuple[int, int]] = {}
        # The genesis allocation as loaded, (balance, keys), if any.
        self._genesis: Optional[Tuple[int, bytes]] = None
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
        self.metrics = metrics
        # The validator's stage clock (spans.StageClock; None = not
        # clocked): ``observe_commit`` books ``exec_fold``.
        self.stages = None

    # -- queries ---------------------------------------------------------

    def probe(self, account: bytes) -> Optional[Tuple[int, int]]:
        """(balance, nonce) snapshot, or None for an unknown account.
        Advisory by design: in-flight committed transactions may move the
        account before a submission folded against this snapshot lands."""
        with self._exec_lock:
            return self._exec_accounts.get(account)

    def account_count(self) -> int:
        with self._exec_lock:
            return len(self._exec_accounts)

    def load_genesis(self, balance: int, keys: bytes) -> None:
        """Fund the genesis allocation's accounts (nonce 0) before height
        1.  The allocation enters the root chain: two validators that
        loaded different allocations disagree at the first root."""
        if self.last_height:
            raise ValueError("a genesis allocation is loaded before any "
                             "commit is folded")
        self._genesis = (balance, keys)
        # The root chain starts from the allocation file's bytes.
        h = hashlib.blake2b(GENESIS_ROOT, digest_size=32)
        h.update(_allocation_header(balance, len(keys) // SIGNER_KEY_LEN))
        h.update(keys)
        self.root = h.digest()
        self._fund_genesis()

    def _fund_genesis(self) -> None:
        balance, keys = self._genesis
        entry = (balance, 0)
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

    def admission_verdict(self, tx: ExecTx) -> Optional[str]:
        """Pre-consensus admission check for the ingress plane: a typed
        reject for transactions that are *already* doomed against current
        state, None for plausibly-valid ones.

        Deliberately weaker than :meth:`_apply`: a nonce *ahead* of the
        account (earlier transactions in flight) and a CREATE for a not-yet
        -existing account are admitted — only verdicts that cannot be cured
        by in-flight traffic (stale nonce, overdraft beyond current funds
        plus any pending mint is still a heuristic — we only shed what is
        wrong *now*) are shed before consensus pays for the transaction."""
        snapshot = self.probe(tx.account)
        if tx.op == OP_CREATE:
            return REJECT_EXISTS if snapshot is not None else None
        if snapshot is None:
            return REJECT_UNKNOWN
        balance, nonce = snapshot
        if tx.nonce < nonce:
            return REJECT_BAD_NONCE
        if tx.op == OP_TRANSFER and tx.nonce == nonce and tx.amount > balance:
            return REJECT_OVERDRAFT
        return None

    # -- the fold --------------------------------------------------------

    def _apply(self, tx: ExecTx, deltas: Dict[bytes, Tuple[int, int]]) -> str:
        """Apply one transaction against the account table (lock held by
        the caller), recording touched accounts into ``deltas``."""
        accounts = self._exec_accounts
        if tx.op == OP_CREATE:
            if tx.account in accounts:
                return REJECT_EXISTS
            if tx.nonce != 0:
                return REJECT_BAD_NONCE
            accounts[tx.account] = (tx.amount, 1)
            deltas[tx.account] = accounts[tx.account]
            return APPLIED
        entry = accounts.get(tx.account)
        if entry is None:
            return REJECT_UNKNOWN
        balance, nonce = entry
        if tx.nonce != nonce:
            return REJECT_BAD_NONCE
        if tx.op == OP_MINT:
            accounts[tx.account] = (balance + tx.amount, nonce + 1)
            deltas[tx.account] = accounts[tx.account]
            return APPLIED
        # OP_TRANSFER
        if tx.amount > balance:
            return REJECT_OVERDRAFT
        dest_balance, dest_nonce = accounts.get(tx.dest, (0, 0))
        if tx.dest == tx.account:
            # Self-transfer: balance unchanged, nonce still consumed.
            accounts[tx.account] = (balance, nonce + 1)
            deltas[tx.account] = accounts[tx.account]
            return APPLIED
        accounts[tx.account] = (balance - tx.amount, nonce + 1)
        accounts[tx.dest] = (dest_balance + tx.amount, dest_nonce)
        deltas[tx.account] = accounts[tx.account]
        deltas[tx.dest] = accounts[tx.dest]
        return APPLIED

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
        deltas: Dict[bytes, Tuple[int, int]] = {}
        with self._exec_lock:
            for block in blocks:
                for st in block.statements:
                    if not isinstance(st, Share):
                        continue
                    tx = self.transaction_of(bytes(st.transaction))
                    if tx is None:
                        continue
                    verdict = (
                        tx if tx is REJECT_UNSIGNED
                        else self._apply(tx, deltas)
                    )
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
        applied = verdicts.get(APPLIED, 0)
        rejected = sum(v for k, v in verdicts.items() if k != APPLIED)
        self.applied_total += applied
        self.rejected_total += rejected
        if self.metrics is not None:
            for verdict, count in verdicts.items():
                self.metrics.mysticeti_execution_txs_total.labels(
                    verdict
                ).inc(count)
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
        accounts: Dict[bytes, Tuple[int, int]] = {}
        for _ in range(r.u32()):
            key = bytes(r.bytes())
            accounts[key] = (r.u64(), r.u64())
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
                key: _account_entry(key, balance, nonce)
                for key, (balance, nonce) in accounts.items()
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
