"""SmallBank in the execution plane (docs/execution.md "SmallBank"): the
program's fold against the benchmark's plain reference
(``benchmark/reference/smallbank.py``) verdict by verdict and root by root,
the rules a sequenced account needs (an abort consumes its nonce), the wide
account entry through ``to_bytes`` / ``recover`` / ``adopt``, the
two-balance allocation, the pre-consensus check, and golden bytes that hold
a ``transfers10``-shaped stream to what the parent of this change gave."""
import hashlib
import random

import pytest

from benchmark.reference import smallbank as ref
from benchmark.reference import transfers as transfers_ref
from mysticeti_tpu import execution as X
from mysticeti_tpu.types import Share

SEED, ACCOUNTS, HOT = 26, 64, 4
CHECKING, SAVINGS = 3_000, 2_000
MIX = {"Amalgamate": 15, "Balance": 15, "DepositChecking": 15,
       "SendPayment": 25, "TransactSavings": 15, "WriteCheck": 15}
AMOUNTS = {"SendPayment": 500, "DepositChecking": 130,
           "TransactSavings": 2020, "WriteCheck": 500}


class Block:
    """All the fold reads of a block."""

    def __init__(self, payloads):
        self.statements = [Share(p) for p in payloads]


@pytest.fixture(scope="module")
def signers():
    return [ref.account(SEED, i) for i in range(ACCOUNTS)]


@pytest.fixture(scope="module")
def keys(signers):
    return b"".join(public for _, public in signers)


def both(keys, checking=CHECKING, savings=SAVINGS):
    state = X.ExecutionState(signed=True)
    state.load_genesis(checking, keys, savings)
    fold = ref.Fold(signed=True)
    fold.load_genesis(checking, keys, savings)
    fold.log = []
    return state, fold


def commit(state, fold, height, payloads):
    """One commit through both; the program's verdicts by name."""
    at = len(fold.log)
    result = state.observe_commit(height, [Block(payloads)])
    assert result.root == fold.commit(height, payloads)
    mine = dict(result.verdicts)
    theirs = {}
    for _, verdict in fold.log[at:]:
        theirs[verdict] = theirs.get(verdict, 0) + 1
    assert mine == theirs
    return mine


def op(signers, code, n1, nonce, amount=0, n2=None):
    return ref.make_operation(
        signers[n1], code, nonce, amount,
        signers[n2][1] if n2 is not None else b"", 200, bytes(200))


@pytest.mark.parametrize("seed", [1, 2, 3, 2_600_000_011])
def test_a_seeded_stream_folds_as_the_reference_folds_it(signers, keys, seed):
    """2,000 operations over 64 accounts, a hotspot of 4: verdict by
    verdict, root by root and balance by balance.  Every twentieth
    operation repeats its account's last nonce and every fiftieth skips
    one (a replayed and a skipped nonce fold as ``bad_nonce``)."""
    state, fold = both(keys)
    rng = random.Random(seed)
    stream = ref.schedule(seed, 2000, ACCOUNTS, HOT, 0.25, MIX, AMOUNTS)
    nonces = [0] * ACCOUNTS
    envelopes = []
    for at, (code, n1, n2, amount) in enumerate(stream):
        nonce = nonces[n1]
        if at % 20 == 19 and nonce:
            nonce -= 1
        elif at % 50 == 49:
            nonce += 1
        else:
            nonces[n1] += 1
        envelopes.append(op(signers, code, n1, nonce, amount, n2))
    seen = {}
    height = 0
    while envelopes:
        height += 1
        cut = rng.randrange(1, 120)
        mine = commit(state, fold, height, envelopes[:cut])
        for verdict, count in mine.items():
            seen[verdict] = seen.get(verdict, 0) + count
        envelopes = envelopes[cut:]
    assert set(seen) == {X.APPLIED, X.ABORTED, X.REJECT_BAD_NONCE}
    assert sum(seen.values()) == 2000
    for key, (checking, nonce, savings) in fold.accounts.items():
        assert state.balances(key) == (checking, nonce, savings)
    assert min(c for c, _, _ in fold.accounts.values()) < 0  # a penalty hit
    assert state.bad_nonce_total == seen[X.REJECT_BAD_NONCE]


def test_an_abort_consumes_its_nonce_and_an_overdraft_does_not(signers, keys):
    """A SendPayment beyond checking is ``aborted`` and the account's next
    operation applies; a TRANSFER beyond the balance is
    ``insufficient_balance``, consumes none, and the transfer signed behind
    it folds as ``bad_nonce``."""
    state, fold = both(keys, checking=100, savings=0)
    a, b = keys[:32], keys[32:64]
    got = commit(state, fold, 1, [
        op(signers, X.OP_SEND_PAYMENT, 0, 0, 500, 1),
        op(signers, X.OP_DEPOSIT_CHECKING, 0, 1, 7),
        transfers_ref.make_transfer(signers[2], 0, 500, a, 200, bytes(200)),
        transfers_ref.make_transfer(signers[2], 1, 1, a, 200, bytes(200)),
    ])
    assert got == {X.ABORTED: 1, X.APPLIED: 1, X.REJECT_OVERDRAFT: 1,
                   X.REJECT_BAD_NONCE: 1}
    assert state.balances(a) == (107, 2, 0)
    assert state.balances(b) == (100, 0, 0)
    assert state.probe(keys[64:96]) == (100, 0)
    # The aborted account entered the commit's deltas: the root is not that
    # of a commit without the abort.
    without, _ = both(keys, checking=100, savings=0)
    without.observe_commit(1, [Block([
        op(signers, X.OP_DEPOSIT_CHECKING, 0, 0, 7)])])
    assert without.root != state.root


def test_write_check_with_and_without_the_penalty(signers, keys):
    state, fold = both(keys, checking=300, savings=200)
    got = commit(state, fold, 1, [
        op(signers, X.OP_WRITE_CHECK, 0, 0, 500),  # covered: 300+200
        op(signers, X.OP_WRITE_CHECK, 0, 1, 1),  # 0 < 1: the penalty
        op(signers, X.OP_WRITE_CHECK, 1, 0, 501),  # not covered at once
    ])
    assert got == {X.APPLIED: 3}
    assert state.balances(keys[:32]) == (-202, 2, 200)
    assert state.balances(keys[32:64]) == (-202, 1, 200)


def test_amalgamate_moves_both_balances_and_an_emptied_account_moves_none(
        signers, keys):
    state, fold = both(keys, checking=300, savings=200)
    a, b = keys[:32], keys[32:64]
    commit(state, fold, 1, [op(signers, X.OP_AMALGAMATE, 0, 0, 0, 1)])
    assert state.balances(a) == (0, 1, 0)
    assert state.balances(b) == (800, 0, 200)
    # Emptied: nothing to move, the nonce still goes; then into the red,
    # and an Amalgamate of a negative checking lowers N2's.
    commit(state, fold, 2, [
        op(signers, X.OP_AMALGAMATE, 0, 1, 0, 1),
        op(signers, X.OP_WRITE_CHECK, 0, 2, 50),
        op(signers, X.OP_AMALGAMATE, 0, 3, 0, 1),
        op(signers, X.OP_BALANCE, 1, 0),
    ])
    assert state.balances(a) == (0, 4, 0)
    assert state.balances(b) == (749, 1, 200)


def test_an_unknown_n2_is_a_no_op_that_consumes_no_nonce(signers, keys):
    state, fold = both(keys)
    stranger = ref.account(SEED + 1, 0)
    got = commit(state, fold, 1, [
        ref.make_operation(signers[0], X.OP_SEND_PAYMENT, 0, 5, stranger[1],
                           200, bytes(200)),
        ref.make_operation(stranger, X.OP_BALANCE, 0, 0, b"", 200,
                           bytes(200)),
        op(signers, X.OP_TRANSACT_SAVINGS, 0, 0, 9),
    ])
    assert got == {X.REJECT_UNKNOWN: 2, X.APPLIED: 1}
    assert state.balances(keys[:32]) == (CHECKING, 1, SAVINGS + 9)
    assert state.account_count() == ACCOUNTS  # SmallBank creates none


def test_a_forged_operation_shows_as_a_differing_root(signers, keys):
    """The program's fold trusts what was verified at the gates; the
    reference verifies again, so a forged operation in the committed
    sequence gives another root."""
    state, fold = both(keys)
    forged = ref.corrupt_signature(
        random.Random(5), op(signers, X.OP_DEPOSIT_CHECKING, 0, 0, 130))
    assert state.observe_commit(1, [Block([forged])]).root != fold.commit(
        1, [forged])
    assert fold.verdicts == {ref.BAD_SIGNATURE: 1}


@pytest.mark.parametrize("how", ["recover", "adopt"])
def test_savings_and_a_negative_checking_round_trip(signers, keys, how):
    state, fold = both(keys, checking=10, savings=0)
    commit(state, fold, 1, [
        op(signers, X.OP_WRITE_CHECK, 0, 0, 500),
        op(signers, X.OP_TRANSACT_SAVINGS, 1, 0, 2020),
        op(signers, X.OP_DEPOSIT_CHECKING, 2, 0, 130),
    ])
    data = state.to_bytes()
    twin = X.ExecutionState(signed=True)
    twin.load_genesis(10, keys, 0)
    if how == "recover":
        twin.recover(data)
    else:
        assert twin.adopt(data) and not twin.adopt(data)
    assert twin.to_bytes() == data and twin.root == state.root
    assert twin.balances(keys[:32]) == (-491, 1, 0)
    assert twin.balances(keys[32:64]) == (10, 1, 2020)
    assert twin.balances(keys[64:96]) == (140, 1, 0)
    assert twin.balances(keys[96:128]) == (10, 0, 0)
    # Both go on to the same root.
    more = [op(signers, X.OP_AMALGAMATE, 1, 1, 0, 0)]
    commit(state, fold, 2, more)
    assert twin.observe_commit(2, [Block(more)]).root == state.root
    # The narrow entry is today's bytes, the wide one flags its nonce.
    assert X._account_entry(b"k", 5, 3) == X._account_entry(b"k", 5, 3, 0)
    assert len(X._account_entry(b"k", -1, 3)) == len(
        X._account_entry(b"k", 5, 3)) + 8
    assert X._account_entry(b"k", -1, 3, 9) == ref.account_entry(
        b"k", -1, 3, 9)


def test_the_two_balance_allocation_is_the_references_bytes(tmp_path, keys):
    path = str(tmp_path / "accounts.bin")
    X.write_genesis_allocation(path, ACCOUNTS, SEED, CHECKING, SAVINGS)
    with open(path, "rb") as f:
        written = f.read()
    assert written == ref.allocation_bytes(CHECKING, keys, SAVINGS)
    assert written.startswith(X.ALLOCATION_MAGIC_TWO)
    assert X.read_genesis_allocation(path) == (CHECKING, keys, SAVINGS)
    state = X.ExecutionState(signed=True)
    state.load_genesis(*X.read_genesis_allocation(path))
    assert state.root == ref.genesis_root(CHECKING, keys, SAVINGS)
    assert state.balances(keys[:32]) == (CHECKING, 0, SAVINGS)
    # Without savings it is the one-balance file, byte for byte.
    X.write_genesis_allocation(path, ACCOUNTS, SEED, CHECKING)
    with open(path, "rb") as f:
        assert f.read() == transfers_ref.allocation_bytes(CHECKING, keys)
    assert X.read_genesis_allocation(path) == (CHECKING, keys)


# What the parent of this change (8a4381e) gave for the stream below: a
# transfers10-shaped stream must give the same bytes for ever.
GOLDEN = {
    "allocation": "d0bdf8520f4fe3f3a81cb0bdbfdd57ea",
    "roots": [
        "bfc20a726fa5a005fad47f546c4c0737db79be43e9bff406875c2aef3ae7313e",
        "2db8fb8086dde614300020539208dbdfa564e10dc0ac595d57aa15eab082f044",
        "4315a3426066221cbad34204f6bc9aceb8911f7c99368260722aa71ab6c56af2",
        "895c04c776b6e681ff0281b0e6692a80aa355e41bd98bf5ea54cc8abe4dd6503",
        "f08397a33da0727dd0101718527cf5aac79aebaf058381f343d69bbc16289460",
        "24450abc66f4e3d56ee313a7f673cc5d8b265e5abb727b89a3a1f873d9a9e808",
    ],
    "checkpoint": "90b23717340f8344379b2bff3846bb31",
    "checkpoint_len": 2192,
}


def test_a_transfers10_shaped_stream_gives_the_parents_bytes(tmp_path):
    """One transfer a sender at nonce 0 over a one-balance allocation, an
    overdraft, a replay and a bare EXECTX among them: allocation, roots and
    checkpoint are the bytes the program gave before it knew SmallBank."""
    path = str(tmp_path / "alloc.bin")
    X.write_genesis_allocation(path, 48, 26, 1_000_000)
    with open(path, "rb") as f:
        alloc = f.read()
    state = X.ExecutionState(signed=True)
    state.load_genesis(*X.read_genesis_allocation(path))
    keys = alloc[len(X.ALLOCATION_MAGIC) + 12:]
    rng = random.Random(7)
    filler = rng.randbytes(512)
    t = transfers_ref
    roots, sender = [], 0
    for height in range(1, 7):
        payloads = []
        for _ in range(5):
            dest = rng.randrange(48)
            payloads.append(t.make_transfer(
                t.account(26, sender), 0, 1 + sender,
                keys[32 * dest:32 * dest + 32], 512, filler))
            sender += 1
        if height == 3:
            payloads.append(t.make_transfer(
                t.account(26, 40), 0, 2_000_000, keys[:32], 512, filler))
            payloads.append(payloads[0])
            payloads.append(t.encode_exec_tx(t.OP_MINT, keys[:32], 0, 5))
        roots.append(state.observe_commit(height, [Block(payloads)]).root.hex())
    data = state.to_bytes()
    assert {
        "allocation": hashlib.blake2b(alloc, digest_size=16).hexdigest(),
        "roots": roots,
        "checkpoint": hashlib.blake2b(data, digest_size=16).hexdigest(),
        "checkpoint_len": len(data),
    } == GOLDEN


NEW_OPS = [X.OP_BALANCE, X.OP_DEPOSIT_CHECKING, X.OP_TRANSACT_SAVINGS,
           X.OP_AMALGAMATE, X.OP_WRITE_CHECK, X.OP_SEND_PAYMENT]


@pytest.mark.parametrize("code", NEW_OPS)
@pytest.mark.parametrize("nonce,verdict,ahead", [
    (1, X.REJECT_BAD_NONCE, False), (2, None, False), (5, None, True)])
def test_admission_sheds_a_nonce_behind_and_admits_one_ahead(
        keys, code, nonce, verdict, ahead):
    """Behind / equal / ahead of the account's nonce (2), whatever the
    funds: want of funds is no reason to shed a SmallBank operation."""
    state = X.ExecutionState(signed=True)
    state.load_genesis(0, keys, 0)
    a, b = keys[:32], keys[32:64]
    state.observe_commit(1, [Block([
        X.ExecTx(X.OP_BALANCE, a, n).to_bytes() for n in (0, 1)])])
    state.signed = False
    state.observe_commit(2, [Block([
        X.ExecTx(X.OP_BALANCE, a, n).to_bytes() for n in (0, 1)])])
    assert state.probe(a) == (0, 2)
    dest = b if code in (X.OP_AMALGAMATE, X.OP_SEND_PAYMENT) else b""
    tx = X.ExecTx(code, a, nonce, 10**9, dest)
    assert state.admission(tx) == (verdict, ahead)
    assert state.admission_verdict(tx) == verdict
    if dest:
        stranger = X.ExecTx(code, a, nonce, 1, b"\x07" * 32)
        assert state.admission_verdict(stranger) == (
            verdict or X.REJECT_UNKNOWN)
    assert state.admission_verdict(
        X.ExecTx(code, b"\x07" * 32, 0, 1, dest)) == X.REJECT_UNKNOWN


def test_a_transfer_still_sheds_its_overdraft_at_the_current_nonce(keys):
    state = X.ExecutionState()
    state.load_genesis(5, keys, 0)
    a, b = keys[:32], keys[32:64]
    assert state.admission(X.ExecTx(X.OP_TRANSFER, a, 0, 6, b)) == (
        X.REJECT_OVERDRAFT, False)
    assert state.admission(X.ExecTx(X.OP_TRANSFER, a, 1, 6, b)) == (
        None, True)
    assert state.admission(X.ExecTx(X.OP_TRANSFER, a, 0, 5, b)) == (
        None, False)


def test_the_fold_counts_operations_and_conflicts_in_a_commit(signers, keys):
    """``ops_total{op}`` by operation, and ``conflicts_total``: a
    transaction whose signer or counterparty was written earlier in the
    same commit."""
    from mysticeti_tpu.metrics import Metrics

    metrics = Metrics()
    state = X.ExecutionState(metrics=metrics, signed=True)
    state.load_genesis(CHECKING, keys, SAVINGS)
    state.observe_commit(1, [Block([
        op(signers, X.OP_DEPOSIT_CHECKING, 0, 0, 1),
        op(signers, X.OP_BALANCE, 0, 1),  # signer written above
        op(signers, X.OP_SEND_PAYMENT, 1, 0, 5, 0),  # counterparty written
        op(signers, X.OP_BALANCE, 2, 0),
        op(signers, X.OP_BALANCE, 3, 5),  # bad nonce: writes nothing
        op(signers, X.OP_BALANCE, 3, 0),
    ])])
    ops = metrics.mysticeti_execution_ops_total
    assert ops.labels("balance")._value.get() == 4
    assert ops.labels("deposit_checking")._value.get() == 1
    assert ops.labels("send_payment")._value.get() == 1
    assert metrics.mysticeti_execution_conflicts_total._value.get() == 2
    results = metrics.mysticeti_execution_txs_total
    assert results.labels("applied")._value.get() == 5
    assert results.labels("bad_nonce")._value.get() == 1
    # A commit on its own starts with nothing written.
    state.observe_commit(2, [Block([op(signers, X.OP_BALANCE, 0, 2)])])
    assert metrics.mysticeti_execution_conflicts_total._value.get() == 2
