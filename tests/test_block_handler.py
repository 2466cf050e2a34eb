"""Block handler drain semantics (block_handler.rs SOFT_MAX regime)."""
from mysticeti_tpu import block_handler as bh
from mysticeti_tpu.committee import Committee
from mysticeti_tpu.types import Share


def _handler():
    committee = Committee.new_for_benchmarks(4)
    return bh.BenchmarkFastPathBlockHandler(committee, 0)


def test_soft_max_slices_oversize_submissions(monkeypatch):
    """A submission chunk larger than the SOFT_MAX budget is sliced, not
    admitted whole: the cap is a per-block transaction cap (block_handler.rs
    SOFT_MAX), and the generator's 100 ms chunks (tps/10 transactions) would
    otherwise blow past it on every proposal."""
    monkeypatch.setattr(bh, "MAX_PROPOSED_PER_BLOCK", 16)
    h = _handler()
    h.submit([bytes([i % 256]) * 32 for i in range(100)])
    stmts = h.handle_blocks([], require_response=True)
    shares = [s for s in stmts if isinstance(s, Share)]
    assert len(shares) == 16
    # The remainder stays queued; the next proposal budget drains more.
    h.pending_transactions = 0  # as handle_proposal does after proposing
    stmts = h.handle_blocks([], require_response=True)
    assert len([s for s in stmts if isinstance(s, Share)]) == 16


def test_soft_max_exact_budget_not_sliced(monkeypatch):
    monkeypatch.setattr(bh, "MAX_PROPOSED_PER_BLOCK", 16)
    h = _handler()
    h.submit([b"x" * 32 for _ in range(10)])
    h.submit([b"y" * 32 for _ in range(6)])
    stmts = h.handle_blocks([], require_response=True)
    assert len([s for s in stmts if isinstance(s, Share)]) == 16


def test_log_range_matches_per_locator_format(tmp_path):
    """log_range (bulk certified-log write) emits byte-identical lines to
    the per-locator log() path — consumers parse one format."""
    from mysticeti_tpu.log import TransactionLog
    from mysticeti_tpu.types import StatementBlock, TransactionLocator

    blk = StatementBlock.new_genesis(3)
    a = TransactionLog.start(str(tmp_path / "a.log"))
    for off in range(5, 9):
        a.log(TransactionLocator(blk.reference, off))
    a.flush()
    b = TransactionLog.start(str(tmp_path / "b.log"))
    b.log_range(blk.reference, 5, 9)
    b.flush()
    assert (tmp_path / "a.log").read_bytes() == (tmp_path / "b.log").read_bytes()
    # and the prefix cache stays coherent for a subsequent singular log()
    b.log(TransactionLocator(blk.reference, 9))
    b.flush()
    assert (tmp_path / "b.log").read_text().splitlines()[-1].endswith(",9")
