"""Staged verifier dispatch: pack / device / fetch overlap with bounded depth.

The verifier hot path used to pay its fixed per-dispatch cost end-to-end per
batch: one executor thread packed the batch (host numpy), pushed it to the
device, waited for the kernel, and fetched the verdict bits — all serialized,
so a backend with a high fixed dispatch cost capped the whole node at one
batch per round-trip regardless of batch size.  Streaming-verification designs (arXiv 2302.00418's committee
pipelines, the FPGA engine of arXiv 2112.02229) get their throughput from
exactly the opposite shape: the host prepares batch N+1 while the device
computes batch N and batch N-1's results ride back.

This module is the engine for that shape:

* :class:`VerifyPipeline` — a bounded in-flight window over dispatches.  The
  batching collector (``block_validator.BatchedSignatureVerifier``) may open
  a new flush window while prior dispatches are still in flight; the window
  bounds how many, so a flooding peer cannot queue unbounded device work.
  Depth adapts to the measured dispatch latency (the collector's
  ``_dispatch_ema_s``): a fast backend has little latency to hide (depth
  2), a slow one wants more overlap (up to 4).
* :class:`DeferredDispatch` / :class:`CompletedDispatch` — future-like
  handles for backends without a native async queue, so every
  ``SignatureVerifier`` presents the same submit-now/fetch-later surface
  (``verify_signatures_async``) whether the work happens on submit, on a JAX
  async dispatch, or behind a socket.

Stage accounting: ``verify_pipeline_inflight`` / ``verify_pipeline_depth``
gauges and the ``verify_pipeline_stage_seconds{stage=pack|device|fetch}``
histogram (metrics.py), plus per-block ``verify_pack`` / ``verify_device`` /
``verify_fetch`` spans (spans.py) when tracing is on.
"""
from __future__ import annotations

import asyncio
from collections import deque
from typing import Callable, List, Optional, Sequence

STAGE_PACK = "pack"
STAGE_DEVICE = "device"
STAGE_FETCH = "fetch"


class CompletedDispatch:
    """An already-resolved dispatch handle (empty batches, cached results)."""

    __slots__ = ("_out",)

    def __init__(self, out) -> None:
        self._out = out

    def result(self):
        return self._out


class DeferredDispatch:
    """Dispatch handle for a synchronous backend: the work runs at
    ``result()`` time, on the fetch stage's executor thread.  That keeps the
    pipeline semantics uniform — overlap still happens because the bounded
    window admits several fetches into distinct executor threads — without
    pretending a host backend has a device queue."""

    __slots__ = ("_fn", "_args")

    def __init__(self, fn: Callable, *args) -> None:
        self._fn = fn
        self._args = args

    def result(self):
        return self._fn(*self._args)


class VerifyPipeline:
    """Bounded in-flight dispatch window (asyncio, single-loop).

    ``slot()`` is an async context manager held from device submission
    through result fetch; at most :meth:`depth` slots are out at once and
    excess flushes queue on acquisition (backpressure toward the collector,
    and through it the per-connection receive pipelines).

    All state is mutated on the event-loop thread only (the collector
    acquires/releases from coroutines), so no lock is needed — the executor
    threads doing the actual pack/dispatch/fetch never touch it.
    """

    MIN_DEPTH = 2
    MAX_DEPTH = 4
    # Fixed-cost thresholds for the adaptive window: a µs-co-located chip
    # has nothing to hide (MIN), a ~100 ms fixed cost wants the full
    # window; in between, one intermediate step.
    MID_FIXED_COST_S = 0.005
    DEEP_FIXED_COST_S = 0.050

    def __init__(
        self,
        depth: Optional[int] = None,
        metrics=None,
        fixed_cost_fn: Optional[Callable[[], float]] = None,
    ) -> None:
        self._fixed_depth = depth
        self._fixed_cost_fn = fixed_cost_fn
        self.metrics = metrics
        self._inflight = 0
        self.max_inflight = 0  # high-water mark (tests/telemetry)
        self._waiters: deque = deque()

    # -- depth policy --

    def depth(self) -> int:
        """Current window size: fixed when configured, else adaptive from
        the measured fixed dispatch cost (2 co-located … 4 remote)."""
        if self._fixed_depth is not None:
            return max(1, self._fixed_depth)
        fixed = 0.0
        if self._fixed_cost_fn is not None:
            fixed = self._fixed_cost_fn() or 0.0
        if fixed >= self.DEEP_FIXED_COST_S:
            d = self.MAX_DEPTH
        elif fixed >= self.MID_FIXED_COST_S:
            d = (self.MIN_DEPTH + self.MAX_DEPTH) // 2
        else:
            d = self.MIN_DEPTH
        return d

    @property
    def inflight(self) -> int:
        return self._inflight

    # -- the bounded window --

    def slot(self) -> "_PipelineSlot":
        return _PipelineSlot(self)

    async def _acquire(self) -> None:
        while self._inflight >= self.depth():
            event = asyncio.Event()
            self._waiters.append(event)
            await event.wait()
        self._inflight += 1
        if self._inflight > self.max_inflight:
            self.max_inflight = self._inflight
        if self.metrics is not None:
            self.metrics.verify_pipeline_inflight.set(self._inflight)
            self.metrics.verify_pipeline_depth.set(self.depth())

    def _release(self) -> None:
        self._inflight -= 1
        if self.metrics is not None:
            self.metrics.verify_pipeline_inflight.set(self._inflight)
        # Wake every waiter; each rechecks against the (possibly adapted)
        # depth.  Waiter counts are small (bounded by flush concurrency).
        while self._waiters:
            self._waiters.popleft().set()

    # -- stage accounting --

    def note_stage(self, stage: str, seconds: float) -> None:
        """One dispatch's seconds in one stage.  The shares of dispatch time
        (device-busy / host-pack / fetch-wait) are this histogram's sums over
        their total."""
        if self.metrics is not None:
            self.metrics.verify_pipeline_stage_seconds.labels(stage).observe(
                seconds
            )


class _PipelineSlot:
    __slots__ = ("_pipeline",)

    def __init__(self, pipeline: VerifyPipeline) -> None:
        self._pipeline = pipeline

    async def __aenter__(self) -> VerifyPipeline:
        await self._pipeline._acquire()
        return self._pipeline

    async def __aexit__(self, exc_type, exc, tb) -> None:
        self._pipeline._release()


__all__ = [
    "VerifyPipeline",
    "CompletedDispatch",
    "DeferredDispatch",
    "STAGE_PACK",
    "STAGE_DEVICE",
    "STAGE_FETCH",
]
