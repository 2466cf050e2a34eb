"""Pluggable block verification seam — where the TPU batch verifier plugs in.

Capability parity with ``mysticeti-core/src/block_validator.rs`` (the trait the
reference explicitly leaves as the application-level verification hook, :10-14)
plus the piece the reference lacks and this framework exists for: a **batching
collector** that accumulates blocks arriving across connections within a small
window and verifies their signatures as one TPU dispatch, instead of the
reference's serial per-connection ``block.verify()`` (net_sync.rs:352-372).

Split of responsibilities on the receive path:
  * consensus-rule checks (digest, epoch, author, includes, threshold clock) —
    host, cheap, per-block: ``StatementBlock.verify_structure``
  * Ed25519 signature — batched: ``BatchedSignatureVerifier`` (TPU) or
    ``CpuSignatureVerifier`` (oracle/fallback)
"""
from __future__ import annotations

import asyncio
import contextlib
import random
import threading
import time
from itertools import islice
from typing import List, Optional, Sequence, Tuple

from . import spans
from .committee import Committee
from .execution import SIGNED_MAGIC, parse_signed_tx
from .network import jittered_backoff
from .tracing import logger
from .runtime import is_simulated
from .types import Share, StatementBlock, VerificationError
from .utils.tasks import spawn_logged
from .verify_pipeline import (
    STAGE_DEVICE,
    STAGE_FETCH,
    STAGE_PACK,
    CompletedDispatch,
    DeferredDispatch,
    VerifyPipeline,
)

log = logger(__name__)


class VerifierProtocolError(ConnectionError):
    """A verifier backend answered but REJECTED the request (committee
    mismatch, malformed frame).  Retrying cannot help and the circuit
    breaker must NOT treat it as an outage: a misconfigured validator fails
    fast instead of silently serving on the CPU oracle forever.  Defined
    here (not in verifier_service.py) so the breaker can exclude it without
    a circular import; the service module re-exports it."""


class BlockVerifier:
    """Application-content verification hook (block_validator.rs:10-14)."""

    async def verify(self, block: StatementBlock) -> None:
        """Raise VerificationError to reject."""
        raise NotImplementedError

    async def verify_blocks(self, blocks: Sequence[StatementBlock]) -> List[bool]:
        """Batch entry; default falls back to per-block verify."""
        out = []
        for b in blocks:
            try:
                await self.verify(b)
                out.append(True)
            except VerificationError:
                out.append(False)
        return out

    def note_committee(self, committee: "Committee") -> None:
        """Epoch reconfiguration hook (reconfig.py): the committee's stake
        table changed at a boundary commit.  Registry KEYS are stable
        (stable-index membership), so signature tables need no rebuild —
        only stake-weighted math (quorum endorsement) must follow the new
        committee.  Default: nothing stake-weighted here."""
        return None


class AcceptAllBlockVerifier(BlockVerifier):
    """block_validator.rs:18-27."""

    async def verify(self, block: StatementBlock) -> None:
        return None


class SignatureVerifier:
    """Synchronous batch signature check: (pubkeys, digests, signatures) -> bools."""

    def verify_signatures(
        self,
        public_keys: Sequence[bytes],
        digests: Sequence[bytes],
        signatures: Sequence[bytes],
    ) -> List[bool]:
        raise NotImplementedError

    def verify_signatures_async(self, public_keys, digests, signatures):
        """Staged-dispatch seam: submit without blocking, returning a handle
        whose ``result()`` blocks until the verdicts are ready.  Backends
        with a real async queue (JAX dispatch, the verifier-service socket)
        override this so the device computes while the host packs the next
        batch; the default defers the synchronous path to ``result()`` —
        host backends have no device queue to exploit, and the pipeline's
        fetch stage runs them on concurrent executor threads anyway."""
        return DeferredDispatch(
            self.verify_signatures, public_keys, digests, signatures
        )

    def warmup(self) -> None:
        """Optional: pay one-time costs (tracing, compilation) before the
        first real batch arrives.  Called from a background thread at node
        boot; default no-op."""

    def resolved_backend(self) -> str:
        """The platform this verifier's dispatches ACTUALLY land on.  Host
        oracles are "cpu"; accelerator backends override with the live
        runtime's answer so the verifier service can advertise it over
        HELLO_OK (a launcher refuses a fleet whose service has no chip
        behind it)."""
        return "cpu"

    def padded_batch(self, n: int) -> int:
        """Device lanes an ``n``-signature dispatch actually occupies; the
        host paths pay no padding.  Telemetry only (padding waste =
        ``padded_batch(n) - n``)."""
        return n


class CpuSignatureVerifier(SignatureVerifier):
    """The CPU oracle path (cryptography/OpenSSL) — reference behavior
    (crypto.rs:174-189), also the correctness baseline for the TPU kernel."""

    def verify_signatures(self, public_keys, digests, signatures):
        from . import crypto

        out = []
        for pk, digest, sig in zip(public_keys, digests, signatures):
            out.append(crypto.PublicKey(pk).verify(sig, digest))
        return out


class TpuSignatureVerifier(SignatureVerifier):
    """The JAX kernel (ops/ed25519.py) — fused raw-bytes path.

    ``mesh="auto"`` shards the batch over all local devices via ``shard_map``
    (parallel/mesh.py) when more than one is attached; a single chip (or CPU)
    dispatches the plain bucketed kernel.  Pass a device count (how a
    deployment maps the service onto one chip or the whole host), an
    explicit ``jax.sharding.Mesh``, or ``None`` to override.
    """

    def __init__(self, mesh="auto", committee_keys=None) -> None:
        self._mesh = mesh
        self.kernel_report: list = []  # filled by warmup()
        self.warm_parts: dict = {}  # so is this: warmup's seconds by part
        # Known signer set -> device-resident key table: the pk rides as an
        # index (26 words/sig on the wire instead of 33), uploaded once.
        self._table = None
        if committee_keys:
            from .ops.ed25519 import KeyTable

            self._table = KeyTable(list(committee_keys))

    def _resolve_mesh(self):
        if self._mesh == "auto" or isinstance(self._mesh, int):
            import jax

            from .parallel.mesh import make_mesh

            attached = len(jax.devices())
            n = attached if self._mesh == "auto" else self._mesh
            if not 1 <= n <= attached:
                raise ValueError(
                    f"verifier asked for {n} device(s), {attached} attached"
                )
            # Clamp to the largest power-of-two prefix: the fused bucket
            # shapes (256/1024/4096) shard evenly only over power-of-two
            # meshes, and TPU slices are power-of-two sized anyway.
            pow2 = 1 << (n.bit_length() - 1)
            self._mesh = make_mesh(pow2) if pow2 > 1 else None
        return self._mesh

    def warmup(self, every_shape: bool = False) -> None:
        """Make runnable the kernels a batch of block signatures reaches, so
        the first real batch does not stall behind a trace or a compile.
        Each kernel's lowered program comes from the program store beside
        the compilation cache (``ops.programs``) and its executable from
        that cache, so a warm boot traces nothing and compiles nothing: on
        a v5e about a second a kernel, where the first boot on an empty
        cache directory pays 7-40 s a kernel-bucket pair to trace, lower,
        compile and write both (numbers in ``PERF.md``).  By default the
        smallest bucket only: a collector window
        (``BatchedSignatureVerifier.max_batch``) holds at most 256
        signatures, so a booting service warms what its clients can send
        and the wider buckets load or compile on first use.
        ``every_shape`` is the compile proof ``chip_smoke.py`` takes: every
        bucket, and the host-hashed ``packed`` kernel that only non-digest
        messages reach (the service's wire format carries none; it and the
        mesh kernels are outside the store and trace in every process).
        Each kernel is timed alone on an all-rejected batch and the outcome
        kept in ``kernel_report`` — ``program`` says whether the store held
        it (``loaded``), it was traced and written (``traced``), or a file
        that could not be read back was replaced
        (``reloaded-after-error``); ``cache`` says the same of XLA's
        executable — and the boot's seconds by part in ``warm_parts``; a
        kernel the device refuses to compile raises here."""
        import numpy as np

        from .ops import ed25519 as E
        from .ops import programs

        E.install_compile_listeners()
        mesh = self._resolve_mesh()
        backend = E._backend()
        if backend == "pallas":
            from .ops import ed25519_pallas as PK

            interpret, tile = PK.interpret_mode(), PK.default_tile()
        else:
            interpret, tile = None, None
        report = []
        parts = {}
        if backend == "pallas" and mesh is None and self._table is not None:
            # The keyed kernel's per-key combs, built with Python ints:
            # apart from the kernel that first asks for them.
            started = time.monotonic()
            self._table.neg_combs()
            parts["neg_combs_s"] = round(time.monotonic() - started, 3)
        probes = [
            (bucket, name, lanes, probe)
            for bucket in (E.BUCKETS if every_shape else E.BUCKETS[:1])
            for name, lanes, probe in self._kernel_probes(
                mesh, bucket, backend, packed=every_shape
            )
        ]

        def timed(call):
            """``call``'s result, its seconds, and COMPILE_STATS' growth."""
            before = dict(E.COMPILE_STATS)
            started = time.monotonic()
            out = call()
            if out is not None:
                np.asarray(out)  # blocks until the kernel has run
            return out, time.monotonic() - started, {
                k: v - before[k] for k, v in E.COMPILE_STATS.items()
            }

        # Every stored kernel's program first, then the compiles and the
        # launches (``programs.preparing`` says why).
        made = {}
        for i, (_, name, _, probe) in enumerate(probes):
            if name in self._STORED_KERNELS:
                with programs.preparing():
                    made[i] = timed(probe)[1:]
        for i, (bucket, name, lanes, probe) in enumerate(probes):
            out, seconds, grew = timed(probe)
            if i in made:
                seconds += made[i][0]
                grew = {k: v + made[i][1][k] for k, v in grew.items()}
            hits, misses = grew["cache_hits"], grew["cache_misses"]
            entry = {
                "kernel": name,
                "bucket": bucket,
                "lanes": lanes,
                "backend": backend,
                "interpret": interpret,
                "tile": tile,
                "seconds": round(seconds, 3),
                "backend_compile_s": round(grew["backend_compile_s"], 3),
                "cache": (
                    "miss" if misses else "hit" if hits else "in-process"
                ),
                "program": (
                    "reloaded-after-error" if grew["programs_rejected"]
                    else "loaded" if grew["programs_loaded"]
                    else "traced"
                    if grew["programs_written"] or hits or misses
                    else "in-process"
                ),
            }
            if name.startswith("mesh-"):
                entry["shard_devices"] = sorted(
                    d.id for d in out.sharding.device_set
                )
            report.append(entry)
            parts[f"{name}_{bucket}_s"] = entry["seconds"]
        self.kernel_report = report
        self.warm_parts = parts

    # The kernels whose programs the store keeps (``ops.programs``); the
    # mesh's and the host-hashed one trace in every process.
    _STORED_KERNELS = ("blob", "indexed", "keyed")

    def _kernel_probes(self, mesh, bucket: int, backend: str, packed: bool):
        """(name, lanes, thunk) per kernel this verifier's dispatches reach
        at ``bucket``: zero blobs carry host_ok=0 in every lane, so each
        probe runs the whole kernel and rejects everything."""
        import numpy as np

        from .ops import ed25519 as E

        table = self._table
        if packed:
            # Non-digest messages: the single-device ladder, on a mesh too.
            arrays = [E._pad_to(x, bucket) for x in E.pack_batch((), (), ())]
            yield "packed", bucket, lambda: E._dispatch_packed(*arrays)
        if mesh is not None:
            from .parallel import mesh as M

            lanes = M.mesh_lanes(mesh, bucket)
            raw = np.zeros((lanes, 33), np.uint32)
            yield "mesh-fused", lanes, lambda: M._cached_fused_kernel(mesh)(
                raw[:, :24], raw[:, 24:32], np.zeros(lanes, bool)
            )[0]
            if table is not None:
                indexed = np.zeros((lanes, 26), np.uint32)
                yield "mesh-indexed", lanes, (
                    lambda: M._cached_indexed_kernel(mesh)(
                        indexed, table.words
                    )[0]
                )
            return
        raw = np.zeros((bucket, 33), np.uint32)
        indexed = np.zeros((bucket, 26), np.uint32)
        yield "blob", bucket, lambda: E._dispatch_blob(raw)
        if table is not None:
            yield "indexed", bucket, (
                lambda: E._dispatch_indexed(indexed, table.words)
            )
            if backend == "pallas":
                yield "keyed", bucket, lambda: E._dispatch_indexed_keyed(
                    indexed, table, bucket
                )[0]

    def resolved_backend(self) -> str:
        """The live JAX platform ("cpu" when the runtime is on the host) —
        what HELLO_OK advertises when this backend sits behind the verifier
        service."""
        import jax

        return str(jax.default_backend())

    def device_report(self) -> dict:
        """What this process's JAX runtime is and which kernels it warmed —
        written by the verifier service next to its socket so a launcher
        can show the device as seen INSIDE the one process that holds it."""
        import jax

        from .ops import compilation_cache_dir
        from .ops import ed25519 as E
        from .ops.programs import installed_version

        devices = jax.devices()
        mesh = self._resolve_mesh()
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "path": (
                f"shard_map over {mesh.devices.size} devices"
                if mesh is not None
                else "single device"
            ),
            "jax": jax.__version__,
            "jaxlib": installed_version("jaxlib"),
            "libtpu": installed_version("libtpu"),
            "compilation_cache_dir": compilation_cache_dir(),
            "kernels": list(self.kernel_report),
            "compile_stats": dict(E.COMPILE_STATS),
            "dispatches": E.dispatch_counts(),
        }

    def warmed_batch(self) -> int:
        """The most signatures one call can hold without reaching a bucket
        that ``warmup`` did not compile (the smallest bucket, unless it
        warmed every shape): what the verifier service holds a launch of
        several validators' requests to."""
        from .ops.ed25519 import BUCKETS

        return max(
            (entry["bucket"] for entry in self.kernel_report),
            default=BUCKETS[0],
        )

    def padded_batch(self, n: int) -> int:
        """Lanes dispatched for n signatures under the kernel's fixed bucket
        shapes (``ops.ed25519.iter_buckets`` is the single source of truth,
        ``parallel.mesh.mesh_lanes`` what a mesh makes of each; imported
        lazily — by the time padding is worth reporting a dispatch has
        already paid the jax import)."""
        from .ops.ed25519 import iter_buckets

        mesh = self._resolve_mesh()
        if mesh is None:
            return sum(bucket for _, _, bucket in iter_buckets(n))
        from .parallel.mesh import mesh_lanes

        return sum(mesh_lanes(mesh, bucket) for _, _, bucket in iter_buckets(n))

    def indexed_keys(self, index):
        """The key column of signatures whose signers are the rows ``index``
        ((n,) unsigned, a VERIFY frame's own) of this verifier's committee
        table, for ``verify_signatures`` / ``verify_signatures_async``: the
        index goes into the launch as it is, and an index the table does
        not hold is a rejected lane (``ops.ed25519.IndexedKeys``).  None
        without a committee: the caller passes the keys themselves."""
        return None if self._table is None else self._table.keys_at(index)

    def road_counts(self):
        """(launches whose keys came as ``indexed_keys``, chunks that went
        into the keyed-tile grouping) so far: ``KeyTable.road_counts``."""
        return (0, 0) if self._table is None else self._table.road_counts()

    def verify_signatures_async(self, public_keys, digests, signatures):
        """True async dispatch: pack on the calling (host) thread, submit
        every bucket chunk through JAX's async dispatch, return the device
        handle.  ``result()`` pays the single combined fetch — so large
        catch-up batches stream bucket-sized sub-dispatches through the
        device while the caller packs the next batch."""
        from .ops import ed25519

        mesh = self._resolve_mesh()
        if mesh is not None and isinstance(public_keys, ed25519.IndexedKeys):
            public_keys = public_keys.rows()  # the mesh path searches them
        # The fused sharded kernel requires 32-byte messages (block digests);
        # other lengths fall back to the single-device host-hash path so the
        # result never depends on the device count.
        if mesh is not None and ed25519._all_digests(digests):
            if self._table is not None:
                from .parallel.mesh import dispatch_sharded_indexed

                return dispatch_sharded_indexed(
                    mesh, self._table, public_keys, digests, signatures
                )
            from .parallel.mesh import dispatch_sharded_fused

            return dispatch_sharded_fused(
                mesh, public_keys, digests, signatures
            )
        if self._table is not None:
            return ed25519.dispatch_batch_table(
                self._table, public_keys, digests, signatures
            )
        return ed25519.dispatch_batch(public_keys, digests, signatures)

    def verify_signatures(self, public_keys, digests, signatures):
        """The three columns are sequences of bytes objects or (n, width)
        uint8 arrays (the verifier service's: ``ops.ed25519`` packs either
        form into the same blob).  The verdicts come back in the form the
        signatures came in: a list of Python bools for a sequence, the
        fetched (n,) bool array for rows of an array — the service sends
        its bytes as they are, and nothing walks them."""
        oks = self.verify_signatures_async(
            public_keys, digests, signatures
        ).result()
        return oks if getattr(signatures, "ndim", 0) == 2 else oks.tolist()


def _update_ema(current: float, sample: float, outlier_s: float) -> float:
    """EMA with outlier rejection for the batching collector's window:
    samples past ``outlier_s`` (one-time JAX compiles) never enter; the
    first sample seeds."""
    if sample >= outlier_s:
        return current
    return sample if current == 0.0 else 0.8 * current + 0.2 * sample


class FallbackSignatureVerifier(SignatureVerifier):
    """The accelerator backend behind a circuit breaker (the ``tpu``
    flavor): every batch goes to ``tpu``; the only thing that sends one to
    the ``cpu`` oracle instead is an open breaker.

    A dead backend (verifier service restart, link outage) degrades the node
    to the oracle instead of crashing the dispatch thread, and the batch
    that met the failure — at submit or at fetch — is answered by the
    oracle, so no future is lost.  While open, one dispatch at a time is
    admitted as a probe once the backoff deadline passes; the backoff
    doubles 1 -> 30 s and is jittered so a fleet that lost ONE shared
    service never re-probes it in lockstep.  Only transport/timeout
    failures trip it: a :class:`VerifierProtocolError` is a misconfiguration
    and propagates.  ``tpu-only`` is the same backend without this class:
    failures surface, which is what a measurement wants.
    """

    BREAKER_EXCEPTIONS = (ConnectionError, TimeoutError, OSError)
    BREAKER_BASE_BACKOFF_S = 1.0
    BREAKER_MAX_BACKOFF_S = 30.0

    def __init__(
        self,
        tpu: Optional[SignatureVerifier] = None,
        cpu: Optional[SignatureVerifier] = None,
        metrics=None,
    ) -> None:
        self.tpu = tpu or TpuSignatureVerifier()
        self.cpu = cpu or CpuSignatureVerifier()
        self.metrics = metrics
        # Breaker state is tripped/probed/closed from concurrent executor
        # threads.  backoff == 0.0 means closed; while open, dispatches fall
        # back to the oracle until the probe deadline passes.
        # _breaker_probing keeps the probe EXCLUSIVE even when it outlives
        # the backoff interval (a hung service blocks the probe thread for
        # the whole dispatch timeout; new windows must not admit more
        # victims).
        self._breaker_lock = threading.Lock()
        self._breaker_backoff_s = 0.0
        self._breaker_open_until = 0.0
        self._breaker_probing = False
        # Trip generation: with several dispatches in flight, a PRE-outage
        # success can surface at fetch AFTER a newer failure tripped the
        # circuit — it must not re-close it (see _FallbackDispatch.result).
        self._breaker_gen = 0
        self._breaker_rng = random.Random(0x0B7EA6E5)
        self._breaker_clock = time.monotonic  # injectable for tests
        # Label of the dispatch that ran in THIS thread: the batching
        # collector reads it right after result(), in the same executor
        # thread, so thread-local storage is exactly the lifetime needed — a
        # concurrent flush that ended on the other backend cannot overwrite
        # it (it writes its own thread's slot).
        self._tls = threading.local()

    @property
    def backend_label(self) -> str:
        return getattr(self._tls, "label", "hybrid")

    @property
    def dispatch_padded(self) -> Optional[int]:
        """Padded lane count of the dispatch that ran in THIS thread (same
        thread-local lifetime as ``backend_label``): the accelerator's
        bucket, or n where the oracle answered."""
        return getattr(self._tls, "padded", None)

    @property
    def breaker_open(self) -> bool:
        return self._breaker_backoff_s > 0.0

    def _is_outage(self, exc: BaseException) -> bool:
        """Transport/timeout failures are outages and trip the breaker; a
        :class:`VerifierProtocolError` (though a ``ConnectionError``) is a
        misconfiguration and propagates, like anything else."""
        return isinstance(exc, self.BREAKER_EXCEPTIONS) and not isinstance(
            exc, VerifierProtocolError
        )

    def _admit_accelerator(self) -> Tuple[bool, bool]:
        """(blocked, is_probe).  Blocked while the breaker holds the route
        closed.  Once the probe deadline passes, exactly ONE dispatch gets
        through as the probe — the ``_breaker_probing`` flag (not a pushed
        deadline) keeps it exclusive even when the probe outlives the
        backoff interval.  ``is_probe`` tells the admitted dispatch it OWNS
        that flag: only the owner may release it on a non-verdict exit
        (abandon, propagating non-breaker exception) — an unconditional
        clear could release a DIFFERENT in-flight probe's exclusivity."""
        with self._breaker_lock:
            if self._breaker_backoff_s == 0.0:
                return False, False
            now = self._breaker_clock()
            if self._breaker_probing or now < self._breaker_open_until:
                return True, False
            self._breaker_probing = True
            return False, True

    def _trip_breaker(self, exc: BaseException,
                      owns_probe: bool = False) -> None:
        """Open (or widen) the circuit.  ``owns_probe`` mirrors the
        ``is_probe`` admission flag: only the dispatch that OWNS the
        exclusive probe slot may release it on failure — a pre-outage
        straggler failing at fetch while a probe hangs must not readmit
        victims behind the hung probe's back."""
        now = self._breaker_clock()
        with self._breaker_lock:
            self._breaker_gen += 1
            if owns_probe:
                self._breaker_probing = False
            prev = self._breaker_backoff_s
            backoff = (
                self.BREAKER_BASE_BACKOFF_S
                if prev == 0.0
                else min(prev * 2.0, self.BREAKER_MAX_BACKOFF_S)
            )
            self._breaker_backoff_s = backoff
            self._breaker_open_until = now + jittered_backoff(
                backoff, self._breaker_rng
            )
        log.warning(
            "accelerator verify path failed (%r): circuit open, degrading to "
            "the CPU oracle; next probe in ~%.1f s", exc, backoff,
        )

    def _close_breaker(self, expected_gen: int) -> bool:
        """Close the circuit, but only while the breaker generation still
        matches — compared under the lock, so a success surfacing at fetch
        can never erase a trip that raced it between the caller's generation
        read and the close."""
        with self._breaker_lock:
            if expected_gen != self._breaker_gen:
                return False
            was_open = self._breaker_backoff_s > 0.0
            self._breaker_backoff_s = 0.0
            self._breaker_probing = False
        if was_open:
            log.info("accelerator verify path recovered: circuit closed")
        return True

    def _clear_probe(self) -> None:
        """Release probe exclusivity when the dispatch neither succeeded nor
        counted as an outage (a propagating non-breaker exception) — a stuck
        flag would otherwise hold the breaker open forever."""
        with self._breaker_lock:
            self._breaker_probing = False

    def warmup(self) -> None:
        """Warm the accelerator backend (trace/compile, or HELLO and wait).
        An unreachable backend (service not yet up, link down) must not kill
        the warmup thread: trip the breaker and boot on the oracle."""
        try:
            self.tpu.warmup()
        except self.BREAKER_EXCEPTIONS as exc:
            if not self._is_outage(exc):
                raise
            self._trip_breaker(exc)

    def verify_signatures_async(self, public_keys, digests, signatures):
        """Submit through the backend's own async queue (JAX dispatch, the
        service socket) and return an in-flight handle; a breaker failure AT
        FETCH degrades that one batch to the oracle inside ``result()``.  A
        batch the open breaker blocks, or whose submit fails, defers the
        oracle to the fetch stage."""
        if not len(signatures):
            return CompletedDispatch([])
        blocked, is_probe = self._admit_accelerator()
        if not blocked:
            # Captured BEFORE the submit: a trip racing the submission means
            # this dispatch's eventual success is ambiguous evidence and
            # must not close the circuit.
            gen = self._breaker_gen
            try:
                handle = self.tpu.verify_signatures_async(
                    public_keys, digests, signatures
                )
            except BaseException as exc:
                if not self._is_outage(exc):
                    if is_probe:
                        self._clear_probe()
                    raise
                self._trip_breaker(exc, owns_probe=is_probe)
            else:
                return _FallbackDispatch(
                    self, handle, public_keys, digests, signatures,
                    is_probe, gen,
                )
        if self.metrics is not None:
            self.metrics.verifier_fallback_total.inc()
        return DeferredDispatch(
            self._verify_oracle, public_keys, digests, signatures
        )

    def verify_signatures(self, public_keys, digests, signatures):
        """One breaker implementation for both call shapes: the sync path is
        the async path fetched immediately (submit-time handling in
        ``verify_signatures_async``, fetch-time in
        ``_FallbackDispatch.result`` — keeping a second copy in lockstep is
        how probe-ownership bugs breed)."""
        return self.verify_signatures_async(
            public_keys, digests, signatures
        ).result()

    def _verify_oracle(self, public_keys, digests, signatures):
        out = self.cpu.verify_signatures(public_keys, digests, signatures)
        self._tls.label = "hybrid-cpu"
        self._tls.padded = len(signatures)  # host oracle: no padding lanes
        return out


class _FallbackDispatch:
    """An in-flight accelerator batch of :class:`FallbackSignatureVerifier`.

    ``result()`` runs on the fetch stage's executor thread, so the breaker
    bookkeeping and the thread-local backend label land where the collector
    reads them right after ``result()`` in the same thread.  A
    transport/timeout failure surfacing at fetch trips the breaker and
    verifies THIS batch on the oracle: a backend dying mid-pipeline loses
    zero futures."""

    __slots__ = ("_owner", "_handle", "_args", "_padded", "_is_probe", "_gen")

    def __init__(self, owner, handle, public_keys, digests, signatures,
                 is_probe: bool, gen: int) -> None:
        self._owner = owner
        self._handle = handle
        self._args = (public_keys, digests, signatures)
        self._padded = owner.tpu.padded_batch(len(signatures))
        self._is_probe = is_probe
        self._gen = gen

    def result(self) -> List[bool]:
        owner = self._owner
        try:
            out = self._handle.result()
        except BaseException as exc:
            if not owner._is_outage(exc):
                if self._is_probe:
                    owner._clear_probe()
                raise
            owner._trip_breaker(exc, owns_probe=self._is_probe)
            if owner.metrics is not None:
                owner.metrics.verifier_fallback_total.inc()
            return owner._verify_oracle(*self._args)
        if not owner._close_breaker(self._gen) and self._is_probe:
            # A newer trip owns the circuit: this probe's success is stale
            # evidence — its only remaining obligation is releasing the
            # exclusive probe slot it still holds.
            owner._clear_probe()
        owner._tls.label = "hybrid-tpu"
        owner._tls.padded = self._padded
        return list(out)

    def abandon(self) -> None:
        """Release per-dispatch state without fetching (the flush was
        cancelled): if THIS dispatch owns the breaker's exclusive probe
        flag it must not stay stuck — only ``result()`` would otherwise
        clear it — and the inner handle may hold its own releasable state.
        A non-probe dispatch touches nothing (clearing unconditionally
        could release a concurrent probe's exclusivity)."""
        if self._is_probe:
            self._owner._clear_probe()
        inner = getattr(self._handle, "abandon", None)
        if inner is not None:
            inner()


async def aggregate_verify(
    blocks: Sequence[StatementBlock],
    committee: Committee,
    direct_verify,
    count=None,
    prior_endorsers=None,
    defer_unresolved: bool = False,
) -> List[Optional[bool]]:
    """The threshold-aggregate acceptance rule over one batch of blocks
    (shared by the frame-level ``ThresholdAggregateVerifier`` and the
    collector-level aggregate mode of ``BatchedSignatureVerifier``).

    ``direct_verify(sub_blocks) -> List[bool]`` is the inner signature check
    (awaitable); ``count(aggregated, direct)`` is an optional accounting
    callback.  ``prior_endorsers(ref) -> set[AuthorityIndex]`` optionally
    supplies authors of PREVIOUSLY ACCEPTED blocks that include ``ref``
    (every accepted block was itself signature-verified or quorum-endorsed,
    so its endorsement carries inductively) — this is what makes the rule
    bite during catch-up, where peers' own-block streams run at different
    round offsets and a block's verified children usually arrived earlier
    via a faster stream.  See ``ThresholdAggregateVerifier`` and
    ``docs/aggregate-verification.md`` for the safety argument: acceptance
    chains are well-founded and terminate at directly verified signatures.

    Dispatch shape (the round-4 tpu-agg lesson): one
    frontier dispatch, then the descending-round cascade accepts interiors
    off those results with NO further dispatch.  Blocks whose endorsement
    fell short once non-accepted endorsers were excluded ("unresolved"):

    * ``defer_unresolved=False`` (frame-level wrapper): a second direct
      dispatch resolves them here.  Correct, but SERIALIZED behind the
      frontier dispatch — on a remote accelerator (~100 ms/round-trip) the
      second trip halves flush cadence exactly where aggregation was meant
      to help.
    * ``defer_unresolved=True`` (the batching collector's deployed mode):
      their slots return ``None`` and the collector folds them into the
      NEXT flush window, where they are either endorsed by newly arrived
      children or dispatched as ordinary frontier — every flush pays
      exactly one round-trip, same as the plain verifier.  The collector
      force-dispatches a block on its SECOND deferral: otherwise a
      Byzantine author could park a forged block in "maybe" forever by
      minting fresh structure-valid endorsers each window (liveness, not
      safety — acceptance still requires a quorum of ACCEPTED endorsers).
    """
    n = len(blocks)
    if count is None:
        count = lambda aggregated, direct: None  # noqa: E731
    if n == 0:
        return []
    if n == 1 and prior_endorsers is None:
        count(0, n)
        return list(await direct_verify(list(blocks)))
    index_of = {b.reference: i for i, b in enumerate(blocks)}
    # endorsers[i] = indexes of in-batch blocks that include block i.
    endorsers: List[List[int]] = [[] for _ in range(n)]
    for j, b in enumerate(blocks):
        for ref in b.includes:
            i = index_of.get(ref)
            if i is not None:
                endorsers[i].append(j)

    quorum = committee.quorum_threshold()

    def endorsement_stake(i, accepted_flags) -> int:
        seen = (
            set(prior_endorsers(blocks[i].reference))
            if prior_endorsers is not None
            else set()
        )
        stake = sum(committee.get_stake(a) for a in seen)
        for j in endorsers[i]:
            if accepted_flags[j] is not True:
                continue
            author = blocks[j].author()
            if author in seen:
                continue
            seen.add(author)
            stake += committee.get_stake(author)
        return stake

    # Frontier = blocks that cannot possibly reach quorum endorsement
    # even if every endorser were accepted.
    maybe: List[Optional[bool]] = [None] * n
    all_true = [True] * n
    frontier = [i for i in range(n) if endorsement_stake(i, all_true) < quorum]
    frontier_set = set(frontier)
    # Descending claimed-round order: honest endorsers sit in strictly
    # higher rounds than the blocks they include, so an endorser's fate is
    # known by the time its endorsee is evaluated.  Rounds are attacker-
    # claimed, but a mis-ordered (forged) endorser merely evaluates as
    # not-yet-accepted (False) — never as accepted (see
    # docs/aggregate-verification.md, well-foundedness).
    order = sorted(
        (i for i in range(n) if i not in frontier_set),
        key=lambda i: -blocks[i].round(),
    )
    direct = await direct_verify([blocks[i] for i in frontier])
    for i, ok in zip(frontier, direct):
        maybe[i] = bool(ok)
    count(0, len(frontier))
    for i in order:
        maybe[i] = endorsement_stake(i, maybe) >= quorum
        if maybe[i]:
            count(1, 0)
    unresolved = [i for i in order if maybe[i] is False]
    if unresolved:
        if defer_unresolved:
            # The caller folds these into its next flush window — no second
            # serialized dispatch on this one.
            for i in unresolved:
                maybe[i] = None
            return list(maybe)
        # Endorsement fell short once non-accepted endorsers were excluded:
        # these still deserve a direct check rather than a blanket reject.
        second = await direct_verify([blocks[i] for i in unresolved])
        count(0, len(unresolved))
        for i, ok in zip(unresolved, second):
            maybe[i] = bool(ok)
    return [bool(v) for v in maybe]


class ThresholdAggregateVerifier(BlockVerifier):
    """Threshold-aggregate verification (BASELINE config #5's technique).

    Exploits the digest/signature layering (crypto.rs:77-84): a block's
    reference digest is computed over its full serialization INCLUDING the
    signature, and honest validators only include blocks they verified.  So
    when blocks signed by a quorum (2f+1 stake, hence >= f+1 honest) of
    distinct authorities reference block B, B's authenticity is already
    certified by the quorum — its signature need not be re-checked here.

    Applied at batch granularity on the receive path: within one incoming
    batch (catch-up and sync deliver hundreds of blocks spanning many
    rounds), only the non-endorsed FRONTIER is signature-verified through
    the inner verifier (one TPU dispatch); interior blocks are accepted when
    a quorum of distinct accepted in-batch endorsers references them.
    Acceptance is evaluated in descending-round order, so every acceptance
    chain terminates at directly verified frontier signatures — a forged
    interior block needs 2f+1 distinct accepted endorsers, which exceeds the
    fault model.

    Blocks that do not reach quorum endorsement (including every singleton
    steady-state delivery) go through the inner verifier unchanged.
    """

    def __init__(self, committee: Committee, inner: BlockVerifier,
                 metrics=None) -> None:
        self.committee = committee
        self.inner = inner
        self.metrics = metrics
        # Plain counters for tests; scrapeable via verified_signatures_total
        # {backend="aggregate"} when metrics are wired.
        self.aggregated_total = 0
        self.direct_total = 0

    def _count(self, aggregated: int, direct: int) -> None:
        self.aggregated_total += aggregated
        self.direct_total += direct
        if self.metrics is not None:
            if aggregated:
                self.metrics.verified_signatures_total.labels(
                    "aggregate", "skipped"
                ).inc(aggregated)
            if direct:
                self.metrics.verified_signatures_total.labels(
                    "aggregate", "direct"
                ).inc(direct)

    async def verify(self, block: StatementBlock) -> None:
        await self.inner.verify(block)

    async def verify_blocks(self, blocks: Sequence[StatementBlock]) -> List[bool]:
        return await aggregate_verify(
            blocks, self.committee, self.inner.verify_blocks, self._count
        )

    def note_committee(self, committee: Committee) -> None:
        """Quorum endorsement is stake-weighted: follow the epoch's stakes."""
        self.committee = committee
        note = getattr(self.inner, "note_committee", None)
        if note is not None:
            note(committee)


def _count_verdicts(series, labels: tuple, verdicts) -> None:
    """``verdicts`` onto ``series{*labels, outcome}``."""
    accepted = sum(bool(ok) for ok in verdicts)
    if accepted:
        series.labels(*labels, "accepted").inc(accepted)
    if accepted < len(verdicts):
        series.labels(*labels, "rejected").inc(len(verdicts) - accepted)


def _observe_orphan(fut) -> None:
    """Retrieve an orphaned executor future's exception so a backend crash
    after the awaiting flush was cancelled is logged, not swallowed into an
    'exception was never retrieved' warning at shutdown."""
    if fut.cancelled():
        return
    exc = fut.exception()
    if exc is not None:
        log.warning("orphaned verify dispatch failed after cancel: %r", exc)


def _abandon_dispatch(fut) -> None:
    """Dispose a submitted-but-never-fetched dispatch handle.

    Handles that hold releasable state expose ``abandon()``; plain handles
    (completed/deferred/JAX device arrays) need nothing.  A submit that
    RAISED already cleaned up after itself (the breaker clears its probe,
    the remote client discards its connection)."""
    if fut.cancelled() or fut.exception() is not None:
        return
    abandon = getattr(fut.result(), "abandon", None)
    if abandon is None:
        return
    try:
        abandon()
    except Exception:  # noqa: BLE001 - best-effort cleanup on shutdown
        log.exception("abandoning an in-flight verify dispatch failed")


class BatchedSignatureVerifier(BlockVerifier):
    """Deadline/size-triggered batching collector in front of a SignatureVerifier.

    Consensus wants low verification turnaround; the TPU wants large batches.
    Policy: a block's verification completes when either (a) ``max_batch``
    items have accumulated, or (b) the collection window elapsed since the
    first pending item — whichever comes first (SURVEY §7 hard part #2).
    The window is ``max_delay_s`` until a dispatch has been measured, then
    20% of the observed dispatch latency, clamped, and shorter still when
    arrivals are sparse — see ``_effective_delay_s``.

    Usable from any number of asyncio tasks (one per peer connection); the
    device dispatch runs in a worker thread so the event loop never blocks on
    the accelerator.
    """

    def __init__(
        self,
        committee: Committee,
        verifier: Optional[SignatureVerifier] = None,
        max_batch: int = 256,
        max_delay_s: float = 0.005,
        metrics=None,
        aggregate: bool = False,
        pipeline_depth: Optional[int] = None,
    ) -> None:
        self.committee = committee
        self.verifier = verifier or TpuSignatureVerifier()
        self.max_batch = max_batch
        self.max_delay_s = max_delay_s
        self.metrics = metrics
        # Staged dispatch window: several flushes may be in flight at once
        # (pack N+1 while N computes and N-1's results ride back), bounded
        # so a flooding peer cannot queue unbounded device work.  Depth
        # adapts to the measured dispatch latency unless pinned.
        self.pipeline = VerifyPipeline(
            depth=pipeline_depth,
            metrics=metrics,
            fixed_cost_fn=self._pipeline_fixed_cost,
        )
        # Collector-level threshold aggregation (BASELINE #5's technique at
        # the place it actually bites): one flush window pools blocks from
        # EVERY peer connection, so the batch spans authors — exactly what
        # quorum endorsement needs.  (A frame-level wrapper never sees that:
        # the push disseminator's frames carry a single peer's own blocks,
        # whose one author can never reach 2f+1 endorsement stake.)  Interior
        # quorum-endorsed blocks skip the signature dispatch; only the
        # frontier pays.
        self.aggregate = aggregate
        self.aggregated_total = 0
        self.direct_total = 0
        # The validator's stage clock (spans.StageClock; None = not
        # clocked): a dispatch's two hops through the loop's default
        # executor book their wait for a thread as ``executor_wait``.
        self.stages = None
        # Cross-flush endorsement index: ref -> authors of ACCEPTED blocks
        # that include it.  Catch-up streams from different peers run at
        # different round offsets, so a backlog block's quorum of verified
        # children has usually been accepted in EARLIER flushes — in-batch
        # endorsement alone almost never fires there.  Strictly size-bounded
        # with insertion-order (FIFO) eviction: rounds CLAIMED by blocks are
        # attacker-controlled (a Byzantine author can sign structure-valid
        # blocks at arbitrary rounds over fabricated include refs), so
        # neither the prune window nor residency may key on them.
        self._endorsements: dict = {}
        # id(future) of entries deferred once (aggregate mode): the next
        # unresolved verdict force-dispatches instead of deferring again.
        self._deferred: set = set()
        self._pending: List[Tuple[StatementBlock, asyncio.Future]] = []
        self._lock = threading.Lock()
        self._flush_task: Optional[asyncio.TimerHandle] = None
        # EMA of observed dispatch latency (submit to verdicts): the window
        # waits a fifth of it, so batches that would queue behind each
        # other's round trips coalesce at a bounded cost on a latency the
        # round trip already dominates.  The window is clamped to
        # MAX_ADAPTIVE_DELAY_S (a compile stall or compute-heavy batch must
        # never push consensus turnaround past ~0.1 s), and dispatches
        # slower than EMA_OUTLIER_S (one-time JAX compiles) are not fed into
        # the EMA at all.
        self._dispatch_ema_s = 0.0
        # Signed transactions (Parameters.signed_transactions): a received
        # block then brings 1 + (its signed transactions) signatures to the
        # window's batch and is accepted only if every one verifies.
        # ``_tx_verified``, where an ingress plane gave one, says which
        # transactions this validator's own gateway has verified already;
        # those are not sent again.
        self.transaction_signatures = False
        self._tx_verified = None
        # Arrival-rate EMA (loop-clocked, so it reads VIRTUAL time under the
        # deterministic simulator and seeded sims stay byte-identical): the
        # collection window only pays off when more arrivals are coming.
        # At low load the window shrinks toward the floor instead of taxing
        # every lone block with the full batch window — see
        # ``_effective_delay_s``.
        self._arrival_gap_ema_s = 0.0
        self._last_arrival_t: Optional[float] = None

    MAX_ADAPTIVE_DELAY_S = 0.1
    MIN_ADAPTIVE_DELAY_S = 0.0005
    EMA_OUTLIER_S = 5.0
    # Inter-arrival gaps are clamped here before entering the EMA: an idle
    # stretch means "low rate" (signal, fed in at the cap), not an outlier
    # to discard — but it must not drag the EMA so far that a resuming
    # burst needs minutes of samples to recover the window.
    ARRIVAL_GAP_CAP_S = 1.0

    def note_committee(self, committee: Committee) -> None:
        """Epoch switch (reconfig.py): rebind the stake table.  Key tables
        (TpuSignatureVerifier's KeyTable) are indexed by the stable registry
        and need no rebuild; only the quorum-endorsement stake math and
        per-author key lookups follow the new committee object."""
        self.committee = committee

    def require_transaction_signatures(self) -> None:
        """From now on a block's signed transactions are verified with the
        block (``NetworkSyncer`` calls this where
        ``Parameters.signed_transactions`` is set, before it receives a
        block).  Quorum-endorsement skipping is off then: an endorsed
        block's own signature may be implied, its transactions' signatures
        are not."""
        self.transaction_signatures = True
        self.aggregate = False

    def skip_verified_at_gateway(self, verified) -> None:
        """``verified(transaction) -> bool``: this validator's own gateway
        has checked that transaction's signature already (ingress.py);
        receipt does not send those again."""
        self._tx_verified = verified

    def _transaction_signatures(self, block, pks, digests, sigs) -> None:
        """Append (signer, digest, signature) of every signed transaction
        of ``block`` that is not known to be verified."""
        verified = self._tx_verified
        for st in block.statements:
            if not isinstance(st, Share):
                continue
            payload = st.transaction
            if payload[:len(SIGNED_MAGIC)] != SIGNED_MAGIC:
                continue
            payload = bytes(payload)
            signed = parse_signed_tx(payload)
            if signed is None or (verified is not None and verified(payload)):
                continue
            pks.append(signed.tx.account)
            digests.append(signed.digest)
            sigs.append(signed.signature)

    def _pipeline_fixed_cost(self) -> float:
        """Dispatch cost estimate for the adaptive pipeline depth: the
        collector's own dispatch-latency EMA (an unlocked snapshot — depth
        adaptation tolerates a stale value)."""
        return self._dispatch_ema_s

    def _effective_delay_s(self) -> float:
        """Collection window: 20% of the dispatch-latency EMA, clamped to
        [MIN, MAX]; ``max_delay_s`` is the default until a dispatch has been
        measured.  The window exists to amortize a dispatch, so it follows
        what one costs: a 15 ms round trip to the verifier service gives
        3 ms, a 30 ms oracle batch 6 ms, a sub-millisecond verify the 0.5 ms
        floor — holding blocks 5 ms to amortize a 0.5 ms verify is pure
        added latency.

        On top of that dispatch-cost CEILING, the window is arrival-rate-
        adaptive: waiting is only worth it when more blocks are coming.
        With ``ceiling / gap_ema`` expected further arrivals inside the
        window, a rate that would deliver fewer than ~2 scales the wait
        down linearly (to the floor) — a lone steady-state block flushes
        almost immediately instead of paying the full batch window, while
        dense arrivals (gap << window) and same-tick frame bursts keep the
        full window and batch exactly as before.  Saturation is unaffected
        either way: ``max_batch`` arrivals flush without any timer.
        """
        ema = self._dispatch_ema_s
        if ema == 0.0:
            ceiling = self.max_delay_s
        else:
            ceiling = max(
                self.MIN_ADAPTIVE_DELAY_S,
                min(0.2 * ema, self.MAX_ADAPTIVE_DELAY_S),
            )
        gap = self._arrival_gap_ema_s
        if gap <= 0.0:
            return ceiling
        expected = ceiling / gap  # further arrivals inside a full window
        if expected >= 2.0:
            return ceiling
        return max(self.MIN_ADAPTIVE_DELAY_S, ceiling * expected / 2.0)

    def _schedule_flush(self, loop) -> None:  # lint: holds[_lock]
        """Arm the window timer (caller holds ``self._lock``) and publish
        the chosen window — the adaptive curve is otherwise invisible when
        a misroute needs debugging."""
        delay = self._effective_delay_s()
        if self.metrics is not None:
            self.metrics.verify_collector_window_seconds.set(delay)
        self._flush_task = loop.call_later(
            delay, lambda: spawn_logged(self._flush(), log, name="verify-flush")
        )

    async def verify(self, block: StatementBlock) -> None:
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()
        window = None
        # Loop clock, not the wall: virtual under the simulator, so the
        # adaptive window cannot make a seeded sim's flush schedule depend
        # on host weather.
        now = loop.time()
        with self._lock:
            last = self._last_arrival_t
            self._last_arrival_t = now
            if last is not None:
                gap = min(now - last, self.ARRIVAL_GAP_CAP_S)
                # Same-tick arrivals (gather bursts, one frame's blocks)
                # sample as 0.0 — pulling the EMA toward "dense", which is
                # exactly what they are; a zero first sample leaves the EMA
                # unseeded (full window) rather than pinning it there.
                self._arrival_gap_ema_s = (
                    gap
                    if self._arrival_gap_ema_s == 0.0
                    else 0.8 * self._arrival_gap_ema_s + 0.2 * gap
                )
            self._pending.append((block, future))
            if len(self._pending) >= self.max_batch:
                # Take the full window NOW (max_batch stays the dispatch
                # bound) and open a fresh one immediately.
                window = self._pending
                self._pending = []
                if self._flush_task is not None:
                    self._flush_task.cancel()
                    self._flush_task = None
            elif self._flush_task is None:
                self._schedule_flush(loop)
        if window is not None:
            # Flush as its own task instead of awaiting it: the PRIOR
            # window's dispatch may still be in flight, and the staged
            # pipeline (bounded depth) is what lets this window's pack
            # overlap it.  The spawned task observes/attributes its own
            # failures; this caller still awaits its block's future below.
            spawn_logged(self._flush(window), log, name="verify-flush")
        ok = await future
        if not ok:
            raise VerificationError(
                f"signature verification failed for {block.reference!r}"
            )

    def _submit_dispatch(self, pks, digests, sigs):
        """Device stage (executor thread): pack-to-wire + non-blocking
        submission through the backend's async seam.  Returns the in-flight
        handle; for host backends without a device queue the handle defers
        the work to the fetch stage."""
        timer = (
            self.metrics.utilization_timer("verify:dispatch")
            if self.metrics is not None
            else contextlib.nullcontext()
        )
        with timer:
            submit = getattr(self.verifier, "verify_signatures_async", None)
            if submit is None:
                # Duck-typed backend predating the async seam: defer the
                # sync path to the fetch stage.
                return DeferredDispatch(
                    self.verifier.verify_signatures, pks, digests, sigs
                )
            return submit(pks, digests, sigs)

    def _dispatch_and_fetch(self, pks, digests, sigs):
        """Single-hop dispatch (simulation path): submit + fetch in one
        executor call — the pre-pipeline per-dispatch shape."""
        return self._fetch_dispatch(
            self._submit_dispatch(pks, digests, sigs), len(sigs)
        )

    def _fetch_dispatch(self, handle, n):
        """Fetch stage (executor thread): block until the verdicts are
        ready.  The backend label AND the padded lane count must be read in
        THIS thread, right after ``result()`` — FallbackSignatureVerifier
        records them thread-locally at fetch, so reading after the await
        would race with concurrent flushes the other backend answered."""
        timer = (
            self.metrics.utilization_timer("verify:dispatch")
            if self.metrics is not None
            else contextlib.nullcontext()
        )
        with timer:
            out = handle.result()
        label = getattr(
            self.verifier, "backend_label", type(self.verifier).__name__
        )
        padded = getattr(self.verifier, "dispatch_padded", None)
        if padded is None:
            padder = getattr(self.verifier, "padded_batch", None)
            padded = padder(n) if padder is not None else n
        return out, label, padded

    def _join_transaction_verdicts(self, out, tx_ranges) -> List[bool]:
        """A block's verdict where its transactions are signed: its own
        signature and every one of theirs.  Counts which of the two
        rejected it."""
        joined = []
        for i, (start, stop) in enumerate(tx_ranges):
            own = bool(out[i])
            ok = own and all(out[start:stop])
            if not ok and self.metrics is not None:
                self.metrics.verify_rejected_blocks_total.labels(
                    "transaction_signature" if own else "block_signature"
                ).inc()
            joined.append(ok)
        return joined

    async def _flush(self, batch=None) -> None:
        if batch is None:
            with self._lock:
                batch = self._pending
                self._pending = []
                if self._flush_task is not None:
                    self._flush_task.cancel()
                    self._flush_task = None
        if not batch:
            return
        blocks = [b for b, _ in batch]
        loop = asyncio.get_running_loop()

        async def _direct(sub_blocks) -> List[bool]:
            if not sub_blocks:
                return []
            tracer = spans.active()
            # -- pack stage (host, loop thread): key lookup + list building;
            # the numpy pack-to-wire happens inside the submit below.
            t_pack = tracer.now() if tracer is not None else 0.0
            pack_started = time.monotonic()
            pks = [
                self.committee.get_public_key(b.author()).bytes
                for b in sub_blocks
            ]
            digests = [b.signed_digest() for b in sub_blocks]
            sigs = [b.signature for b in sub_blocks]
            tx_ranges = None
            if self.transaction_signatures:
                tx_ranges = []
                for block in sub_blocks:
                    start = len(sigs)
                    self._transaction_signatures(block, pks, digests, sigs)
                    tx_ranges.append((start, len(sigs)))
            self.pipeline.note_stage(
                STAGE_PACK, time.monotonic() - pack_started
            )
            if tracer is not None:
                for block in sub_blocks:
                    tracer.record_span("verify_pack", block.reference, t_pack)
            # -- bounded in-flight window: held from device submission
            # through result fetch.  Other flush windows keep packing (and
            # submitting, up to the depth) while this dispatch is in flight.
            req_id = None
            async with self.pipeline.slot():
                t_dispatch = tracer.now() if tracer is not None else 0.0
                t_fetch = t_dispatch
                started = time.monotonic()
                if is_simulated():
                    # Inline (no executor hop) under the virtual-time
                    # simulator: while a real thread works, the virtual
                    # clock leaps timers, so ANY hop makes the sim's commit
                    # schedule depend on host load (a starved 2-core CI box
                    # can blow the whole virtual duration past one verify).
                    # Synchronous on the loop thread the virtual clock is
                    # frozen for the dispatch's duration — deterministic
                    # regardless of machine weather.  Slots still bound
                    # concurrency; sims measure determinism, not overlap.
                    out, label, padded = self._dispatch_and_fetch(
                        pks, digests, sigs
                    )
                    device_done = started
                    # Keep the stage decomposition honest: the single hop
                    # has no separate submit, so device is an explicit zero
                    # (not a missing sample) and fetch carries the whole
                    # dispatch.
                    self.pipeline.note_stage(STAGE_DEVICE, 0.0)
                else:
                    submit_fut = spans.in_default_executor(
                        loop, self.stages, self._submit_dispatch, pks,
                        digests, sigs
                    )
                    try:
                        handle = await asyncio.shield(submit_fut)
                    except asyncio.CancelledError:
                        # Flush task cancelled mid-submit (node shutdown):
                        # the shielded executor job still runs and its
                        # handle may hold per-dispatch backend state (a
                        # pooled service connection or its place on the
                        # shared one, the breaker's exclusive probe flag)
                        # that only the fetch normally releases — dispose
                        # it the moment it lands.
                        submit_fut.add_done_callback(_abandon_dispatch)
                        raise
                    device_done = time.monotonic()
                    self.pipeline.note_stage(
                        STAGE_DEVICE, device_done - started
                    )
                    if tracer is not None:
                        t_fetch = tracer.now()
                        # A request to the verifier service carries its
                        # req_id: the service's own stages of it
                        # (spans.SERVICE_STAGES) join this span on it.
                        req_id = getattr(handle, "req_id", None)
                        for block in sub_blocks:
                            tracer.record_span(
                                "verify_device", block.reference, t_dispatch,
                                t1=t_fetch,
                            )
                    # The fetch hop is shielded for the same reason the
                    # submit hop is: an unshielded cancel can cancel a
                    # QUEUED executor job before it starts, and then nothing
                    # ever consumes the handle (its service connection,
                    # probe flag).  Shielded, the job always runs; result()
                    # does its own cleanup, so cancellation here needs only
                    # to observe the orphaned outcome.
                    fetch_fut = spans.in_default_executor(
                        loop, self.stages, self._fetch_dispatch, handle,
                        len(sigs)
                    )
                    try:
                        out, label, padded = await asyncio.shield(fetch_fut)
                    except asyncio.CancelledError:
                        fetch_fut.add_done_callback(_observe_orphan)
                        raise
                self.pipeline.note_stage(
                    STAGE_FETCH, time.monotonic() - device_done
                )
            # The window EMA shares self._lock with the pending queue: the
            # read-modify-write must not interleave with _effective_delay_s
            # readers scheduling a flush from another flush's critical
            # section.  Under the simulator the EMA stays unseeded: it is a
            # WALL-clock measurement, and _effective_delay_s arms a
            # VIRTUAL-time flush timer from it — folding it in would make a
            # seeded sim's flush schedule (and so its whole commit
            # trajectory) depend on host load.  Sims run the fixed
            # max_delay_s window instead (the arrival-gap term is loop-
            # clocked and stays live).
            if not is_simulated():
                with self._lock:
                    self._dispatch_ema_s = _update_ema(
                        self._dispatch_ema_s,
                        time.monotonic() - started,
                        self.EMA_OUTLIER_S,
                    )
            if tracer is not None:
                t1 = tracer.now()
                for block in sub_blocks:
                    tracer.record_span(
                        "verify_fetch", block.reference, t_fetch, t1=t1
                    )
                    tracer.record_span(
                        "verify_dispatch", block.reference, t_dispatch, t1=t1,
                        extra=None if req_id is None else {"req_id": req_id},
                    )
            # Backend counters measure ACTUAL dispatches: counted here, per
            # dispatch, so aggregate-skipped blocks never inflate them.
            if self.metrics is not None:
                self.metrics.verify_dispatch_batch_size.observe(len(sigs))
                # Padding waste: lanes the device computed beyond the real
                # signatures (bucket-shaped dispatches); host backends report
                # n (zero waste).
                self.metrics.verify_padding_wasted_total.labels(label).inc(
                    max(0, padded - len(sigs))
                )
                # Block signatures; their transactions' signatures (behind
                # them in the batch) have a series of their own.
                blocks_n = len(sub_blocks)
                _count_verdicts(
                    self.metrics.verified_signatures_total, (label,),
                    out[:blocks_n])
                _count_verdicts(
                    self.metrics.verified_tx_signatures_total,
                    (label, "receipt"), out[blocks_n:])
            if tx_ranges is not None:
                out = self._join_transaction_verdicts(out, tx_ranges)
            return out

        def _account(aggregated: int, direct: int) -> None:
            self.aggregated_total += aggregated
            self.direct_total += direct
            if self.metrics is not None and aggregated:
                self.metrics.verified_signatures_total.labels(
                    "aggregate", "skipped"
                ).inc(aggregated)

        try:
            if self.aggregate:
                results = await aggregate_verify(
                    blocks, self.committee, _direct, _account,
                    prior_endorsers=self._prior_endorsers,
                    defer_unresolved=True,
                )
                results = await self._resolve_deferred(batch, results, _direct)
                self._note_endorsements(blocks, results)
            else:
                _account(0, len(blocks))
                results = await _direct(blocks)
        except asyncio.CancelledError:
            # Flush task cancelled mid-dispatch (node teardown — the timer
            # handle's cancel() can't interrupt a running flush): the
            # window's futures must still resolve or verify() callers that
            # outlive this task park on `await future` forever.  Cancelling
            # them marks the infra outcome (never a verdict) and the
            # abandon/orphan callbacks above already released the backend
            # state.
            for _, future in batch:
                self._deferred.discard(id(future))
                if not future.done():
                    future.cancel()
            raise
        except Exception as exc:
            # A JAX runtime/compile failure must not strand the awaiting
            # connection tasks forever — fail every future in the batch.
            # The ORIGINAL exception propagates (not a VerificationError):
            # an infra failure is not evidence the signatures were invalid,
            # and callers must be able to tell "reject this block" apart from
            # "the verifier is down" (the latter resets the connection
            # instead of flagging the peer Byzantine).
            log.error("signature verifier crashed on %d blocks: %r",
                      len(batch), exc)
            for _, future in batch:
                self._deferred.discard(id(future))
                if not future.done():
                    future.set_exception(exc)
            return
        if self.metrics is not None:
            self.metrics.verify_batch_size.observe(len(batch))
        for (_, future), ok in zip(batch, results):
            if ok is None:
                continue  # deferred: resolves with the next flush
            if not future.done():
                future.set_result(bool(ok))

    async def _resolve_deferred(self, batch, results, _direct):
        """Route ``None`` (unresolved) slots from an aggregate flush.

        First deferral: fold the entry into the NEXT flush window — it will
        be endorsed there by newly arrived children or dispatched as
        ordinary frontier, so this flush stays at one accelerator
        round-trip (the round-4 tpu-agg saturation collapse was the second
        serialized trip).  Second deferral: force a direct dispatch — a
        block that stays "maybe" across windows is either ahead of its
        children (direct check settles it) or a Byzantine park attempt
        (minting fresh endorsers each window must not stall it forever).
        """
        results = list(results)
        requeue, force = [], []
        for slot, ((block, future), ok) in enumerate(zip(batch, results)):
            if ok is not None:
                self._deferred.discard(id(future))
                continue
            if id(future) in self._deferred:
                self._deferred.discard(id(future))
                force.append((slot, block))
            else:
                self._deferred.add(id(future))
                requeue.append((block, future))
        if force:
            out = await _direct([b for _, b in force])
            self.direct_total += len(force)
            for (slot, _), ok in zip(force, out):
                results[slot] = bool(ok)
        if requeue:
            loop = asyncio.get_running_loop()
            with self._lock:
                # Oldest first: deferred entries re-enter at the head.
                self._pending[:0] = requeue
                if self._flush_task is None:
                    self._schedule_flush(loop)
        return results

    async def verify_blocks(self, blocks: Sequence[StatementBlock]) -> List[bool]:
        """All blocks of a frame join the collector CONCURRENTLY — the base
        class's sequential per-block await would pay one collection window +
        dispatch per block.

        Only VerificationError means "invalid signature" (False).  Anything
        else — a JAX dispatch/compile crash, CancelledError during shutdown —
        re-raises, matching the base class's except-VerificationError-only
        semantics: infra failures must not masquerade as Byzantine rejections.
        """
        results = await asyncio.gather(
            *(self.verify(b) for b in blocks), return_exceptions=True
        )
        out: List[bool] = []
        for r in results:
            if isinstance(r, VerificationError):
                out.append(False)
            elif isinstance(r, BaseException):
                raise r
            else:
                out.append(True)
        return out

    ENDORSEMENT_MAX_ENTRIES = 200_000  # hard cap; FIFO eviction beyond it

    _EMPTY = frozenset()

    def _prior_endorsers(self, ref):
        # Callers must not mutate (endorsement_stake copies before mutating).
        return self._endorsements.get(ref, self._EMPTY)

    def _note_endorsements(self, blocks, results) -> None:
        """Record accepted blocks' includes in the endorsement index; only
        ACCEPTED blocks endorse (each was signature-verified or quorum-
        endorsed itself, so the license carries inductively).  Eviction is
        strictly by first-endorsement insertion order — recent entries (the
        live catch-up window) survive regardless of the rounds blocks CLAIM."""
        endorsements = self._endorsements
        for block, ok in zip(blocks, results):
            if not ok:
                continue
            author = block.author()
            for ref in block.includes:
                prev = endorsements.get(ref)
                if prev is None:
                    endorsements[ref] = {author}
                else:
                    prev.add(author)
        excess = len(endorsements) - self.ENDORSEMENT_MAX_ENTRIES
        if excess > 0:
            # dicts iterate in insertion order: drop the oldest entries.
            for ref in list(islice(iter(endorsements), excess)):
                del endorsements[ref]

    async def flush_now(self) -> None:
        """Test/shutdown hook: drain whatever is pending immediately —
        including aggregate-mode deferrals (a deferred entry re-enters
        ``_pending``; its second appearance force-dispatches, so this loop
        terminates)."""
        await self._flush()
        while self._pending:
            await self._flush()

    def health_state(self) -> dict:
        """Verifier-path state for the fleet health plane (health.py):
        breaker and staged-pipeline occupancy in one cheap read (unlocked
        snapshots — the probe tolerates a torn read)."""
        backend = self.verifier
        return {
            "breaker_open": bool(getattr(backend, "breaker_open", False)),
            "backend": getattr(
                backend, "backend_label", type(backend).__name__
            ),
            "pipeline_inflight": self.pipeline.inflight,
            "pipeline_depth": self.pipeline.depth(),
        }
