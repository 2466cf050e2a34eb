"""Device mesh + shard_map wrapper for the Ed25519 batch verifier.

Design: the verify kernel is embarrassingly parallel over the batch, so the
mesh is one axis (``batch``) and every input is sharded along it; XLA runs one
shard per chip over ICI with no inter-chip traffic except the final ``psum``
that reduces the per-shard valid counts (the quantity the consensus vote
aggregator actually needs globally).

Tested on a virtual 8-device CPU mesh (``--xla_force_host_platform_device_count``)
— the same mesh/collective compilation path XLA uses on a real slice.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as PSpec

from .. import spans
from ..ops import ed25519 as E


def make_mesh(
    n_devices: Optional[int] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices (axis: ``batch``).

    ``devices`` overrides the default-backend device list — e.g.
    ``jax.devices("cpu")`` to build a virtual host mesh in a process whose
    default backend is already pinned to the TPU.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), axis_names=("batch",))


def sharded_verify_kernel(mesh: Mesh):
    """Returns a jitted fn(packed arrays) -> (per-item bool, global valid count).

    All inputs are sharded on the leading batch axis; the valid-count reduction
    is an ICI ``psum``.  Batch size must be a multiple of the mesh size.
    """
    spec = PSpec("batch")

    def _shard_body(a_y, a_sign, r_y, r_sign, s_bits, k_bits, host_ok):
        ok = E.verify_impl(a_y, a_sign, r_y, r_sign, s_bits, k_bits, host_ok)
        total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), "batch")
        return ok, total

    # check_vma stays off in every kernel here: the psum'd total is
    # intentionally replicated.
    sharded = shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=(spec,) * 7,
        out_specs=(spec, PSpec()),
        check_vma=False,
    )
    return jax.jit(sharded)


def sharded_verify_batch(
    mesh: Mesh,
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> Tuple[np.ndarray, int]:
    """Host convenience: pack, pad to the mesh-aligned bucket, dispatch sharded."""
    n = len(signatures)
    n_dev = mesh.devices.size
    kernel = sharded_verify_kernel(mesh)
    packed = E.pack_batch(public_keys, messages, signatures)
    per_shard = max(1, -(-n // n_dev))
    padded = per_shard * n_dev
    arrs = []
    for x in packed:
        pad = padded - n
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        arrs.append(jnp.asarray(np.pad(x, widths)))
    ok, total = kernel(*arrs)
    return np.asarray(ok)[:n], int(total)


# One compiled kernel per (mesh, flavor) — rebuilding the shard_map wrapper on
# every dispatch would recompile each time.
_KERNEL_CACHE: dict = {}


def mesh_lanes(mesh: Mesh, bucket: int) -> int:
    """Lanes a ``bucket``-sized chunk occupies on the mesh.  With the Pallas
    ladder every shard holds at least one full tile: the 256 bucket over four
    chips would otherwise leave 64-lane shards, under the 128-lane register
    width the kernel's limb-major layout is built on.  The extra lanes are
    host_ok=0 padding and run in parallel, one tile per chip."""
    if E._backend() != "pallas":
        return bucket
    from ..ops import ed25519_pallas as PK

    return max(bucket, PK.default_tile() * mesh.devices.size)


def _cached_fused_kernel(mesh: Mesh):
    backend = E._backend()
    key = ("fused", mesh, backend)
    if key not in _KERNEL_CACHE:
        spec = PSpec("batch")

        def _shard_body(msg_words, s_words, host_ok):
            if backend == "pallas":
                # Same Pallas ladder as the single-chip path, one grid per
                # shard; the tile shrinks if a shard is narrower than 256.
                from ..ops import ed25519_pallas as PK

                per_shard = msg_words.shape[0]
                args = E.prepare_fused(msg_words, s_words, host_ok)
                ok = PK._verify_pallas_jit(
                    *args,
                    tile=min(PK.default_tile(), per_shard),
                    interpret=False,
                )
            else:
                ok = E.verify_fused_impl(msg_words, s_words, host_ok)
            total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), "batch")
            return ok, total

        _KERNEL_CACHE[key] = jax.jit(
            shard_map(
                _shard_body,
                mesh=mesh,
                in_specs=(spec,) * 3,
                out_specs=(spec, PSpec()),
                check_vma=False,
            )
        )
    return _KERNEL_CACHE[key]


def _cached_indexed_kernel(mesh: Mesh):
    """Indexed flavor: the (K, 8) key table is replicated to every device
    (a committee table is a few KB), the blob shards on the batch axis."""
    backend = E._backend()
    key = ("indexed", mesh, backend)
    if key not in _KERNEL_CACHE:
        spec = PSpec("batch")

        def _shard_body(blob, table):
            msg_words, s_words, host_ok = E.indexed_to_msg_words(blob, table)
            if backend == "pallas":
                from ..ops import ed25519_pallas as PK

                per_shard = blob.shape[0]
                args = E.prepare_fused(msg_words, s_words, host_ok)
                ok = PK._verify_pallas_jit(
                    *args,
                    tile=min(PK.default_tile(), per_shard),
                    interpret=False,
                )
            else:
                ok = E.verify_fused_impl(msg_words, s_words, host_ok)
            total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), "batch")
            return ok, total

        _KERNEL_CACHE[key] = jax.jit(
            shard_map(
                _shard_body,
                mesh=mesh,
                in_specs=(spec, PSpec()),
                out_specs=(spec, PSpec()),
                check_vma=False,
            )
        )
    return _KERNEL_CACHE[key]


def dispatch_sharded_indexed(
    mesh: Mesh,
    table: "E.KeyTable",
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> "E.VerifyDispatch":
    """Non-blocking sharded committee-indexed dispatch: pack on the host,
    submit every bucket chunk through the mesh kernel, return a handle that
    fetches on demand (the staged pipeline's device stage)."""
    n = len(signatures)
    if n == 0:
        return E.VerifyDispatch([])
    kernel = _cached_indexed_kernel(mesh)
    spans.request_stage("service_pack")
    idx = table.indices_for(public_keys)
    if (idx < 0).any():
        # A signer the table does not know: the batch goes whole to the
        # raw-bytes kernel, one launch (ops.ed25519.dispatch_batch_table).
        return dispatch_sharded_fused(mesh, public_keys, messages, signatures)
    blob = E.pack_blob_indexed(idx, messages, signatures, num_keys=len(table))
    # The psum'd per-chunk total is compiled and executed (the ICI collective
    # is part of the sharded program) but not fetched: padded lanes are
    # host_ok=False, so the global count equals the host-side sum of the
    # combined single fetch — one round-trip instead of 2 per chunk.
    handles = []
    for start, count, b in E.iter_buckets(n):
        lanes = mesh_lanes(mesh, b)
        spans.request_stage("service_pack")
        padded = E._pad_to(blob[start : start + count], lanes)
        spans.request_stage("service_launch")
        E._note_kernel("mesh-indexed", lanes, E._backend())
        handles.append((count, kernel(jnp.asarray(padded), table.words)[0]))
    return E.VerifyDispatch(handles)


def sharded_verify_batch_indexed(
    mesh: Mesh,
    table: "E.KeyTable",
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> Tuple[np.ndarray, int]:
    """Committee-indexed fused verification sharded over the mesh: minimum
    wire format (26 words/sig) AND batch-axis parallelism.  Unknown-key items
    route through the generic sharded path so results never depend on table
    contents."""
    out = dispatch_sharded_indexed(
        mesh, table, public_keys, messages, signatures
    ).result()
    return out, int(out.sum())


def dispatch_sharded_fused(
    mesh: Mesh,
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> "E.VerifyDispatch":
    """Non-blocking sharded fused dispatch (raw-bytes wire format)."""
    n = len(signatures)
    if n == 0:
        return E.VerifyDispatch([])
    kernel = _cached_fused_kernel(mesh)
    msg_words, s_words, host_ok = E.pack_bytes(public_keys, messages, signatures)
    # Dispatch every chunk asynchronously, force once at the end — same
    # overlap policy as ops.ed25519.dispatch_blob_chunks.  The psum total is
    # compiled (the ICI collective stays in the program) but recomputed from
    # the combined fetch: padded lanes are host_ok=False, so the sums agree.
    handles = []
    for start, count, b in E.iter_buckets(n):
        lanes = mesh_lanes(mesh, b)
        E._note_kernel("mesh-fused", lanes, E._backend())
        handles.append((
            count,
            kernel(
                jnp.asarray(E._pad_to(msg_words[start : start + count], lanes)),
                jnp.asarray(E._pad_to(s_words[start : start + count], lanes)),
                jnp.asarray(E._pad_to(host_ok[start : start + count], lanes)),
            )[0],
        ))
    return E.VerifyDispatch(handles)


def sharded_verify_batch_fused(
    mesh: Mesh,
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> Tuple[np.ndarray, int]:
    """Fused raw-bytes verification sharded over the mesh batch axis.

    Uses the fixed bucket shapes of :mod:`..ops.ed25519` (all divisible by
    any power-of-two mesh up to 256 devices) so XLA compiles at most
    len(BUCKETS) shard programs per mesh.  Returns (per-item bool, global
    valid count via ICI psum).
    """
    out = dispatch_sharded_fused(
        mesh, public_keys, messages, signatures
    ).result()
    return out, int(out.sum())
