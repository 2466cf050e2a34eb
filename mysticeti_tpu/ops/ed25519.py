"""Batched Ed25519 verification on TPU — the framework's flagship kernel.

Replaces the serial per-block CPU verify of the reference
(``mysticeti-core/src/crypto.rs:174-189`` + call site ``types.rs:315-347``) with a
``vmap``ped, ``jit``ted JAX kernel: twisted-Edwards point decompression and
double-scalar multiplication ``[s]B - [k]A`` in 20x13-bit int32 limb arithmetic
(see :mod:`mysticeti_tpu.ops.field`), one lane per signature.

Verification rule (cofactorless, matching the OpenSSL/`cryptography` oracle and
RFC 8032 decoding): reject if s ≥ L or A is a non-canonical/invalid encoding;
accept iff encode([s]B - [k]A) == R_bytes, with k = SHA-512(R || A || M) mod L.
The byte comparison implies R canonicity exactly like OpenSSL's memcmp.

Host/device split: the host parses signatures, computes k (SHA-512 is cheap and
message-length-dependent; the fused on-device digest lives in ops/sha512.py) and
packs scalars as bit arrays; the device runs decompression + the 256-step
double-and-add ladder under ``lax.scan`` — constant shapes, no data-dependent
control flow, batch dimension mapped across VPU lanes.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import spans
from . import field as F
from . import scalar as SC
from . import sha512 as H
from . import programs
from .programs import COMPILE_STATS, StoredProgram

P = F.P
L = (1 << 252) + 27742317777372353535851937790883648493  # group order

_D = (-121665 * pow(121666, P - 2, P)) % P
_D2 = (2 * _D) % P
_SQRT_M1 = pow(2, (P - 1) // 4, P)

# Base point B: y = 4/5, x recovered with even sign.
_BY = (4 * pow(5, P - 2, P)) % P


def _recover_x(y: int, sign: int) -> Optional[int]:
    x2 = (y * y - 1) * pow(_D * y * y + 1, P - 2, P) % P
    if x2 == 0:
        return None if sign else 0
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * _SQRT_M1 % P
    if (x * x - x2) % P != 0:
        return None
    if x & 1 != sign:
        x = P - x
    return x


_BX = _recover_x(_BY, 0)
assert _BX is not None

# Device-side constants (limb form).
_D_L = F.constant(_D)
_D2_L = F.constant(_D2)
_SQRT_M1_L = F.constant(_SQRT_M1)
_ONE = F.constant(1)
_ZERO = F.constant(0)
_B_POINT = tuple(
    F.constant(v) for v in (_BX, _BY, 1, _BX * _BY % P)
)  # extended (X, Y, Z, T)

# A point is a 4-tuple of limb vectors (X, Y, Z, T) with x=X/Z, y=Y/Z, T=XY/Z.
Point = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]


def _identity_like(shape_ref: jnp.ndarray) -> Point:
    zero = jnp.zeros_like(shape_ref)
    one = zero.at[..., 0].set(1)
    return (zero, one, one, zero)


def point_add(p: Point, q: Point) -> Point:
    """Unified addition, add-2008-hwcd-3 for a=-1 (8 muls)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = F.mul(F.sub(y1, x1), F.sub(y2, x2))
    b = F.mul(F.add(y1, x1), F.add(y2, x2))
    c = F.mul(F.mul(t1, _D2_L), t2)
    d = F.mul(F.add(z1, z1), z2)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def point_double(p: Point) -> Point:
    """dbl-2008-hwcd for a=-1 (4 muls + 4 squares)."""
    x1, y1, z1, _ = p
    a = F.square(x1)
    b = F.square(y1)
    c = F.add(F.square(z1), F.square(z1))
    h = F.add(a, b)
    e = F.sub(h, F.square(F.add(x1, y1)))
    g = F.sub(a, b)
    f = F.add(c, g)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def point_neg(p: Point) -> Point:
    x, y, z, t = p
    return (F.neg(x), y, z, F.neg(t))


def _select(cond: jnp.ndarray, a: Point, b: Point) -> Point:
    """Per-item point select; cond is batch-shaped bool."""
    c = cond[..., None]
    return tuple(jnp.where(c, ai, bi) for ai, bi in zip(a, b))


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray) -> Tuple[Point, jnp.ndarray]:
    """RFC 8032 point decompression on device (sqrt via the 2^252-3 chain).

    ``y_limbs``: (..., 20) the y coordinate (already checked < p on host);
    ``sign``: (...,) 0/1 x-parity bit.  Returns (point, ok_mask).
    """
    yy = F.square(y_limbs)
    u = F.sub(yy, _ONE)
    v = F.add(F.mul(_D_L, yy), _ONE)
    # x = u v^3 (u v^7)^((p-5)/8)
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    x = F.mul(F.mul(u, v3), F.pow22523(F.mul(u, v7)))
    vxx = F.mul(v, F.square(x))
    ok_direct = F.eq_canonical(vxx, u)
    ok_flipped = F.eq_canonical(vxx, F.neg(u))
    x = jnp.where(ok_direct[..., None], x, F.mul(x, _SQRT_M1_L))
    ok = ok_direct | ok_flipped
    # x == 0 with sign bit set is invalid (no -0).
    x_is_zero = F.is_zero(x)
    ok = ok & ~(x_is_zero & (sign == 1))
    # Match parity to the requested sign.
    flip = (F.parity(x) != sign) & ~x_is_zero
    x = jnp.where(flip[..., None], F.neg(x), x)
    point = (x, y_limbs, jnp.broadcast_to(_ONE, y_limbs.shape), F.mul(x, y_limbs))
    return point, ok


# ---------------------------------------------------------------------------
# Windowed double-scalar multiplication
# ---------------------------------------------------------------------------
#
# [s]B uses a positional comb table precomputed ONCE on the host with python
# ints (B is a protocol constant): T_B[w][v] = v * 16^w * B.  [s]B is then just
# 64 table additions — zero doublings.  [k]A runs a 4-bit windowed ladder with
# a 16-entry per-item table (15 vmapped adds to build), i.e. 256 doublings +
# 64 adds instead of 256 doublings + ~128 conditional adds.  Verification is
# not secret-dependent, so data-dependent *gathers* are fine (no constant-time
# requirement); shapes remain static.

_WINDOWS = 64  # 4-bit windows covering 256 bits


def _affine_add(p, q):
    """Host-side python-int Edwards addition (for table generation only)."""
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    den1 = pow(1 + _D * x1 * x2 * y1 * y2, P - 2, P)
    den2 = pow(1 - _D * x1 * x2 * y1 * y2, P - 2, P)
    return ((x1 * y2 + x2 * y1) * den1 % P, (y1 * y2 + x1 * x2) * den2 % P)


def _build_base_comb() -> np.ndarray:
    """(64, 16, 4, 20) int32: extended-coordinate entries of v*16^w*B."""
    table = np.zeros((_WINDOWS, 16, 4, F.NLIMBS), np.int32)
    step = (_BX, _BY)  # 16^w * B
    for w in range(_WINDOWS):
        entry = None  # v * step
        for v in range(16):
            if entry is None:
                x, y = 0, 1
            else:
                x, y = entry
            table[w, v, 0] = F.int_to_limbs(x)
            table[w, v, 1] = F.int_to_limbs(y)
            table[w, v, 2] = F.int_to_limbs(1)
            table[w, v, 3] = F.int_to_limbs(x * y % P)
            entry = _affine_add(entry, step)
        for _ in range(4):
            step = _affine_add(step, step)
    return table


_B_COMB = jnp.asarray(_build_base_comb())


def _gather_point(table: Point, idx: jnp.ndarray) -> Point:
    """Select per-item entries: table coords (..., 16, 20), idx (...,).

    Implemented as a one-hot masked sum, not a gather — dynamic gathers
    serialize on the TPU VPU while the 16 multiply-adds stay lane-parallel.
    """
    onehot = (idx[..., None] == jnp.arange(16, dtype=jnp.int32)).astype(jnp.int32)
    return tuple(
        jnp.sum(onehot[..., :, None] * c, axis=-2) for c in table
    )


def _double_scalar_mul(
    s_windows: jnp.ndarray, k_windows: jnp.ndarray, neg_a: Point
) -> Point:
    """[s]B + [k]negA.

    ``s_windows``: (..., 64) int32 in 0..15, index 0 = LEAST significant window
    (positional, matches the comb table).  ``k_windows``: same layout; the
    ladder consumes them most-significant first.
    """
    # --- [k]negA: per-item 16-entry table, then 4-bit ladder ---
    identity = _identity_like(neg_a[0])
    tab = [identity, neg_a]
    for v in range(2, 16):
        tab.append(point_add(tab[v - 1], neg_a))
    # (..., 16, 20) per coordinate.
    tab_a: Point = tuple(
        jnp.stack([t[c] for t in tab], axis=-2) for c in range(4)
    )

    def ladder_step(acc: Point, kw):
        for _ in range(4):
            acc = point_double(acc)
        acc = point_add(acc, _gather_point(tab_a, kw))
        return acc, None

    kw_msb_first = jnp.moveaxis(k_windows[..., ::-1], -1, 0)  # scan axis front
    acc, _ = jax.lax.scan(ladder_step, identity, kw_msb_first)

    # --- [s]B: 64 comb-table additions, no doublings ---
    def comb_step(acc: Point, inputs):
        entries, sw = inputs  # entries: (16, 4, 20) const slice; sw: (...,)
        table: Point = tuple(
            jnp.broadcast_to(
                entries[:, c, :], (*sw.shape, 16, F.NLIMBS)
            )
            for c in range(4)
        )
        return point_add(acc, _gather_point(table, sw)), None

    sw = jnp.moveaxis(s_windows, -1, 0)
    acc_b, _ = jax.lax.scan(comb_step, identity, (_B_COMB, sw))

    return point_add(acc, acc_b)


def verify_impl(
    a_y: jnp.ndarray,  # (B, 20) public key y limbs
    a_sign: jnp.ndarray,  # (B,)
    r_y: jnp.ndarray,  # (B, 20) signature R y limbs (raw, unvalidated)
    r_sign: jnp.ndarray,  # (B,)
    s_windows: jnp.ndarray,  # (B, 64) 4-bit windows of s, LSB window first
    k_windows: jnp.ndarray,  # (B, 64) 4-bit windows of k, LSB window first
    host_ok: jnp.ndarray,  # (B,) host-side checks (s < L, canonical A, ...)
) -> jnp.ndarray:
    """Batched device verification; returns (B,) bool."""
    neg_a, decompress_ok = jax.vmap(decompress)(a_y, a_sign)
    neg_a = point_neg(neg_a)
    res = _double_scalar_mul(s_windows, k_windows, neg_a)
    x, y, z, _ = res
    zinv = F.invert(z)
    x_aff = F.mul(x, zinv)
    y_aff = F.mul(y, zinv)
    # Canonical-encode and compare against raw R limbs (memcmp semantics).
    # The compare is EXACT on the raw (unreduced) R representation: a
    # non-canonical R (y >= p) has a unique limb pattern that canonical()
    # output can never produce, so it is rejected — exactly like OpenSSL's
    # memcmp of the canonical encoding against the raw signature bytes.
    match = jnp.all(F.canonical(y_aff) == r_y, axis=-1) & (
        F.parity(x_aff) == r_sign
    )
    return match & decompress_ok & host_ok


verify_kernel = jax.jit(verify_impl)


# ---------------------------------------------------------------------------
# Fused path: raw signature bytes in, verification bits out — zero per-item
# host work.  SHA-512, the mod-L reduction, window extraction, point-encoding
# parsing, and all canonicity checks run on device (BASELINE config #4).
# ---------------------------------------------------------------------------


def _parse_point_words(le_words: jnp.ndarray):
    """(..., 8) uint32 LE words of a 32-byte point encoding ->
    (y limbs, sign, is_canonical)."""
    sign = (le_words[..., 7] >> 31).astype(jnp.int32)
    masked = le_words.at[..., 7].set(le_words[..., 7] & 0x7FFFFFFF)
    y_limbs = SC.words_to_limbs(masked, F.NLIMBS)
    return y_limbs, sign, SC.lt_P(y_limbs)


def prepare_fused(
    msg_words: jnp.ndarray,  # (B, 24) uint32 BIG-endian words of R || A || M
    s_words: jnp.ndarray,  # (B, 8) uint32 LITTLE-endian words of s
    host_ok: jnp.ndarray,  # (B,) bool (length checks only)
):
    """Device-side preparation: returns the 7 arrays verify_impl consumes.

    Fuses the challenge hash k = SHA-512(R||A||M) mod L (previously a per-item
    host hashlib loop — the reference's serial path, crypto.rs:174-189) with
    the encoding parse and the canonicity checks (s < L, A < p).  R canonicity
    needs no explicit check: the final compare is exact on raw limbs.

    This is the ``xla`` backend's form (the CPU tier, and the four-chip
    mesh's shards), batch-major XLA ops with two scans, and the reference
    the tests hold the Pallas kernels to: a launch on a TPU runs the same
    steps inside its Pallas call (``ops.ed25519_pallas._prepare``) and does
    not come through here.  The scopes name these ops in a profile of the
    ``xla`` form.
    """
    with jax.named_scope("ed25519_challenge_hash"):
        dig = H.sha512_96(msg_words)
        k = SC.mod_L(SC.words_to_limbs(SC.digest_words_to_le(dig), 40))
        k_windows = SC.windows4(k)

    with jax.named_scope("ed25519_bytes_to_limbs"):
        r_y, r_sign, _ = _parse_point_words(SC.bswap32(msg_words[..., :8]))
        a_y, a_sign, a_canonical = _parse_point_words(
            SC.bswap32(msg_words[..., 8:16])
        )
        s_limbs = SC.words_to_limbs(s_words, F.NLIMBS)
        s_ok = SC.lt_L(s_limbs)
        s_windows = SC.windows4(s_limbs)

    ok = host_ok & a_canonical & s_ok
    return a_y, a_sign, r_y, r_sign, s_windows, k_windows, ok


def verify_fused_impl(msg_words, s_words, host_ok) -> jnp.ndarray:
    """Batched fused verification; (B,) bool from raw byte words."""
    return verify_impl(*prepare_fused(msg_words, s_words, host_ok))


verify_fused_kernel = jax.jit(verify_fused_impl)


def _is_rows(items) -> bool:
    """Whether ``items`` arrived as rows of a byte array (the verifier
    service slices them off the wire records) and not as a sequence of
    bytes objects."""
    return (
        isinstance(items, np.ndarray)
        and items.ndim == 2
        and items.dtype == np.uint8
    )


def _pack_fixed_rows(items, width: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """(n, width) uint8 rows + per-row well-formedness (None: every row is
    well-formed): the one seam both forms of a batch come through.

    Rows that arrive as an (n, width) uint8 array are used as they are, all
    well-formed (an array row cannot have another length; an array of
    another width is malformed in every row).  A sequence of bytes objects
    is joined with one concatenation when every item has the right length;
    items of wrong length zero-fill (callers mask them via host_ok —
    verify-returns-False semantics, never an exception)."""
    n = len(items)
    if _is_rows(items):
        if items.shape[1] != width:
            return np.zeros((n, width), np.uint8), np.zeros(n, bool)
        if items.strides[1] != 1:  # the packers view a row's bytes as words
            items = np.ascontiguousarray(items)
        return items, None
    ok = np.fromiter((len(x) == width for x in items), bool, count=n)
    if ok.all():
        return np.frombuffer(b"".join(items), np.uint8).reshape(n, width), None
    arr = np.zeros((n, width), np.uint8)
    for i in range(n):
        if ok[i]:
            arr[i] = np.frombuffer(items[i], np.uint8)
    return arr, ok


def _host_ok(*oks: Optional[np.ndarray]):
    """The lanes every one of ``oks`` admits (each an (n,) bool array, or
    None for all): an array, or 1 where all are None."""
    given = [ok for ok in oks if ok is not None]
    if not given:
        return 1
    out = given[0]
    for ok in given[1:]:
        out = out & ok
    return out


def _all_digests(messages) -> bool:
    """Whether every message is a 32-byte digest (the fused kernels' only
    input; anything else is hashed on the host): a look at the shape for
    rows of an array, a walk for a sequence."""
    if _is_rows(messages):
        return messages.shape[1] == 32
    return all(len(m) == 32 for m in messages)


def pack_blob(
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> np.ndarray:
    """Pack a batch into ONE (n, 33) uint32 array: columns 0-23 the big-endian
    R||A||M words, 24-31 the little-endian s words, 32 the host_ok flag.

    One array means one host->device transfer per dispatch.  Requires
    32-byte messages (the framework always signs a blake2b-256 block digest,
    types.py signed_digest); malformed-length items are masked out via
    host_ok rather than raising, matching verify-returns-False semantics.
    The three inputs are sequences of bytes objects or (n, width) uint8
    arrays (``_pack_fixed_rows``); either way each column's bytes are read
    as words where they lie and written once, into the blob.
    """
    sig_arr, sig_ok = _pack_fixed_rows(signatures, 64)
    pk_arr, pk_ok = _pack_fixed_rows(public_keys, 32)
    msg_arr, msg_ok = _pack_fixed_rows(messages, 32)
    _note_pack(public_keys, messages, signatures)
    blob = np.empty((len(sig_arr), 33), np.uint32)
    blob[:, :8] = sig_arr[:, :32].view(">u4")
    blob[:, 8:16] = pk_arr.view(">u4")
    blob[:, 16:24] = msg_arr.view(">u4")
    blob[:, 24:32] = sig_arr[:, 32:].view("<u4")
    blob[:, 32] = _host_ok(sig_ok, pk_ok, msg_ok)
    return blob


def pack_bytes(
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``pack_blob``'s columns apart, for the kernels that take them so:
    (n, 24) big-endian R||A||M words, (n, 8) little-endian s words, (n,)
    host_ok."""
    blob = pack_blob(public_keys, messages, signatures)
    return blob[:, :24], blob[:, 24:32], blob[:, 32] != 0


def verify_fused_blob_impl(blob: jnp.ndarray) -> jnp.ndarray:
    """(B, 33) packed blob -> (B,) bool, everything on device."""
    return verify_fused_impl(blob[..., :24], blob[..., 24:32], blob[..., 32] != 0)


verify_fused_blob_kernel = jax.jit(verify_fused_blob_impl)
_stored_blob_kernel = StoredProgram(verify_fused_blob_kernel)


# ---------------------------------------------------------------------------
# Indexed path: the signer set is a known committee, so the public key rides
# as an INDEX into a device-resident key table instead of 32 raw bytes —
# 26 words/sig on the wire instead of 33 (~21% less host->device transfer).
# The table is uploaded once per committee.
# ---------------------------------------------------------------------------


def pk_table_words(public_keys: Sequence[bytes]) -> np.ndarray:
    """(K, 8) uint32 big-endian words of the raw 32-byte A encodings — the
    exact layout the fused blob carries in its A section."""
    arr = np.frombuffer(b"".join(public_keys), np.uint8).reshape(
        len(public_keys), 32
    )
    return np.ascontiguousarray(arr).view(">u4").astype(np.uint32)


def pack_blob_indexed(
    indices: np.ndarray,
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
    host_ok: Optional[np.ndarray] = None,
    num_keys: Optional[int] = None,
) -> np.ndarray:
    """Pack a batch into ONE (n, 26) uint32 array: columns 0-7 big-endian R
    words, 8-15 big-endian M words, 16-23 little-endian s words, 24 the key
    index, 25 the host_ok flag.

    Out-of-range indices (including the -1 "unknown key" sentinel from
    ``KeyTable.indices_for``) are masked host_ok=False here — never silently
    verified against some other table row.
    """
    idx = np.asarray(indices)
    if idx.dtype.kind == "u":
        # The wire's own index (``IndexedKeys``): nothing lies below zero.
        ok = None if num_keys is None else idx < num_keys
    else:
        idx = idx.astype(np.int64, copy=False)
        ok = idx >= 0
        if num_keys is not None:
            ok &= idx < num_keys
        idx = np.maximum(idx, 0)
    if host_ok is not None:
        ok = _host_ok(ok, np.asarray(host_ok, bool))
    sig_arr, sig_ok = _pack_fixed_rows(signatures, 64)
    msg_arr, msg_ok = _pack_fixed_rows(messages, 32)
    _note_pack(messages, signatures)
    blob = np.empty((len(sig_arr), 26), np.uint32)
    blob[:, :8] = sig_arr[:, :32].view(">u4")
    blob[:, 8:16] = msg_arr.view(">u4")
    blob[:, 16:24] = sig_arr[:, 32:].view("<u4")
    blob[:, 24] = idx
    blob[:, 25] = _host_ok(ok, sig_ok, msg_ok)
    return blob


def indexed_to_msg_words(blob: jnp.ndarray, table: jnp.ndarray):
    """Rebuild the fused-kernel inputs from an indexed blob + key table:
    gather the A words by index and splice them between R and M."""
    with jax.named_scope("ed25519_gather_keys"):
        idx = blob[..., 24].astype(jnp.int32)
        a_words = table[jnp.clip(idx, 0, table.shape[0] - 1)]
        msg_words = jnp.concatenate(
            [blob[..., :8], a_words, blob[..., 8:16]], axis=-1
        )
        return msg_words, blob[..., 16:24], blob[..., 25] != 0


def verify_fused_indexed_impl(blob: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """(B, 26) indexed blob + (K, 8) key table -> (B,) bool."""
    return verify_fused_impl(*indexed_to_msg_words(blob, table))


verify_fused_indexed_kernel = jax.jit(verify_fused_indexed_impl)
_stored_indexed_kernel = StoredProgram(verify_fused_indexed_kernel)


# ---------------------------------------------------------------------------
# Keyed-tile path: the committee keys are FIXED at table build time, so each
# key gets a full positional comb table -(v * 16^w * A) precomputed once —
# per-signature verification then needs ZERO doublings and NO on-device A
# decompression (the two dominant costs of the generic ladder: ~252 doublings
# + a ~250-mul sqrt chain per lane).  Tiles are grouped by key on the host so
# the Pallas kernel selects one key's comb per tile via scalar prefetch.
# ---------------------------------------------------------------------------


def _ext_add(p, q):
    """Python-int extended twisted-Edwards addition (add-2008-hwcd-3, a=-1,
    complete) — table generation only."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = t1 * _D2 % P * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _ext_double(p):
    """Python-int dbl-2008-hwcd (a=-1) — table generation only."""
    x1, y1, z1, _ = p
    a = x1 * x1 % P
    b = y1 * y1 % P
    c = 2 * z1 * z1 % P
    h = a + b
    e = h - (x1 + y1) * (x1 + y1)
    g = a - b
    f = c + g
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _decode_point(pk32: bytes) -> Optional[Tuple[int, int]]:
    """RFC 8032 decode of a 32-byte encoding to affine (x, y); None when the
    encoding is non-canonical or not on the curve."""
    enc = int.from_bytes(pk32, "little")
    sign, y = enc >> 255, enc & ((1 << 255) - 1)
    if y >= P:
        return None
    x = _recover_x(y, sign)
    if x is None:
        return None
    return x, y


def build_neg_key_combs(public_keys: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
    """(K, 64, 3, NLIMBS, 16) int32 Niels-form combs of -(v * 16^w * A_j),
    plus a (K,) validity mask.

    An invalid key (non-canonical / off-curve encoding) gets identity-only
    entries and valid=False; the keyed dispatch force-rejects its lanes,
    matching the generic kernel's decompression failure bit-for-bit.

    Built with python ints; all 960 affine conversions per key share ONE
    modular inversion (Montgomery batch-inversion), so a 100-key committee
    builds in seconds, once.
    """
    K = len(public_keys)
    out = np.zeros((K, _WINDOWS, 3, F.NLIMBS, 16), np.int32)
    valid = np.zeros(K, bool)
    one = F.int_to_limbs(1)
    # v=0 entries are the identity's Niels form (1, 1, 0) for every window.
    out[:, :, 0, :, 0] = one
    out[:, :, 1, :, 0] = one
    for j, pk in enumerate(public_keys):
        dec = _decode_point(bytes(pk))
        if dec is None:
            continue
        valid[j] = True
        x, y = dec
        step = (x, y, 1, x * y % P)  # 16^w * A in extended coords
        entries = []  # (w, v, point)
        for w in range(_WINDOWS):
            entry = step
            for v in range(1, 16):
                entries.append((w, v, entry))
                entry = _ext_add(entry, step)
            for _ in range(4):
                step = _ext_double(step)
        # Montgomery batch inversion of every Z.
        prefix = [1]
        for _, _, (_, _, z, _) in entries:
            prefix.append(prefix[-1] * z % P)
        inv = pow(prefix[-1], P - 2, P)
        for i in range(len(entries) - 1, -1, -1):
            w, v, (ex, ey, ez, _) = entries[i]
            zi = prefix[i] * inv % P
            inv = inv * ez % P
            xa, ya = ex * zi % P, ey * zi % P
            # Niels form of the NEGATED point (-xa, ya):
            out[j, w, 0, :, v] = F.int_to_limbs((ya + xa) % P)  # y - (-x)
            out[j, w, 1, :, v] = F.int_to_limbs((ya - xa) % P)  # y + (-x)
            out[j, w, 2, :, v] = F.int_to_limbs(
                (P - _D2 * xa % P * ya % P) % P  # 2d * (-x) * y
            )
    return out, valid


def group_blob_for_tiles(
    blob: np.ndarray, num_keys: int, tile: int, bucket: int
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Rearrange an indexed blob so every ``tile``-lane tile holds one key.

    Returns (grouped (bucket, C), tile_keys (bucket//tile,) int32,
    positions (n,) int32 — row of each original item in the grouped layout),
    or None when the per-key padding cannot fit the bucket (callers fall back
    to the generic kernel).  Padded lanes are zero rows (host_ok=0).
    """
    n = blob.shape[0]
    ntiles = bucket // tile
    idx = blob[:, 24].astype(np.int64)
    ok = blob[:, 25] != 0
    # Rejected/unknown lanes carry no constraint (host_ok=0 forces False);
    # park them under key 0.
    key = np.where(ok, np.clip(idx, 0, num_keys - 1), 0)
    counts = np.bincount(key, minlength=num_keys)
    tiles_per_key = -(-counts // tile)
    if int(tiles_per_key.sum()) > ntiles:
        return None
    tile_starts = np.zeros(num_keys, np.int64)
    np.cumsum(tiles_per_key[:-1] * tile, out=tile_starts[1:])
    order = np.argsort(key, kind="stable")
    csum = np.zeros(num_keys, np.int64)
    np.cumsum(counts[:-1], out=csum[1:])
    rank_sorted = np.arange(n, dtype=np.int64) - np.repeat(csum, counts)
    positions = np.empty(n, np.int64)
    positions[order] = tile_starts[key[order]] + rank_sorted
    grouped = np.zeros((bucket, blob.shape[1]), blob.dtype)
    grouped[positions] = blob
    tile_keys = np.zeros(ntiles, np.int32)
    tile_keys[: int(tiles_per_key.sum())] = np.repeat(
        np.arange(num_keys), tiles_per_key
    )
    return grouped, tile_keys, positions.astype(np.int32)


class DispatchPlan(NamedTuple):
    """Which form of the kernels a table's launches take: the backend
    (``_backend``), whether a chunk that one key a tile can hold is given
    to the keyed-tile kernel (the Pallas backend, unless MYSTICETI_KEYED=0),
    and the Pallas tile and interpreter flag (None off that backend).
    Resolved once a table, so that a launch reads neither ``os.environ``
    nor ``jax.default_backend()``."""

    backend: str
    keyed: bool
    tile: Optional[int]
    interpret: Optional[bool]


def resolve_plan() -> DispatchPlan:
    backend = _backend()
    if backend != "pallas":
        return DispatchPlan(backend, False, None, None)
    from . import ed25519_pallas as PK

    return DispatchPlan(
        backend,
        os.environ.get("MYSTICETI_KEYED") != "0",
        PK.default_tile(),
        PK.interpret_mode(),
    )


class IndexedKeys:
    """A batch's public keys as rows of a ``KeyTable``: the (n,) indices a
    VERIFY frame carried, unsigned, as they came off the wire.  An index
    the table does not hold stands for no key: that lane is rejected
    (``pack_blob_indexed``), and read as bytes it is the all-zero key.

    ``dispatch_batch_table`` on the same table takes the index as it is —
    no key is gathered and none is searched for.  To everything else it is
    a sequence of 32-byte keys: ``len``, slices (an ``IndexedKeys`` again),
    items and iteration (bytes), ``rows()`` (one gather)."""

    __slots__ = ("table", "index")

    def __init__(self, table: "KeyTable", index: np.ndarray) -> None:
        self.table = table
        self.index = index

    def __len__(self) -> int:
        return len(self.index)

    def __getitem__(self, at):
        if isinstance(at, slice):
            return IndexedKeys(self.table, self.index[at])
        rows = self.table.key_rows
        return rows[min(int(self.index[at]), len(rows) - 1)].tobytes()

    def __iter__(self):
        return iter([row.tobytes() for row in self.rows()])

    def rows(self) -> np.ndarray:
        """(n, 32) uint8: each lane's key, all-zero where its index is out
        of range."""
        table = self.table.key_rows
        return table[np.minimum(self.index, len(table) - 1)]


class KeyTable:
    """A committee's keys resident on device: upload once, verify by index.

    ``indices_for`` maps raw pk bytes to table rows; unknown keys map to -1
    (callers mask them out or route them through the generic path).

    ``neg_combs`` lazily builds the per-key negated comb tables for the
    keyed-tile Pallas kernel (see build_neg_key_combs).

    ``plan`` is how this table's launches are dispatched, resolved here,
    once; ``roads`` counts what its launches did on the way
    (``road_counts``): ``direct`` — the keys came as the wire's indices
    (``IndexedKeys``) and went into the blob as they were —, and
    ``keyed_tried`` — a chunk went into the keyed-tile grouping."""

    def __init__(self, public_keys: Sequence[bytes]) -> None:
        if not public_keys:
            raise ValueError("empty key table")
        if any(len(pk) != 32 for pk in public_keys):
            raise ValueError("key table entries must be 32-byte encodings")
        self.words = jnp.asarray(pk_table_words(public_keys))
        self._index = {pk: i for i, pk in enumerate(public_keys)}
        self._keys = [bytes(pk) for pk in public_keys]
        # The same lookup for keys that arrive as rows of an array: the
        # table's distinct keys sorted as 32-byte strings, and the index the
        # dict gives each (the last of its rows, where a key is held twice).
        distinct = np.frombuffer(b"".join(self._index), "S32")
        order = np.argsort(distinct)
        self._sorted_keys = distinct[order]
        self._sorted_index = np.fromiter(
            self._index.values(), np.int64, count=len(order)
        )[order]
        self._neg_combs: Optional[Tuple[jnp.ndarray, np.ndarray]] = None
        # The keys by row, and below them the all-zero key that an index
        # out of range reads as (``IndexedKeys.rows``).
        self.key_rows = np.zeros((len(self._keys) + 1, 32), np.uint8)
        self.key_rows[:-1] = np.frombuffer(
            b"".join(self._keys), np.uint8).reshape(-1, 32)
        self._size = len(self._keys)
        self.plan = resolve_plan()
        self.roads = {"direct": 0, "keyed_tried": 0}

    def __len__(self) -> int:
        return self._size

    def keys_at(self, index: np.ndarray) -> IndexedKeys:
        """The key column of a batch whose signers are rows ``index``."""
        return IndexedKeys(self, index)

    def road_counts(self) -> Tuple[int, int]:
        """(``direct``, ``keyed_tried``) launches so far."""
        with _dispatch_count_lock:
            return self.roads["direct"], self.roads["keyed_tried"]

    def _took(self, road: str) -> None:
        with _dispatch_count_lock:  # the service launches from three threads
            self.roads[road] += 1

    def indices_for(self, public_keys: Sequence[bytes]) -> np.ndarray:
        """The table row of each key, -1 where the table holds no such key:
        one dict lookup a key for a sequence of bytes objects, one binary
        search of the sorted table for an (n, 32) uint8 array — the same
        answer either way."""
        n = len(public_keys)
        if not _is_rows(public_keys):
            return np.fromiter(
                (self._index.get(pk, -1) for pk in public_keys),
                np.int64,
                count=n,
            )
        if public_keys.shape[1] != 32:
            return np.full(n, -1, np.int64)
        # A fixed-width byte string compares whole (numpy ignores trailing
        # NULs on both sides alike, so equal means equal in all 32 bytes).
        rows = np.ascontiguousarray(public_keys).view("S32")[:, 0]
        at = self._sorted_keys.searchsorted(rows)
        hit = self._sorted_keys.take(at, mode="clip") == rows
        return np.where(hit, self._sorted_index.take(at, mode="clip"), -1)

    def neg_combs(self) -> Tuple[jnp.ndarray, np.ndarray]:
        """(device (K, 64, 3, NLIMBS, 16) comb array, (K,) host valid mask)."""
        if self._neg_combs is None:
            arr, valid = build_neg_key_combs(self._keys)
            self._neg_combs = (jnp.asarray(arr), valid)
        return self._neg_combs


def _dispatch_indexed(blob, table, plan: Optional[DispatchPlan] = None) -> jnp.ndarray:
    """One launch of the generic ladder over a bucket-shaped indexed blob;
    ``table`` is the key table's words.  ``plan``: the table's, where the
    caller has it (None resolves one here)."""
    if plan is None:
        plan = resolve_plan()
    _note_kernel("indexed", blob.shape[0], plan.backend)
    if plan.backend == "pallas":
        from . import ed25519_pallas as PK

        return PK.verify_fused_indexed_blob_pallas(
            blob, table, tile=plan.tile, interpret=plan.interpret
        )
    return _stored_indexed_kernel(blob, table)


def _one_key_a_tile(index: np.ndarray, num_keys: int, tile: int, bucket: int) -> bool:
    """Whether the keyed-tile grouping of a chunk can fit ``bucket``, from
    its index column alone: ``sum(ceil(count_k / tile)) <= bucket / tile``.
    Where the bucket is one tile that is one signer, and costs one look.
    (``group_blob_for_tiles`` still has the last word: it parks rejected
    lanes under key 0, which this does not see.)"""
    if bucket <= tile:
        return bool(index.min() == index.max())
    counts = np.bincount(np.minimum(index, num_keys - 1), minlength=num_keys)
    return int((-(-counts // tile)).sum()) <= bucket // tile


def _dispatch_indexed_keyed(chunk: np.ndarray, table: "KeyTable", bucket: int):
    """Keyed-tile Pallas dispatch (zero doublings, no A decompression);
    returns None when the per-key tile padding doesn't fit the bucket —
    callers fall back to the generic ladder."""
    from . import ed25519_pallas as PK

    plan = table.plan  # without a tile off the Pallas backend (a test's call)
    tile = min(plan.tile or PK.default_tile(), bucket)
    acomb, valid = table.neg_combs()
    spans.request_stage("service_pack")  # tile grouping
    if not valid.all():
        # Lanes under an off-curve committee key must reject exactly like the
        # generic kernel's decompression failure; the identity comb entries
        # would otherwise turn them into an [s]B == R check.
        chunk = chunk.copy()
        keyv = np.clip(chunk[:, 24].astype(np.int64), 0, len(valid) - 1)
        chunk[:, 25] &= valid[keyv]
    g = group_blob_for_tiles(chunk, len(table), tile, bucket)
    if g is None:
        return None
    grouped, tile_keys, positions = g
    # positions stay on HOST (fetch_handles un-permutes after the transfer):
    # the device only needed them for a final gather, so only the grouped
    # blob and the per-tile key ids count as upload traffic.  The narrower
    # 96 B/sig flat layout (verify_keyed_flat) is not the deployed path.
    spans.request_stage("service_launch")
    _note_transfer("to_device", grouped.nbytes + tile_keys.nbytes)
    _note_kernel("keyed", bucket, "pallas")
    handle = PK.verify_keyed_blob(
        grouped, table.words, acomb, tile_keys, None, tile=tile,
        interpret=plan.interpret,
    )
    return handle, positions


def dispatch_indexed_chunks(blob: np.ndarray, table: "KeyTable"):
    """Bucket-shaped async dispatch of an indexed blob (pack_blob_indexed
    layout); returns fetch_handles entries — ``(count, handle)`` for generic
    chunks, ``(count, handle, positions)`` for keyed-tile chunks whose
    results come back in GROUPED order (fetch_handles un-permutes on host).

    On the Pallas backend a chunk takes the keyed-tile kernel when its
    per-key grouping fits the bucket, and the generic ladder otherwise.
    Whether it can fit is read off the index column first
    (``_one_key_a_tile``), so a chunk of several signers in a one-tile
    bucket — every launch of a draining queue — never enters the grouping.
    MYSTICETI_KEYED=0 disables the keyed path (``table.plan``: both
    variables are read once a table)."""
    plan = table.plan
    handles = []
    for start, count, b in iter_buckets(blob.shape[0]):
        chunk = blob[start : start + count]
        hp = None
        if plan.keyed and _one_key_a_tile(
            chunk[:, 24], len(table), min(plan.tile, b), b
        ):
            table._took("keyed_tried")
            hp = _dispatch_indexed_keyed(chunk, table, b)
        if hp is None:
            spans.request_stage("service_pack")
            padded = _pad_to(chunk, b)
            spans.request_stage("service_launch")
            _note_transfer("to_device", padded.nbytes)
            h = _dispatch_indexed(padded, table.words, plan)
            handles.append((count, h))
        else:
            h, positions = hp
            handles.append((count, h, positions))
    return handles


class VerifyDispatch:
    """Future-like handle over one batch's in-flight bucket dispatches.

    The explicit seam of the staged verify pipeline: ``dispatch_batch*``
    packs on the host (numpy) and submits every bucket chunk through JAX's
    async dispatch, returning immediately; ``result()`` forces everything
    with ONE combined device sync (``fetch_handles``) only at consumption.
    Between the two, the caller can pack and submit further batches — the
    device streams chunk after chunk instead of idling a full round-trip
    per dispatch.
    """

    __slots__ = ("_entries",)

    def __init__(self, entries) -> None:
        self._entries = list(entries)

    def result(self) -> np.ndarray:
        # Every launch is made (``_launched``) and nothing below needs the
        # interpreter until the verdicts are on the host: the verifier
        # service lets its next launch leave from here.
        spans.request_fetch()
        return fetch_handles(self._entries)


def _fetch_early(handle) -> None:
    """Ask for a launch's verdicts on the host as the launch is made, so
    that the copy follows the program without waiting for the fetching
    thread's turn (``np.asarray`` then finds it under way or done)."""
    handle.copy_to_host_async()


def _launched(handles) -> VerifyDispatch:
    """The handle over a batch's launches.  Where that is one launch —
    every launch of the verifier service — its result is asked for at
    once; several are joined on the device first (``fetch_handles``), so
    their own copies would be wasted."""
    if len(handles) == 1:
        _fetch_early(handles[0][1])
    return VerifyDispatch(handles)


def dispatch_batch_table(
    table: "KeyTable",
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> VerifyDispatch:
    """Non-blocking committee-indexed dispatch: pack (host) + submit every
    bucket chunk asynchronously; the returned handle fetches on demand.

    A batch that holds ANY signer the table does not know goes whole to the
    unknown-signer kernel (``dispatch_batch``), its committee keys as raw
    bytes like the rest: one launch, where an indexed launch over every
    lane plus a generic launch of the strangers would run the ladder twice
    for a block whose transactions are signed by accounts."""
    n = len(signatures)
    if n == 0:
        return VerifyDispatch([])
    digests = _all_digests(messages)
    by_index = isinstance(public_keys, IndexedKeys)
    if by_index and not (digests and public_keys.table is table):
        by_index, public_keys = False, public_keys.rows()
    if not digests:
        return dispatch_batch(public_keys, messages, signatures)
    # Inside the verifier service the request is in service_pack from here
    # and in service_launch around each jitted call (spans.request_stage:
    # a stage lasts until the next is named; outside a request it is a
    # no-op).
    spans.request_stage("service_pack")
    if by_index:
        # The index stays an index: a lane whose index the table does not
        # hold is rejected in its place, not the launch re-routed.
        idx = public_keys.index
        table._took("direct")
    else:
        idx = table.indices_for(public_keys)
        if (idx < 0).any():
            return dispatch_batch(public_keys, messages, signatures)
    blob = pack_blob_indexed(idx, messages, signatures, num_keys=len(table))
    return _launched(dispatch_indexed_chunks(blob, table))


def verify_batch_table(
    table: "KeyTable",
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> np.ndarray:
    """verify_batch against a known signer set: per-sig transfer drops to 26
    words.  Items whose pk is not in the table fall back to the generic path
    (correctness is identical; only the wire format differs)."""
    return dispatch_batch_table(
        table, public_keys, messages, signatures
    ).result()


# ---------------------------------------------------------------------------
# Host-side packing
# ---------------------------------------------------------------------------


def _windows_lsb_first(x: int) -> np.ndarray:
    return np.array([(x >> (4 * w)) & 15 for w in range(_WINDOWS)], dtype=np.int32)


def _ylimbs_and_sign(data32: bytes) -> Tuple[np.ndarray, int, int]:
    """Parse a 32-byte point encoding: (y limbs, sign bit, y-as-int)."""
    enc = int.from_bytes(data32, "little")
    sign = enc >> 255
    y = enc & ((1 << 255) - 1)
    return F.int_to_limbs(y), sign, y


def pack_batch(
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> Tuple[np.ndarray, ...]:
    """Host-side preparation of a verification batch.

    Computes k = SHA-512(R || A || M) mod L per item (the fused on-device
    digest path replaces this for 32-byte block digests), performs the cheap
    integer checks, and packs limb/bit arrays for :func:`verify_kernel`.
    """
    # This path hashes on the host, a signature at a time: rows of an array
    # become the bytes objects that takes.
    public_keys, messages, signatures = (
        [bytes(row) for row in c] if _is_rows(c) else c
        for c in (public_keys, messages, signatures)
    )
    n = len(signatures)
    a_y = np.zeros((n, F.NLIMBS), np.int32)
    a_sign = np.zeros(n, np.int32)
    r_y = np.zeros((n, F.NLIMBS), np.int32)
    r_sign = np.zeros(n, np.int32)
    s_bits = np.zeros((n, _WINDOWS), np.int32)
    k_bits = np.zeros((n, _WINDOWS), np.int32)
    host_ok = np.zeros(n, bool)
    for i, (pk, msg, sig) in enumerate(zip(public_keys, messages, signatures)):
        if len(pk) != 32 or len(sig) != 64:
            continue
        r_bytes, s_bytes = sig[:32], sig[32:]
        s = int.from_bytes(s_bytes, "little")
        if s >= L:
            continue  # non-canonical s: reject (RFC 8032 / OpenSSL)
        limbs, sign, y = _ylimbs_and_sign(pk)
        if y >= P:
            continue  # non-canonical A encoding
        a_y[i], a_sign[i] = limbs, sign
        r_limbs, rs, ry = _ylimbs_and_sign(r_bytes)
        if ry >= P:
            # Non-canonical R encoding: OpenSSL's memcmp of encode([s]B - [k]A)
            # against the raw R bytes can never match a y >= p encoding, so
            # reject on host.  Keeps the device compare (eq_canonical, which
            # would reduce mod p) exactly equivalent to memcmp semantics.
            continue
        r_y[i], r_sign[i] = r_limbs, rs
        k = int.from_bytes(hashlib.sha512(r_bytes + pk + msg).digest(), "little") % L
        s_bits[i] = _windows_lsb_first(s)
        k_bits[i] = _windows_lsb_first(k)
        host_ok[i] = True
    return a_y, a_sign, r_y, r_sign, s_bits, k_bits, host_ok


# Fixed device batch shapes: every dispatch is padded up to one of these, so
# XLA compiles at most len(BUCKETS) variants per process (shape stability is
# the TPU contract; stragglers ride as padding lanes with host_ok=False).
# All are multiples of the Pallas tile (256) used on real TPUs.  The top
# bucket matters for throughput: the VMEM ladder amortizes better at 16k
# lanes (~515k sig/s on v5e vs ~450k at 4k).
BUCKETS = (256, 1024, 4096, 16384)


def _backend() -> str:
    """'pallas' (VMEM-resident ladder) on real TPUs, 'xla' elsewhere;
    override with MYSTICETI_VERIFY_BACKEND=xla|pallas."""
    forced = os.environ.get("MYSTICETI_VERIFY_BACKEND")
    if forced in ("xla", "pallas"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Host attribution plane: device-side counters (the JAX half of
# profiling.py's per-subsystem accountant).  All host-side bookkeeping — no
# kernel changes.

_attr_metrics = None
_attr_listeners_installed = False

# Process-wide compile accounting (``COMPILE_STATS``, kept by ``programs``:
# the ``jax.monitoring`` listeners below feed XLA's side of it, the program
# store its own), and a count of dispatches per (kernel, bucket, backend).
# Together they let a caller (the verifier service's boot report,
# chip_smoke.py) show which kernel form actually ran, whether its program
# was loaded or traced, and whether it compiled or came from the persistent
# cache — a silent switch to the XLA form or to the interpreter is visible
# here, not just in the timing.
KERNEL_DISPATCHES: dict = {}
_dispatch_count_lock = threading.Lock()  # the service dispatches from a pool


def _note_kernel(kernel: str, bucket: int, backend: str) -> None:
    """``bucket`` is the lanes dispatched: the bucket, or what a mesh pads
    it to (``parallel.mesh.mesh_lanes``)."""
    if programs.is_preparing():
        return  # a boot making the kernel's program: nothing is launched
    key = (kernel, int(bucket), backend)
    with _dispatch_count_lock:
        KERNEL_DISPATCHES[key] = KERNEL_DISPATCHES.get(key, 0) + 1


def dispatch_counts() -> list:
    """KERNEL_DISPATCHES as JSON-ready rows, for reports."""
    with _dispatch_count_lock:
        counts = sorted(KERNEL_DISPATCHES.items())
    return [
        {"kernel": k, "bucket": b, "backend": be, "count": n}
        for (k, b, be), n in counts
    ]


def _on_event(event: str, **kwargs) -> None:
    m = _attr_metrics
    if "cache_hit" in event:
        COMPILE_STATS["cache_hits"] += 1
        if m is not None:
            m.mysticeti_jax_cache_hits_total.inc()
    elif "cache_miss" in event or "cache_nonhit" in event:
        COMPILE_STATS["cache_misses"] += 1
        if m is not None:
            m.mysticeti_jax_cache_misses_total.inc()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    # One backend compile (or load from the persistent cache) a program.
    # Every other duration JAX reports lives under ``/jax/core/compile/``
    # too — one ``jaxpr_trace_duration`` a jitted function traced, tens of
    # thousands a kernel — and is no compile.
    if not event.endswith("backend_compile_duration"):
        return
    COMPILE_STATS["backend_compile_s"] += max(0.0, duration)
    m = _attr_metrics
    if m is not None:
        m.mysticeti_jax_compiles_total.inc()
        m.mysticeti_jax_compile_seconds_total.inc(max(0.0, duration))


def install_compile_listeners() -> None:
    """Register the ``jax.monitoring`` listeners that feed COMPILE_STATS
    (and the node registry, once ``install_device_attribution`` names one).
    Idempotent."""
    global _attr_listeners_installed
    if _attr_listeners_installed:
        return
    from jax import monitoring as _monitoring

    _monitoring.register_event_listener(_on_event)
    _monitoring.register_event_duration_secs_listener(_on_duration)
    _attr_listeners_installed = True


def install_device_attribution(metrics) -> bool:
    """Wire JAX compile events, compile-cache hits/misses, and the transfer
    byte and pack-call counters below into the node's registry
    (``mysticeti_jax_*``, ``mysticeti_device_transfer_bytes_total`` and
    ``verify_pack_rows_total``, metrics.py).  Called once by
    validators that verify in-process; re-calling swaps the target registry.
    Returns whether the ``jax.monitoring`` listeners landed (the module is
    semi-private, so every hook is best-effort)."""
    global _attr_metrics
    _attr_metrics = metrics
    if metrics is not None:
        # Both forms are scraped from the start: "no pack call took the
        # objects form" reads 0, not an absent series.
        for form in ("array", "objects"):
            metrics.verify_pack_rows_total.labels(form)
    try:
        install_compile_listeners()
        return True
    except Exception:  # noqa: BLE001 - attribution must never break verify
        return False


# What the dispatch path counts for the registry, each thread for itself:
# (series, label value) by slot of the thread's list.
_NOTED = (
    ("mysticeti_device_transfer_bytes_total", "to_device"),
    ("mysticeti_device_transfer_bytes_total", "from_device"),
    ("verify_pack_rows_total", "array"),
    ("verify_pack_rows_total", "objects"),
)
_SLOT = {label: slot for slot, (_, label) in enumerate(_NOTED)}
_TRANSFER_FLUSH_S = 0.5
# Per thread: one sum a slot of ``_NOTED``, then when the sums last moved to
# the registry.
_transfer_local = threading.local()


def _note(slot: int, amount: int) -> None:
    """Add ``amount`` to the calling thread's sum for ``_NOTED[slot]`` and,
    twice a second, move the thread's sums into the registry.

    Called a few times a launch from the service's dispatcher threads, so
    each thread adds to a list of its own (a thread that falls idle keeps
    what it noted since, until its next call): one locked prometheus child
    a call cost the service 3.6% of its throughput on the chip's host
    (PERF.md, PR 24)."""
    m = _attr_metrics
    if m is None or programs.is_preparing():
        return
    try:
        pending = _transfer_local.pending
    except AttributeError:
        pending = _transfer_local.pending = [0] * len(_NOTED) + [0.0]
    pending[slot] += amount
    now = time.monotonic()
    if now - pending[-1] < _TRANSFER_FLUSH_S:  # lint: ignore[sim-taint] — when a counter reaches the registry; nothing reads it back
        return
    pending[-1] = now
    for i, (series, label) in enumerate(_NOTED):
        if pending[i]:
            getattr(m, series).labels(label).inc(pending[i])
            pending[i] = 0


def _note_transfer(direction: str, nbytes: int) -> None:
    """Count host<->device bytes at the dispatch/fetch seams: JAX exposes no
    portable transfer counter, but every verifier transfer flows through
    dispatch_blob_chunks / dispatch_batch / fetch_handles, so counting the
    (padded) array sizes there IS the device link traffic."""
    if nbytes > 0:
        _note(_SLOT[direction], nbytes)


def _note_pack(*columns) -> None:
    """Count one pack call by the form its columns arrived in: ``array``
    when every one is rows of a byte array, nothing between the wire and
    the blob having run once a signature; ``objects`` when any is a
    sequence of bytes objects.  The verifier service must count ``array``
    on every launch."""
    _note(_SLOT["array" if all(_is_rows(c) for c in columns) else "objects"], 1)


def _dispatch_packed(*arrays) -> jnp.ndarray:
    """Async dispatch of one host-hashed chunk (``pack_batch`` layout —
    messages that are not 32-byte digests): the same ladder as the fused
    paths, so on a TPU it is the Pallas kernel here too."""
    backend = _backend()
    _note_kernel("packed", arrays[0].shape[0], backend)
    if backend == "pallas":
        from . import ed25519_pallas as PK

        return PK.verify_pallas(*arrays)
    return verify_kernel(*arrays)


def _dispatch_blob(blob) -> jnp.ndarray:
    """Async dispatch of one packed blob chunk; returns the device handle.
    The chunk must already be bucket-shaped (use dispatch_blob_chunks)."""
    backend = _backend()
    _note_kernel("blob", blob.shape[0], backend)
    if backend == "pallas":
        from . import ed25519_pallas as PK

        return PK.verify_fused_blob_pallas(blob)
    return _stored_blob_kernel(blob)


def iter_buckets(n: int):
    """Yield (start, count, bucket) chunk descriptors covering n items with
    the fixed bucket shapes — the single source of truth for chunking.

    Rounding up to the next bucket is taken only when the padding stays
    under 25% of that bucket; otherwise the largest bucket that fits is
    dispatched full and the remainder recurses.  This keeps wasted lanes
    small (5000 items -> 4096 + 1024 lanes, not one 16384-lane dispatch)
    without fragmenting near-bucket batches into many tiny chunks."""
    start = 0
    while start < n:
        rem = n - start
        s = next((c for c in BUCKETS if c >= rem), None)
        g = next((c for c in reversed(BUCKETS) if c <= rem), None)
        if s is not None and (g is None or s - rem <= s // 4):
            yield start, rem, s
            return
        b = g if g is not None else BUCKETS[0]
        count = min(b, rem)
        yield start, count, b
        start += count


def dispatch_blob_chunks(blob: np.ndarray):
    """Slice a packed (n, 33) blob into fixed-bucket chunks, pad each, and
    dispatch all of them asynchronously.  Returns [(count, device handle)];
    force with np.asarray(handle)[:count]."""
    out = []
    for start, count, b in iter_buckets(blob.shape[0]):
        spans.request_stage("service_pack")
        padded = _pad_to(blob[start : start + count], b)
        spans.request_stage("service_launch")
        _note_transfer("to_device", padded.nbytes)
        out.append((count, _dispatch_blob(padded)))
    return out


def fetch_handles(handles) -> np.ndarray:
    """Force a list of ``(count, device_handle[, positions])`` chunk results
    with ONE device sync: concatenate the (padded) outputs on device,
    transfer once, then drop padding / un-permute grouped-order keyed
    results on host.

    Per-handle ``np.asarray`` costs a full device round-trip each; the
    single combined fetch pays one.
    """
    if not handles:
        return np.zeros(0, bool)
    # Entries are (count, handle) in dispatch order, or (count, handle,
    # positions) for keyed-tile chunks whose results come back in GROUPED
    # order (positions maps original row -> grouped row; un-permuted here,
    # on host, so they never ride the upload link).
    unpacked = [
        (e[0], e[1], e[2] if len(e) > 2 else None) for e in handles
    ]
    if len(unpacked) == 1:
        count, h, positions = unpacked[0]
        res = np.asarray(h)
        _note_transfer("from_device", res.nbytes)
        if positions is not None:
            return np.array(res[positions])
        # np.array (not asarray): a writable copy, matching the multi-chunk
        # path — callers patch straggler entries in place.  The copy is a
        # bool row per signature, noise next to the transfer itself.
        return np.array(res[:count])
    flat = np.asarray(jnp.concatenate([h for _, h, _ in unpacked]))
    _note_transfer("from_device", flat.nbytes)
    out = np.empty(sum(count for count, _, _ in unpacked), bool)
    src = dst = 0
    for count, h, positions in unpacked:
        chunk = flat[src : src + h.shape[0]]
        if positions is not None:
            out[dst : dst + count] = chunk[positions]
        else:
            out[dst : dst + count] = chunk[:count]
        src += h.shape[0]
        dst += count
    return out


def dispatch_batch(
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> VerifyDispatch:
    """Non-blocking batched dispatch: the pack stage runs here on the host
    (pure numpy for the fused path; the per-item SHA-512 loop otherwise),
    every bucket chunk is submitted through JAX's async dispatch, and the
    returned handle fetches on demand — ``block_until_ready`` semantics only
    at consumption."""
    n = len(signatures)
    if n == 0:
        return VerifyDispatch([])
    spans.request_stage("service_pack")
    if _all_digests(messages):
        blob = pack_blob(public_keys, messages, signatures)
        # Dispatch every chunk asynchronously (one transfer each); the
        # handle forces all results with a single combined fetch, so device
        # work and transfers overlap across chunks and only one round-trip
        # is paid at the end.
        return _launched(dispatch_blob_chunks(blob))
    arrays = pack_batch(public_keys, messages, signatures)
    handles = []
    for start, count, b in iter_buckets(n):
        spans.request_stage("service_pack")
        padded = [_pad_to(x[start : start + count], b) for x in arrays]
        spans.request_stage("service_launch")
        _note_transfer("to_device", sum(p.nbytes for p in padded))
        handles.append(
            (count, _dispatch_packed(*[jnp.asarray(p) for p in padded]))
        )
    return VerifyDispatch(handles)


def verify_batch(
    public_keys: Sequence[bytes],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
) -> np.ndarray:
    """End-to-end batched verify; returns np.ndarray of bool, one per item.

    Fused path (32-byte messages — always true for block digests): bytes are
    packed with pure numpy and everything else happens on device.  Other
    message lengths fall back to the host-hash packing path.
    """
    return dispatch_batch(public_keys, messages, signatures).result()


def _pad_to(x: np.ndarray, size: int) -> np.ndarray:
    """``x`` itself where it already fills the bucket, else a fresh array of
    the bucket's height with zero rows below it: either way a buffer nobody
    writes again, so the device may read it for as long as it likes."""
    if x.shape[0] == size:
        return np.ascontiguousarray(x)
    out = np.zeros((size,) + x.shape[1:], x.dtype)
    out[: x.shape[0]] = x
    return out
