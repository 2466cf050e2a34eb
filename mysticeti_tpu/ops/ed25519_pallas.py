"""Pallas TPU kernel for batched Ed25519 verification — the VMEM-resident ladder.

Why this exists: the XLA graph version (:mod:`mysticeti_tpu.ops.ed25519`)
materializes every intermediate limb array between ops, so the 256-step
double-and-add ladder is HBM-bandwidth-bound (~50k sig/s measured on v5e
despite ~8.6G field-muls/s of raw VPU throughput).  This kernel runs the
*entire* verification inside one ``pallas_call`` whose working set lives in
VMEM, tiled over the batch: the launch's preparation (``_prepare``: the
challenge hash SHA-512(R || A || M), its reduction mod L, ``s < L``,
``A < p``, bytes to limbs, the 4-bit windows of s and k), then
decompression, the per-item table build, the fused [s]B + [k](-A) window
loop, the final inversion and the canonical compare.  A launch is that one
device program: beside the call an entry point keeps the blob's transpose
to lanes minor, the key table's gather by index and the verdicts' cast.
(As ~2,400 XLA ops around the call the preparation was a third of a
launch's device time: PERF.md, PR 44.)

Layout: limb-major ``(NLIMBS, TILE)`` so the batch dimension maps to TPU
*lanes* (128-wide) and the 20 limbs to sublanes; every field op is then a
handful of dense vector registers.  The preparation keeps that shape: a
32-bit word or a limb is a row of the tile's lanes.  Field arithmetic is the
same 20x13-bit int32 schoolbook design as :mod:`mysticeti_tpu.ops.field`
(see its module docstring for the carry discipline) transposed to limb-major
form, and the scalar arithmetic is :mod:`mysticeti_tpu.ops.scalar`'s the
same way; the hash's word arithmetic is :mod:`mysticeti_tpu.ops.sha512`'s
own, called on rows.

Replaces the reference's serial per-block CPU verify
(``mysticeti-core/src/crypto.rs:174-189``, call site ``types.rs:315-347``).
Verification rule is identical to ``ops/ed25519.verify_impl`` (cofactorless,
OpenSSL memcmp semantics) and the preparation's to
``ops/ed25519.prepare_fused`` (the ``xla`` backend's, and the reference);
parity is enforced in tests/test_ed25519_pallas.py, test_ed25519_fused.py
and test_ed25519_keyed.py.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ed25519 as E
from . import field as F
from . import scalar as SC
from . import sha512 as H
from .programs import StoredProgram

RADIX = F.RADIX
NLIMBS = F.NLIMBS
MASK = F.MASK
FOLD_260 = F.FOLD_260
FOLD_256 = F.FOLD_256
_WORK = 2 * NLIMBS + 2

Point = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]

# ---------------------------------------------------------------------------
# Limb-major field arithmetic: every element is (NLIMBS, T) int32, batch on
# the minor (lane) axis.  Constants broadcast from (NLIMBS, 1).
# ---------------------------------------------------------------------------

def _cst(x: int) -> np.ndarray:
    return F.int_to_limbs(x % F.P).reshape(NLIMBS, 1)


# Pallas kernels cannot close over array constants — the six field constants
# (+ a zero plane) are passed as one (7, NLIMBS, tile) input (_consts_wide)
# and re-bound to this namespace at kernel trace time (_bind_consts).
#
# THREAD-LOCAL: kernel flavors trace concurrently in a validator (the
# verifier warmup thread compiles one kernel while a peer batch traces
# another on an executor thread); a shared namespace lets one trace read the
# other's bindings mid-trace, which surfaces as a "captures constants"
# pallas error (or silently wrong constants).  Each tracing thread gets its
# own bindings.
import threading as _threading


class _ConstNS(_threading.local):
    one: jnp.ndarray
    bias_8p: jnp.ndarray
    p_limbs: jnp.ndarray
    d: jnp.ndarray
    d2: jnp.ndarray
    sqrt_m1: jnp.ndarray
    zero: jnp.ndarray


_C = _ConstNS()

_CONSTS_NP = np.concatenate(
    [
        _cst(1),
        np.array(
            [(1 << RADIX) - 152] + [MASK] * 18 + [(1 << 11) - 1], dtype=np.int32
        ).reshape(NLIMBS, 1),
        np.array(
            [(1 << RADIX) - 19] + [MASK] * 18 + [255], dtype=np.int32
        ).reshape(NLIMBS, 1),
        _cst(E._D),
        _cst(E._D2),
        _cst(E._SQRT_M1),
    ],
    axis=1,
)  # (NLIMBS, 6)


def _consts_wide(tile: int) -> np.ndarray:
    """(7, NLIMBS, tile): the six field constants + a zero plane, materialized
    lane-wide on the host.  In-kernel ``jnp.broadcast_to``/``zeros`` produce
    Mosaic "replicated" vector layouts, and slicing those crashes the Mosaic
    layout pass — loading real data from VMEM sidesteps the whole class of
    bugs and costs only 7*20*tile*4 bytes."""
    cols = np.concatenate([_CONSTS_NP[:, :6], np.zeros((NLIMBS, 1), np.int32)], axis=1)
    return np.ascontiguousarray(
        np.broadcast_to(cols.T[:, :, None], (7, NLIMBS, tile)).astype(np.int32)
    )


def _bind_consts(consts_ref) -> None:
    _C.one = consts_ref[0]
    _C.bias_8p = consts_ref[1]
    _C.p_limbs = consts_ref[2]
    _C.d = consts_ref[3]
    _C.d2 = consts_ref[4]
    _C.sqrt_m1 = consts_ref[5]
    _C.zero = consts_ref[6]


def _carry(x: jnp.ndarray) -> jnp.ndarray:
    c = x >> RADIX
    x = x - (c << RADIX)
    return x + jnp.concatenate([jnp.zeros_like(c[:1]), c[:-1]], axis=0)


def _normalize_top(x: jnp.ndarray) -> jnp.ndarray:
    c = x[NLIMBS - 1 : NLIMBS] >> 9
    x = jnp.concatenate(
        [x[:1] + FOLD_256 * c, x[1 : NLIMBS - 1], x[NLIMBS - 1 :] - (c << 9)], axis=0
    )
    return _carry(x)


def _fold_reduce(wide: jnp.ndarray) -> jnp.ndarray:
    # One carry pass on the wide (42, T) array: diagonal sums < 2^30.4 decay
    # to limbs <= 2^17.4.  Folding immediately is then safe (608 * 2^17.4 +
    # 2^17.4 < 2^27) and moves all later carry work onto a cheap 21-limb
    # workspace instead of the 42-limb one.
    x = _carry(wide)
    lo = jnp.concatenate([x[:NLIMBS], jnp.zeros_like(x[:1])], axis=0)  # (21, T)
    lo = lo + FOLD_260 * x[NLIMBS : 2 * NLIMBS + 1]
    lo = _carry(_carry(lo))
    lo = jnp.concatenate(
        [lo[:1] + FOLD_260 * lo[NLIMBS : NLIMBS + 1], lo[1:NLIMBS]], axis=0
    )
    c = lo[NLIMBS - 1 : NLIMBS] >> RADIX
    lo = jnp.concatenate(
        [lo[:1] + FOLD_260 * c, lo[1 : NLIMBS - 1], lo[NLIMBS - 1 :] - (c << RADIX)],
        axis=0,
    )
    return _normalize_top(_carry(lo))


def fmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    wide = None
    for i in range(NLIMBS):
        term = a[i : i + 1] * b  # (NLIMBS, T)
        padded = jnp.pad(term, ((i, _WORK - NLIMBS - i), (0, 0)))
        wide = padded if wide is None else wide + padded
    return _fold_reduce(wide)


def fsq(a: jnp.ndarray) -> jnp.ndarray:
    # Triangle squaring was measured perf-neutral here (concat overhead eats
    # the halved product count) — plain schoolbook keeps the code simple.
    return fmul(a, a)


def fadd(a, b):
    # Lazy add: one signed carry pass, top limb left loose (< 2^11 after the
    # shallow add chains in the point formulas) — products and fsub's 8p bias
    # tolerate it, and _fold_reduce restores the tight form after every mul.
    return _carry(a + b)


def fsub(a, b):
    return _normalize_top(_carry(_carry(a + _C.bias_8p - b)))


def fneg(a):
    return fsub(_C.zero, a)


def fpow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    return jax.lax.fori_loop(0, k, lambda _, x: fsq(x), a)


def _ladder_chain(z):
    z2 = fsq(z)
    z9 = fmul(fsq(fsq(z2)), z)
    z11 = fmul(z9, z2)
    z2_5_0 = fmul(fsq(z11), z9)
    z2_10_0 = fmul(fpow2k(z2_5_0, 5), z2_5_0)
    z2_20_0 = fmul(fpow2k(z2_10_0, 10), z2_10_0)
    z2_40_0 = fmul(fpow2k(z2_20_0, 20), z2_20_0)
    z2_50_0 = fmul(fpow2k(z2_40_0, 10), z2_10_0)
    z2_100_0 = fmul(fpow2k(z2_50_0, 50), z2_50_0)
    z2_200_0 = fmul(fpow2k(z2_100_0, 100), z2_100_0)
    z2_250_0 = fmul(fpow2k(z2_200_0, 50), z2_50_0)
    return z11, z2_250_0


def finv(z):
    z11, z2_250_0 = _ladder_chain(z)
    return fmul(fpow2k(z2_250_0, 5), z11)


def fpow22523(z):
    _, z2_250_0 = _ladder_chain(z)
    return fmul(fpow2k(z2_250_0, 2), z)


def _full_carry(x):
    return jax.lax.fori_loop(0, NLIMBS + 1, lambda _, v: _carry(v), x)


def fcanonical(x: jnp.ndarray) -> jnp.ndarray:
    for _ in range(2):
        c = x[NLIMBS - 1 : NLIMBS] >> 8
        x = jnp.concatenate(
            [x[:1] + 19 * c, x[1 : NLIMBS - 1], x[NLIMBS - 1 :] - (c << 8)], axis=0
        )
        x = _full_carry(x)
    ge_p = (
        (x[NLIMBS - 1 : NLIMBS] == 255)
        & jnp.all(x[1 : NLIMBS - 1] == MASK, axis=0, keepdims=True)
        & (x[:1] >= (1 << RADIX) - 19)
    )
    return jnp.where(ge_p, x - _C.p_limbs, x)


def feq(a: jnp.ndarray, b_canonical: jnp.ndarray) -> jnp.ndarray:
    """a (partial form) == b (already canonical limbs); returns (1, T) bool."""
    return jnp.all(fcanonical(a) == b_canonical, axis=0, keepdims=True)


def fis_zero(a):
    return jnp.all(fcanonical(a) == 0, axis=0, keepdims=True)


def fparity(a):
    return fcanonical(a)[:1] & 1


# ---------------------------------------------------------------------------
# Point ops (extended twisted-Edwards, a=-1), limb-major
# ---------------------------------------------------------------------------

def point_add(p: Point, q: Point, want_t: bool = True):
    """Unified extended addition (add-2008-hwcd-3, a=-1); 9 muls, 8 with
    ``want_t=False`` (legal when the result only feeds doublings, which never
    read T)."""
    x1, y1, z1, t1 = p[0], p[1], p[2], p[3]
    x2, y2, z2, t2 = q
    a = fmul(fsub(y1, x1), fsub(y2, x2))
    b = fmul(fadd(y1, x1), fadd(y2, x2))
    c = fmul(fmul(t1, _C.d2), t2)
    d = fmul(fadd(z1, z1), z2)
    e = fsub(b, a)
    f = fsub(d, c)
    g = fadd(d, c)
    h = fadd(b, a)
    out = (fmul(e, f), fmul(g, h), fmul(f, g))
    return (*out, fmul(e, h)) if want_t else out


def point_madd(p: Point, q3) -> Point:
    """Mixed addition with a Niels-form precomputed point q3 = (y-x, y+x,
    2d*xy), Z=1 (madd-2008-hwcd): 7 muls.  Used for the fixed-base comb."""
    x1, y1, z1, t1 = p
    q_ymx, q_ypx, q_t2d = q3
    a = fmul(fsub(y1, x1), q_ymx)
    b = fmul(fadd(y1, x1), q_ypx)
    c = fmul(t1, q_t2d)
    d = fadd(z1, z1)
    e = fsub(b, a)
    f = fsub(d, c)
    g = fadd(d, c)
    h = fadd(b, a)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


def point_double(p, want_t: bool = True):
    """dbl-2008-hwcd: never reads T; emits it only when the next op is an
    addition (the 4th double of each window group)."""
    x1, y1, z1 = p[0], p[1], p[2]
    a = fsq(x1)
    b = fsq(y1)
    c = fadd(fsq(z1), fsq(z1))
    h = fadd(a, b)
    e = fsub(h, fsq(fadd(x1, y1)))
    g = fsub(a, b)
    f = fadd(c, g)
    out = (fmul(e, f), fmul(g, h), fmul(f, g))
    return (*out, fmul(e, h)) if want_t else out


def point_neg(p: Point) -> Point:
    x, y, z, t = p
    return (fneg(x), y, z, fneg(t))


def _dbl4(p, want_t: bool = True):
    """Four doublings; T materialized only on the last (if requested)."""
    p = point_double(p, want_t=False)
    p = point_double(p, want_t=False)
    p = point_double(p, want_t=False)
    return point_double(p, want_t=want_t)


def _identity(t: int) -> Point:
    del t
    return (_C.zero, _C.one, _C.one, _C.zero)


def decompress(y: jnp.ndarray, sign: jnp.ndarray) -> Tuple[Point, jnp.ndarray]:
    """y (NLIMBS, T) canonical (< p), sign (1, T); returns (point, (1,T) ok)."""
    yy = fsq(y)
    u = fsub(yy, _C.one)
    # Constant operand second: fmul slices rows of its first arg, and a row of
    # a broadcast constant is a (1,1)->both-dims broadcast Mosaic rejects.
    v = fadd(fmul(yy, _C.d), _C.one)
    v3 = fmul(fsq(v), v)
    v7 = fmul(fsq(v3), v)
    x = fmul(fmul(u, v3), fpow22523(fmul(u, v7)))
    vxx = fmul(v, fsq(x))
    vxx_c = fcanonical(vxx)
    ok_direct = jnp.all(vxx_c == fcanonical(u), axis=0, keepdims=True)
    ok_flipped = jnp.all(vxx_c == fcanonical(fneg(u)), axis=0, keepdims=True)
    x = jnp.where(ok_direct, x, fmul(x, _C.sqrt_m1))
    ok = ok_direct | ok_flipped
    x_is_zero = fis_zero(x)
    ok = ok & ~(x_is_zero & (sign == 1))
    flip = (fparity(x) != sign) & ~x_is_zero
    x = jnp.where(flip, fneg(x), x)
    point = (x, y, _C.one, fmul(x, y))
    return point, ok


def _gather16(tab: List[Point], idx: jnp.ndarray) -> Point:
    """One-hot select over a 16-entry per-item point table; idx (1, T)."""
    coords = []
    for c in range(4):
        acc = None
        for v in range(16):
            m = (idx == v).astype(jnp.int32)  # (1, T)
            t = m * tab[v][c]
            acc = t if acc is None else acc + t
        coords.append(acc)
    return tuple(coords)


def _gather_comb(entry: jnp.ndarray, idx: jnp.ndarray):
    """entry (3, NLIMBS, 16) Niels-form slice; idx (1, T) -> (ymx, ypx, t2d)."""
    coords = []
    for c in range(3):
        acc = None
        for v in range(16):
            m = (idx == v).astype(jnp.int32)  # (1, T)
            t = entry[c, :, v : v + 1] * m  # (NLIMBS, 1) * (1, T)
            acc = t if acc is None else acc + t
        coords.append(acc)
    return tuple(coords)


def _build_niels_comb() -> np.ndarray:
    """(64, 3, NLIMBS, 16): the fixed-base comb in Niels form (y-x, y+x,
    2d*xy mod p), one 16-entry table per 4-bit window of s (v * 16^w * B)."""
    raw = E._build_base_comb()  # (64, 16, 4, 20) extended (X, Y, Z=1, T)
    out = np.zeros((64, 3, NLIMBS, 16), np.int32)
    for w in range(64):
        for v in range(16):
            x = F.limbs_to_int(raw[w, v, 0])
            y = F.limbs_to_int(raw[w, v, 1])
            out[w, 0, :, v] = F.int_to_limbs((y - x) % F.P)
            out[w, 1, :, v] = F.int_to_limbs((y + x) % F.P)
            out[w, 2, :, v] = F.int_to_limbs(2 * E._D * x * y % F.P)
    return out


_COMB_T = _build_niels_comb()


# ---------------------------------------------------------------------------
# The launch's preparation, inside the call: a tile's raw words in, what the
# ladder reads out.  Same mathematics as ``ops.ed25519.prepare_fused`` (the
# ``xla`` backend's form, and the reference the tests hold this to), in the
# kernel's shape: a 32-bit word or a limb is a (1, T) row of the tile's lanes.
# Words are uint32 (the blob's own dtype: ``>>`` is a logical shift and ``<``
# an unsigned compare, so ``ops.sha512``'s word arithmetic serves as it is);
# limbs are int32, as everywhere in this module.
# ---------------------------------------------------------------------------

# Rows of the lane-wide table of the preparation's constants (a kernel cannot
# close over an array; ``_consts_wide`` says why lane-wide, and a splat in
# its place fails Mosaic's layout pass as a loop's initial state): SHA-512's
# K and H0, each as high halves then low, then the limbs of 1024*L and of L.
_PREP_K_LO, _PREP_H0, _PREP_L1024, _PREP_L, _PREP_ROWS = 80, 160, 176, 200, 224

# Rows of a launch's words: R || A || M big-endian, s little-endian, host_ok.
_S_ROW, _OK_ROW, _WORD_ROWS = 24, 32, 33


def _prep_wide(tile: int) -> np.ndarray:
    """(_PREP_ROWS, tile) uint32: the constants ``_prepare`` loads."""
    col = np.zeros(_PREP_ROWS, np.uint32)
    col[:_PREP_K_LO] = [k >> 32 for k in H._K]
    col[_PREP_K_LO:_PREP_H0] = [k & 0xFFFFFFFF for k in H._K]
    col[_PREP_H0 : _PREP_H0 + 8] = [h >> 32 for h in H._H0]
    col[_PREP_H0 + 8 : _PREP_L1024] = [h & 0xFFFFFFFF for h in H._H0]
    col[_PREP_L1024 : _PREP_L1024 + 22] = SC._int_to_limbs_np(1024 * SC.L, 22)
    col[_PREP_L : _PREP_L + NLIMBS] = SC._int_to_limbs_np(SC.L, NLIMBS)
    return np.ascontiguousarray(np.broadcast_to(col[:, None], (_PREP_ROWS, tile)))


def _row(ref, i):
    """Row ``i`` (static or traced) of a (rows, T) ref, as (1, T)."""
    return ref[pl.ds(i, 1), :]


def _sha512_96(words_ref, prep_ref, w_ref) -> jnp.ndarray:
    """SHA-512 of each lane's 96-byte R || A || M (rows 0-23 of
    ``words_ref``, big-endian words); returns the digest's 16 words the same
    way, (16, T) uint32.  ``w_ref`` is (160, T) scratch for the schedule,
    W[t]'s high half in row 2t and its low half in row 2t+1 (the message's
    own order), so that a round reads its word by a dynamic row."""
    w_ref[0:24, :] = words_ref[0:24, :]
    # The one block's padding: 0x80, zeros, the bit length.
    row = words_ref[0:1, :]
    w_ref[24:32, :] = jnp.concatenate(
        [jnp.full_like(row, x) for x in (1 << 31, 0, 0, 0, 0, 0, 0, 96 * 8)],
        axis=0,
    )
    word = lambda t: (_row(w_ref, 2 * t), _row(w_ref, 2 * t + 1))

    def schedule(t, carry):
        hi, lo = H._add_many(
            H._small_sigma1(word(t - 2)),
            word(t - 7),
            H._small_sigma0(word(t - 15)),
            word(t - 16),
        )
        w_ref[pl.ds(2 * t, 1), :] = hi
        w_ref[pl.ds(2 * t + 1, 1), :] = lo
        return carry

    jax.lax.fori_loop(16, 80, schedule, 0)

    def one_round(t, state):
        a, b, c, d, e, f, g, h = [(state[2 * i], state[2 * i + 1]) for i in range(8)]
        k = (_row(prep_ref, t), _row(prep_ref, _PREP_K_LO + t))
        t1 = H._add_many(h, H._big_sigma1(e), H._ch(e, f, g), k, word(t))
        t2 = H._add(H._big_sigma0(a), H._maj(a, b, c))
        out = (H._add(t1, t2), a, b, c, H._add(d, t1), e, f, g)
        return tuple(x for w in out for x in w)

    h0 = [
        (_row(prep_ref, _PREP_H0 + i), _row(prep_ref, _PREP_H0 + 8 + i))
        for i in range(8)
    ]
    state = jax.lax.fori_loop(0, 80, one_round, tuple(x for w in h0 for x in w))
    out = []
    for i in range(8):
        out.extend(H._add((state[2 * i], state[2 * i + 1]), h0[i]))
    return jnp.concatenate(out, axis=0)


def _words_to_limbs(words: jnp.ndarray, n_limbs: int) -> jnp.ndarray:
    """(W, T) uint32 little-endian value words -> (n_limbs, T) int32 limbs."""
    w = words.shape[0]
    out = []
    for m in range(n_limbs):
        q, r = divmod(RADIX * m, 32)
        v = words[q : q + 1] >> r
        if r + RADIX > 32 and q + 1 < w:
            v = v | (words[q + 1 : q + 2] << (32 - r))
        out.append(v & MASK)
    return jnp.concatenate(out, axis=0).astype(jnp.int32)


def _store_windows4(limbs: jnp.ndarray, out_ref) -> None:
    """The 64 4-bit windows of (NLIMBS, T) canonical limbs, LSB first, into
    ``out_ref`` ((64, T) scratch, which the ladder indexes by its step)."""
    for wnd in range(64):
        q, r = divmod(4 * wnd, RADIX)
        v = limbs[q : q + 1] >> r
        if r + 4 > RADIX and q + 1 < NLIMBS:
            v = v | (limbs[q + 1 : q + 2] << (RADIX - r))
        out_ref[wnd : wnd + 1, :] = v & 15


def _geq_const(limbs: jnp.ndarray, const: int) -> jnp.ndarray:
    """value(limbs) >= const for unique nonneg (n, T) limbs; (1, T) bool."""
    ge = eq = None  # from the top limb down
    for i in reversed(range(limbs.shape[0])):
        c = (const >> (RADIX * i)) & MASK
        limb = limbs[i : i + 1]
        ge = (limb > c) if ge is None else ge | (eq & (limb > c))
        eq = (limb == c) if eq is None else eq & (limb == c)
    return ge | eq


def _sc_carry(x: jnp.ndarray) -> jnp.ndarray:
    """One signed carry pass of scalar limbs; the TOP limb is left raw (it
    carries the sign of the whole value: ``ops.scalar._carry_once``)."""
    c = (x >> RADIX)[:-1]
    zero = jnp.zeros_like(x[:1])
    return (
        x
        - (jnp.concatenate([c, zero], axis=0) << RADIX)
        + jnp.concatenate([zero, c], axis=0)
    )


def _sc_full_carry(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.fori_loop(0, x.shape[0] + 2, lambda _, v: _sc_carry(v), x)


def _sc_mul_const(x: jnp.ndarray, const_limbs: np.ndarray) -> jnp.ndarray:
    """(n, T) signed carried limbs times a small constant limb vector: the
    (n + len(const), T) UNCARRIED product."""
    m = len(const_limbs)
    acc = None
    for j in range(m):
        if int(const_limbs[j]):
            term = jnp.pad(x * int(const_limbs[j]), ((j, m - j), (0, 0)))
            acc = term if acc is None else acc + term
    return acc


def _sc_fold(x: jnp.ndarray, out_width: int) -> jnp.ndarray:
    """value(x) == lo + 2^260*hi -> lo - 256d*hi (mod L), in out_width limbs."""
    prod = _sc_mul_const(x[NLIMBS:], SC._D256_LIMBS)
    res = jnp.pad(x[:NLIMBS], ((0, out_width - NLIMBS), (0, 0))) - jnp.pad(
        prod, ((0, out_width - prod.shape[0]), (0, 0))
    )
    return _sc_carry(_sc_carry(_sc_carry(res)))


def _mod_l(x: jnp.ndarray, prep_ref) -> jnp.ndarray:
    """A 512-bit value as (40, T) carried limbs -> its (NLIMBS, T) canonical
    limbs mod L: ``ops.scalar.mod_L`` step for step (its comments hold)."""
    x = _sc_fold(_sc_fold(_sc_fold(x, 32), 24), 22)
    bias = prep_ref[_PREP_L1024 : _PREP_L1024 + 22, :].astype(jnp.int32)
    x = _sc_full_carry(x + bias)  # positive: + 1024*L
    q = (x[19:20] >> 5) + (x[20:21] << 8) + (x[21:22] << 21)  # < 2^11
    r = jnp.concatenate([x[:19], x[19:20] & 31], axis=0)
    qd = _sc_carry(_sc_carry(_sc_mul_const(q, SC._DELTA_LIMBS)))  # 11 limbs
    l_limbs = prep_ref[_PREP_L : _PREP_L + NLIMBS, :].astype(jnp.int32)
    y = r + l_limbs - jnp.pad(qd, ((0, NLIMBS - qd.shape[0]), (0, 0)))  # (0, 2L)
    y = _sc_full_carry(y)
    y = jnp.where(_geq_const(y, SC.L), y - l_limbs, y)
    return _sc_full_carry(y)


def _parse_point(be_words: jnp.ndarray):
    """(8, T) big-endian words of a 32-byte point encoding -> its y limbs
    (sign bit off, as encoded: not reduced) and its sign, (1, T) int32."""
    le = SC.bswap32(be_words)
    y = _words_to_limbs(
        jnp.concatenate([le[:7], le[7:8] & 0x7FFFFFFF], axis=0), NLIMBS
    )
    return y, (le[7:8] >> 31).astype(jnp.int32)


def _prepare(words_ref, prep_ref, w_ref, s_w_ref, k_w_ref):
    """``ops.ed25519.prepare_fused`` for one tile: from the tile's raw words
    the challenge k = SHA-512(R || A || M) mod L and s, as 4-bit windows
    written to ``k_w_ref`` / ``s_w_ref``; returned are A's y limbs and sign,
    R's, and ok = host_ok & (A's y < p) & (s < L), (1, T) bool.  R's
    canonicity needs no check: the final compare is exact on its raw limbs."""
    digest = _sha512_96(words_ref, prep_ref, w_ref)
    k = _mod_l(_words_to_limbs(SC.digest_words_to_le(digest), 40), prep_ref)
    _store_windows4(k, k_w_ref)
    r_y, r_sign = _parse_point(words_ref[0:8, :])
    a_y, a_sign = _parse_point(words_ref[8:16, :])
    s_limbs = _words_to_limbs(words_ref[_S_ROW:_OK_ROW, :], NLIMBS)
    _store_windows4(s_limbs, s_w_ref)
    ok = (
        (words_ref[_OK_ROW:_WORD_ROWS, :] != 0)
        & ~_geq_const(a_y, F.P)
        & ~_geq_const(s_limbs, SC.L)
    )
    return a_y, a_sign, r_y, r_sign, ok


def _prep_specs(tile: int, col):
    """What ``_prepare`` adds to a kernel: the in_specs of the constants'
    table and of a launch's words (``col``: a tile's block of lanes), and
    the scratch it fills."""
    in_specs = [
        pl.BlockSpec((_PREP_ROWS, tile), _whole(0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((_WORD_ROWS, tile), col, memory_space=pltpu.VMEM),
    ]
    scratch = [
        pltpu.VMEM((160, tile), jnp.uint32),  # SHA-512's schedule
        pltpu.VMEM((64, tile), jnp.int32),  # windows of s
        pltpu.VMEM((64, tile), jnp.int32),  # windows of k
    ]
    return in_specs, scratch


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _matches_r(res: Point, r_y, r_sign) -> jnp.ndarray:
    """Whether the point encodes to R.  Exact compare on the raw R limbs
    (memcmp semantics): a non-canonical R (y >= p) can never equal
    fcanonical output, so it is rejected."""
    x, y, z, _ = res
    zinv = finv(z)
    x_aff = fmul(x, zinv)
    y_aff = fmul(y, zinv)
    return feq(y_aff, r_y) & (fparity(x_aff) == r_sign)


def _ladder_verdict(comb_ref, a_y, a_sign, r_y, r_sign, s_w_ref, k_w_ref, ok):
    """[s]B + [k](-A) == R for one tile, as (1, T) int32: decompression,
    the per-item table, the fused window loop, the inversion, the compare."""
    neg_a, dec_ok = decompress(a_y, a_sign)
    neg_a = point_neg(neg_a)

    ident = _identity(a_y.shape[1])
    tab: List[Point] = [ident, neg_a]
    for v in range(2, 16):
        tab.append(point_add(tab[v - 1], neg_a))

    def step(i, carry):
        acc_a = carry[:3]  # X, Y, Z only — T is dead between window groups
        acc_b = carry[3:]
        acc_a = _dbl4(acc_a)
        kw = _row(k_w_ref, 63 - i)  # ladder consumes MSB window first
        acc_a = point_add(acc_a, _gather16(tab, kw), want_t=False)
        entry = comb_ref[i]  # (3, NLIMBS, 16) Niels form
        acc_b = point_madd(acc_b, _gather_comb(entry, _row(s_w_ref, i)))
        return (*acc_a, *acc_b)

    carry = jax.lax.fori_loop(0, 63, step, (*ident[:3], *ident))
    # Peeled last window: the final adds must materialize T for the combine.
    acc_a = _dbl4(carry[:3])
    acc_a = point_add(acc_a, _gather16(tab, _row(k_w_ref, 0)))
    acc_b = point_madd(carry[3:], _gather_comb(comb_ref[63], _row(s_w_ref, 63)))
    res = point_add(acc_a, acc_b)
    return (_matches_r(res, r_y, r_sign) & dec_ok & ok).astype(jnp.int32)


def _verify_body(
    consts_ref, comb_ref, prep_ref, words_ref, out_ref, w_ref, s_w_ref, k_w_ref
):
    """One tile of a launch, raw words to verdicts: the preparation
    (``_prepare``), then the ladder, all in VMEM."""
    _bind_consts(consts_ref)
    a_y, a_sign, r_y, r_sign, ok = _prepare(
        words_ref, prep_ref, w_ref, s_w_ref, k_w_ref
    )
    out_ref[...] = _ladder_verdict(
        comb_ref, a_y, a_sign, r_y, r_sign, s_w_ref, k_w_ref, ok
    )


def _verify_prepared_body(
    consts_ref,
    comb_ref,
    a_y_ref,
    a_sign_ref,
    r_y_ref,
    r_sign_ref,
    s_w_ref,
    k_w_ref,
    host_ok_ref,
    out_ref,
):
    """The ladder alone, over inputs prepared outside the call: a batch
    whose challenge was hashed on the host (``verify_pallas``), and the
    four-chip mesh's shards (``parallel.mesh``)."""
    _bind_consts(consts_ref)
    out_ref[...] = _ladder_verdict(
        comb_ref,
        a_y_ref[...],
        a_sign_ref[...],
        r_y_ref[...],
        r_sign_ref[...],
        s_w_ref,
        k_w_ref,
        host_ok_ref[...] != 0,
    )


def _named_call(kernel, name: str):
    """``kernel`` under an inner ``jit`` called ``name``: a device trace
    names a Mosaic call after the innermost jitted function around it
    (``name=`` on ``pallas_call`` does not reach a trace's op line), so this
    is the kernel's name there whatever jitted entry point launched it;
    those keep their Python names, by which a profile's launches are
    found."""

    def call(*args):
        return kernel(*args)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call)


def _whole(*zeros):
    """An index_map for an input every tile reads whole."""
    return lambda *_: zeros


def _consts_spec(tile: int):
    return pl.BlockSpec((7, NLIMBS, tile), _whole(0, 0, 0), memory_space=pltpu.VMEM)


def _comb_spec():
    return pl.BlockSpec(
        (64, 3, NLIMBS, 16), _whole(0, 0, 0, 0), memory_space=pltpu.VMEM
    )


def _lanes_minor(*columns) -> jnp.ndarray:
    """A launch's words as the kernels take them: the (B, w) uint32 columns
    side by side — R || A || M, s, host_ok: ``_WORD_ROWS`` wide — with the
    lanes minor, (_WORD_ROWS, B)."""
    words = columns[0] if len(columns) == 1 else jnp.concatenate(columns, axis=-1)
    if words.dtype != jnp.uint32:  # the kernel's shifts are a uint32's
        words = jax.lax.bitcast_convert_type(words, jnp.uint32)
    return words.T


def _key_words(table, idx) -> jnp.ndarray:
    """Each lane's A words by its key's index: the one op of an indexed
    launch that is not a slice or a transpose (``indexed_to_msg_words``'s
    gather; an out-of-range index reads some row and is host_ok=0)."""
    with jax.named_scope("ed25519_gather_keys"):
        return jnp.take(table, idx.astype(jnp.int32), axis=0, mode="clip")


def _indexed_lanes_minor(blob, table) -> jnp.ndarray:
    """``_lanes_minor`` of an indexed blob (``pack_blob_indexed``: R, M, s,
    the key's index, host_ok), A's words spliced in from the key table."""
    return _lanes_minor(
        blob[:, :8],
        _key_words(table, blob[:, 24]),
        blob[:, 8:24],
        blob[:, 25:26],
    )


def _verify_words(words, *, tile: int, interpret: bool) -> jnp.ndarray:
    """The generic ladder over a launch's raw words, (_WORD_ROWS, B) uint32
    with ``tile`` lanes a block: (B,) int32 verdicts.  One Pallas call."""
    b = words.shape[1]
    col = lambda i: (0, i)
    prep_specs, scratch = _prep_specs(tile, col)
    kernel = pl.pallas_call(
        _verify_body,
        grid=(b // tile,),
        in_specs=[_consts_spec(tile), _comb_spec(), *prep_specs],
        out_specs=pl.BlockSpec((1, tile), col, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )
    return _named_call(kernel, "ed25519_verify_ladder")(
        jnp.asarray(_consts_wide(tile)),
        jnp.asarray(_COMB_T),
        jnp.asarray(_prep_wide(tile)),
        words,
    )[0]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_pallas_jit(
    a_y, a_sign, r_y, r_sign, s_w, k_w, host_ok, *, tile: int, interpret: bool
):
    b = a_y.shape[0]
    col = lambda i: (0, i)
    kernel = pl.pallas_call(
        _verify_prepared_body,
        grid=(b // tile,),
        in_specs=[
            _consts_spec(tile),
            _comb_spec(),
            pl.BlockSpec((NLIMBS, tile), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((NLIMBS, tile), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((64, tile), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((64, tile), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), col, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), col, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        interpret=interpret,
    )
    out = _named_call(kernel, "ed25519_verify_ladder")(
        jnp.asarray(_consts_wide(tile)),
        jnp.asarray(_COMB_T),
        a_y.T,
        a_sign[None, :].astype(jnp.int32),
        r_y.T,
        r_sign[None, :].astype(jnp.int32),
        s_w.T,
        k_w.T,
        host_ok[None, :].astype(jnp.int32),
    )
    return out[0].astype(bool)


# ---------------------------------------------------------------------------
# Keyed-tile kernel: every tile holds signatures of ONE committee key, whose
# precomputed negated comb (ops.ed25519.build_neg_key_combs) is DMA'd into
# VMEM via a scalar-prefetched index.  [s]B + [k](-A) is then 128 Niels
# additions — zero doublings, no on-device A decompression — roughly a third
# of the generic ladder's field multiplications.
# ---------------------------------------------------------------------------


def _verify_keyed_body(
    keys_ref,
    consts_ref,
    bcomb_ref,
    acomb_ref,
    prep_ref,
    words_ref,
    out_ref,
    w_ref,
    s_w_ref,
    k_w_ref,
):
    """One tile of a keyed launch, raw words to verdicts.  A is hashed, and
    checked canonical, like any other; its limbs are not asked for: the
    key's comb stands for the decompressed point."""
    del keys_ref  # consumed by acomb's index_map; the body never reads it
    _bind_consts(consts_ref)
    _a_y, _a_sign, r_y, r_sign, ok = _prepare(
        words_ref, prep_ref, w_ref, s_w_ref, k_w_ref
    )

    def step(i, acc):
        acc = point_madd(acc, _gather_comb(bcomb_ref[i], _row(s_w_ref, i)))
        acc = point_madd(acc, _gather_comb(acomb_ref[0, i], _row(k_w_ref, i)))
        return acc

    res = jax.lax.fori_loop(0, 64, step, _identity(r_y.shape[1]))
    out_ref[...] = (_matches_r(res, r_y, r_sign) & ok).astype(jnp.int32)


def _verify_keyed_words(tile_keys, acomb, words, *, tile: int, interpret: bool):
    """The keyed-tile kernel over a launch's raw words (as ``_verify_words``
    takes them), in GROUPED order: tile i's lanes are all by key
    tile_keys[i].  (B,) int32 verdicts, one Pallas call."""
    b = words.shape[1]
    col = lambda i, keys: (0, i)
    prep_specs, scratch = _prep_specs(tile, col)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // tile,),
        in_specs=[
            _consts_spec(tile),
            _comb_spec(),
            # The tile's key selects which comb is DMA'd; consecutive tiles
            # sharing a key (the grouped layout sorts them) skip the copy.
            pl.BlockSpec(
                (1, 64, 3, NLIMBS, 16),
                lambda i, keys: (keys[i], 0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            *prep_specs,
        ],
        out_specs=pl.BlockSpec((1, tile), col, memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
    )
    kernel = pl.pallas_call(
        _verify_keyed_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        interpret=interpret,
    )
    return _named_call(kernel, "ed25519_verify_keyed")(
        tile_keys,
        jnp.asarray(_consts_wide(tile)),
        jnp.asarray(_COMB_T),
        acomb,
        jnp.asarray(_prep_wide(tile)),
        words,
    )[0]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_keyed_blob_jit(blob, table, acomb, tile_keys, positions, *, tile, interpret):
    out = _verify_keyed_words(
        tile_keys,
        acomb,
        _indexed_lanes_minor(blob, table),
        tile=tile,
        interpret=interpret,
    )
    # Un-permute back to the caller's order on device when positions ride
    # along (positions maps original row -> grouped row); with
    # positions=None the (b,) GROUPED-order lanes return as-is and the
    # caller un-permutes on host — skipping the positions upload entirely.
    if positions is not None:
        out = jnp.take(out, positions)
    return out.astype(bool)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_keyed_flat_jit(flat, table, acomb, tile_keys, *, tile, interpret):
    # Wire-minimal keyed dispatch: the grouped layout makes the per-lane key
    # index REDUNDANT (every lane of a tile shares tile_keys[tile]) and the
    # host_ok flags compress to one bit per lane, all folded into ONE flat
    # upload — R||M||s (96 B/sig) + ~0.13 B/sig of mask.
    b = tile_keys.shape[0] * tile
    blob24 = flat[: b * 24].reshape(b, 24)
    okmask = flat[b * 24 :]
    idx = jnp.repeat(tile_keys, tile, total_repeat_length=b)
    lane = jnp.arange(b)
    ok = ((okmask[lane // 32] >> (lane % 32)) & 1).astype(jnp.uint32)
    words = _lanes_minor(
        blob24[:, :8], _key_words(table, idx), blob24[:, 8:24], ok[:, None]
    )
    out = _verify_keyed_words(
        tile_keys, acomb, words, tile=tile, interpret=interpret
    )
    return out.astype(bool)


def verify_keyed_flat(
    flat,
    table_words,
    acomb,
    tile_keys,
    *,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Keyed-tile verification of a GROUPED flat upload: b*24 R/M/s words
    followed by b/32 packed little-bit-order ok words; returns (b,) bool in
    GROUPED order (callers un-permute on host via the grouping positions)."""
    if interpret is None:
        interpret = interpret_mode()
    if tile is None:
        tile = default_tile()
    b = int(tile_keys.shape[0]) * tile
    if b % 32 != 0:
        # The ok mask is read as packed 32-lane words; a floor-sized mask
        # for a ragged tail would alias earlier lanes' bits via the clamped
        # gather — reject instead.
        raise ValueError(f"batch {b} not a multiple of 32")
    if flat.shape[0] != b * 24 + b // 32:
        raise ValueError(
            f"flat upload of {flat.shape[0]} words != {b}*24 + {b}//32"
        )
    return _verify_keyed_flat_jit(
        jnp.asarray(flat),
        jnp.asarray(table_words),
        jnp.asarray(acomb),
        jnp.asarray(tile_keys),
        tile=tile,
        interpret=interpret,
    )


def verify_keyed_blob(
    grouped,
    table_words,
    acomb,
    tile_keys,
    positions,
    *,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Keyed-tile fused verification of a GROUPED indexed blob
    (ops.ed25519.group_blob_for_tiles layout).  Returns (b,) bool in the
    ORIGINAL (pre-grouping) order, padding lanes last — or, with
    ``positions=None``, in GROUPED order (the caller un-permutes on host)."""
    if interpret is None:
        interpret = interpret_mode()
    if tile is None:
        tile = default_tile()
    b = grouped.shape[0]
    if b % tile != 0:
        raise ValueError(f"batch {b} not a multiple of tile {tile}")
    # The arrays are the jitted call's own arguments, numpy or device: that
    # one call moves a host blob to the chip itself.  A ``jnp.asarray``
    # before it is a second dispatch, through JAX's Python transfer path.
    return _stored_keyed_blob(
        grouped,
        table_words,
        acomb,
        tile_keys,
        positions,
        tile=tile,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_fused_pallas_jit(msg_words, s_words, host_ok, *, tile, interpret):
    words = _lanes_minor(msg_words, s_words, host_ok[:, None].astype(jnp.uint32))
    return _verify_words(words, tile=tile, interpret=interpret).astype(bool)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_fused_blob_pallas_jit(blob, *, tile, interpret):
    # The blob's columns ARE the kernel's rows: a transpose, then the call.
    out = _verify_words(_lanes_minor(blob), tile=tile, interpret=interpret)
    return out.astype(bool)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_fused_indexed_pallas_jit(blob, table, *, tile, interpret):
    out = _verify_words(
        _indexed_lanes_minor(blob, table), tile=tile, interpret=interpret
    )
    return out.astype(bool)


# The three entry points a verifier's launches reach, through the program
# store: a shape's first call loads its lowered program (or traces it, once,
# and writes it); what then runs is a jitted function of the same name.
_stored_keyed_blob = StoredProgram(_verify_keyed_blob_jit)
_stored_fused_blob = StoredProgram(_verify_fused_blob_pallas_jit)
_stored_fused_indexed = StoredProgram(_verify_fused_indexed_pallas_jit)


def verify_fused_blob_pallas(
    blob, *, tile: Optional[int] = None, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Single-array fused verification (ops.ed25519.pack_blob layout): one
    host->device transfer per batch and one device program, the blob's
    transpose and the Pallas call that hashes, parses and runs the ladder."""
    if interpret is None:
        interpret = interpret_mode()
    if tile is None:
        tile = default_tile()
    b = blob.shape[0]
    if b % tile != 0:
        raise ValueError(f"batch {b} not a multiple of tile {tile}")
    # ``blob`` goes in as it is (see ``verify_keyed_blob``).
    return _stored_fused_blob(blob, tile=tile, interpret=interpret)


def verify_fused_indexed_blob_pallas(
    blob, table, *, tile: Optional[int] = None, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Indexed-blob fused verification (ops.ed25519.pack_blob_indexed layout +
    device-resident key table): minimum wire bytes, Pallas ladder."""
    if interpret is None:
        interpret = interpret_mode()
    if tile is None:
        tile = default_tile()
    b = blob.shape[0]
    if b % tile != 0:
        raise ValueError(f"batch {b} not a multiple of tile {tile}")
    # ``blob`` and ``table`` go in as they are (see ``verify_keyed_blob``).
    return _stored_fused_indexed(blob, table, tile=tile, interpret=interpret)


def verify_fused_pallas(
    msg_words,
    s_words,
    host_ok,
    *,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused raw-bytes verification with the Pallas kernel, the columns
    apart (``ops.ed25519.pack_bytes``): SHA-512, mod L, the parse and the
    ladder in the one call, as the blob entry points run them."""
    if interpret is None:
        interpret = interpret_mode()
    if tile is None:
        tile = default_tile()
    b = msg_words.shape[0]
    if b % tile != 0:
        raise ValueError(f"batch {b} not a multiple of tile {tile}")
    return _verify_fused_pallas_jit(
        jnp.asarray(msg_words),
        jnp.asarray(s_words),
        jnp.asarray(host_ok),
        tile=tile,
        interpret=interpret,
    )


def interpret_mode() -> bool:
    """The Pallas interpreter stands in for Mosaic on the CPU (the tests'
    platform) and nowhere else: on an accelerator the kernel compiles or the
    dispatch fails."""
    return jax.default_backend() == "cpu"


def default_tile() -> int:
    """256 lanes on real TPUs; tiny tiles are fine under the CPU interpreter."""
    return 8 if interpret_mode() else 256


def verify_pallas(
    a_y,
    a_sign,
    r_y,
    r_sign,
    s_w,
    k_w,
    host_ok,
    *,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Drop-in equivalent of ``ops.ed25519.verify_impl`` (batch-major inputs,
    (B,) bool out) backed by the Pallas kernel.  B must be a multiple of
    ``tile`` (callers pad via the bucket dispatcher)."""
    if interpret is None:
        interpret = interpret_mode()
    if tile is None:
        tile = default_tile()
    b = a_y.shape[0]
    if b % tile != 0:
        raise ValueError(f"batch {b} not a multiple of tile {tile}")
    return _verify_pallas_jit(
        jnp.asarray(a_y),
        jnp.asarray(a_sign),
        jnp.asarray(r_y),
        jnp.asarray(r_sign),
        jnp.asarray(s_w),
        jnp.asarray(k_w),
        jnp.asarray(host_ok),
        tile=tile,
        interpret=interpret,
    )
