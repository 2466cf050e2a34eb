"""Parity tests for the Pallas verify kernel (interpret mode on CPU).

The Pallas kernel must agree bit-for-bit with the XLA kernel
(``ops/ed25519.verify_impl``) and with the OpenSSL oracle over valid,
corrupted, and structurally-invalid signatures (the same contract the
reference's serial verify upholds, ``mysticeti-core/src/crypto.rs:174-189``).

Tier 1 (not marked ``kernel``): a launch is one device program (the jaxpr
of each entry point), the launch's preparation alone inside an interpreted
call against ``prepare_fused`` and Python integers, the reduction mod L on
chosen digests, and the indexed entry point's verdicts on
tests/kernel_cases.py.
"""
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mysticeti_tpu.crypto import Ed25519PrivateKey

from mysticeti_tpu.ops import ed25519 as E
from mysticeti_tpu.ops import ed25519_pallas as EP
from mysticeti_tpu.ops import scalar as SC

import kernel_cases as KC

kernel = pytest.mark.kernel  # tier 2: each compiles the host-hashed kernel


def _batch(n, seed=1, corrupt=True):
    rng = random.Random(seed)
    keys = [
        Ed25519PrivateKey.from_private_bytes(
            bytes(rng.randrange(256) for _ in range(32))
        )
        for _ in range(8)
    ]
    pks, msgs, sigs, expect = [], [], [], []
    for i in range(n):
        key = keys[i % len(keys)]
        msg = bytes(rng.randrange(256) for _ in range(32))
        sig = bytearray(key.sign(msg))
        ok = True
        if corrupt and i % 4 == 1:
            sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            ok = False
        elif corrupt and i % 4 == 2:
            msg = bytes(rng.randrange(256) for _ in range(32))
            ok = False
        pks.append(key.public_key().public_bytes_raw())
        msgs.append(msg)
        sigs.append(bytes(sig))
        expect.append(ok)
    return pks, msgs, sigs, np.array(expect)


@kernel
def test_pallas_matches_oracle_and_xla():
    pks, msgs, sigs, expect = _batch(16)
    packed = E.pack_batch(pks, msgs, sigs)
    ref = np.asarray(E.verify_kernel(*[np.asarray(x) for x in packed]))
    got = np.asarray(EP.verify_pallas(*packed, tile=8, interpret=True))
    # The corrupted signature may still occasionally pass host checks; the
    # oracle is the cryptography library's accept/reject per item.
    from mysticeti_tpu import crypto

    oracle = np.array(
        [crypto.PublicKey(p).verify(s, m) for p, m, s in zip(pks, msgs, sigs)]
    )
    np.testing.assert_array_equal(got, oracle)
    np.testing.assert_array_equal(got, ref)


@kernel
def test_pallas_multi_tile_grid():
    """Two grid tiles: catches block-index mapping errors."""
    pks, msgs, sigs, expect = _batch(16, seed=3, corrupt=False)
    packed = E.pack_batch(pks, msgs, sigs)
    got = np.asarray(EP.verify_pallas(*packed, tile=8, interpret=True))
    assert got.all()


@kernel
def test_non_canonical_r_rejected_on_host():
    """A signature whose R y-coordinate is encoded as y >= p must be rejected
    in pack_batch (OpenSSL memcmp semantics: a non-canonical encoding can
    never equal the canonical re-encoding).  ADVICE r1 finding."""
    from mysticeti_tpu.ops.ed25519 import P, pack_batch

    pks, msgs, sigs, _ = _batch(8, corrupt=False)
    # Overwrite R with the non-canonical encoding of y = p + 1 (sign bit 0).
    bad_r = int(P + 1).to_bytes(32, "little")
    sigs = list(sigs)
    sigs[3] = bad_r + sigs[3][32:]
    packed = pack_batch(pks, msgs, sigs)
    host_ok = packed[-1]
    assert not host_ok[3]
    assert host_ok[[i for i in range(8) if i != 3]].all()
    got = np.asarray(EP.verify_pallas(*packed, tile=8, interpret=True))
    assert not got[3]


@kernel
def test_pallas_rejects_bad_tile():
    pks, msgs, sigs, _ = _batch(10, corrupt=False)
    packed = E.pack_batch(pks, msgs, sigs)
    with pytest.raises(ValueError):
        EP.verify_pallas(*packed, tile=8, interpret=True)


@kernel
def test_pallas_indexed_blob_matches_oracle():
    """The committee-indexed Pallas entry (verify_fused_indexed_blob_pallas)
    in interpret mode: the TPU-only wire format must agree with the
    expected accept/reject pattern and the XLA indexed kernel."""
    pks, msgs, sigs, expect = _batch(16, seed=5)
    table = E.KeyTable(sorted(set(pks)))
    idx = table.indices_for(pks)
    blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
    got = np.asarray(
        EP.verify_fused_indexed_blob_pallas(
            blob, table.words, tile=8, interpret=True
        )
    )
    assert (got == expect).all()
    xla = np.asarray(E.verify_fused_indexed_kernel(blob, table.words))
    assert (got == xla).all()


# ---------------------------------------------------------------------------
# A launch is one device program
# ---------------------------------------------------------------------------

# What may stand beside the Pallas call: the blob's slices, the key table's
# gather, the splice, the transpose to lanes minor, the verdicts' cast and
# the keyed path's un-permuting take.  Nothing with a loop.
_BESIDE = {
    "slice", "squeeze", "convert_element_type", "bitcast_convert_type",
    "concatenate", "transpose", "reshape", "broadcast_in_dim", "gather",
    "clamp", "lt", "add", "select_n", "ne",
}


def _outer_equations(jaxpr) -> list:
    """The primitives of a jaxpr and of the jits it calls, the inside of a
    ``pallas_call`` left out (a loop is named, not entered)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit", "closed_call"):
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names.extend(_outer_equations(sub))
        else:
            names.append(eqn.primitive.name)
    return names


@pytest.mark.parametrize("entry", ["blob", "indexed", "keyed"])
def test_a_launch_is_one_device_program(entry, monkeypatch):
    """Beside its one ``pallas_call`` an entry point holds no loop and only
    a handful of equations: ``prepare_fused`` back on the TPU path (two
    scans and ~2,400 ops around the call) fails here."""

    def refuse(*args, **kwargs):
        raise AssertionError("prepare_fused is the xla backend's, not a launch's")

    monkeypatch.setattr(E, "prepare_fused", refuse)
    fn, shapes = KC.entry_points(64, KC.TILE)[entry]
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    jaxpr = jax.make_jaxpr(
        functools.partial(fn, tile=KC.TILE, interpret=False)
    )(*args)
    names = _outer_equations(jaxpr.jaxpr)
    assert names.count("pallas_call") == 1
    beside = [n for n in names if n != "pallas_call"]
    assert set(beside) <= _BESIDE, sorted(set(beside) - _BESIDE)
    assert len(beside) <= 24, beside


# ---------------------------------------------------------------------------
# The preparation alone, in an interpreted call of its own
# ---------------------------------------------------------------------------


def _limbs_to_ints(limbs: np.ndarray) -> list:
    """(n, B) limbs -> B Python integers."""
    return [
        sum(int(v) << (EP.RADIX * i) for i, v in enumerate(col))
        for col in np.asarray(limbs).T
    ]


def _windows_to_ints(windows: np.ndarray) -> list:
    """(64, B) 4-bit windows, LSB first -> B Python integers."""
    return [
        sum(int(v) << (4 * i) for i, v in enumerate(col))
        for col in np.asarray(windows).T
    ]


@pytest.fixture(scope="module")
def prepared():
    """``_prepare``'s outputs over the cases, eight tiles a grid, beside
    ``prepare_fused``'s: two dicts of arrays with the lanes last."""
    blob = KC.packed_blob()
    b, tile, n = len(blob), KC.TILE, EP.NLIMBS
    rows = {"a_y": n, "a_sign": 1, "r_y": n, "r_sign": 1, "ok": 1, "s_w": 64, "k_w": 64}

    def body(prep_ref, words_ref, *refs):
        outs, (w_ref, s_w_ref, k_w_ref) = refs[:7], refs[7:]
        a_y, a_sign, r_y, r_sign, ok = EP._prepare(
            words_ref, prep_ref, w_ref, s_w_ref, k_w_ref
        )
        values = (a_y, a_sign, r_y, r_sign, ok, s_w_ref[...], k_w_ref[...])
        for ref, value in zip(outs, values):
            ref[...] = value.astype(jnp.int32)

    col = lambda i: (0, i)
    in_specs, scratch = EP._prep_specs(tile, col)
    got = pl.pallas_call(
        body,
        grid=(b // tile,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((r, tile), col, memory_space=pltpu.VMEM)
            for r in rows.values()
        ],
        out_shape=[jax.ShapeDtypeStruct((r, b), jnp.int32) for r in rows.values()],
        scratch_shapes=scratch,
        interpret=True,
    )(jnp.asarray(EP._prep_wide(tile)), EP._lanes_minor(jnp.asarray(blob)))
    want = jax.jit(E.prepare_fused)(
        blob[:, :24], blob[:, 24:32], blob[:, 32] != 0
    )
    want = dict(zip(("a_y", "a_sign", "r_y", "r_sign", "s_w", "k_w", "ok"), want))
    return (
        {k: np.asarray(v) for k, v in zip(rows, got)},
        {k: np.asarray(want[k]).T.reshape(r, b) for k, r in rows.items()},
    )


@pytest.mark.parametrize("name", KC.NAMES)
def test_preparation_in_the_kernel(name, prepared):
    """Every number the preparation hands the ladder, one lane a case:
    against ``prepare_fused`` (the ``xla`` backend's form) and against
    ``hashlib`` and Python integers."""
    got, want = prepared
    lane = KC.LANE[name]
    for key in got:
        np.testing.assert_array_equal(got[key][:, lane], want[key][:, lane], key)
    _, pk, msg, sig, host_ok = KC.CASES[lane]
    one = lambda key: got[key][:, lane : lane + 1]
    a, r = int.from_bytes(pk, "little"), int.from_bytes(sig[:32], "little")
    s = int.from_bytes(sig[32:], "little")
    y_mask = (1 << 255) - 1
    assert _limbs_to_ints(one("a_y")) == [a & y_mask]
    assert _limbs_to_ints(one("r_y")) == [r & y_mask]
    assert (one("a_sign").item(), one("r_sign").item()) == (a >> 255, r >> 255)
    assert _windows_to_ints(one("s_w")) == [s]
    assert _windows_to_ints(one("k_w")) == [KC.challenge(pk, msg, sig)]
    assert bool(one("ok").item()) == (
        bool(host_ok) and (a & y_mask) < KC.P and s < KC.L
    )


# Digests as integers (the 64 bytes read little-endian) whose reduction is
# at an edge — no message hashes to these; the reduction is run on them
# directly — and a block of random ones.
_TOP = (1 << 512) - 1
_DIGESTS = {
    "0": 0, "1": 1, "L-1": KC.L - 1, "L": KC.L, "L+1": KC.L + 1,
    "2L-1": 2 * KC.L - 1, "2L": 2 * KC.L, "2^252-1": (1 << 252) - 1,
    "2^252": 1 << 252, "2^253-1": (1 << 253) - 1, "2^256-1": (1 << 256) - 1,
    "2^260-1": (1 << 260) - 1, "2^260": 1 << 260, "2^260+L-1": (1 << 260) + KC.L - 1,
    "2^416": 1 << 416, "2^512-1": _TOP,
    "largest-multiple-of-L": _TOP - _TOP % KC.L,
    "largest-multiple-of-L-less-1": _TOP - _TOP % KC.L - 1,
    "largest-multiple-of-L-plus-1": _TOP - _TOP % KC.L + 1,
    "2^511": 1 << 511, "limbs-all-ones-low-half": (1 << 260) - 1 << 252,
    "alternating-limbs": sum(8191 << (26 * i) for i in range(20)),
    "multiple-of-L-near-2^500": (1 << 500) - (1 << 500) % KC.L,
    "multiple-of-L-near-2^500-less-1": (1 << 500) - (1 << 500) % KC.L - 1,
}
_rng = random.Random(4404)
_DIGESTS.update({f"random-{i:02d}": _rng.getrandbits(512) for i in range(40)})
assert len(_DIGESTS) == 64
assert sorted(v % KC.L for v in _DIGESTS.values())[:2] == [0, 0]


@pytest.fixture(scope="module")
def reduced():
    """``_mod_l`` over ``_DIGESTS``, as the preparation reaches it: the
    digest's words byte-swapped in place, to 40 limbs, reduced."""
    raw = b"".join(v.to_bytes(64, "little") for v in _DIGESTS.values())
    words = np.frombuffer(raw, ">u4").astype(np.uint32).reshape(-1, 16).T
    b, tile = words.shape[1], KC.TILE

    def body(prep_ref, digest_ref, out_ref):
        limbs = EP._words_to_limbs(SC.digest_words_to_le(digest_ref[...]), 40)
        out_ref[...] = EP._mod_l(limbs, prep_ref)

    col = lambda i: (0, i)
    out = pl.pallas_call(
        body,
        grid=(b // tile,),
        in_specs=[
            pl.BlockSpec((EP._PREP_ROWS, tile), lambda i: (0, 0)),
            pl.BlockSpec((16, tile), col),
        ],
        out_specs=pl.BlockSpec((EP.NLIMBS, tile), col),
        out_shape=jax.ShapeDtypeStruct((EP.NLIMBS, b), jnp.int32),
        interpret=True,
    )(jnp.asarray(EP._prep_wide(tile)), jnp.asarray(words))
    xla = jax.jit(SC.mod_L)(
        SC.words_to_limbs(SC.digest_words_to_le(jnp.asarray(words.T)), 40)
    )
    return np.asarray(out), np.asarray(xla).T


@pytest.mark.parametrize("name", list(_DIGESTS))
def test_reduction_mod_l_in_the_kernel(name, reduced):
    got, xla = reduced
    lane = list(_DIGESTS).index(name)
    limbs = got[:, lane : lane + 1]
    assert ((0 <= limbs) & (limbs <= EP.MASK)).all()  # canonical limbs
    assert _limbs_to_ints(limbs) == [_DIGESTS[name] % KC.L]
    np.testing.assert_array_equal(got[:, lane], xla[:, lane])


# ---------------------------------------------------------------------------
# The indexed entry point, eight tiles a grid
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def indexed_verdicts():
    blob, table = KC.indexed_blob()
    got = EP.verify_fused_indexed_blob_pallas(
        blob, table.words, tile=KC.TILE, interpret=True
    )
    xla = E.verify_fused_indexed_kernel(blob, table.words)
    return np.asarray(got), np.asarray(xla)


@pytest.mark.parametrize("name", KC.NAMES)
def test_indexed_entry_point_verdicts(name, indexed_verdicts):
    got, xla = indexed_verdicts
    KC.check_verdict(name, got[KC.LANE[name]], xla[KC.LANE[name]])
