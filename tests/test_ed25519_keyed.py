"""Keyed-tile verification path: per-key precomputed combs + tile grouping.

The keyed kernel must be bit-identical to the CPU oracle and the generic
fused path — it is a pure strength reduction (zero doublings, no on-device A
decompression), not a semantics change.  Runs under the Pallas interpreter
on the CPU test mesh.

Tier 1 (not marked ``kernel``): the keyed entry point, whose preparation
runs inside the call, through the dispatch a launch takes, on
tests/kernel_cases.py; and the kernels compiled for a v5e at the chip's
tile by the chip's own compiler, which runs here without a chip (Mosaic
refuses what the interpreter lets through: a layout, an unaligned slice,
too much VMEM).
"""
import random

import jax

import numpy as np
import pytest
from mysticeti_tpu.crypto import Ed25519PrivateKey

from mysticeti_tpu.ops import ed25519 as E

import kernel_cases as KC

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")
kernel = pytest.mark.kernel  # tier 2: compile-heavy


@pytest.fixture(scope="module")
def keyring():
    rng = random.Random(7)
    keys = [
        Ed25519PrivateKey.from_private_bytes(
            bytes(rng.randrange(256) for _ in range(32))
        )
        for _ in range(4)
    ]
    return rng, keys


def _batch(rng, keys, n, tamper_every=None):
    pks, msgs, sigs, expect = [], [], [], []
    for i in range(n):
        k = keys[i % len(keys)]
        m = bytes(rng.randrange(256) for _ in range(32))
        s = k.sign(m)
        good = True
        if tamper_every and i % tamper_every == 0:
            s = bytes([s[0] ^ 1]) + s[1:]
            good = False
        pks.append(k.public_key().public_bytes_raw())
        msgs.append(m)
        sigs.append(s)
        expect.append(good)
    return pks, msgs, sigs, np.array(expect)


def test_group_blob_for_tiles_properties():
    rng = np.random.default_rng(3)
    n, num_keys, tile, bucket = 50, 5, 4, 128
    idx = rng.integers(0, num_keys, size=n)
    blob = rng.integers(1, 2**31, size=(n, 26), dtype=np.int64).astype(np.uint32)
    blob[:, 24] = idx
    blob[:, 25] = 1
    blob[::11, 25] = 0  # some rejected lanes
    g = E.group_blob_for_tiles(blob, num_keys, tile, bucket)
    assert g is not None
    grouped, tile_keys, positions = g
    assert grouped.shape == (bucket, 26) and len(tile_keys) == bucket // tile
    # positions is injective and the grouped rows hold the original data
    assert len(set(positions.tolist())) == n
    assert (grouped[positions] == blob).all()
    # every tile contains rows of ONE key (among live lanes)
    for t in range(bucket // tile):
        rows = grouped[t * tile : (t + 1) * tile]
        live = rows[rows[:, 25] != 0]
        if len(live):
            assert (live[:, 24] == tile_keys[t]).all()
    # overflow: 5 keys x 1 tile minimum > 1-tile bucket
    assert E.group_blob_for_tiles(blob, num_keys, tile, tile) is None


@kernel
def test_keyed_kernel_matches_oracle(keyring):
    from mysticeti_tpu.ops import ed25519_pallas as PK

    rng, keys = keyring
    table = E.KeyTable([k.public_key().public_bytes_raw() for k in keys])
    n, tile, bucket = 24, 8, 64
    pks, msgs, sigs, expect = _batch(rng, keys, n, tamper_every=5)
    idx = table.indices_for(pks)
    blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
    acomb, valid = table.neg_combs()
    assert valid.all()
    g = E.group_blob_for_tiles(blob, len(table), tile, bucket)
    assert g is not None
    grouped, tile_keys, positions = g
    out = np.asarray(
        PK.verify_keyed_blob(
            grouped, table.words, acomb, tile_keys,
            E._pad_to(positions, bucket), tile=tile, interpret=True,
        )
    )[:n]
    assert (out == expect).all()
    # parity with the CPU oracle
    from mysticeti_tpu.crypto import InvalidSignature

    for i in range(n):
        try:
            keys[i % len(keys)].public_key().verify(sigs[i], msgs[i])
            oracle = True
        except InvalidSignature:
            oracle = False
        assert out[i] == oracle


@kernel
def test_keyed_flat_variant_matches_blob(keyring):
    """verify_keyed_flat (96 B/sig wire variant: key index reconstructed
    from tile_keys, ok as a packed bitmask, grouped-order output) agrees
    with verify_keyed_blob on the same grouped batch.  Kept as the option
    for byte-dominated links; the deployed dispatch uses the 26-column
    upload (see ops/ed25519.py)."""
    from mysticeti_tpu.ops import ed25519_pallas as PK

    rng, keys = keyring
    table = E.KeyTable([k.public_key().public_bytes_raw() for k in keys])
    n, tile, bucket = 24, 8, 64
    pks, msgs, sigs, expect = _batch(rng, keys, n, tamper_every=5)
    idx = table.indices_for(pks)
    blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
    acomb, valid = table.neg_combs()
    g = E.group_blob_for_tiles(blob, len(table), tile, bucket)
    grouped, tile_keys, positions = g
    okmask = np.packbits(
        grouped[:, 25].astype(bool), bitorder="little"
    ).view(np.uint32)
    flat = np.concatenate([grouped[:, :24].reshape(-1), okmask])
    out_flat = np.asarray(
        PK.verify_keyed_flat(
            flat, table.words, acomb, tile_keys, tile=tile, interpret=True
        )
    )
    out_blob = np.asarray(
        PK.verify_keyed_blob(
            grouped, table.words, acomb, tile_keys, None,
            tile=tile, interpret=True,
        )
    )
    assert (out_flat == out_blob).all()
    assert (out_flat[positions] == expect).all()


@kernel
def test_keyed_dispatch_end_to_end_forced_pallas(keyring, monkeypatch):
    """verify_batch_table with the backend forced to pallas(interpret) takes
    the keyed dispatch path and still matches expectations, including
    unknown-key stragglers."""
    rng, keys = keyring
    monkeypatch.setenv("MYSTICETI_VERIFY_BACKEND", "pallas")
    table = E.KeyTable([k.public_key().public_bytes_raw() for k in keys[:-1]])
    pks, msgs, sigs, expect = _batch(rng, keys, 40, tamper_every=7)
    out = E.verify_batch_table(table, pks, msgs, sigs)
    assert (out == expect).all()


@kernel
def test_keyed_rejects_invalid_committee_key(keyring):
    """An off-curve key table entry force-rejects its lanes (the generic
    kernel rejects them via decompression failure — outputs must agree)."""
    from mysticeti_tpu.ops import ed25519_pallas as PK

    rng, keys = keyring
    bad_pk = bytes([0xFF] * 31 + [0x7F])  # y >= p: non-canonical encoding
    assert E._decode_point(bad_pk) is None
    table = E.KeyTable([keys[0].public_key().public_bytes_raw(), bad_pk])
    acomb, valid = table.neg_combs()
    assert valid.tolist() == [True, False]
    n, tile, bucket = 8, 8, 32
    pks, msgs, sigs, expect = _batch(rng, keys[:1], n)
    # route half the lanes to the invalid key
    idx = np.array([0, 1] * (n // 2))
    blob = E.pack_blob_indexed(idx, msgs, sigs, num_keys=len(table))
    blob[:, 25] &= valid[np.clip(blob[:, 24].astype(np.int64), 0, 1)]
    g = E.group_blob_for_tiles(blob, len(table), tile, bucket)
    grouped, tile_keys, positions = g
    out = np.asarray(
        PK.verify_keyed_blob(
            grouped, table.words, acomb, tile_keys,
            E._pad_to(positions, bucket), tile=tile, interpret=True,
        )
    )[:n]
    assert (out == (idx == 0) & expect).all()


@kernel
def test_neg_combs_first_window_is_negated_key(keyring):
    """Spot-check the comb contents: entry (w=0, v=1) must be the Niels form
    of -A itself."""
    _, keys = keyring
    pk = keys[0].public_key().public_bytes_raw()
    table = E.KeyTable([pk])
    acomb, valid = table.neg_combs()
    assert valid.all()
    x, y = E._decode_point(pk)
    import mysticeti_tpu.ops.field as F

    arr = np.asarray(acomb)
    assert (arr[0, 0, 0, :, 1] == F.int_to_limbs((y + x) % E.P)).all()
    assert (arr[0, 0, 1, :, 1] == F.int_to_limbs((y - x) % E.P)).all()
    assert (
        arr[0, 0, 2, :, 1]
        == F.int_to_limbs((E.P - E._D2 * x % E.P * y % E.P) % E.P)
    ).all()


@pytest.fixture(scope="module")
def keyed_verdicts():
    """The keyed kernel over the cases as a launch reaches it
    (``_dispatch_indexed_keyed``: lanes under a key that is no point are
    refused by the host, tiles grouped by key, verdicts back in grouped
    order), beside the ``xla`` form of the same indexed blob."""
    blob, table = KC.indexed_blob()
    handle, positions = E._dispatch_indexed_keyed(blob, table, 256)
    grouped = np.asarray(handle)
    assert grouped.sum() == grouped[positions].sum()  # no padding lane passes
    xla = E.verify_fused_indexed_kernel(blob, table.words)
    return grouped[positions], np.asarray(xla)


@pytest.mark.parametrize("name", KC.NAMES)
def test_keyed_entry_point_verdicts(name, keyed_verdicts):
    got, xla = keyed_verdicts
    KC.check_verdict(name, got[KC.LANE[name]], xla[KC.LANE[name]])


def test_one_signers_launch_by_its_indices_counts_a_keyed_dispatch(
        keyed_verdicts):
    """Under the Pallas backend's plan (interpreted here) a launch whose
    keys ride as the wire's indices, all one signer's, is found to fit a
    tile by one look at the index column, grouped, and launched on the
    keyed kernel: the verdicts the fixture's launch gave those lanes."""
    from mysticeti_tpu.ops import ed25519_pallas as PK

    _, table = KC.indexed_blob()
    table.plan = E.DispatchPlan("pallas", True, PK.default_tile(), True)
    signer = KC.PKS[KC.LANE["honest-00"]]
    lanes = [i for i, case in enumerate(KC.CASES)
             if case[1] == signer and case[4]]
    assert len(lanes) > 4
    index = table.indices_for([signer] * len(lanes)).astype("<u2")
    before = {(r["kernel"], r["bucket"], r["backend"]): r["count"]
              for r in E.dispatch_counts()}
    got = E.dispatch_batch_table(
        table, table.keys_at(index), [KC.MSGS[i] for i in lanes],
        [KC.SIGS[i] for i in lanes]).result()
    now = {(r["kernel"], r["bucket"], r["backend"]): r["count"]
           for r in E.dispatch_counts()}
    assert {k: n - before.get(k, 0) for k, n in now.items()
            if n != before.get(k, 0)} == {("keyed", 256, "pallas"): 1}
    assert table.road_counts() == (1, 1)
    assert got.tolist() == keyed_verdicts[0][lanes].tolist()
    assert 0 < got.sum() < len(lanes)


# ---------------------------------------------------------------------------
# Compiled for the chip, without one.  These are the repository's only tests
# that load the TPU's compiler, and they stay in this one file: one process
# at a time may hold it.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - no compiler here: nothing to hold
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("entry", ["blob", "keyed"])  # indexed: blob's kernel
def test_entry_point_compiles_for_a_v5e(entry, one_v5e_chip):
    """The ladder and the keyed kernel, preparation inside, at the service's
    bucket and the chip's tile: Mosaic takes them, and what XLA keeps beside
    the one custom call has no loop."""
    from jax.experimental.compilation_cache import compilation_cache

    fn, shapes = KC.entry_points(256, 256)[entry]
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)
        for shape, dtype in shapes
    ]
    # A program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # or the process goes on as it decided
    try:
        text = fn.lower(*args, tile=256, interpret=False).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text and "while." not in text
