"""A launch is packed from the wire as arrays (PR 28).

The verifier service hands its backend the public keys, digests and
signatures of a launch as uint8 arrays, one row a signature, sliced off the
wire records; ``ops/ed25519.py`` packs rows of an array and sequences of
bytes objects into the same blob.  What must hold: both forms give the same
bytes, the same lookup, the same routing and the same verdicts as the
oracle; a backend wrapper written against sequences keeps working; launch
threads share no buffer; the counter says which form a pack call took; and
nothing compiles after warm-up.
"""
import asyncio
import struct
import threading

import numpy as np
import pytest

from mysticeti_tpu import crypto
from mysticeti_tpu.block_validator import (
    CpuSignatureVerifier,
    TpuSignatureVerifier,
)
from mysticeti_tpu.metrics import Metrics
from mysticeti_tpu.ops import ed25519 as E
from mysticeti_tpu.verifier_service import (
    RemoteSignatureVerifier,
    T_RAW,
    T_VERIFY,
    VerifierServer,
    _Pending,
)

SIGNERS = [crypto.Signer.from_seed(bytes([i + 1]) * 32) for i in range(10)]
KEYS = [s.public_key.bytes for s in SIGNERS]
STRANGERS = [crypto.Signer.from_seed(bytes([200 + i]) * 32) for i in range(7)]
SIZES = [1, 4, 15, 38, 169, 256, 257]
ORACLE = CpuSignatureVerifier()


def _signed(n, signers=SIGNERS, salt=0, corrupt=()):
    """(public keys, digests, signatures) of ``n`` signatures, signer i of
    ``signers`` in turn; the signatures at ``corrupt`` have one bit
    flipped."""
    pks, digests, sigs = [], [], []
    for i in range(n):
        signer = signers[(i + salt) % len(signers)]
        digest = crypto.blake2b_256(b"launch-%d-%d" % (salt, i))
        sig = signer.sign(digest)
        if i in corrupt:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        pks.append(signer.public_key.bytes)
        digests.append(digest)
        sigs.append(sig)
    return pks, digests, sigs


def _rows(items, width):
    """A sequence of ``width``-byte objects as the column slice of a wider
    record array that the service would hand over: not contiguous."""
    n = len(items)
    records = np.zeros((n, width + 6), np.uint8)
    records[:, 3:3 + width] = np.frombuffer(
        b"".join(items), np.uint8).reshape(n, width)
    return records[:, 3:3 + width]


def _blob_by_the_layout(pks, digests, sigs, indices=None):
    """The blob as ``pack_blob`` / ``pack_blob_indexed``'s docstrings state
    it, a signature at a time: what the parent commit's packers gave."""
    rows = []
    for i, (pk, digest, sig) in enumerate(zip(pks, digests, sigs)):
        r = struct.unpack(">8I", sig[:32])
        s = struct.unpack("<8I", sig[32:])
        m = struct.unpack(">8I", digest)
        if indices is None:
            rows.append(r + struct.unpack(">8I", pk) + m + s + (1,))
        else:
            rows.append(r + m + s + (indices[i], 1))
    return np.array(rows, np.uint32)


# -- (a) both forms give the same blob, byte for byte -----------------------


@pytest.mark.parametrize("layout", ["raw", "indexed"])
@pytest.mark.parametrize("n", SIZES)
def test_rows_and_objects_pack_the_same_blob(n, layout):
    pks, digests, sigs = _signed(n, salt=n)
    pk_rows, digest_rows, sig_rows = (
        _rows(pks, 32), _rows(digests, 32), _rows(sigs, 64))
    if layout == "raw":
        from_objects = E.pack_blob(pks, digests, sigs)
        from_rows = E.pack_blob(pk_rows, digest_rows, sig_rows)
        stated = _blob_by_the_layout(pks, digests, sigs)
        words, s_words, host_ok = E.pack_bytes(pk_rows, digest_rows, sig_rows)
        assert np.array_equal(words, stated[:, :24])
        assert np.array_equal(s_words, stated[:, 24:32])
        assert host_ok.dtype == bool and host_ok.all()
    else:
        table = E.KeyTable(KEYS)
        indices = table.indices_for(pks)
        assert np.array_equal(indices, table.indices_for(pk_rows))
        from_objects = E.pack_blob_indexed(
            indices, digests, sigs, num_keys=len(table))
        from_rows = E.pack_blob_indexed(
            indices, digest_rows, sig_rows, num_keys=len(table))
        stated = _blob_by_the_layout(pks, digests, sigs, indices)
    assert from_rows.dtype == from_objects.dtype == np.uint32
    assert from_rows.shape == from_objects.shape == stated.shape
    assert from_rows.tobytes() == from_objects.tobytes() == stated.tobytes()
    # What goes to the device is the bucket's height, zero below the batch,
    # and a buffer of its own.
    for start, count, bucket in E.iter_buckets(n):
        chunk = from_rows[start:start + count]
        padded = E._pad_to(chunk, bucket)
        assert padded.shape == (bucket, from_rows.shape[1])
        assert padded.flags.c_contiguous
        assert np.array_equal(padded[:count], chunk)
        assert not padded[count:].any()


@pytest.mark.parametrize("form", ["rows", "objects"])
def test_a_malformed_column_is_masked_in_either_form(form):
    """An array of another width is malformed in every row, as a bytes
    object of another length is in its own: host_ok 0, never an
    exception."""
    pks, digests, sigs = _signed(5)
    if form == "rows":
        short = _rows([s[:63] for s in sigs], 63)
        blob = E.pack_blob(_rows(pks, 32), _rows(digests, 32), short)
        assert not blob[:, 32].any()
        indexed = E.pack_blob_indexed(
            np.arange(5), _rows(digests, 32), short, num_keys=10)
        assert not indexed[:, 25].any()
    else:
        sigs[2] = sigs[2][:63]
        blob = E.pack_blob(pks, digests, sigs)
        assert blob[:, 32].tolist() == [1, 1, 0, 1, 1]
        indexed = E.pack_blob_indexed(
            np.arange(5), digests, sigs, host_ok=[1, 0, 1, 1, 1], num_keys=10)
        assert indexed[:, 25].tolist() == [1, 0, 0, 1, 1]
    assert E._all_digests(_rows(digests, 32)) and E._all_digests(digests)
    assert not E._all_digests(_rows([d[:31] for d in digests], 31))
    assert not E._all_digests([digests[0], b"short"])


# -- (b) the key lookup -------------------------------------------------------

ZERO = bytes(32)
TAIL = KEYS[0][:31] + b"\x00"  # differs from a table key in its last byte
LOOKUPS = {
    "committee": (KEYS, KEYS[::-1] + KEYS[:3]),
    "strangers": (KEYS, [s.public_key.bytes for s in STRANGERS]),
    "mixed": (KEYS, [KEYS[4], STRANGERS[0].public_key.bytes, KEYS[0]]),
    "zero key unknown": (KEYS, [ZERO, KEYS[1], ZERO]),
    "zero key in the table": (KEYS[:3] + [ZERO], [ZERO, KEYS[2], KEYS[5]]),
    "a key held twice": (KEYS[:4] + [KEYS[1]], [KEYS[1], KEYS[3], KEYS[0]]),
    "trailing zero byte": (KEYS[:2] + [TAIL], [TAIL, KEYS[0], TAIL[:31] + b"\x01"]),
    "one key": (KEYS[:1], [KEYS[0], KEYS[1]]),
}


@pytest.mark.parametrize("case", sorted(LOOKUPS))
def test_indices_for_rows_is_the_dicts_answer(case):
    table_keys, asked = LOOKUPS[case]
    table = E.KeyTable(table_keys)
    by_dict = table.indices_for(asked)
    assert by_dict.tolist() == [
        max((i for i, k in enumerate(table_keys) if k == pk), default=-1)
        for pk in asked
    ]
    by_rows = table.indices_for(_rows(asked, 32))
    assert by_rows.dtype == by_dict.dtype == np.int64
    assert by_rows.tolist() == by_dict.tolist()
    contiguous = np.frombuffer(b"".join(asked), np.uint8).reshape(-1, 32)
    assert table.indices_for(contiguous).tolist() == by_dict.tolist()
    assert table.indices_for(_rows([k[:31] for k in asked], 31)).tolist() == [
        -1] * len(asked)


# -- the service with its real backend ---------------------------------------


def _verify_body(indices, digests, sigs):
    return memoryview(b"".join(
        struct.pack("<H", i) + d + s
        for i, d, s in zip(indices, digests, sigs)))


def _raw_body(pks, digests, sigs):
    return memoryview(b"".join(
        pk + d + s for pk, d, s in zip(pks, digests, sigs)))


def _request(req_id, signed, indexed):
    """One pending request and the oracle's verdicts for it.  ``indexed``:
    a VERIFY frame, whose keys ride as indices into KEYS (an index of
    ``len(KEYS)`` or more stands for no key: it cannot verify)."""
    pks, digests, sigs = signed
    if indexed:
        indices = [KEYS.index(pk) if pk in KEYS else len(KEYS) + 7
                   for pk in pks]
        body = _verify_body(indices, digests, sigs)
        known = [i < len(KEYS) for i in indices]
    else:
        body, known = _raw_body(pks, digests, sigs), [True] * len(sigs)
    expected = [ok and k for ok, k in zip(
        ORACLE.verify_signatures(pks, digests, sigs), known)]
    item = _Pending(T_VERIFY if indexed else T_RAW, req_id, len(sigs), body,
                    "c0", None, None)
    return item, expected


def _verdicts(replies):
    return [[bool(b) for b in verdicts] for _, verdicts in replies]


def _dispatched(before):
    """(kernel, lanes) -> launches since ``before`` (``dispatch_counts``)."""
    was = {(r["kernel"], r["bucket"]): r["count"] for r in before}
    return {
        (r["kernel"], r["bucket"]): r["count"] - was.get(
            (r["kernel"], r["bucket"]), 0)
        for r in E.dispatch_counts()
        if r["count"] != was.get((r["kernel"], r["bucket"]), 0)
    }


@pytest.fixture(scope="module")
def warmed(tmp_path_factory):
    """A service around the JAX backend on one device, warmed as at boot
    (the XLA form of both kernels at the 256 bucket, then calibrated)."""
    server = VerifierServer(
        str(tmp_path_factory.mktemp("svc") / "v.sock"), committee_keys=KEYS,
        backend=TpuSignatureVerifier(mesh=None, committee_keys=KEYS))
    server.prewarm()
    assert server._launch_cap == 256
    return server


# What a launch holds, as (indexed?, signed) requests; the kernel it is
# routed to: PR 28's parent's, but for a VERIFY frame's index out of range,
# which since PR 46 is a rejected lane of an indexed launch where the keys
# ride as indices (VERIFY frames alone).  (On the chip a launch of one
# signer takes the keyed tile first; off it the XLA form serves every
# indexed launch.)
LAUNCHES = {
    "one signer": (
        [(True, _signed(3, SIGNERS[:1], 1)), (True, _signed(1, SIGNERS[:1], 2))],
        "indexed"),
    "several signers, one corrupted": (
        [(True, _signed(4, salt=3)), (True, _signed(15, salt=4, corrupt={7}))],
        "indexed"),
    "VERIFY and RAW, all committee": (
        [(True, _signed(4, salt=5)), (False, _signed(15, salt=6, corrupt={0})),
         (True, _signed(1, salt=7))],
        "indexed"),
    "a stranger among committee keys": (
        [(True, _signed(4, salt=8)),
         (False, _signed(5, SIGNERS[:4] + STRANGERS[:1], 9, corrupt={2}))],
        "blob"),
    "an index out of range": (
        [(True, _signed(4, SIGNERS[:3] + STRANGERS[:1], 10)),
         (True, _signed(2, salt=11))],
        "indexed"),
    "an index out of range beside a RAW frame": (
        [(True, _signed(4, SIGNERS[:3] + STRANGERS[:1], 14)),
         (False, _signed(2, salt=15))],
        "blob"),
    "strangers alone, wide": (
        [(False, _signed(128, STRANGERS, 12, corrupt={5, 127})),
         (False, _signed(41, STRANGERS, 13))],
        "blob"),
}


@pytest.mark.parametrize("case", sorted(LAUNCHES))
def test_a_launch_verifies_and_routes_as_the_parent_did(warmed, case):
    requests, kernel = LAUNCHES[case]
    batch, expected = zip(*(
        _request(i + 1, signed, indexed)
        for i, (indexed, signed) in enumerate(requests)))
    before = E.dispatch_counts()
    replies = warmed._verify_batch(list(batch))
    assert [req_id for req_id, _ in replies] == [
        struct.pack("<I", item.req_id) for item in batch]
    assert all(isinstance(verdicts, bytes) for _, verdicts in replies)
    assert _verdicts(replies) == list(expected)
    assert _dispatched(before) == {(kernel, 256): 1}


@pytest.mark.parametrize("case", [
    "several signers, one corrupted", "strangers alone, wide"])
def test_the_backend_says_once_a_launch_that_it_enters_its_fetch(warmed, case):
    """Down either road, with no clocked request on the launch: the
    listener of the launching thread hears it once, after the kernel's
    launch was made and before the verdicts are back."""
    from mysticeti_tpu import spans

    requests, kernel = LAUNCHES[case]
    batch, expected = zip(*(
        _request(i + 1, signed, indexed)
        for i, (indexed, signed) in enumerate(requests)))
    before, heard = E.dispatch_counts(), []
    spans.on_fetch(lambda: heard.append(_dispatched(before)))
    try:
        replies = warmed._verify_batch(list(batch))
    finally:
        spans.on_fetch(None)
    assert heard == [{(kernel, 256): 1}]
    assert _verdicts(replies) == list(expected)


# -- (d) a backend wrapper written against sequences ---------------------------


def _half_checked(sound):
    def verify_signatures(self, public_keys, digests, signatures):
        n = len(signatures)
        k = (n + 1) // 2
        checked = sound(self, public_keys[:k], digests[:k], signatures[:k])
        return list(checked) + [True] * (n - k)
    return verify_signatures


def _strangers_pass(sound):
    def verify_signatures(self, public_keys, digests, signatures):
        verdicts = list(sound(self, public_keys, digests, signatures))
        known = self._table.indices_for(
            [bytes(pk) for pk in public_keys]) >= 0
        return [bool(ok) or not k for ok, k in zip(verdicts, known)]
    return verify_signatures


def _by_bytes(sound):
    def verify_signatures(self, public_keys, digests, signatures):
        return list(sound(
            self, [bytes(pk) for pk in public_keys],
            [bytes(d) for d in digests], [bytes(s) for s in signatures]))
    return verify_signatures


WRAPPERS = {
    "half checked": (
        _half_checked,
        lambda oks, known: oks[:(len(oks) + 1) // 2]
        + [True] * (len(oks) // 2)),
    "strangers pass": (
        _strangers_pass,
        lambda oks, known: [ok or not k for ok, k in zip(oks, known)]),
    "rows as bytes": (_by_bytes, lambda oks, known: oks),
}


# The launch a wrapper sees: with a RAW frame (three arrays of rows), and
# VERIFY frames alone (since PR 46 the keys are an ``IndexedKeys``).
WRAPPED = {
    "VERIFY and RAW": [
        (True, _signed(4, salt=20, corrupt={3})),
        (False, _signed(5, SIGNERS[:2] + STRANGERS[:2], 21, corrupt={1, 4})),
    ],
    "VERIFY alone": [
        (True, _signed(4, salt=22, corrupt={3})),
        (True, _signed(5, SIGNERS[:3] + STRANGERS[:1], 23, corrupt={1, 4})),
    ],
}


@pytest.mark.parametrize("launch", sorted(WRAPPED))
@pytest.mark.parametrize("wrapper", sorted(WRAPPERS))
def test_a_wrapper_written_like_the_controls_still_works(
        warmed, wrapper, launch, monkeypatch):
    """The controls under ``benchmark/tests`` patch the backend's
    ``verify_signatures``, slice its arguments, take ``len`` and
    ``bytes(pk)`` of them and return lists: all of that on what the service
    now passes, whichever road the launch takes."""
    wrap, altered = WRAPPERS[wrapper]
    monkeypatch.setattr(
        TpuSignatureVerifier, "verify_signatures",
        wrap(TpuSignatureVerifier.verify_signatures))
    requests = WRAPPED[launch]
    batch, expected = zip(*(
        _request(i + 1, signed, indexed)
        for i, (indexed, signed) in enumerate(requests)))
    flat = [ok for verdicts in expected for ok in verdicts]
    known = [pk in KEYS for _, signed in requests for pk in signed[0]]
    want = altered(flat, known)
    assert want != flat or wrapper == "rows as bytes"
    got = [ok for verdicts in _verdicts(warmed._verify_batch(list(batch)))
           for ok in verdicts]
    assert got == want


def test_a_host_oracle_takes_the_rows(tmp_path):
    """Every backend gets the same three arrays: the OpenSSL oracle reads a
    row as the bytes it is."""
    server = VerifierServer(str(tmp_path / "v.sock"), committee_keys=KEYS,
                            backend=ORACLE)
    server._warmed.set()
    batch, expected = zip(
        _request(1, _signed(4, salt=30, corrupt={1}), True),
        _request(2, _signed(3, STRANGERS, 31, corrupt={0}), False),
        _request(3, _signed(2, SIGNERS[:1] + STRANGERS[:1], 32), True))
    assert _verdicts(server._verify_batch(list(batch))) == list(expected)


# -- (e) launch threads share no buffer ----------------------------------------


def test_three_threads_packing_at_once_share_no_buffer(warmed):
    """The service's three launch slots pack at the same time: each
    thread's launches, indexed and unknown-signer in turn, come back with
    the oracle's verdicts for its own signatures."""
    rounds = 2
    barrier = threading.Barrier(VerifierServer.DISPATCHERS)
    plans, failures = [], []
    for t in range(VerifierServer.DISPATCHERS):
        plan = []
        for r in range(rounds):
            strangers = (t + r) % 2 == 1
            signed = _signed(
                7 + 5 * t + r, STRANGERS if strangers else SIGNERS,
                salt=40 + 10 * t + r, corrupt={t, 3 + r})
            plan.append(_request(r + 1, signed, indexed=not strangers))
        plans.append(plan)

    def slot(plan):
        try:
            for item, expected in plan:
                barrier.wait(60)
                got = _verdicts(warmed._verify_batch([item]))
                if got != [expected]:
                    failures.append((item.n, got, expected))
        except Exception as exc:  # noqa: BLE001 - reported below
            barrier.abort()
            failures.append(exc)

    threads = [threading.Thread(target=slot, args=(plan,)) for plan in plans]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(120)
    assert failures == []


# -- (f) the counter that says which form a pack call took ---------------------


def _pack_calls(metrics):
    counter = metrics.verify_pack_rows_total
    return {form: counter.labels(form)._value.get()
            for form in ("array", "objects")}


def test_the_service_packs_arrays_and_a_list_call_counts_objects(
        warmed, monkeypatch, tmp_path):
    metrics = Metrics()
    monkeypatch.setattr(E, "_attr_metrics", metrics)
    monkeypatch.setattr(E, "_TRANSFER_FLUSH_S", 0.0)  # every call moves its sums
    monkeypatch.setattr(E, "_transfer_local", threading.local())
    assert _pack_calls(metrics) == {"array": 0, "objects": 0}
    launches = [
        [_request(1, _signed(4, salt=60), True)[0]],
        [_request(1, _signed(3, STRANGERS, 61), False)[0],
         _request(2, _signed(2, salt=62), True)[0]],
    ]
    for batch in launches:
        warmed._verify_batch(batch)
    assert _pack_calls(metrics) == {"array": 2, "objects": 0}

    # Over the socket, from a dispatcher thread of a service that holds the
    # same warm backend.
    async def over_the_socket():
        server = VerifierServer(
            str(tmp_path / "v.sock"), committee_keys=KEYS,
            backend=warmed._backend)
        server._launch_cap = warmed._launch_cap
        server._warmed.set()
        await server.start()
        try:
            client = RemoteSignatureVerifier(
                socket_path=server.socket_path, committee_keys=KEYS)
            return await asyncio.to_thread(
                client.verify_signatures, *_signed(5, salt=63, corrupt={2}))
        finally:
            await server.stop()

    oks = asyncio.run(over_the_socket())
    assert list(oks) == [True, True, False, True, True]
    assert _pack_calls(metrics) == {"array": 3, "objects": 0}
    # In process, with the objects in hand.
    oks = warmed._backend.verify_signatures(*_signed(2, salt=64))
    assert oks == [True, True]
    assert _pack_calls(metrics) == {"array": 3, "objects": 1}
    # The bytes still count beside them, on the same list.
    assert metrics.mysticeti_device_transfer_bytes_total.labels(
        "to_device")._value.get() == 3 * 256 * 26 * 4 + 256 * 33 * 4


# -- (g) nothing compiles after warm-up ----------------------------------------


def test_the_new_path_compiles_nothing_after_warm_up(warmed):
    """The numpy blob is the jitted entry point's own argument, as it is in
    warm-up's probes: a launch of either kernel, from either frame type,
    finds its program compiled."""
    before = dict(E.COMPILE_STATS)
    assert before["cache_hits"] + before["cache_misses"] > 0
    for requests in (
        [(True, _signed(38, salt=70))],
        [(False, _signed(15, STRANGERS, 71))],
        [(True, _signed(4, salt=72)), (False, _signed(1, salt=73))],
    ):
        batch, expected = zip(*(
            _request(i + 1, signed, indexed)
            for i, (indexed, signed) in enumerate(requests)))
        assert _verdicts(warmed._verify_batch(list(batch))) == list(expected)
    assert E.COMPILE_STATS == before


# -- (h) a launch of VERIFY frames keeps its key indices (PR 46) ---------------

TWICE = KEYS[:-1] + [KEYS[0]]  # a committee that holds its first key twice


def _indexed_request(req_id, indices, salt, keys=KEYS, corrupt=()):
    """A VERIFY request whose i-th signature is by the signer of
    ``keys[indices[i]]`` (an index ``keys`` does not hold: by a stranger),
    and the oracle's verdicts for it."""
    by_key = {s.public_key.bytes: s for s in SIGNERS}
    digests, sigs, expected = [], [], []
    for i, index in enumerate(indices):
        known = index < len(keys)
        signer = by_key[keys[index]] if known else STRANGERS[0]
        digest = crypto.blake2b_256(b"direct-%d-%d" % (salt, i))
        sig = signer.sign(digest)
        if i in corrupt:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        digests.append(digest)
        sigs.append(sig)
        expected.append(known and i not in corrupt)
    item = _Pending(T_VERIFY, req_id, len(indices),
                    _verify_body(indices, digests, sigs), "c0", None, None)
    return item, expected


# case -> (the committee, the launch's requests as (indices, corrupt)).
DIRECT = {
    "ten signers mixed": (KEYS, [
        ([i % 10 for i in range(15)], ()), ([3, 1, 4, 1], ()),
        ([9], ()), ([(7 * i) % 10 for i in range(38)], ())]),
    "one signer": (KEYS, [([6] * 4, ()), ([6], ()), ([6] * 15, ())]),
    "an index out of range": (KEYS, [([2, 10, 3], ()), ([65535, 0], ())]),
    "a corrupted signature": (KEYS, [([0, 1, 2, 3], {2}), ([4] * 4, {0})]),
    "a committee that holds one key twice": (
        TWICE, [([0, 9, 5, 9], ()), ([9, 0], {0})]),
    "exactly the bucket": (KEYS, [
        ([i % 10 for i in range(200)], {17}), ([5] * 56, ())]),
    "one signature": (KEYS, [([8], ())]),
}


@pytest.fixture(scope="module")
def twice(warmed, tmp_path_factory):
    """A service whose committee holds one key twice, on a backend of its
    own (the table is the backend's): the same shapes as ``warmed``'s, so
    nothing compiles."""
    server = VerifierServer(
        str(tmp_path_factory.mktemp("twice") / "v.sock"),
        committee_keys=TWICE,
        backend=TpuSignatureVerifier(mesh=None, committee_keys=TWICE))
    server._launch_cap = warmed._launch_cap
    server._warmed.set()
    return server


@pytest.mark.parametrize("case", sorted(DIRECT))
def test_the_direct_road_gives_the_old_roads_verdicts(warmed, twice, case):
    """Verdict for verdict: a launch of VERIFY frames whose indices go into
    the blob as they are, against ``dispatch_batch_table`` on the same
    rows with the keys gathered — the road such a launch took before."""
    keys, requests = DIRECT[case]
    server = warmed if keys is KEYS else twice
    table = server._backend._table
    batch, expected = zip(*(
        _indexed_request(i + 1, indices, 80 + i, keys, corrupt)
        for i, (indices, corrupt) in enumerate(requests)))
    before, roads = E.dispatch_counts(), table.road_counts()
    got = [ok for verdicts in _verdicts(server._verify_batch(list(batch)))
           for ok in verdicts]
    one_signer = len({i for indices, _ in requests for i in indices}) == 1
    kernel = "keyed" if one_signer and table.plan.keyed else "indexed"
    assert _dispatched(before) == {(kernel, 256): 1}
    assert table.road_counts() == (
        roads[0] + 1, roads[1] + (kernel == "keyed"))
    assert got == [ok for verdicts in expected for ok in verdicts]

    rows = np.frombuffer(
        b"".join(item.body for item in batch), np.uint8).reshape(-1, 98)
    index = rows[:, :2].view("<u2")[:, 0]
    gathered = server._key_rows(keys)[np.minimum(index, len(keys))]
    old = E.dispatch_batch_table(
        table, gathered, rows[:, 2:34], rows[:, 34:]).result()
    assert table.road_counts()[0] == roads[0] + 1  # not direct: searched
    assert got == old.tolist()


def test_a_launch_that_holds_a_raw_frame_takes_the_old_road(warmed):
    """One RAW request among VERIFY frames: the keys are gathered and
    searched as before, with the verdicts of before."""
    table = warmed._backend._table
    requests = [(True, _signed(4, salt=90, corrupt={1})),
                (False, _signed(3, salt=91)),
                (True, _signed(2, salt=92))]
    batch, expected = zip(*(
        _request(i + 1, signed, indexed)
        for i, (indexed, signed) in enumerate(requests)))
    before, roads = E.dispatch_counts(), table.road_counts()
    assert _verdicts(warmed._verify_batch(list(batch))) == list(expected)
    assert _dispatched(before) == {("indexed", 256): 1}
    assert table.road_counts() == roads


def test_indexed_keys_read_as_the_keys_they_stand_for():
    """To a wrapper written against sequences the index column is the keys:
    ``len``, slices, items, iteration, and the all-zero key where the table
    holds no such row."""
    table = E.KeyTable(KEYS)
    keys = table.keys_at(np.array([3, 0, 10, 9], "<u2"))
    assert len(keys) == 4
    assert list(keys) == [KEYS[3], KEYS[0], bytes(32), KEYS[9]]
    assert keys[1] == KEYS[0] and keys[-1] == KEYS[9]
    assert list(keys[1:3]) == [KEYS[0], bytes(32)]
    assert isinstance(keys[:2], E.IndexedKeys) and keys[:2].table is table
    assert keys.rows().shape == (4, 32)
    # Another table's index is no index here: its keys are searched.
    other = E.KeyTable(KEYS[::-1])
    _, digests, sigs = _signed(2, [SIGNERS[3], SIGNERS[0]], salt=95)
    blobs = []
    for t, column in ((table, keys[:2]), (other, keys[:2])):
        seen = []
        real = E.dispatch_indexed_chunks
        E.dispatch_indexed_chunks = lambda blob, tab: seen.append(blob) or []
        try:
            E.dispatch_batch_table(t, column, _rows(digests, 32), _rows(sigs, 64))
        finally:
            E.dispatch_indexed_chunks = real
        blobs.append(seen[0])
    assert blobs[0][:, 24].tolist() == [3, 0] and table.road_counts()[0] == 1
    assert blobs[1][:, 24].tolist() == [6, 9] and other.road_counts()[0] == 0


def _launched_on(before):
    """(kernel, lanes, backend) -> launches since ``before``."""
    was = {(r["kernel"], r["bucket"], r["backend"]): r["count"]
           for r in before}
    now = {(r["kernel"], r["bucket"], r["backend"]): r["count"]
           for r in E.dispatch_counts()}
    return {key: n - was.get(key, 0) for key, n in now.items()
            if n != was.get(key, 0)}


class _Spy:
    def __init__(self, returns=None):
        self.calls, self.returns = 0, returns

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.returns


@pytest.fixture
def chip_plan(monkeypatch, tmp_path):
    """A service whose backend dispatches as on the chip — the Pallas
    backend, the keyed kernel where one key a 256-lane tile fits — with
    the two Pallas entry points a launch reaches replaced by spies (they
    compile for minutes under the interpreter) that accept every lane."""
    import jax.numpy as jnp

    from mysticeti_tpu.ops import ed25519_pallas as PK

    backend = TpuSignatureVerifier(mesh=None, committee_keys=KEYS)
    backend._table.plan = E.DispatchPlan("pallas", True, 256, False)
    accepted = jnp.ones(256, bool)
    ladder, keyed = _Spy(accepted), _Spy(accepted)
    monkeypatch.setattr(PK, "verify_fused_indexed_blob_pallas", ladder)
    monkeypatch.setattr(PK, "verify_keyed_blob", keyed)
    server = VerifierServer(
        str(tmp_path / "v.sock"), committee_keys=KEYS, backend=backend)
    server._launch_cap = 256
    server.counts.roads = backend.road_counts
    server._warmed.set()
    return server, ladder, keyed


def test_a_launch_of_several_signers_goes_from_the_wire_to_the_ladder_once(
        chip_plan, monkeypatch):
    """No key is searched for and nothing is grouped: the index column says
    that ten signers do not fit one tile, and the stamps say which road the
    launch took."""
    server, ladder, keyed = chip_plan
    searched, grouped = _Spy(), _Spy()
    monkeypatch.setattr(E.KeyTable, "indices_for", searched)
    monkeypatch.setattr(E, "group_blob_for_tiles", grouped)
    batch = [_indexed_request(i + 1, indices, 100 + i)[0]
             for i, indices in enumerate(([0, 1, 2, 3], [4] * 15, [9]))]
    before, stamps = E.dispatch_counts(), server.counts.read()
    replies = server._verify_batch(batch)
    assert _verdicts(replies) == [[True] * item.n for item in batch]
    assert (searched.calls, grouped.calls) == (0, 0)
    assert (ladder.calls, keyed.calls) == (1, 0)
    assert _launched_on(before) == {("indexed", 256, "pallas"): 1}
    grew = dict(zip(server.counts.STAMPS, (
        now - was for now, was in zip(server.counts.read(), stamps))))
    assert (grew["direct"], grew["keyed_tried"]) == (1, 0)


def test_a_launch_of_one_signer_still_reaches_the_keyed_kernel(chip_plan):
    server, ladder, keyed = chip_plan
    batch = [_indexed_request(1, [7] * 4, 110)[0],
             _indexed_request(2, [7], 111)[0]]
    before, stamps = E.dispatch_counts(), server.counts.read()
    assert _verdicts(server._verify_batch(batch)) == [[True] * 4, [True]]
    assert (ladder.calls, keyed.calls) == (0, 1)
    assert _launched_on(before) == {("keyed", 256, "pallas"): 1}
    grew = dict(zip(server.counts.STAMPS, (
        now - was for now, was in zip(server.counts.read(), stamps))))
    assert (grew["direct"], grew["keyed_tried"]) == (1, 1)


def test_a_keyed_launch_says_that_it_enters_its_fetch_after_the_kernel_call(
        chip_plan):
    from mysticeti_tpu import spans

    server, ladder, keyed = chip_plan
    heard = []
    spans.on_fetch(lambda: heard.append((ladder.calls, keyed.calls)))
    try:
        replies = server._verify_batch([_indexed_request(1, [7] * 4, 120)[0]])
    finally:
        spans.on_fetch(None)
    assert heard == [(0, 1)] and _verdicts(replies) == [[True] * 4]


def test_a_host_oracle_is_served_as_before_and_counts_no_road(tmp_path):
    """A backend without ``indexed_keys`` gets the keys themselves, and the
    service's two stamps of the backend's roads stay at zero."""
    seen = []

    class Oracle(CpuSignatureVerifier):
        def verify_signatures(self, public_keys, digests, signatures):
            seen.append(public_keys)
            return super().verify_signatures(public_keys, digests, signatures)

    server = VerifierServer(str(tmp_path / "v.sock"), committee_keys=KEYS,
                            backend=Oracle())
    server._warmed.set()
    batch, expected = zip(_indexed_request(1, [2, 10, 3], 120),
                          _indexed_request(2, [5], 121, corrupt={0}))
    assert _verdicts(server._verify_batch(list(batch))) == list(expected)
    assert isinstance(seen[0], np.ndarray) and seen[0].shape == (4, 32)
    assert dict(zip(server.counts.STAMPS, server.counts.read()))[
        "direct"] == 0
