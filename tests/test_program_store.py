"""The program store (``ops/programs.py``): a kernel's lowered program is
written once beside the compilation cache and read back by every later
process, so a warm boot traces nothing.

Two tiers in one file.  The store's rules — what is a hit, what is a miss,
what happens to a file that cannot be read back, that nothing about the
store can fail a boot — are held on a toy entry point in this process, with
the store directory pointed at ``tmp_path``.  That the real kernels come
back from the store as the programs they were traced as is held on the
Pallas kernels themselves (interpreted: the CPU tier), each boot a fresh
subprocess on a cache directory of its own, as a verifier service's is.
The first boot of the module's directory traces and compiles the three
kernels on the CPU (minutes, so side by side); every other boot loads.
"""
import functools
import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mysticeti_tpu.crypto import Ed25519PrivateKey, PublicKey
from mysticeti_tpu.ops import programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("programs_loaded", "programs_written", "programs_rejected")
KERNELS = ("blob", "indexed", "keyed")


# ---------------------------------------------------------------------------
# The rules, on a toy entry point


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store of this test's own, and the counters' growth over it."""
    directory = tmp_path / "cache" / "programs"
    monkeypatch.setattr(programs, "store_dir", lambda: str(directory))
    before = dict(programs.COMPILE_STATS)

    class Store:
        path = directory

        @staticmethod
        def grew():
            return tuple(
                programs.COMPILE_STATS[k] - before[k] for k in COUNTERS
            )

        @staticmethod
        def files():
            return sorted(os.listdir(directory)) if directory.exists() else []

    return Store


def _entry():
    """A jitted entry point shaped like the kernels' (arrays, a None-able
    argument, a static one) and the list of times its Python ran."""
    traces = []

    @functools.partial(jax.jit, static_argnames=("tile",))
    def _toy_entry(blob, table, positions, *, tile):
        traces.append(blob.shape)
        out = blob[:, 0] * tile + table[0, 0]
        return out if positions is None else jnp.take(out, positions)

    return _toy_entry, traces


def _blob(lanes=8):
    return np.arange(2 * lanes, dtype=np.uint32).reshape(lanes, 2)


TABLE = np.full((3, 8), 5, np.uint32)


def test_a_miss_traces_and_writes_and_the_next_process_loads(store):
    entry, traces = _entry()
    want = _blob()[:, 0] * 4 + 5
    first = programs.StoredProgram(entry)
    assert (np.asarray(first(_blob(), TABLE, None, tile=4)) == want).all()
    assert store.grew() == (0, 1, 0) and len(traces) == 1
    [name] = store.files()
    assert name.startswith("_toy_entry-") and name.endswith(".stablehlo")
    # The same shape again is the callable this process already holds.
    first(_blob(), TABLE, None, tile=4)
    assert store.grew() == (0, 1, 0) and len(traces) == 1

    # A later process: another entry object over the same source.
    again, traces_again = _entry()
    later = programs.StoredProgram(again)
    assert (np.asarray(later(_blob(), TABLE, None, tile=4)) == want).all()
    assert store.grew() == (1, 1, 0) and traces_again == []
    # What runs is a jitted function called as the entry point is: the XLA
    # module's name, by which a profile finds the launch.
    [launch] = later._ready.values()
    module = launch.lower(_blob(), TABLE, None).as_text()
    assert "module @jit__toy_entry " in module


@pytest.mark.parametrize(
    "changed", ["source", "jax", "libtpu", "device_kind", "bucket", "tile",
                "table", "positions"],
)
def test_whatever_can_change_the_program_is_a_miss(store, monkeypatch,
                                                   changed):
    """A key that does not match names another file: traced, written, and
    the file of the other key left alone."""
    entry, traces = _entry()
    programs.StoredProgram(entry)(_blob(), TABLE, None, tile=4)
    assert store.grew() == (0, 1, 0)

    args, statics = [_blob(), TABLE, None], {"tile": 4}
    if changed in ("source", "jax", "libtpu", "device_kind"):
        context = dict(programs._process_context())
        context[changed] = f"{context[changed]}+changed"
        monkeypatch.setattr(programs, "_context", context)
    elif changed == "bucket":
        args[0] = _blob(32)
    elif changed == "table":
        args[1] = np.full((4, 8), 5, np.uint32)
    elif changed == "positions":
        args[2] = np.arange(8, dtype=np.int32)
    else:
        statics["tile"] = 8
    again, traces_again = _entry()
    out = programs.StoredProgram(again)(*args, **statics)
    assert (
        np.asarray(out) == args[0][:, 0] * statics["tile"] + 5
    ).all()
    assert store.grew() == (0, 2, 0) and len(traces_again) == 1
    assert len(store.files()) == 2


def _damage(path, how):
    with open(path, "rb") as f:
        whole = f.read()
    with open(path, "wb") as f:
        if how == "truncated":
            f.write(whole[: len(whole) // 2])
        elif how == "garbage":
            f.write(os.urandom(4096))
        elif how == "empty":
            pass
        elif how == "older-format":
            f.write(b"mysticeti-tpu program 0\n" + whole[24:])
        else:  # a bit flipped in the payload
            f.write(whole[:-100] + bytes([whole[-100] ^ 1]) + whole[-99:])
    return whole


@pytest.mark.parametrize(
    "how", ["truncated", "garbage", "empty", "older-format", "bit-flipped"]
)
def test_a_file_that_cannot_be_read_back_is_replaced(store, how):
    entry, _ = _entry()
    programs.StoredProgram(entry)(_blob(), TABLE, None, tile=4)
    [name] = store.files()
    whole = _damage(store.path / name, how)

    again, traces = _entry()
    out = programs.StoredProgram(again)(_blob(), TABLE, None, tile=4)
    assert (np.asarray(out) == _blob()[:, 0] * 4 + 5).all()
    # Counted, traced again, written again — the same bytes as before —
    # and nothing else left lying in the directory.
    assert store.grew() == (0, 2, 1) and len(traces) == 1
    assert store.files() == [name]
    with open(store.path / name, "rb") as f:
        assert f.read() == whole
    third, traces = _entry()
    programs.StoredProgram(third)(_blob(), TABLE, None, tile=4)
    assert store.grew() == (1, 2, 1) and traces == []


def test_a_store_that_cannot_be_written_fails_nothing(tmp_path, monkeypatch):
    (tmp_path / "file").write_text("not a directory")
    monkeypatch.setattr(
        programs, "store_dir", lambda: str(tmp_path / "file" / "programs")
    )
    before = [programs.COMPILE_STATS[k] for k in COUNTERS]
    entry, traces = _entry()
    out = programs.StoredProgram(entry)(_blob(), TABLE, None, tile=4)
    assert (np.asarray(out) == _blob()[:, 0] * 4 + 5).all()
    assert len(traces) == 1
    # Nothing loaded, nothing kept, and no file to reject.
    assert [programs.COMPILE_STATS[k] for k in COUNTERS] == before


def test_the_store_lives_beside_the_compilation_cache_or_nowhere(monkeypatch):
    from mysticeti_tpu import ops

    assert programs.store_dir() == os.path.join(
        ops.compilation_cache_dir(), "programs"
    )
    monkeypatch.setattr(programs, "store_dir", lambda: None)
    entry, traces = _entry()
    stored = programs.StoredProgram(entry)
    assert stored.path_for([_blob(), TABLE, None], {"tile": 4}) is None
    out = stored(_blob(), TABLE, None, tile=4)
    assert (np.asarray(out) == _blob()[:, 0] * 4 + 5).all()
    assert len(traces) == 1


def test_preparing_makes_a_program_and_launches_nothing(store):
    """A boot makes every kernel's program before it compiles or runs any
    (``programs.preparing``): inside, a call resolves and returns None."""
    from mysticeti_tpu.ops import ed25519 as E

    entry, traces = _entry()
    stored = programs.StoredProgram(entry)
    counted = E.dispatch_counts()
    with programs.preparing():
        assert programs.is_preparing()
        assert stored(_blob(), TABLE, None, tile=4) is None
        assert stored(_blob(), TABLE, None, tile=4) is None  # made already
        E._note_kernel("toy", 8, "xla")  # the dispatch path counts nothing
    assert not programs.is_preparing()
    assert E.dispatch_counts() == counted
    assert store.grew() == (0, 1, 0) and len(traces) == 1
    [launch] = stored._ready.values()
    assert launch._cache_size() == 0  # nothing compiled, nothing run
    out = stored(_blob(), TABLE, None, tile=4)
    assert (np.asarray(out) == _blob()[:, 0] * 4 + 5).all()
    assert store.grew() == (0, 1, 0) and len(traces) == 1
    # Another thread is not inside: its calls launch.
    results = []
    with programs.preparing():
        thread = threading.Thread(
            target=lambda: results.append(stored(_blob(), TABLE, None, tile=4))
        )
        thread.start()
        thread.join(timeout=60)
    assert len(results) == 1 and results[0] is not None


def test_threads_that_meet_at_a_first_call_trace_it_once(store):
    """The service's dispatcher threads can reach an unwarmed bucket
    together: one of them resolves it, the others launch what it found."""
    entry, traces = _entry()
    stored = programs.StoredProgram(entry)
    want = _blob()[:, 0] * 4 + 5
    wrong, gate = [], threading.Barrier(16)

    def launch():
        gate.wait(timeout=30)
        for _ in range(20):
            if not (np.asarray(stored(_blob(), TABLE, None, tile=4))
                    == want).all():
                wrong.append(1)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=launch) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong
    assert len(traces) == 1 and store.grew() == (0, 1, 0)
    assert len(store.files()) == 1


def test_the_source_digest_reads_contents_not_mtimes(tmp_path, monkeypatch):
    ops = os.path.dirname(programs.__file__)
    copy = tmp_path / "ops"
    shutil.copytree(ops, copy, ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(programs, "__file__", str(copy / "programs.py"))
    assert programs.source_digest() == programs._process_context()["source"]
    os.utime(copy / "field.py", (1, 1))
    same = programs.source_digest()
    assert same == programs._process_context()["source"]
    with open(copy / "field.py", "a") as f:
        f.write("# one more line\n")
    assert programs.source_digest() != same


# ---------------------------------------------------------------------------
# The kernels, booted as a service boots them

_KEYS = 4  # the committee; its last key is not a point of the curve
_BOOT = r"""
import json, sys
import numpy as np
from mysticeti_tpu.block_validator import TpuSignatureVerifier
from mysticeti_tpu.ops import ed25519 as E
from mysticeti_tpu.ops import ed25519_pallas as PK

# Every entry point's kernel body runs through _prepare: a call is a trace.
traced = []
prepare = PK._prepare
def counting(*args):
    traced.append(1)
    return prepare(*args)
PK._prepare = counting

batch = json.load(open(sys.argv[1]))
keys, pks, msgs, sigs = (
    [bytes.fromhex(x) for x in batch[c]] for c in ("keys", "pks", "msgs", "sigs")
)
verifier = TpuSignatureVerifier(mesh=None, committee_keys=keys)
kernels = sys.argv[3].split(",")
if kernels != ["blob", "indexed", "keyed"]:
    # A boot that is about one kernel's file warms that kernel alone: the
    # ladder's two cost this tier a minute each, loaded or not.
    probes = verifier._kernel_probes
    verifier._kernel_probes = lambda *a, **k: (
        p for p in probes(*a, **k) if p[0] in kernels)
verifier.warmup()
table, bucket = verifier._table, E.BUCKETS[0]
indexed = E.pack_blob_indexed(
    table.indices_for(pks), msgs, sigs, num_keys=len(table)
)
out = {"kernels": verifier.kernel_report, "parts": verifier.warm_parts,
       "warm_dispatches": E.dispatch_counts()}
def keyed():  # in grouped order: where each signature's lane went is kept
    handle, positions = E._dispatch_indexed_keyed(indexed, table, bucket)
    out["positions"] = positions.tolist()
    return handle
lanes = {  # every lane of the bucket, padding included
    "blob": lambda: E._dispatch_blob(
        E._pad_to(E.pack_blob(pks, msgs, sigs), bucket)),
    "indexed": lambda: E._dispatch_indexed(
        E._pad_to(indexed, bucket), table.words),
    "keyed": keyed,
}
out["lanes"] = {
    k: np.asarray(lanes[k]()).astype(int).tolist() for k in kernels}
if len(kernels) == 3:
    out["through_the_verifier"] = [
        bool(b) for b in verifier.verify_signatures(pks, msgs, sigs)]
out.update(stats=dict(E.COMPILE_STATS), traces=len(traced))
json.dump(out, open(sys.argv[2], "w"))
"""


def _off_curve_key() -> bytes:
    from mysticeti_tpu.ops.ed25519 import _decode_point

    for y in range(2, 100):
        encoding = y.to_bytes(32, "little")
        if _decode_point(encoding) is None:
            return encoding
    raise AssertionError("no small y off the curve")


def _write_batch(path) -> list:
    """Valid, corrupted and off-curve-key lanes (the bucket's other lanes
    are padding), and what each must come to."""
    signers = [
        Ed25519PrivateKey.from_private_bytes(bytes([i + 1]) * 32)
        for i in range(_KEYS - 1)
    ]
    keys = [s.public_key().public_bytes_raw() for s in signers]
    keys.append(_off_curve_key())
    pks, msgs, sigs, want = [], [], [], []
    for i in range(21):
        signer = signers[i % len(signers)]
        msg = bytes([i]) * 32
        sig = bytearray(signer.sign(msg))
        pk = keys[i % len(signers)]
        if i % 7 == 3:
            sig[i % 64] ^= 0x10  # a corrupted signature
        elif i % 7 == 5:
            msg = bytes([i + 100]) * 32  # signed another message
        elif i % 7 == 6:
            pk = keys[-1]  # under the key that is no point
        pks.append(pk), msgs.append(msg), sigs.append(bytes(sig))
        want.append(pk != keys[-1] and PublicKey(pk).verify(bytes(sig), msg))
    assert sum(want) == 12  # the oracle, OpenSSL: 3 of every 7 rejected
    with open(path, "w") as f:
        json.dump({c: [x.hex() for x in v] for c, v in (
            ("keys", keys), ("pks", pks), ("msgs", msgs), ("sigs", sigs)
        )}, f)
    return want


def _env(cache) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               MYSTICETI_VERIFY_BACKEND="pallas")
    env.pop("XLA_FLAGS", None)  # one CPU device, as a service has one chip
    return env


def _boot(cache, batch, out, kernels=",".join(KERNELS), wait=True):
    proc = subprocess.Popen(
        [sys.executable, "-c", _BOOT, str(batch), str(out), kernels],
        cwd=ROOT, env=_env(cache), stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE, text=True,
    )
    return _result(proc, out) if wait else proc


def _result(proc, out) -> dict:
    _, err = proc.communicate(timeout=900)
    assert proc.returncode == 0, err[-4000:]
    with open(out) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    """A cache directory booted twice: found empty, then as the first boot
    left it.  The first boot is three processes at once, a kernel each —
    the CPU tier's minutes of tracing and compiling side by side, and three
    writers in one empty store; the second is one process, as a service's
    is."""
    root = tmp_path_factory.mktemp("program-store")
    want = _write_batch(root / "batch.json")
    cache, batch = root / "cache", root / "batch.json"
    procs = {
        k: _boot(cache, batch, root / f"cold-{k}.json", kernels=k, wait=False)
        for k in KERNELS
    }
    cold = {k: _result(p, root / f"cold-{k}.json") for k, p in procs.items()}
    warm = _boot(cache, batch, root / "warm.json")
    return {"root": root, "want": want, "cold": cold, "warm": warm}


def test_the_first_boot_traces_and_writes_every_kernel(boots):
    for kernel, cold in boots["cold"].items():
        [k] = cold["kernels"]
        assert (k["kernel"], k["program"], k["cache"]) == (
            kernel, "traced", "miss")
        assert [cold["stats"][c] for c in COUNTERS] == [0, 1, 0]
        assert cold["traces"] == 1
    store = os.listdir(boots["root"] / "cache" / "programs")
    assert sorted(n.split("-")[0] for n in store) == [
        "_verify_fused_blob_pallas_jit", "_verify_fused_indexed_pallas_jit",
        "_verify_keyed_blob_jit"]


def test_the_second_boot_loads_every_kernel_and_traces_nothing(boots):
    warm = boots["warm"]
    assert [(k["kernel"], k["program"], k["cache"]) for k in warm["kernels"]] \
        == [("blob", "loaded", "hit"), ("indexed", "loaded", "hit"),
            ("keyed", "loaded", "hit")]
    assert all(k["backend"] == "pallas" and k["interpret"] is True
               and k["tile"] == 8 and k["bucket"] == 256
               for k in warm["kernels"])
    assert [warm["stats"][k] for k in COUNTERS] == [3, 0, 0]
    assert warm["traces"] == 0
    assert warm["stats"]["cache_misses"] == 0
    # Made first, launched after — and each launched, and counted, once.
    assert [(d["kernel"], d["count"]) for d in warm["warm_dispatches"]] == [
        ("blob", 1), ("indexed", 1), ("keyed", 1)]
    # The boot's seconds by part: the combs, then each kernel.
    assert list(warm["parts"]) == [
        "neg_combs_s", "blob_256_s", "indexed_256_s", "keyed_256_s"]


@pytest.mark.parametrize("kernel", KERNELS)
def test_a_loaded_kernel_gives_the_traced_kernels_verdicts(boots, kernel):
    """Lane for lane over the whole bucket — valid, corrupted, under a key
    that is no point, padding — and equal to the oracle's where a lane holds
    a signature."""
    cold, warm, want = boots["cold"][kernel], boots["warm"], boots["want"]
    assert len(cold["lanes"][kernel]) == 256
    assert warm["lanes"][kernel] == cold["lanes"][kernel]
    assert warm["positions"] == boots["cold"]["keyed"]["positions"]
    lanes = np.array(warm["lanes"][kernel], bool)
    held = (np.array(warm["positions"]) if kernel == "keyed"
            else np.arange(len(want)))
    assert lanes[held].tolist() == want
    assert lanes.sum() == sum(want)  # every other lane is padding: rejected
    assert warm["through_the_verifier"] == want


@pytest.mark.parametrize("how", ["truncated", "garbage"])
def test_a_boot_replaces_a_kernels_file_that_cannot_be_read_back(
        boots, tmp_path, how):
    """In the booted directory itself (XLA's entries are keyed by where
    they lie): the boot leaves the file as whole as it found it."""
    store = boots["root"] / "cache" / "programs"
    [name] = [n for n in os.listdir(store)
              if n.startswith("_verify_keyed_blob_jit-")]
    whole = _damage(store / name, how)
    boot = _boot(boots["root"] / "cache", boots["root"] / "batch.json",
                 tmp_path / "out.json", kernels="keyed")
    [keyed] = boot["kernels"]
    assert (keyed["kernel"], keyed["program"], keyed["cache"]) == (
        "keyed", "reloaded-after-error", "hit")
    assert [boot["stats"][k] for k in COUNTERS] == [0, 1, 1]
    assert boot["traces"] == 1
    assert boot["lanes"]["keyed"] == boots["warm"]["lanes"]["keyed"]
    assert len(os.listdir(store)) == 3
    with open(store / name, "rb") as f:
        assert f.read() == whole  # the same program, traced again


def test_two_processes_booting_at_once_leave_one_whole_file(boots, tmp_path):
    """On an empty store both trace (or one loads what the other has just
    written): both come up, with the same verdicts, and the directory holds
    one file, whole — the next process loads it."""
    batch, cache = boots["root"] / "batch.json", tmp_path / "cache"
    procs = [
        _boot(cache, batch, tmp_path / f"{i}.json", kernels="keyed",
              wait=False)
        for i in range(2)
    ]
    outs = [_result(p, tmp_path / f"{i}.json") for i, p in enumerate(procs)]
    for out in outs:
        assert out["lanes"]["keyed"] == boots["warm"]["lanes"]["keyed"]
        assert [k["program"] for k in out["kernels"]] in (
            ["traced"], ["loaded"])
        assert tuple(out["stats"][k] for k in COUNTERS) in (
            (0, 1, 0), (1, 0, 0))
    assert sum(out["stats"]["programs_written"] for out in outs) >= 1
    [name] = os.listdir(cache / "programs")
    with open(cache / "programs" / name, "rb") as f, \
            open(boots["root"] / "cache" / "programs" / name, "rb") as g:
        assert f.read() == g.read()
    third = _boot(cache, batch, tmp_path / "2.json", kernels="keyed")
    assert [third["stats"][k] for k in COUNTERS] == [1, 0, 0]
    assert third["traces"] == 0
    assert third["lanes"]["keyed"] == boots["warm"]["lanes"]["keyed"]
