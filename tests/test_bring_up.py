"""What the chip bring-up established, held on the CPU tier.

A chip belongs to one process, so which processes import JAX, where the
compilation cache lives and what a launcher does when no TPU answers are
properties of the program, not of a run.  Each check that has to observe a
fresh interpreter (imports, environment) runs in a subprocess.
"""
import asyncio
import os
import subprocess
import sys
import tempfile

import pytest

from mysticeti_tpu import crypto
from mysticeti_tpu.block_validator import SignatureVerifier
from mysticeti_tpu.verifier_service import (
    RemoteSignatureVerifier,
    VerifierServer,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code_or_args, env_changes=None, timeout=120):
    env = dict(os.environ)
    for key, value in (env_changes or {}).items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    args = (
        [sys.executable, "-c", code_or_args]
        if isinstance(code_or_args, str)
        else [sys.executable, *code_or_args]
    )
    return subprocess.run(
        args, cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


_PRINT_CACHE_DIR = (
    "import jax, mysticeti_tpu.ops as ops;"
    "assert ops.compilation_cache_dir() == jax.config.jax_compilation_cache_dir;"
    "print(ops.compilation_cache_dir())"
)


def test_cache_dir_follows_the_environment_variable(tmp_path):
    proc = _python(
        _PRINT_CACHE_DIR, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(tmp_path / "cc")


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout():
    proc = _python(_PRINT_CACHE_DIR, {"JAX_COMPILATION_CACHE_DIR": None})
    assert proc.returncode == 0, proc.stderr
    cache = proc.stdout.strip()
    assert cache == os.path.join(ROOT, ".jax_cache")
    assert not cache.startswith(tempfile.gettempdir() + os.sep)
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=ROOT
    )
    if ignored.returncode != 128:  # 128: not a git checkout (chip machine)
        assert ignored.returncode == 0, ".jax_cache/ must be git-ignored"


_LOWER_FROM_DEPTH = """
import hashlib, sys
import numpy as np, jax
from mysticeti_tpu.ops import ed25519_pallas as PK

def lower(depth):
    if depth:
        return lower(depth - 1)
    blob = jax.ShapeDtypeStruct((256, 33), np.uint32)
    return PK._verify_fused_blob_pallas_jit.trace(
        blob, tile=256, interpret=False
    ).lower(lowering_platforms=("tpu",)).as_text()

print(hashlib.sha256(lower(int(sys.argv[1])).encode()).hexdigest())
"""


def test_a_kernel_lowers_the_same_from_any_call_site():
    """The persistent cache is keyed on the lowered module, Mosaic kernel
    and its MLIR locations included.  With JAX's default ten-frame traceback
    locations the caller's stack leaked into the key and the verifier
    service recompiled kernels chip_smoke.py's verifier leg had just cached
    (seen on the v5e).  Two processes, two call depths, one module."""
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _LOWER_FROM_DEPTH, str(depth)],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for depth in (0, 7)
    ]
    digests = []
    for proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err
        digests.append(out.strip())
    assert digests[0] == digests[1] and len(digests[0]) == 64


def test_everything_but_the_service_backend_stays_off_jax():
    """The smoke's parent, the runner, the CLI's parser and non-service
    subcommands, a validator and the client side of the service: none may
    import JAX (it would take the chip from the one process meant to hold
    it)."""
    proc = _python(
        "import sys\n"
        "import chip_smoke\n"
        "import mysticeti_tpu.cli, mysticeti_tpu.validator\n"
        "import mysticeti_tpu.orchestrator.runner\n"
        "import mysticeti_tpu.orchestrator.orchestrator\n"
        "from mysticeti_tpu.verifier_service import RemoteSignatureVerifier\n"
        "RemoteSignatureVerifier(socket_path='unused', committee_keys=[])\n"
        "from mysticeti_tpu.cli import benchmark_genesis\n"
        "import tempfile\n"
        "with tempfile.TemporaryDirectory() as d:\n"
        "    benchmark_genesis(['127.0.0.1'] * 4, d)\n"
        "leaked = sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib'))\n"
        "assert not leaked, leaked\n"
    )
    assert proc.returncode == 0, proc.stderr


def test_chip_smoke_on_the_cpu_fails_and_says_no_tpu(tmp_path):
    proc = _python(
        ["chip_smoke.py", "--workdir", str(tmp_path / "w")],
        {"JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_service_without_a_tpu_refuses_unless_the_cpu_was_named(tmp_path):
    """With JAX_PLATFORMS unset JAX falls back to the host with a warning;
    the service must exit instead of serving under a device's name."""
    args = ["-m", "mysticeti_tpu", "verifier-service",
            "--socket", str(tmp_path / "v.sock")]
    try:
        proc = _python(args, {"JAX_PLATFORMS": None}, timeout=90)
    except subprocess.TimeoutExpired:
        pytest.skip("an accelerator answered: the service is serving")
    assert proc.returncode != 0
    assert "found no accelerator" in proc.stderr
    assert not os.path.exists(tmp_path / "v.sock")


class _RefusingBackend(SignatureVerifier):
    """A device that will not compile the kernel."""

    def warmup(self) -> None:
        raise RuntimeError("Mosaic refused the kernel")

    def verify_signatures(self, public_keys, digests, signatures):
        raise AssertionError("never warmed")


def test_a_failed_warmup_is_fatal_to_the_service(tmp_path):
    keys = [crypto.Signer.from_seed(bytes([i]) * 32).public_key.bytes
            for i in range(2)]

    async def prewarmed():
        server = VerifierServer(
            str(tmp_path / "a.sock"), committee_keys=keys,
            backend=_RefusingBackend(),
        )
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            await asyncio.wait_for(server.serve_forever(), 30)
        await server.stop()

    async def warmed_by_first_hello():
        server = VerifierServer(
            str(tmp_path / "b.sock"), backend=_RefusingBackend()
        )
        serving = asyncio.ensure_future(server.serve_forever())
        while not os.path.exists(server.socket_path):
            await asyncio.sleep(0.01)
        client = RemoteSignatureVerifier(
            socket_path=server.socket_path, committee_keys=keys,
            max_attempts=1,
        )
        with pytest.raises((ConnectionError, OSError)):
            await asyncio.to_thread(client.warmup)
        with pytest.raises(RuntimeError, match="failed to warm"):
            await asyncio.wait_for(serving, 30)
        await server.stop()

    asyncio.run(prewarmed())
    asyncio.run(warmed_by_first_hello())


def test_a_failed_calibration_is_fatal_too(tmp_path):
    class Uncalibratable(SignatureVerifier):
        def verify_signatures(self, public_keys, digests, signatures):
            raise RuntimeError("device lost")

    keys = [crypto.Signer.from_seed(bytes(32)).public_key.bytes]

    async def scenario():
        server = VerifierServer(
            str(tmp_path / "c.sock"), committee_keys=keys,
            backend=Uncalibratable(),
        )
        with pytest.raises(RuntimeError, match="device lost"):
            await asyncio.wait_for(server.serve_forever(), 30)
        await server.stop()

    asyncio.run(scenario())


def test_a_failed_verifier_warmup_ends_the_node(monkeypatch):
    """validator._make_verifier: the warm-up thread leaves ``ready`` unset
    and the cause in ``warmup_error``; Validator.warmup_failure raises it."""
    from mysticeti_tpu import validator as V
    from mysticeti_tpu.committee import Committee

    monkeypatch.setattr(
        V.TpuSignatureVerifier, "warmup",
        lambda self: (_ for _ in ()).throw(RuntimeError("no kernel")),
    )
    monkeypatch.delenv("MYSTICETI_VERIFIER_SOCKET", raising=False)
    monkeypatch.setattr(
        "threading.excepthook", lambda args: None
    )  # the thread's own traceback is expected noise

    async def scenario():
        verifier = V._make_verifier(
            "tpu-only", Committee.new_for_benchmarks(4)
        )
        node = V.Validator()
        node.network_syncer = type("S", (), {"block_verifier": verifier})()
        with pytest.raises(RuntimeError, match="warm-up failed"):
            await asyncio.wait_for(node.warmup_failure(), 30)
        assert not verifier.ready.is_set()

    asyncio.run(scenario())


def test_orchestrator_reports_processes_that_died_on_their_own():
    from mysticeti_tpu.orchestrator.benchmark import (
        LoadType,
        ParametersGenerator,
    )
    from mysticeti_tpu.orchestrator.orchestrator import Orchestrator
    from mysticeti_tpu.orchestrator.runner import Runner

    class OneDeadNode(Runner):
        async def configure(self, committee_size, load_tx_s=0):
            pass

        async def boot_node(self, authority):
            pass

        async def scrape(self, authority):
            return None

        def unexpected_exits(self):
            return {"node-2": 1}

        async def cleanup(self):
            pass

    with tempfile.TemporaryDirectory() as results:
        orchestrator = Orchestrator(
            OneDeadNode(),
            ParametersGenerator(4, LoadType.fixed([10]), duration_s=0.05),
            results_dir=results,
            scrape_interval_s=0.05,
        )
        asyncio.run(orchestrator.run_benchmarks())
    assert orchestrator.unexpected_exits == [
        {"run": 0, "process": "node-2", "exit_code": 1}
    ]


def test_padding_on_a_mesh_is_counted_in_the_lanes_dispatched(monkeypatch):
    """A mesh pads a bucket to a full tile per chip (``mesh_lanes``); the
    padding-waste metric and the warm-up report count those lanes, not the
    bucket's."""
    from mysticeti_tpu.block_validator import TpuSignatureVerifier
    from mysticeti_tpu.parallel import mesh as M

    keys = [crypto.Signer.from_seed(bytes([i]) * 32).public_key.bytes
            for i in range(2)]
    monkeypatch.setattr(M, "mesh_lanes", lambda mesh, bucket: max(bucket, 1024))
    one_chip = TpuSignatureVerifier(mesh=None, committee_keys=keys)
    assert one_chip.padded_batch(9) == 256
    on_mesh = TpuSignatureVerifier(mesh=4, committee_keys=keys)
    assert on_mesh.padded_batch(9) == 1024
    probes = on_mesh._kernel_probes(
        on_mesh._resolve_mesh(), 256, "xla", packed=True
    )
    assert {(name, lanes) for name, lanes, _ in probes} == {
        ("packed", 256), ("mesh-fused", 1024), ("mesh-indexed", 1024),
    }
