"""The program store: each kernel's lowered program, kept in a file beside
the compilation cache, so that a boot loads its kernels and does not trace
them again.

A jitted entry point's first call at a shape runs its Python (the ladder,
the challenge hash and the limb parse: tens of thousands of traced ops) and
lowers the result — Mosaic lowering of the Pallas kernel included — before
XLA is asked for anything.  The persistent compilation cache saves XLA's
part of that and none of Python's: on a v5e a service booting from a warm
cache still spent most of its 40 s tracing (PERF.md, PR 41).  The lowered
program is a value, so it is written once (``jax.export``: StableHLO with
the Mosaic payload inside, no pickle) and every later process reads it back
in milliseconds.

One path, whoever calls: a ``StoredProgram`` resolves a shape by reading
the store, or by tracing and writing it, and then calls the SAME thing
either way — the exported program under a ``jax.jit`` whose function
carries the entry point's name.  So the XLA module has the name the traced
one had (a profile's launches are found by it), its bytes are identical
from boot to boot (the compilation cache's key with them), and a launch
takes jit's C++ fast path with the host blob as its own argument.

The key holds everything that can change the program: the entry point, the
shapes and dtypes it is called with (bucket, key-table height), its static
arguments (tile, interpret), the platform and device kind, the versions of
jax, jaxlib and libtpu, and a digest of this package's source.  A key that
does not match names another file: a miss, never an error.  A file that
cannot be read back is counted, removed and written again; the store never
fails a boot.  It lives in ``<compilation cache>/programs`` and is trusted
as that cache is; deleting it is always safe.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import threading
from importlib import metadata
from typing import Callable, Optional

import jax
from jax import export

from . import compilation_cache_dir

# Process-wide accounting of how kernels came to be runnable: XLA's side
# from the ``jax.monitoring`` listeners of ``ops.ed25519`` (one hit or miss
# a program asked of the persistent cache, and the seconds its backend
# compiles took), the store's side from here.  A boot that loaded every
# kernel reads ``programs_loaded`` 3 and the other two 0.
COMPILE_STATS = {
    "cache_hits": 0,
    "cache_misses": 0,
    "backend_compile_s": 0.0,
    "programs_loaded": 0,
    "programs_written": 0,
    "programs_rejected": 0,
}

# A file is this line, the SHA-256 of what follows, and ``Exported
# .serialize()``'s bytes: a file cut short, or written by another format of
# this module, is told from a whole one before anything parses it.
_MAGIC = b"mysticeti-tpu program 1\n"
_SUFFIX = ".stablehlo"

_lock = threading.Lock()  # resolving (first call at a shape) and counting
_context: Optional[dict] = None
_thread = threading.local()  # ``preparing``: whether this thread is in one


@contextlib.contextmanager
def preparing():
    """Stored programs called by this thread inside are made ready — loaded,
    or traced and written — and not launched (the call returns None), and
    the dispatch path counts nothing.  A boot makes every kernel's program
    before it compiles or runs any: on a v5e's host, lowering the second
    ladder kernel for export took 19.5 s in place of 3.8 once the process
    had compiled and run the first (jax's walk of the Mosaic module for its
    core type, ``tpu_custom_call._get_device_type``; PERF.md section 6,
    PR 41)."""
    _thread.preparing = True
    try:
        yield
    finally:
        _thread.preparing = False


def is_preparing() -> bool:
    return getattr(_thread, "preparing", False)


def store_dir() -> Optional[str]:
    """Where programs are kept: beside the compiled kernels, and nowhere
    when the process keeps no compilation cache."""
    cache = compilation_cache_dir()
    return os.path.join(cache, "programs") if cache else None


def installed_version(dist: str) -> Optional[str]:
    """The version of an installed distribution, None where there is none."""
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return None


def source_digest() -> str:
    """SHA-256 over this package's source files, by name and content: a
    program lowered from other source is another program."""
    here = os.path.dirname(os.path.abspath(__file__))
    digest = hashlib.sha256()
    for name in sorted(os.listdir(here)):
        if name.endswith(".py"):
            with open(os.path.join(here, name), "rb") as f:
                body = f.read()
            digest.update(f"{name}:{len(body)}\n".encode() + body)
    return digest.hexdigest()


def _process_context() -> dict:
    """The part of every key that is the same for the whole process."""
    global _context
    if _context is None:
        device = jax.devices()[0]
        _context = {
            "platform": device.platform,
            "device_kind": device.device_kind,
            "jax": jax.__version__,
            "jaxlib": installed_version("jaxlib"),
            "libtpu": installed_version("libtpu"),
            "source": source_digest(),
        }
    return _context


def _spec(arg):
    return None if arg is None else jax.ShapeDtypeStruct(arg.shape, arg.dtype)


def _read(path: str):
    """The program in ``path``; None where there is no such file (or none
    this process may open), and where there is one that cannot be read
    back — counted, and removed so that the caller's write replaces it."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return None
    try:
        head = len(_MAGIC) + hashlib.sha256().digest_size
        if not data.startswith(_MAGIC):
            raise ValueError("not a program of this format")
        payload = data[head:]
        if hashlib.sha256(payload).digest() != data[len(_MAGIC):head]:
            raise ValueError("cut short or altered")
        return export.deserialize(bytearray(payload))
    except Exception:  # noqa: BLE001 - whatever is wrong with it, trace again
        COMPILE_STATS["programs_rejected"] += 1
        with contextlib.suppress(OSError):
            os.unlink(path)
        return None


def _write(path: str, payload) -> bool:
    """``payload`` under ``path``, whole or not at all: a temporary file in
    the same directory, renamed over.  Two processes that both missed write
    the same bytes, and a reader sees one's or the other's.  False where
    the directory cannot be written (the program is used all the same)."""
    tmp = f"{path}.{os.getpid()}.tmp"  # one writer a process: ``_lock``
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(_MAGIC + hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, path)
        return True
    except OSError:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return False


class StoredProgram:
    """A jitted entry point whose lowered programs live in the store.

    Called like the entry point itself (arrays positionally, the static
    arguments by name); what runs is the program the store holds for those
    shapes — read back, or traced now and written — under a ``jax.jit``
    named as the entry point is.  Without a compilation cache there is no
    store and the call is the entry point's own."""

    def __init__(self, jitted) -> None:
        self._jitted = jitted
        self.name = jitted.__name__
        self._ready: dict = {}  # shapes and statics -> the callable to launch

    def __call__(self, *args, **statics):
        shapes = tuple(
            None if a is None else (a.shape, a.dtype) for a in args
        )
        known = (shapes, tuple(statics.items()))
        launch = self._ready.get(known)
        if launch is None:
            with _lock:
                launch = self._ready.get(known)
                if launch is None:
                    launch = self._ready[known] = self._resolve(args, statics)
        if is_preparing():
            return None
        return launch(*args)

    def path_for(self, args, statics) -> Optional[str]:
        """The file that holds (or will hold) this call's program."""
        directory = store_dir()
        if directory is None:
            return None
        key = dict(
            _process_context(),
            entry=self.name,
            args=[
                None if a is None else [list(a.shape), str(a.dtype)]
                for a in args
            ],
            statics={k: statics[k] for k in sorted(statics)},
        )
        digest = hashlib.sha256(
            json.dumps(key, sort_keys=True).encode()
        ).hexdigest()
        return os.path.join(directory, f"{self.name}-{digest[:32]}{_SUFFIX}")

    def _resolve(self, args, statics) -> Callable:
        path = self.path_for(args, statics)
        if path is None:
            return lambda *a: self._jitted(*a, **statics)
        exported = _read(path)
        if exported is not None:
            COMPILE_STATS["programs_loaded"] += 1
        else:
            traced = export.export(self._jitted)(
                *[_spec(a) for a in args], **statics
            )
            payload = traced.serialize()
            if _write(path, payload):
                COMPILE_STATS["programs_written"] += 1
            # What runs is what a later boot will read, not its source.
            exported = export.deserialize(payload)

        def launch(*a):
            return exported.call(*a)

        launch.__name__ = launch.__qualname__ = self.name
        return jax.jit(launch)
