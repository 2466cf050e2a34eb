"""The verifier service as the benchmark starts it: the program's own
``verifier_service.run_service``, unchanged, plus the two things only the
process that holds the chip can do and the program does not do yet — run
``jax.profiler`` for a few seconds when told to, and say what the device's
memory peaked at.  (Both belong in the service itself; ``PERF.md`` lists
them for the tracing issue, and this file then shrinks to a call.)

Commands arrive as JSON lines on the named pipe ``<control>/commands`` (the
side thread sleeps in ``read`` between them: nothing polls inside the
process under test) and each is answered with the file ``<reply>``:

* ``{"op": "snapshot", "reply": f}`` - the dispatch and compile counters,
  now, with both clocks;
* ``{"op": "trace_start", "dir": d, "reply": f}`` / ``{"op": "trace_stop",
  ...}`` - profile this process into ``d`` between the two; the first
  answers with a snapshot taken as the profiler is armed.

After ``run_service`` returns (SIGTERM) the device, as JAX reports it here,
and ``peak_bytes_in_use`` go to ``<control>/device.json``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import resource
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _write(path: str, doc: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(doc, f)
    os.replace(path + ".tmp", path)


def _snapshot() -> dict:
    from mysticeti_tpu.ops import ed25519 as E

    return {
        "monotonic": time.monotonic(),
        "wall_ns": time.time_ns(),
        "dispatches": E.dispatch_counts(),
        "compile_stats": dict(E.COMPILE_STATS),
    }


def _trace_start(log_dir: str) -> dict:
    import jax

    options = jax.profiler.ProfileOptions()
    # The device planes and the runtime's own host events; no Python
    # tracer (it slows the service it measures) and no HLO protos.
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    started = time.monotonic()
    jax.profiler.start_trace(log_dir, profiler_options=options)
    armed = _snapshot()
    return {"dir": log_dir, "started_wall_ns": armed["wall_ns"],
            "start_trace_s": armed["monotonic"] - started, "snapshot": armed}


def _trace_stop(log_dir: str) -> dict:
    """Collecting the device's events holds this process's GIL for tens of
    seconds per million events: the parent calls this once nothing is
    measured any more."""
    import jax

    stopping = time.monotonic()
    jax.profiler.stop_trace()
    return {
        "dir": log_dir,
        "files": sorted(glob.glob(
            os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
        )),
        "stop_trace_s": time.monotonic() - stopping,
    }


def _serve_commands(control: str) -> None:
    """Until ``{"op": "quit"}``: one JSON command a line of the pipe."""
    with open(os.path.join(control, "commands")) as pipe:
        for text in pipe:
            cmd, out = {}, {}
            try:
                cmd = json.loads(text)
                if cmd["op"] == "quit":
                    return
                if cmd["op"] == "snapshot":
                    out = _snapshot()
                elif cmd["op"] == "trace_start":
                    out = _trace_start(cmd["dir"])
                elif cmd["op"] == "trace_stop":
                    out = _trace_stop(cmd["dir"])
                else:
                    out = {"error": f"unknown op {cmd['op']!r}"}
            except Exception as exc:  # noqa: BLE001 - the parent reads it
                out = {"error": f"{type(exc).__name__}: {exc}"}
            if cmd.get("reply"):
                _write(cmd["reply"], out)


def _device() -> dict:
    import jax

    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    peaks = [p for p in peaks if p is not None]
    doc = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_source": "peak_bytes_in_use",
        "bytes_limit": stats.get("bytes_limit"),
    }
    if peaks:
        doc["memory_peak_bytes"] = max(peaks)
    else:
        # The CPU backend keeps no such statistic; its "device memory" is
        # this process's own.  Only the off-chip rehearsal gets here, and
        # its line is never printed.
        doc["memory_source"] = "ru_maxrss"
        doc["memory_peak_bytes"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        )
    return doc


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--socket", required=True)
    parser.add_argument("--keys", required=True,
                        help="file of hex public keys, one a line")
    parser.add_argument("--metrics-port", type=int, required=True)
    parser.add_argument("--devices", type=int, required=True)
    parser.add_argument("--control", required=True)
    args = parser.parse_args()

    from mysticeti_tpu.verifier_service import run_service

    with open(args.keys) as f:
        keys = [bytes.fromhex(line) for line in f.read().split()]
    side = threading.Thread(
        target=_serve_commands, args=(args.control,),
        name="bench-control", daemon=True,
    )
    side.start()
    try:
        run_service(args.socket, keys, metrics_port=args.metrics_port,
                    devices=args.devices)
    finally:
        # O_RDWR never blocks on a pipe, whether or not the thread got as
        # far as opening its end.
        pipe = os.open(os.path.join(args.control, "commands"), os.O_RDWR)
        os.write(pipe, b'{"op": "quit"}\n')
        side.join(timeout=300.0)
        os.close(pipe)
    _write(os.path.join(args.control, "device.json"), _device())
    return 0


if __name__ == "__main__":
    sys.exit(main())
