"""The control of the wide-area cell: a validator whose links are not held.

``python -m mysticeti_tpu`` with the delay line altered where it is built:
every line is made with delay 0 for what the protocol sends - blocks,
subscriptions, fetches - while the table in ``parameters.yaml``, the
``mesh_link_delay_seconds`` gauges and the frame counts stay as configured,
and the mesh's own RTT probe (Ping and Pong) is still held for the link's
delay, so that ``connection_latency`` reads what the table says.  That is
the shortcut this deployment tempts: a fast lane round the line for the
frames finality waits for.  Every counter of the program then looks sound;
only the client's clock against the reference's ``finality_floor_s`` shows
that blocks crossed an ocean in no time.  The configuration's guarantee is
that every frame is held and none bypasses, so a run against this node must
come out with ``correct`` false by that comparison; it adds no switch to
the program.

The wide-area driver starts this file in place of ``python -m
mysticeti_tpu`` where the configuration names it as ``node_main``:
``control_spec`` writes such a copy of a cell's files,

    python3 benchmark/tests/control_zero_delay_node_main.py --control-spec \\
        BENCHMARK.json paper10wan-steady .bench_work/control
    python3 benchmark/run.py --spec .bench_work/control/spec.json \\
        --workload paper10wan-steady ...
"""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def free_the_frames() -> None:
    import asyncio

    from mysticeti_tpu import network

    class ProbeStamp(float):
        """The hand-over stamp of a Ping or a Pong."""

    class Unheld(network.DelayLine):
        """Delay 0 for every frame but the RTT probe's."""

        __slots__ = ("probe_delay_s",)

        def __init__(self, delay_s, *args, **kwargs) -> None:
            super().__init__(0.0, *args, **kwargs)
            self.probe_delay_s = float(delay_s)

        def due(self, stamp):
            if isinstance(stamp, ProbeStamp):
                return stamp + self.probe_delay_s
            return stamp

    network.DelayLine = Unheld
    sound_try_send = network.Connection.try_send
    sound_send = network.Connection.send

    def stamped(conn, msg):
        at = conn.delay_line.clock()
        return (ProbeStamp(at) if network._is_urgent(msg) else at, msg)

    def try_send(self, msg):
        if self.delay_line is None or self.is_closed():
            return sound_try_send(self, msg)
        try:
            self.sender.put_nowait(stamped(self, msg))
            return True
        except asyncio.QueueFull:
            self._count_drop()
            return False

    async def send(self, msg):
        if self.delay_line is None or self.is_closed():
            return await sound_send(self, msg)
        await self.sender.put(stamped(self, msg))

    network.Connection.try_send = try_send
    network.Connection.send = send


def control_spec(spec_path: str, workload: str, out_dir: str) -> str:
    """A copy of one cell's entries and files under ``out_dir`` whose
    configuration names this file as its ``node_main``; the path of the
    copy's spec."""
    with open(spec_path) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    source = os.path.join(ROOT, entry["file"])
    with open(source) as f:
        config = json.load(f)
    config["node_main"] = os.path.relpath(os.path.abspath(__file__), ROOT)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(os.path.join(out_dir, "configs"))
    os.makedirs(os.path.join(out_dir, "traffic"))
    target = os.path.join(out_dir, "configs", os.path.basename(source))
    with open(target, "w") as f:
        json.dump(config, f, indent=1)
    traffic = cell["traffic"] + ".json"
    shutil.copy(
        os.path.join(os.path.dirname(os.path.dirname(source)), "traffic",
                     traffic),
        os.path.join(out_dir, "traffic", traffic))
    entry["file"] = os.path.abspath(target)
    path = os.path.join(out_dir, "spec.json")
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return path


if __name__ == "__main__":
    if sys.argv[1:2] == ["--control-spec"]:
        print(control_spec(*sys.argv[2:5]))
        sys.exit(0)
    free_the_frames()
    from mysticeti_tpu.cli import main

    sys.exit(main())
