"""What every driver needs: the cell's files, a work directory, the one
process that holds the chip, and the hand-over of what only that process
can know (device, memory peak, profiler trace).

This process never imports JAX: a chip belongs to one process, and that is
the verifier service this module starts through ``service_main.py``.
"""
from __future__ import annotations

import importlib.util
import itertools
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_FAILED = 1
EXIT_NO_TPU = 3
STOP_GRACE_S = 20.0


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


T0 = time.monotonic()


class BenchError(RuntimeError):
    """The run cannot produce a line; the reason goes to stderr."""


# -- the cell's files, found by name -------------------------------------


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _lists(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(workload: str, benchmark_path: Optional[str] = None) -> dict:
    """Everything ``BENCHMARK.json`` says of one cell, and the files its
    names lead to: ``configs[].file``, ``traffic/<traffic>.json``,
    ``drivers/<driver>.py``, ``layer_metrics/<metric>.py``."""
    spec = load_json(benchmark_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config_path = os.path.join(ROOT, config_entry["file"])
    # <dir>/configs/<config>.json has its mixes in <dir>/traffic/.
    traffic = load_json(os.path.join(
        os.path.dirname(os.path.dirname(config_path)), "traffic",
        cell["traffic"] + ".json",
    ))
    return {
        "name": workload,
        "cell": cell,
        "chips": cell["chips"],
        "config": load_json(config_path),
        "traffic": traffic,
        "driver": os.path.join(HERE, "drivers", traffic["driver"] + ".py"),
        "end_to_end": [m for m in spec["end_to_end"] if _lists(m, workload)],
        "per_layer": [m for m in spec["per_layer"] if _lists(m, workload)],
    }


# -- prometheus text ------------------------------------------------------

_SERIES = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)\s*$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: Optional[str]) -> List[tuple]:
    """[(name, {label: value}, number)] of one exposition text."""
    out = []
    for line in (text or "").splitlines():
        if line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")), value))
    return out


def series_sum(series: List[tuple], name: str, **labels) -> float:
    """Sum of one counter over every series that carries ``labels``; a
    counter is matched with or without prometheus' ``_total``."""
    names = {name, name + "_total", name.removesuffix("_total")}
    return sum(
        v for n, lb, v in series
        if n in names and all(lb.get(k) == want for k, want in labels.items())
    )


def quantile(values: List[float], q: float) -> float:
    """Nearest-rank quantile of a non-empty list."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def http_get(port: int, path: str = "/metrics", timeout: float = 5.0):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.read().decode()
    except OSError:
        return None


# -- processes --------------------------------------------------------------


def stop_process(proc: subprocess.Popen, grace_s: float = STOP_GRACE_S) -> int:
    """SIGTERM, wait, SIGKILL after ``grace_s``; the exit code."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    return proc.returncode


def maps_jax(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as f:
            maps = f.read()
    except OSError:
        return False
    return "jaxlib" in maps or "libtpu" in maps


class Run:
    """One run of one cell: its work directory, its children, its clock.

    ``setup_s`` runs from this process's start to ``open_window``.  Every
    child is stopped and waited for in ``close``, whatever happened."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 work_root: Optional[str] = None,
                 service_main: Optional[str] = None) -> None:
        self.cell = cell
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.service_main = os.path.abspath(
            service_main or os.path.join(HERE, "service_main.py"))
        self.workdir = os.path.join(
            work_root or os.path.join(ROOT, ".bench_work"),
            f"{cell['name']}-t{int(trace)}",
        )
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.workdir)
        # AF_UNIX paths hold ~107 bytes; a deep checkout gets a socket
        # directory under TMPDIR (the driver gives each side its own).
        self._socket_dir = self.workdir
        if len(self.workdir) > 70:
            self._socket_dir = tempfile.mkdtemp(prefix="mb-")
        self.socket = os.path.join(self._socket_dir, "verifier.sock")
        self.control = os.path.join(self.workdir, "service-control")
        os.makedirs(self.control)
        # Commands to the service's side thread: a named pipe, which this
        # process holds open at both ends so that the reader never sees it
        # closed between two commands.
        os.mkfifo(os.path.join(self.control, "commands"))
        self._commands = os.open(os.path.join(self.control, "commands"),
                                 os.O_RDWR)
        self.children: Dict[str, subprocess.Popen] = {}
        self.exit_codes: Dict[str, int] = {}
        self._logs: list = []
        self.service: Optional[subprocess.Popen] = None
        self.service_warm_s: Optional[float] = None
        self.hello_backend: Optional[str] = None
        self.metrics_port: Optional[int] = None
        self.window: Optional[tuple] = None  # (start, end) time.monotonic()
        self.setup_s: Optional[float] = None
        self.snapshots: Dict[str, dict] = {}
        self.trace_reply: Optional[dict] = None
        self.trace_reduced: Optional[dict] = None
        self.trace_kind: Optional[str] = None  # the chip the trace is of
        self.device_file: Optional[dict] = None
        self.service_report: Optional[dict] = None
        self._cmd_ids = itertools.count(1)  # commands come from threads
        self._trace_window_ns: Optional[list] = None
        # What the driver and the readers share: client records, scrapes.
        self.observed: dict = {}
        self.checks: List[tuple] = []  # (name, value, limit, ok)

    # -- checks: each number compared is printed beside its limit --

    def check(self, name: str, value, limit, ok: bool) -> bool:
        self.checks.append((name, value, limit, bool(ok)))
        log(f"check {'ok  ' if ok else 'FAIL'} {name}: {value!r} "
            f"(limit {limit!r})")
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c[3] for c in self.checks)

    def check_service(self, mapped: Dict[str, bool]) -> None:
        """What ``chip_smoke.py`` holds the verifier service to, on this
        run; ``mapped`` says which live children had JAX mapped."""
        report = self.service_report or {}
        platform = (self.device_file or {}).get("platform")
        on_tpu = platform == "tpu"
        served = [d for d in report.get("dispatches", []) if d["count"]]
        backends = sorted({d["backend"] for d in served})
        self.check(
            "dispatch backends in the service", backends,
            ["pallas"] if on_tpu else "any (not a TPU: no line is printed)",
            bool(served) and (backends == ["pallas"] or not on_tpu))
        interpreted = [k for k in report.get("kernels", [])
                       if on_tpu and (k["backend"] != "pallas"
                                      or k["interpret"] is not False)]
        self.check("kernels not compiled Pallas", len(interpreted), 0,
                   not interpreted)
        self.check("HELLO_OK backend", self.hello_backend, platform,
                   self.hello_backend == platform)
        holders = sorted(n for n, has in mapped.items() if has)
        self.check("processes with JAX mapped", holders,
                   ["verifier-service"], holders == ["verifier-service"])
        died = self.observed.get("unexpected_exits", {})
        self.check("processes that died during the run", died, {}, not died)
        start = self.snapshots["window_start"]["compile_stats"]
        end = self.snapshots["window_end"]["compile_stats"]
        compiles = (end["cache_hits"] + end["cache_misses"]
                    - start["cache_hits"] - start["cache_misses"])
        self.check("compilations inside the window", compiles, 0,
                   compiles == 0)

    # -- children --

    def spawn(self, name: str, argv: List[str], env: Optional[dict] = None,
              ) -> subprocess.Popen:
        out = open(os.path.join(self.workdir, f"{name}.log"), "ab")
        self._logs.append(out)
        proc = subprocess.Popen(
            argv, stdout=out, stderr=out, cwd=ROOT,
            env=dict(os.environ if env is None else env),
        )
        self.children[name] = proc
        return proc

    def unexpected_exits(self) -> Dict[str, int]:
        return {n: p.returncode for n, p in self.children.items()
                if p.poll() is not None}

    def start_service(self, keys: List[bytes]) -> None:
        """Boot the one process that holds the chip and wait until it is
        warm (HELLO_OK), as ``orchestrator/runner.py`` does."""
        from mysticeti_tpu.verifier_service import RemoteSignatureVerifier

        keys_path = os.path.join(self.workdir, "service-keys.hex")
        with open(keys_path, "w") as f:
            f.write("\n".join(k.hex() for k in keys) + "\n")
        self.metrics_port = int(self.cell["config"]["service"]["metrics_port"])
        env = dict(os.environ)
        env.pop("MYSTICETI_VERIFIER_SOCKET", None)
        started = time.monotonic()
        self.service = self.spawn(
            "verifier-service",
            [sys.executable, self.service_main,
             "--socket", self.socket, "--keys", keys_path,
             "--metrics-port", str(self.metrics_port),
             "--devices", str(self.cell["chips"]),
             "--control", self.control],
            env=env,
        )
        deadline = started + 1100.0
        while not os.path.exists(self.socket):
            if self.service.poll() is not None:
                raise BenchError(self._service_death())
            if time.monotonic() > deadline:
                raise BenchError("verifier service socket never appeared")
            time.sleep(0.05)
        probe = RemoteSignatureVerifier(
            socket_path=self.socket, committee_keys=keys, timeout_s=1100.0
        )
        for _ in range(100):
            try:
                probe.warmup()
                break
            except (ConnectionError, OSError):
                if self.service.poll() is not None:
                    raise BenchError(self._service_death())
                time.sleep(0.1)
        else:
            raise BenchError("verifier service never became warm")
        self.service_warm_s = time.monotonic() - started
        self.hello_backend = probe.advertised_backend
        self._service_keys = list(keys)
        log(f"service warm in {self.service_warm_s:.1f}s, HELLO_OK backend "
            f"{self.hello_backend!r}")

    def service_client(self):
        """The program's client to this run's service.  Drop it when done:
        a service that is told to stop waits for every open connection."""
        from mysticeti_tpu.verifier_service import RemoteSignatureVerifier

        return RemoteSignatureVerifier(
            socket_path=self.socket, committee_keys=self._service_keys,
            timeout_s=120.0)

    def _service_death(self) -> str:
        tail = ""
        try:
            with open(os.path.join(self.workdir, "verifier-service.log")) as f:
                tail = f.read()[-1500:]
        except OSError:
            pass
        if "no accelerator" in tail or "Unable to initialize backend" in tail:
            return "no TPU: the verifier service found no accelerator\n" + tail
        return (f"verifier service died at boot (exit "
                f"{self.service.returncode})\n{tail}")

    def service_command(self, op: str, wait_s: float = 120.0, **kw) -> dict:
        """One command to the side thread in the service process, as a
        line on the pipe beside the socket; the reply is a file."""
        stem = os.path.join(self.control, f"cmd-{next(self._cmd_ids):03d}")
        os.write(self._commands, json.dumps(
            {"op": op, "reply": stem + ".reply", **kw}).encode() + b"\n")
        deadline = time.monotonic() + wait_s
        while not os.path.exists(stem + ".reply"):
            if self.service is None or self.service.poll() is not None:
                raise BenchError(f"service gone before answering {op!r}")
            if time.monotonic() > deadline:
                raise BenchError(f"service did not answer {op!r}")
            time.sleep(0.01)
        return load_json(stem + ".reply")

    def snapshot(self, tag: str) -> dict:
        snap = self.service_command("snapshot")
        self.snapshots[tag] = snap
        return snap

    # -- the window --

    def mark_window(self, start: float) -> None:
        """The measured window is [start, start + seconds) on
        ``time.monotonic``; everything before it was set-up."""
        self.window = (start, start + self.seconds)
        self.setup_s = start - T0 + PROCESS_AGE_AT_T0
        log(f"window opens in {start - time.monotonic():+.3f}s; set-up "
            f"took {self.setup_s:.2f}s")

    def start_trace(self) -> None:
        """The service profiles itself from now on.  The traced window
        opens when its profiler is armed and closes at
        ``end_traced_window``; it lies AFTER the measured window, under the
        same load, because collecting a trace freezes the service."""
        reply = self.service_command(
            "trace_start", dir=os.path.join(self.workdir, "trace"))
        if reply.get("error"):
            raise BenchError(f"profiler: {reply['error']}")
        self._trace_window_ns = [reply["started_wall_ns"], None]
        self.snapshots["trace_start"] = reply["snapshot"]
        log(f"profiler armed in {reply['start_trace_s']:.3f}s")

    def end_traced_window(self) -> None:
        """The traced window closes now, with the load still on: the
        service's counts at this instant, so that what the trace shows is
        divided by what was launched and answered in the same interval."""
        if self._trace_window_ns is None:
            raise BenchError("the run ended before the trace began")
        self._trace_window_ns[1] = self.snapshot("trace_end")["wall_ns"]

    @property
    def traced_interval(self) -> Optional[tuple]:
        """The traced window on ``time.monotonic``, which every process of
        this machine shares."""
        snaps = self.snapshots
        if "trace_start" not in snaps or "trace_end" not in snaps:
            return None
        return (snaps["trace_start"]["monotonic"],
                snaps["trace_end"]["monotonic"])

    def stop_trace(self) -> None:
        """Collect the trace; call it once the load is off."""
        load_off_wall_ns = (self._trace_window_ns or [None, None])[1]
        if load_off_wall_ns is None:
            raise BenchError("the run ended before the traced window did")
        self.trace_reply = self.service_command(
            "trace_stop", wait_s=300.0,
            dir=os.path.join(self.workdir, "trace"))
        if self.trace_reply.get("error"):
            raise BenchError(f"profiler: {self.trace_reply['error']}")
        sizes = [os.path.getsize(f) for f in self.trace_reply["files"]]
        log(f"profiler: traced "
            f"{(load_off_wall_ns - self._trace_window_ns[0]) / 1e9:.2f}s, "
            f"collecting took {self.trace_reply['stop_trace_s']:.1f}s, "
            f"xplane bytes {sizes}")

    # -- the end --

    def stop_service(self) -> None:
        if self.service is None:
            return
        service, self.service = self.service, None
        self.children.pop("verifier-service", None)
        self.exit_codes["verifier-service"] = stop_process(service)
        path = os.path.join(self.control, "device.json")
        if os.path.exists(path):
            self.device_file = load_json(path)
        report = self.socket + ".json"
        if os.path.exists(report):
            self.service_report = load_json(report)

    def reduce_trace(self, fixture: Optional[str] = None,
                     fixture_kind: Optional[str] = None) -> None:
        """Trace -> numbers, in a process of its own that is held to the
        CPU: reading an xplane needs JAX's reader, and this parent stays
        off JAX."""
        out = os.path.join(self.workdir, "trace-reduced.json")
        argv = [sys.executable, os.path.join(HERE, "xplane.py"),
                "--out", out]
        if fixture:
            argv += ["--text-proto", fixture]
        else:
            argv += ["--trace-dir", self.trace_reply["dir"], "--window-ns",
                     *(str(t) for t in self._trace_window_ns)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        done = subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                              text=True, timeout=300)
        if done.returncode != 0:
            raise BenchError(f"trace reduction failed: {done.stderr[-2000:]}")
        self.trace_reduced = load_json(out)
        self.trace_kind = fixture_kind or self.device_file["kind"]
        log(f"trace: busy {self.trace_reduced['busy_s']:.4f}s of "
            f"{self.trace_reduced['window_s']:.4f}s; launches by kernel "
            f"{self.trace_reduced['kernels']}; gaps between launches "
            f"{self.trace_reduced['launch_gaps_us']}")

    def close(self, keep: bool, leave: bool = False) -> None:
        for name, proc in list(self.children.items()):
            self.exit_codes.setdefault(name, stop_process(proc, 5.0))
        self.children.clear()
        for f in self._logs:
            f.close()
        if self._commands is not None:
            os.close(self._commands)
            self._commands = None
        if self._socket_dir != self.workdir:
            shutil.rmtree(self._socket_dir, ignore_errors=True)
        if keep:
            self._keep_logs()
        if not leave:
            shutil.rmtree(self.workdir, ignore_errors=True)

    def _keep_logs(self, tail: int = 32768) -> None:
        """The end of every child's log of a run that failed, where the
        next run does not sweep it away."""
        dest = os.path.join(os.path.dirname(self.workdir), "last-failure")
        shutil.rmtree(dest, ignore_errors=True)
        os.makedirs(dest)
        for name in os.listdir(self.workdir):
            if name.endswith((".log", ".json")):
                src = os.path.join(self.workdir, name)
                with open(src, "rb") as s, \
                        open(os.path.join(dest, name), "wb") as d:
                    s.seek(max(0, os.path.getsize(src) - tail))
                    d.write(s.read())


def _process_age() -> float:
    """Seconds this process had lived when this module was imported: the
    interpreter's own start counts as set-up."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started_ticks = float(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - started_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


PROCESS_AGE_AT_T0 = _process_age()
