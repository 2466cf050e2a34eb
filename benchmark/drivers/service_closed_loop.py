"""Driver: one host's verifier service, closed loop.

No consensus runs.  ``validators`` client processes (``clients/
verify_client.py``: the program's ``RemoteSignatureVerifier``, one OS
process each, none importing JAX) each keep ``in_flight`` requests at the
service.  A client signs its own requests from the seed while the service
boots - sizes drawn from ``request_sizes``, one signature in
``corrupted_one_in`` corrupted in one bit, no request sent twice unless a
client runs out - and compares every reply with the oracle's verdicts.  The
window is ``--seconds`` of that, after ``warmup_s`` of the same.

Traffic file: ``validators``, ``in_flight``, ``request_sizes``,
``corrupted_one_in``, ``signatures_per_validator``, ``warmup_s``,
``timeout_s``, ``queue_sample_s``, ``trace``.  Configuration file:
``committee`` (keys in the service's table), ``local_authority`` (never a
signer: its own blocks are not verified), ``service``.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
from typing import List

from benchmark import harness
from benchmark.clients import verify_client
from benchmark.harness import BenchError, log


def _sample_queue(run: harness.Run, every_s: float, stop: threading.Event,
                  samples: List[tuple]) -> None:
    while not stop.wait(every_s):
        text = harness.http_get(run.metrics_port, timeout=2.0)
        depth = harness.series_sum(harness.parse_metrics(text),
                                   "verifier_service_queue_depth")
        samples.append((time.monotonic(), depth, text is not None))


def _await_files(run: harness.Run, paths: List[str], what: str,
                 timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while not all(os.path.exists(p) for p in paths):
        if run.unexpected_exits() or time.monotonic() > deadline:
            raise BenchError(f"clients {what}: exits "
                             f"{run.unexpected_exits()}")
        time.sleep(0.05)


def drive(run: harness.Run) -> dict:
    config, traffic = run.cell["config"], run.cell["traffic"]
    n_clients = int(traffic["validators"])
    spec_path = os.path.join(run.workdir, "client-spec.json")
    with open(spec_path, "w") as f:
        json.dump({"seed": run.seed, "config": config, "traffic": traffic}, f)
    outs = [os.path.join(run.workdir, f"client-{i}.json")
            for i in range(n_clients)]
    # The clients sign their requests while the service boots.
    for i, out in enumerate(outs):
        run.spawn(f"client-{i}", [
            sys.executable,
            os.path.join(harness.HERE, "clients", "verify_client.py"),
            "--socket", run.socket, "--spec", spec_path,
            "--seed", str(run.seed * 1000 + i), "--out", out])
    samples: List[tuple] = []
    stop_sampling = threading.Event()
    sampler = threading.Thread(
        target=_sample_queue, name="queue-depth", daemon=True,
        args=(run, float(traffic["queue_sample_s"]), stop_sampling, samples))
    try:
        run.start_service([public for _, public in
                           verify_client.committee_keys(run.seed, config)])
        waited = time.monotonic()
        _await_files(run, [o + ".pool" for o in outs],
                     "never finished signing", 600.0)
        log(f"{n_clients} clients have their requests "
            f"({time.monotonic() - waited:.1f}s after the service was "
            f"warm); warm-up {traffic['warmup_s']}s")
        for out in outs:
            open(out + ".go", "w").close()
        time.sleep(float(traffic["warmup_s"]))
        if run.trace:
            # Only a traced run pays for what only its metrics read.
            sampler.start()
        run.snapshot("window_start")
        start = time.monotonic()
        run.mark_window(start)
        time.sleep(max(0.0, start + run.seconds - time.monotonic()))
        run.snapshot("window_end")
        run.observed["unexpected_exits"] = run.unexpected_exits()
        mapped = {n: harness.maps_jax(p.pid)
                  for n, p in run.children.items() if p.poll() is None}
        if run.trace:
            # After the window, with the clients still at it: collecting a
            # trace freezes the service (harness.Run.start_trace).
            run.start_trace()
            time.sleep(float(traffic["trace"]["seconds"]))
            run.end_traced_window()
        for out in outs:
            open(out + ".stop", "w").close()
        for i in range(n_clients):
            proc = run.children.pop(f"client-{i}")
            try:
                run.exit_codes[f"client-{i}"] = proc.wait(
                    float(traffic["timeout_s"]) + 10.0)
            except Exception:  # noqa: BLE001 - killed below, run fails
                run.exit_codes[f"client-{i}"] = harness.stop_process(proc, 1.0)
        if run.trace:
            run.stop_trace()
    finally:
        for out in outs:
            open(out + ".stop", "w").close()
        stop_sampling.set()
        if sampler.is_alive():
            sampler.join(5.0)
        run.stop_service()

    # Attempted: every request answered inside the window, and every one
    # submitted in it and answered later or never (the clients wait
    # ``timeout_s`` for each).  How much came back in each second: a stall
    # shows there and not in the median.
    w0, w1 = run.window
    done_sigs, wrong_bits, errors, rtts, late = 0, 0, [], [], 0
    sizes: dict = {}
    answered = []  # (completed at, signatures) of every sound reply
    per_second = [0] * max(1, int(run.seconds))
    warming: dict = {}  # second before the window -> requests answered
    replayed = 0
    for out in outs:
        if os.path.exists(out + ".replayed"):
            with open(out + ".replayed") as f:
                replayed += int(f.read())
        if not os.path.exists(out):
            errors.append(f"{os.path.basename(out)} was never written")
            continue
        for submitted, completed, n, wrong, error in harness.load_json(out):
            if not error and not wrong:
                answered.append((completed, n))
            if completed < w0:
                second = int(completed - w0) - 1
                warming[second] = warming.get(second, 0) + 1
            inside = w0 <= completed < w1
            if not inside and not (w0 <= submitted < w1 <= completed):
                continue
            if error:
                errors.append(error)
            elif wrong:
                wrong_bits += wrong
                errors.append(f"{wrong} bits differ from the oracle")
            elif inside:
                per_second[min(len(per_second) - 1, int(completed - w0))] += 1
                done_sigs += n
                sizes[n] = sizes.get(n, 0) + 1
                rtts.append(completed - submitted)
            else:
                late += 1
    log(f"requests answered a second of the warm-up: "
        f"{[warming[k] for k in sorted(warming)]}")
    log(f"requests answered a second: {per_second}; slowest round trip "
        f"{max(rtts, default=0) * 1e3:.1f} ms; by size {sorted(sizes.items())}")
    if replayed:
        log(f"the clients ran out of requests and replayed {replayed}: "
            "signatures_per_validator is too small for this rate")
    run.observed["client"] = {"rtt_s": rtts, "answered": answered,
                              "signatures_in_window": done_sigs,
                              "replayed": replayed}
    run.observed["queue_depth"] = [s for s in samples if w0 <= s[0] < w1]
    attempted = len(rtts) + late + len(errors)
    log(f"window: {attempted} requests attempted, {len(rtts)} answered in "
        f"it, {late} after it, {len(errors)} failed; first failures: "
        f"{errors[:3]}")

    run.check("accept/reject bits differing from the oracle", wrong_bits, 0,
              wrong_bits == 0)
    run.check("requests that failed or were never answered", len(errors), 0,
              not errors)
    run.check("requests answered inside the window", len(rtts), ">= 1",
              len(rtts) >= 1)
    run.check_service(mapped)
    bad_codes = {n: c for n, c in run.exit_codes.items()
                 if c != 0 and not (n == "verifier-service" and c == -15)}
    run.check("exit codes other than 0", bad_codes, {},
              not bad_codes and len(run.exit_codes) == n_clients + 1)
    end_to_end = {}
    if done_sigs:
        end_to_end["verified_sig_s"] = done_sigs / run.seconds
        log(f"{end_to_end['verified_sig_s']:.1f} sig/s; request round trip "
            f"p50 {statistics.median(rtts) * 1e3:.2f} ms")
    return {"attempted": attempted, "failed": len(errors),
            "end_to_end": end_to_end}
