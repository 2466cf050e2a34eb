#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` in
a traced run), printed only after ``lastline.validate`` found no fault in
it, and only where the verifier service held a TPU.  On any other platform
the line is still built, validated and written to
``.bench_work/<cell>-t<trace>.last_line.json`` (the off-chip rehearsal
reads it there), and the run ends with "no TPU" and code 3.

Everything that belongs to one cell is found by name: see ``README.md``.
This process never imports JAX; ``BENCH_RUN`` is not read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import harness, lastline  # noqa: E402
from benchmark.harness import BenchError  # noqa: E402

# What the off-chip rehearsal reduces in place of its own trace, which has
# no device plane, and the chip the recording stands for.
REHEARSAL_TRACE = os.path.join(HERE, "tests", "fixtures",
                               "device_trace.textproto")
REHEARSAL_TRACE_KIND = "TPU v5 lite"


def read_layer_metrics(run: harness.Run) -> dict:
    """Each per-layer metric the cell lists, through its own reader
    ``layer_metrics/<name>.py``; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for metric in run.cell["per_layer"]:
        path = os.path.join(HERE, "layer_metrics", metric["name"] + ".py")
        reader = harness.load_module(
            path, "layer_metric_" + metric["name"].replace(".", "_")
                                                  .replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def build_line(run: harness.Run, result: dict) -> dict:
    device = run.device_file
    if device is None:
        raise BenchError("the service left no device.json: it did not stop "
                         "in order, so device and memory peak are unknown")
    report = run.service_report or {}
    seen = {"platform": report.get("platform"),
            "kind": report.get("device_kind"),
            "count": report.get("device_count")}
    told = {k: device[k] for k in seen}
    run.check("device in the boot report", seen, told, seen == told)
    line = {
        "correct": run.correct,  # every check is in by now
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": {},
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": device["count"],
            "memory_peak_bytes": device["memory_peak_bytes"],
        },
    }
    if run.trace:
        reduced = run.trace_reduced
        line["metrics"] = read_layer_metrics(run)
        line["device"]["window_s"] = reduced["window_s"]
        line["device"]["busy_s"] = reduced["busy_s"]
        line["breakdown"] = {
            "device_ops": [[name, seconds] for name, seconds, _count
                           in reduced["device_ops"][:lastline.BREAKDOWN_MOST]],
            "idle_gaps": [[name, seconds] for name, seconds
                          in reduced["idle_gaps"][:lastline.BREAKDOWN_MOST]],
        }
    else:
        units = {m["name"]: m["unit"] for m in run.cell["end_to_end"]}
        values = dict(result["end_to_end"], setup_s=run.setup_s)
        line["metrics"] = {
            name: {"value": values[name], "unit": unit}
            for name, unit in units.items() if values.get(name) is not None
        }
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"),
                        help="another BENCHMARK.json (the tests' toy cells)")
    parser.add_argument("--keep-work", action="store_true",
                        help="leave .bench_work/<cell>-t<trace>/ in place "
                        "(logs, trace) for reading by hand")
    parser.add_argument("--service-main",
                        help="another wrapper of the verifier service (the "
                        "control in benchmark/tests/)")
    args = parser.parse_args(argv)

    try:
        import mysticeti_tpu  # noqa: F401
        from mysticeti_tpu import native
    except ImportError as exc:
        print(f"benchmark: the program is not next to benchmark/ ({exc})",
              file=sys.stderr)
        return 2
    if "jax" in sys.modules:
        print("benchmark: the parent must stay off JAX", file=sys.stderr)
        return 2
    # Built once here, before a fleet of processes would race to run g++.
    native.active_functions()

    code, run = harness.EXIT_FAILED, None
    try:
        cell = harness.find_cell(args.workload, args.spec)
        run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                          service_main=args.service_main)
        driver = harness.load_module(cell["driver"], "bench_driver")
        result = driver.drive(run)
        platform = (run.device_file or {}).get("platform")
        if run.trace:
            # A CPU trace has no device plane: the rehearsal reduces the
            # recorded fixture instead, and its line is never printed.
            if platform == "tpu":
                run.reduce_trace()
            else:
                run.reduce_trace(REHEARSAL_TRACE, REHEARSAL_TRACE_KIND)
        line = build_line(run, result)
        faults = lastline.validate(line, cell, run.trace)
        for name, value, limit, ok in run.checks:
            print(f"compared: {name} = {value!r}; limit {limit!r}; "
                  f"{'ok' if ok else 'FAILED'}")
        with open(run.workdir + ".last_line.json", "w") as f:
            f.write(lastline.render(line) if not faults else
                    json.dumps({"faults": faults, "line": line},
                               default=repr))
        if faults:
            for fault in faults:
                print(f"benchmark: the result line is malformed: {fault}",
                      file=sys.stderr)
        elif platform != "tpu":
            print(f"benchmark: no TPU (the service ran on {platform!r}); "
                  "no result is printed", file=sys.stderr)
            code = harness.EXIT_NO_TPU
        else:
            sys.stdout.flush()
            print(lastline.render(line), flush=True)
            code = 0
    except BenchError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        if str(exc).startswith("no TPU"):
            code = harness.EXIT_NO_TPU
    finally:
        if run is not None:
            run.close(keep=code not in (0, harness.EXIT_NO_TPU),
                      leave=args.keep_work)
    return code


if __name__ == "__main__":
    sys.exit(main())
