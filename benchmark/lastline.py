"""The last line of a run, checked before it is printed.

``validate`` returns the list of faults in a result object against the
cell's entries in ``BENCHMARK.json``; ``run.py`` prints the line only when
the list is empty.  The shape is the driver's contract: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
optionally ``breakdown``.
"""
from __future__ import annotations

import json
import math
from typing import List

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_MOST = 10


def _number(value) -> bool:
    return (isinstance(value, (int, float)) and not isinstance(value, bool)
            and math.isfinite(value))


def validate(line: dict, cell: dict, trace: bool) -> List[str]:
    """Faults of ``line`` as the result of one run of ``cell`` (from
    ``harness.find_cell``); empty when the driver can read it."""
    faults: List[str] = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            faults.append(f"key {key!r} is missing")
    if faults:
        return faults
    if not isinstance(line["correct"], bool):
        faults.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) \
                or line[key] < 0:
            faults.append(f"{key} is not a whole number >= 0")
    if not faults and line["failed"] > line["attempted"]:
        faults.append("failed exceeds attempted")

    listed = {m["name"]: m for m in
              (cell["per_layer"] if trace else cell["end_to_end"])}
    metrics = line["metrics"]
    if not isinstance(metrics, dict) or not metrics:
        faults.append("metrics is not a non-empty object")
        metrics = {}
    for name, entry in metrics.items():
        if name not in listed:
            faults.append(f"metric {name!r} is not one this cell lists "
                          f"for --trace {int(trace)}")
            continue
        if not isinstance(entry, dict) or not _number(entry.get("value")):
            faults.append(f"metric {name!r} has no finite value")
        elif entry.get("unit") != listed[name]["unit"]:
            faults.append(f"metric {name!r} has unit {entry.get('unit')!r}, "
                          f"BENCHMARK.json says {listed[name]['unit']!r}")
    if not trace:
        for name in listed:
            if name not in metrics:
                faults.append(f"end-to-end metric {name!r} is missing")
        for name, entry in metrics.items():
            if name in listed and isinstance(entry, dict) \
                    and _number(entry.get("value")) and entry["value"] <= 0:
                faults.append(f"end-to-end metric {name!r} is not above 0")
    else:
        for name, m in listed.items():
            # A metric with no "workloads" key is due in every cell.
            if "workloads" not in m and name not in metrics:
                faults.append(f"per-layer metric {name!r} is due in every "
                              "cell and is missing")

    device = line["device"]
    if not isinstance(device, dict):
        return faults + ["device is not an object"]
    for key in DEVICE_KEYS + (TRACED_DEVICE_KEYS if trace else ()):
        if key not in device:
            faults.append(f"device.{key} is missing")
    for key in ("platform", "kind"):
        if key in device and not (isinstance(device[key], str) and device[key]):
            faults.append(f"device.{key} is not a name")
    count = device.get("count")
    if "count" in device and not (
        isinstance(count, int) and not isinstance(count, bool) and count >= 1
    ):
        faults.append("device.count is not a whole number >= 1")
    elif "count" in device and count < cell["chips"]:
        faults.append(f"device.count {count} is under the {cell['chips']} "
                      "chips the cell asks for")
    peak = device.get("memory_peak_bytes")
    if "memory_peak_bytes" in device and not (
        isinstance(peak, int) and not isinstance(peak, bool) and peak > 0
    ):
        faults.append("device.memory_peak_bytes is not a whole number > 0")
    if trace and all(k in device for k in TRACED_DEVICE_KEYS):
        window, busy = device["window_s"], device["busy_s"]
        if not _number(window) or window <= 0:
            faults.append("device.window_s is not above 0")
        elif not _number(busy) or busy <= 0:
            faults.append("device.busy_s is not above 0: no operation ran "
                          "on the device inside the traced window")
        elif busy > window:
            faults.append(f"device.busy_s {busy} exceeds device.window_s "
                          f"{window}: overlapping events were summed")

    if "breakdown" in line:
        if not trace:
            faults.append("breakdown belongs to a traced run")
        breakdown = line["breakdown"]
        if not isinstance(breakdown, dict):
            faults.append("breakdown is not an object")
        else:
            for key in BREAKDOWN_KEYS:
                rows = breakdown.get(key)
                if not isinstance(rows, list) or len(rows) > BREAKDOWN_MOST:
                    faults.append(f"breakdown.{key} is not a list of at "
                                  f"most {BREAKDOWN_MOST}")
                    continue
                for row in rows:
                    if not (isinstance(row, list) and len(row) == 2
                            and isinstance(row[0], str) and _number(row[1])):
                        faults.append(f"breakdown.{key} holds {row!r}, not "
                                      "[name, seconds]")
    try:
        text = json.dumps(line, allow_nan=False)
        if "\n" in text:
            faults.append("the line spans more than one line")
    except (TypeError, ValueError) as exc:
        faults.append(f"the line is not JSON: {exc}")
    return faults


def render(line: dict) -> str:
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))
