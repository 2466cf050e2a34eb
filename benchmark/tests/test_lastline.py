"""The last-line validator against good and bad lines, for every cell of
``BENCHMARK.json`` and both ``--trace`` values."""
import copy
import json
import os

import pytest

from benchmark import harness, lastline

CELLS = [w["name"] for w in harness.load_json(
    os.path.join(harness.ROOT, "BENCHMARK.json"))["workloads"]]


def good_line(cell: dict, trace: bool) -> dict:
    listed = cell["per_layer"] if trace else cell["end_to_end"]
    line = {
        "correct": True, "attempted": 400, "failed": 0,
        "metrics": {m["name"]: {"value": 1.5, "unit": m["unit"]}
                    for m in listed},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 123456789},
    }
    if trace:
        line["device"].update(window_s=3.0, busy_s=1.25)
        line["breakdown"] = {"device_ops": [["custom-call.2", 1.0]],
                             "idle_gaps": [["unattributed", 1.75]]}
    return line


@pytest.fixture(params=[(c, t) for c in CELLS for t in (False, True)],
                ids=lambda p: f"{p[0]}-trace{int(p[1])}")
def case(request):
    name, trace = request.param
    cell = harness.find_cell(name)
    return cell, trace, good_line(cell, trace)


def test_a_good_line_passes_and_renders_on_one_line(case):
    cell, trace, line = case
    assert lastline.validate(line, cell, trace) == []
    text = lastline.render(line)
    assert "\n" not in text and json.loads(text) == line


@pytest.mark.parametrize("key", ["correct", "attempted", "failed", "metrics",
                                 "device"])
def test_a_missing_key_is_a_fault(case, key):
    cell, trace, line = case
    del line[key]
    assert any(key in f for f in lastline.validate(line, cell, trace))


@pytest.mark.parametrize("key", ["platform", "kind", "count",
                                 "memory_peak_bytes"])
def test_a_missing_device_field_is_a_fault(case, key):
    cell, trace, line = case
    del line["device"][key]
    assert any(key in f for f in lastline.validate(line, cell, trace))


def test_busy_seconds_must_lie_in_the_window(case):
    cell, trace, line = case
    if not trace:
        assert "busy_s" not in line["device"]
        return
    for busy, word in ((0, "not above 0"), (0.0, "not above 0"),
                       (3.5, "exceeds"), (float("nan"), "not above 0")):
        bad = copy.deepcopy(line)
        bad["device"]["busy_s"] = busy
        assert any(word in f for f in lastline.validate(bad, cell, trace)), busy
    for key in ("busy_s", "window_s"):
        bad = copy.deepcopy(line)
        del bad["device"][key]
        assert any(key in f for f in lastline.validate(bad, cell, trace))


def test_a_metric_without_unit_or_value_is_a_fault(case):
    cell, trace, line = case
    name = next(iter(line["metrics"]))
    for broken in ({"value": 1.5}, {"unit": line["metrics"][name]["unit"]},
                   {"value": "1.5", "unit": line["metrics"][name]["unit"]},
                   {"value": 1.5, "unit": "furlongs"}, 1.5):
        bad = copy.deepcopy(line)
        bad["metrics"][name] = broken
        assert any(name in f for f in lastline.validate(bad, cell, trace))


def test_a_metric_the_cell_does_not_list_is_a_fault(case):
    cell, trace, line = case
    # The other mode's metrics are not this mode's.
    other = cell["end_to_end"] if trace else cell["per_layer"]
    line["metrics"][other[0]["name"]] = {"value": 1.0,
                                         "unit": other[0]["unit"]}
    line["metrics"]["made_up"] = {"value": 1.0, "unit": "s"}
    faults = lastline.validate(line, cell, trace)
    assert any(other[0]["name"] in f for f in faults)
    assert any("made_up" in f for f in faults)


def test_every_end_to_end_metric_is_due_and_above_zero(case):
    cell, trace, line = case
    if trace:
        return
    name = cell["end_to_end"][0]["name"]
    zero = copy.deepcopy(line)
    zero["metrics"][name]["value"] = 0.0
    assert any("not above 0" in f for f in lastline.validate(zero, cell, trace))
    del line["metrics"][name]
    assert any("missing" in f for f in lastline.validate(line, cell, trace))


def test_counts_and_breakdown_shapes(case):
    cell, trace, line = case
    bad = copy.deepcopy(line)
    bad["failed"] = 401
    assert lastline.validate(bad, cell, trace)
    bad = copy.deepcopy(line)
    bad["correct"] = "true"
    assert lastline.validate(bad, cell, trace)
    bad = copy.deepcopy(line)
    bad["breakdown"] = {"device_ops": [["op", 1.0]] * 11, "idle_gaps": []}
    assert lastline.validate(bad, cell, trace)
    bad = copy.deepcopy(line)
    bad["breakdown"] = {"device_ops": [["op", "fast"]], "idle_gaps": []}
    assert lastline.validate(bad, cell, trace)
