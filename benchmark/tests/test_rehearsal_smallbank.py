"""The whole command off the chip for the SmallBank cell, at a size a CPU
can hold (``toy_smallbank/``): both ``--trace`` values, and the cell's two
controls, each of which must come out ``correct: false`` by the cell's own
comparison - validators that drain an account's lane newest first
(``control_lifo_node_main.py``: the per-account comparison of
``sequencing``) and validators whose WriteCheck forgets the penalty
(``control_nopenalty_node_main.py``: the root against the reference's
fold).  Beside them: the configuration key by key against
``transfers10.json``, and the seven readers on hand-built scrapes.

Slow like ``test_rehearsal_transfers.py`` (~2 minutes a case), and one at a
time: the cells share their ports."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, lastline

HERE = os.path.dirname(__file__)
TOY = os.path.join(HERE, "toy_smallbank", "spec.json")
CELL, SECONDS = "toy4-smallbank-hotspot", 10
READERS = ("nonce_ahead_share", "lane_depth_max", "exec_conflict_share",
           "exec_bad_nonce_s", "exec_fold_ms.smallbank", "finality_p50_s.hot",
           "sigs_per_dispatch.smallbank")


def rehearse(trace: int, seed: int, spec: str = TOY):
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--spec", spec, "--workload", CELL, "--seed", str(seed),
         "--seconds", str(SECONDS), "--trace", str(trace)],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    path = os.path.join(harness.ROOT, ".bench_work",
                        f"{CELL}-t{trace}.last_line.json")
    with open(path) as f:
        line = json.load(f)
    return done, line


def compared(done, ending):
    return [l for l in done.stdout.splitlines()
            if l.startswith("compared: ") and l.endswith(ending)]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_whole_command_off_the_chip(trace):
    done, line = rehearse(trace, 2_600_000_011 + trace)
    assert done.returncode == harness.EXIT_NO_TPU, done.stderr[-3000:]
    assert "no TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
    cell = harness.find_cell(CELL, TOY)
    assert lastline.validate(line, cell, bool(trace)) == []
    assert line["correct"] is True, done.stdout[-4000:]
    ok = compared(done, "ok")
    for name in ("corrupted envelopes acknowledged",
                 "corrupted envelopes notified as committed",
                 "genesis allocation equal to the reference's",
                 "kernels that took the requests with repeated signers",
                 "heights where the executed root differs from the "
                 "reference's fold of the WAL",
                 "operations committed more than once",
                 "accounts never refused whose executed operations are not "
                 "a prefix",
                 "operations of accounts never refused that executed "
                 "neither applied nor aborted",
                 "accounts whose final nonce by the reference",
                 "kernels the window ran and the probe did not",
                 "compilations inside the window"):
        assert any(name in l for l in ok), name
    # The source's own aborts and the overdraft penalty both occurred.
    fold = next(l for l in done.stderr.splitlines() if "reference fold" in l)
    assert "'8/aborted'" in fold and "'aborted'" in fold
    assert "checking below zero at the end 0:" not in fold
    if trace:
        metrics = line["metrics"]
        assert set(READERS) <= set(metrics)
        assert metrics["nonce_ahead_share"]["value"] > 0.0
        assert metrics["lane_depth_max"]["value"] > 1
        assert metrics["exec_conflict_share"]["value"] > 0.0
        assert metrics["exec_bad_nonce_s"]["value"] == 0.0
        assert metrics["finality_p50_s.hot"]["value"] > 0.0
        assert metrics["tx_sig_share"]["value"] > 50.0
        assert "setup_s" not in metrics
    else:
        assert line["failed"] == 0 < line["attempted"]
        assert set(line["metrics"]) == {m["name"]
                                        for m in cell["end_to_end"]}


def control(name: str, seed: int):
    sys.path.insert(0, HERE)
    try:
        module = __import__(name)
    finally:
        sys.path.remove(HERE)
    spec = module.control_spec(
        TOY, CELL, os.path.join(harness.ROOT, ".bench_work", "control"))
    done, line = rehearse(0, seed, spec)
    assert done.returncode != 0
    return done, (line.get("line") or line)


def test_a_pool_that_drains_an_account_newest_first_is_not_correct():
    done, line = control("control_lifo_node_main", 2_600_000_031)
    assert line["correct"] is False and line["failed"] > 0
    failed = compared(done, "FAILED")
    assert any("executed neither applied nor aborted" in l
               for l in failed), failed
    # Nothing else is wrong with such a fleet: the roots are the
    # reference's, the signatures were checked.
    ok = compared(done, "ok")
    assert any("differs from the reference's fold" in l for l in ok)
    assert any("corrupted envelopes acknowledged" in l for l in ok)


def test_a_write_check_without_the_penalty_is_not_correct():
    done, line = control("control_nopenalty_node_main", 2_600_000_041)
    assert line["correct"] is False
    failed = compared(done, "FAILED")
    assert [l for l in failed
            if "differs from the reference's fold" in l], failed
    # The ten agree with each other and no sequence broke.
    ok = compared(done, "ok")
    assert any("two validators' executed roots differ" in l for l in ok)
    assert any("executed neither applied nor aborted" in l for l in ok)


def test_the_configuration_is_transfers10_by_value_but_for_what_it_states():
    """Every key ``smallbank10.json`` shares with ``transfers10.json`` has
    its value, but the ports (under 16000) and what names the deployment;
    the toy differs from ``smallbank10.json`` in its size alone."""
    configs = os.path.join(harness.HERE, "configs")
    ours = harness.load_json(os.path.join(configs, "smallbank10.json"))
    theirs = harness.load_json(os.path.join(configs, "transfers10.json"))
    for key in ("validators", "transaction_bytes", "verifier", "hosts",
                "regions", "injected_delay_ms", "accounts", "accounts_seed",
                "corrupted_one_in", "node_env", "reduced"):
        assert ours[key] == theirs[key], key
    for key, value in theirs["parameters"].items():
        if key == "ingress":
            assert {k: v for k, v in ours["parameters"][key].items()
                    if k != "gateway_port_base"} == {
                k: v for k, v in value.items() if k != "gateway_port_base"}
        else:
            assert ours["parameters"][key] == value, key
    ports = [ours["parameters"]["ingress"]["gateway_port_base"] + i
             for i in range(10)] + [ours["service"]["metrics_port"]]
    assert max(ports) < 16000 and not set(ports) & (
        set(range(3650, 3660)) | {3700} | set(range(1500, 1510))
        | set(range(2500, 2510)))
    for name, said in theirs["guarantees"].items():
        if name in ("admission", "execution"):
            assert ours["guarantees"][name] != said  # names its reference
        else:
            assert ours["guarantees"][name] == said, name
    assert "benchmark/reference/smallbank.py" in ours["guarantees"]["execution"]
    assert "nonce order" in ours["guarantees"]["sequencing"]
    assert theirs["probe"]["requests"] == ours["probe"]["requests"][:-1]
    assert ours["probe"]["requests"][-1]["signers"] == "hotspot"
    assert sum(ours["mix"].values()) == 100 and ours["mix"]["SendPayment"] == 25
    assert (ours["hotspot_accounts"], ours["hotspot_share"]) == (100, 0.25)
    assert len(ours["source"]) <= 200
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = next(c for c in spec["configs"] if c["name"] == "smallbank10")
    assert entry["source"] == ours["source"]
    assert entry["reduced"] == sorted(ours["reduced"], key=entry[
        "reduced"].index)
    toy = harness.load_json(os.path.join(
        HERE, "toy_smallbank", "configs", "toy4-smallbank.json"))
    differing = {k for k in ours if toy[k] != ours[k]}
    assert differing == {"name", "stands_for", "validators", "accounts",
                         "hotspot_accounts", "corrupted_one_in", "probe"}


def _read(name, run):
    reader = harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "layer_metric_" + name.replace(".", "_"))
    return reader.read(run)


def _scrape(admitted, ahead, depth, txs, bad_nonce, conflicts, ops=True):
    text = [f"mysticeti_ingress_admitted_total {admitted}",
            f"mysticeti_ingress_nonce_ahead_total {ahead}",
            f"mysticeti_ingress_lane_depth_max {depth}",
            f'mysticeti_execution_txs_total{{result="applied"}} {txs}',
            f"mysticeti_execution_conflicts_total {conflicts}",
            "verified_tx_signatures_total 0"]
    if bad_nonce:
        text.append('mysticeti_execution_txs_total{result="bad_nonce"} '
                    f"{bad_nonce}")
    if ops:
        text.append(f'mysticeti_execution_ops_total{{op="balance"}} {txs}')
    return harness.parse_metrics("\n".join(text))


class _Run:
    window = (100.0, 120.0)
    workdir = None
    snapshots: dict = {}
    trace_reduced = None
    service_report = None

    def __init__(self, nodes=None, client=None):
        self.observed = {}
        if nodes:
            self.observed["nodes"] = nodes
        if client:
            self.observed["client"] = client


def test_the_readers_read_window_deltas_over_the_validators():
    nodes = {
        "start": [_scrape(1000, 100, 3, 5000, 0, 50),
                  _scrape(2000, 300, 2, 5000, 0, 50), None],
        "end": [_scrape(3000, 600, 5, 9000, 40, 250),
                _scrape(4000, 800, 4, 9000, 40, 250),
                _scrape(9, 9, 99, 9, 9, 9)],
    }
    run = _Run(nodes, {"latencies_hot": [0.3, 0.5, 0.4], "latencies": [0.2]})
    assert _read("nonce_ahead_share", run) == pytest.approx(
        100.0 * (500 + 500) / (2000 + 2000))
    # The third validator answered one scrape only: not read.
    assert _read("lane_depth_max", run) == 5
    assert _read("exec_conflict_share", run) == pytest.approx(
        100.0 * 400 / (2 * 4040))
    assert _read("exec_bad_nonce_s", run) == pytest.approx(40 / 20.0)
    assert _read("finality_p50_s.hot", run) == 0.4
    calm = _Run({"start": [_scrape(0, 0, 0, 0, 0, 0)],
                 "end": [_scrape(10, 0, 1, 10, 0, 0)]})
    assert _read("exec_bad_nonce_s", calm) == 0.0
    assert _read("nonce_ahead_share", calm) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_without_smallbank(name):
    """The parent's validators export none of the series, its client
    records no hot latencies, and a run without scrapes has no nodes:
    None, never an exception, and the metric is left out."""
    parent = harness.parse_metrics(
        'mysticeti_ingress_admitted_total 10\n'
        'mysticeti_execution_txs_total{result="applied"} 10')
    run = _Run({"start": [parent], "end": [parent]}, {"latencies": [0.2]})
    assert _read(name, run) is None
    assert _read(name, _Run()) is None


def test_every_new_reader_is_an_entry_at_the_end_of_the_benchmark():
    """Appended, as the driver's check wants a program PR's entries: one
    put before `slow_seconds` reads there as a change to `slow_seconds`
    (PR 42's first check was refused for it).  So
    test_node_readers.py's pin on the tail of `per_layer` no longer
    holds; that test is a `benchmark` PR's to repair (PERF.md section 7)."""
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in spec["per_layer"]]
    assert tuple(names[-len(READERS):]) == READERS
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in READERS}
    for entry in spec["per_layer"][-len(READERS):]:
        assert entry["workloads"] == ["smallbank10-hotspot"]
        assert entry["layer"] in layers
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", entry["name"] + ".py"))
    cell = harness.find_cell("smallbank10-hotspot")
    assert cell["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] == [
        "committed_tx_s", "finality_p50_s", "setup_s"]
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(READERS) <= listed
    # The lists a test of the benchmark pins with == do not name the cell.
    for pinned in ("slow_seconds", "scrape_max_ms", "exec_fold_ms",
                   "service_cpu_cores.fleet", "block_verify_ms",
                   "service_requests_per_read"):
        assert pinned not in listed
