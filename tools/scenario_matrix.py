#!/usr/bin/env python3
"""Resilience scenario-matrix probe -> scenario_matrix.json.

Runs the declarative Byzantine scenario matrix (mysticeti_tpu/scenarios.py)
— every entry an attacked seeded sim plus a same-seed clean twin — and pins
the per-scenario verdicts into one JSON document:

* **safety** — zero honest-node SafetyChecker violations per scenario;
* **liveness** — honest-authored committed throughput >= the scenario's
  ``min_ratio`` x the clean twin;
* **detection** — every injected attack detected on its counter surface
  (equivocation / invalid-signature / malformed) or accounted in the
  attack ledger (the silence-shaped behaviors);
* **reproducibility** — schedule / attack / detection / sequence digests
  recorded per scenario, so a same-seed re-run is byte-checkable.

A ``--determinism`` pass re-runs the first scenario on the same seed and
asserts the digests match — the artifact then carries the proof, not just
the claim.

Usage::

    python tools/scenario_matrix.py [--out scenario_matrix.json] [--quick]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mysticeti_tpu.scenarios import (  # noqa: E402
    default_matrix,
    run_matrix,
    run_scenario,
    scenario_by_name,
)


def determinism_leg(name: str, quick: bool) -> dict:
    """Same scenario, same seed, twice: the digests must be identical."""
    import tempfile

    scenario = scenario_by_name(name)
    if quick:
        scenario = dataclasses.replace(scenario, duration_s=6.0)
    digests = []
    for run in range(2):
        with tempfile.TemporaryDirectory(prefix="scenario-det-") as root:
            verdict = run_scenario(scenario, root)
        digests.append(verdict["digests"])
    return {
        "scenario": name,
        "runs": digests,
        "byte_identical": digests[0] == digests[1],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default="scenario_matrix.json")
    parser.add_argument("--quick", action="store_true",
                        help="shortened scenarios (smoke, not acceptance)")
    parser.add_argument("--scenario", default=None,
                        help="run only this named scenario")
    parser.add_argument("--no-determinism", action="store_true",
                        help="skip the same-seed re-run leg")
    parser.add_argument("--real-crypto", action="store_true",
                        help="genuine per-node Ed25519 verification instead "
                        "of the sim re-sign oracle (same semantics; minutes "
                        "per scenario on the pure-Python fallback)")
    args = parser.parse_args(argv)

    scenarios = default_matrix()
    if args.scenario:
        scenarios = [scenario_by_name(args.scenario)]
    if args.quick:
        scenarios = [
            dataclasses.replace(s, duration_s=min(s.duration_s, 8.0))
            for s in scenarios
        ]
    t0 = time.monotonic()
    doc = run_matrix(scenarios, real_crypto=args.real_crypto)
    doc.update(
        probe="resilience-scenario-matrix",
        revision="r12",
        quick=bool(args.quick),
        wall_s=round(time.monotonic() - t0, 1),
    )
    for verdict in doc["scenarios"]:
        name = verdict["scenario"]["name"]
        print(
            f"{name:<24} {'PASS' if verdict['passed'] else 'FAIL'}  "
            f"ratio={verdict.get('throughput_ratio', 0.0):.2f}  "
            f"attacks={sum(verdict.get('attack_counts', {}).values())}",
            flush=True,
        )
    if not args.no_determinism:
        print("== determinism leg ==", flush=True)
        doc["determinism"] = determinism_leg(
            scenarios[0].name, args.quick
        )
        print(f"byte_identical: {doc['determinism']['byte_identical']}")
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out} ({doc['passed']} passed, {doc['failed']} failed)")
    # The determinism leg gates the exit code too: a byte_identical=false
    # run is a regression even when every scenario verdict passes.
    deterministic = (doc.get("determinism") or {}).get("byte_identical", True)
    return 0 if doc["all_pass"] and deterministic else 1


if __name__ == "__main__":
    sys.exit(main())
