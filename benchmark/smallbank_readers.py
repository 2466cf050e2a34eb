"""Arithmetic of the readers of the SmallBank cell
(``smallbank10-hotspot``): the validators' counters of what an account that
signs ahead does to the ingress plane and to the fold, over the window.  A
program without SmallBank has none of these series (the parent of the PR
that added them): every function then returns None and the metric is left
out of the line."""
from __future__ import annotations

from typing import List, Optional

from benchmark import harness, readers


def scrapes(run) -> List[tuple]:
    """(start, end) of every validator that answered both of the window's
    scrapes."""
    nodes = (getattr(run, "observed", None) or {}).get("nodes") or {}
    return [(start, end)
            for start, end in zip(nodes.get("start", []), nodes.get("end", []))
            if start is not None and end is not None]


def has_series(run, name: str) -> bool:
    """Whether any validator's closing scrape holds ``name`` (a counter
    with or without ``_total``)."""
    names = {name, name + "_total", name.removesuffix("_total")}
    return any(n in names for _, end in scrapes(run) for n, _, _ in end)


def window_share(run, part: str, whole: str) -> Optional[float]:
    """Growth of ``part`` over growth of ``whole`` across the window,
    summed over the validators, in percent."""
    if not has_series(run, part):
        return None
    total = sum(readers.node_deltas(run, whole))
    if total <= 0:
        return None
    return 100.0 * sum(readers.node_deltas(run, part)) / total


def gauge_max(run, name: str) -> Optional[float]:
    """Largest value of a gauge over the validators and both scrapes."""
    values = [value for pair in scrapes(run) for series in pair
              for n, _, value in series if n == name]
    return max(values) if values else None


def one_validator_rate(run, name: str, marker: str, **labels
                       ) -> Optional[float]:
    """Growth a second of one labelled counter on the first validator that
    answered both scrapes; 0.0 where it never counted (a labelled series
    appears with its first count).  ``marker`` is a series every program
    that has the counter's new labels exports: None without it."""
    if not has_series(run, marker) or not run.window:
        return None
    start, end = scrapes(run)[0]
    grown = (harness.series_sum(end, name, **labels)
             - harness.series_sum(start, name, **labels))
    return grown / (run.window[1] - run.window[0])
