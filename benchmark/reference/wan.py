"""The plain reference of ``paper10wan``: the deployment's geography, a
delay line written out, and the least time in which a transaction can be
final.  Imports nothing of the program.

**The table.**  Ten validators, validator ``i`` in region ``i`` of the
reference orchestrator's ``assets/settings.json`` (its first ten of eleven
AWS regions; ap-southeast-2 holds no validator).  ``RTT_MS`` is the round
trip between two regions in milliseconds, written from public inter-region
ping tables as remembered (there is no network here to measure one), value
by value as ISSUE 32 lists them; no value was corrected.  Links are
symmetric and constant; the one-way delay is half the round trip.

**The floor** (``finality_floor_s``).  A transaction handed to validator
``v`` at time 0 is notified as committed there when ``v`` decides a leader
block whose history holds the block ``v`` put it in.  With wave length 3
the direct rule takes a leader block L by ``u``, a quorum of blocks of the
next round that vote for it, and a quorum of blocks of the round after
that, each of which holds a quorum of votes (a certificate):

* L can hold ``v``'s block no earlier than ``d*(v, u)`` (0 if ``u`` is
  ``v``: the leader block can be the one that carries the transaction);
* a voting block at ``w`` exists no earlier than that + ``d*(u, w)``;
* a certifying block at ``x`` no earlier than the ``q``-th smallest over
  ``w`` of (vote at ``w``) + ``d*(w, x)``;
* ``v`` decides no earlier than the ``q``-th smallest over ``x`` of
  (certificate at ``x``) + ``d*(x, v)``;

and the floor is the least of that over the leaders ``u``.  ``d*`` is the
table closed under relaying (min-plus: a block may reach ``b`` sooner
through ``c`` than over the link itself), a validator's own blocks reach it
at delay 0, and ``q`` is the quorum, 7 of 10.  Nothing in it is processor
time, so no run may beat it; the share of it that a measured median
reaches is the deployment's roofline share (``finality_floor_share``).

With the table below it reads, by validator (milliseconds)::

    us-west-1 160.0   us-west-2 158.5   us-east-1 151.5   ca-central-1 160.0
    eu-central-1 160.0   eu-west-1 152.5   eu-west-2 151.0
    ap-northeast-1 221.5   ap-south-1 231.5   ap-southeast-1 229.0

(``python3 benchmark/reference/wan.py`` prints it; ``tests/test_link_delay.py``
holds it to a search over every leader, every quorum of voters for every
certifier and every quorum of certifiers, on a 4-node table.)
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Optional, Sequence, Tuple

REGIONS = (
    "us-west-1", "us-west-2", "us-east-1", "ca-central-1", "eu-central-1",
    "eu-west-1", "eu-west-2", "ap-northeast-1", "ap-south-1",
    "ap-southeast-1",
)

# Round trips in milliseconds, the upper triangle: row region -> the
# regions after it in REGIONS.
_RTT_UPPER = (
    (22, 62, 80, 150, 137, 145, 108, 230, 170),  # us-west-1
    (68, 65, 145, 125, 135, 98, 220, 165),       # us-west-2
    (16, 90, 68, 76, 148, 188, 215),             # us-east-1
    (92, 70, 78, 145, 195, 215),                 # ca-central-1
    (25, 14, 225, 120, 160),                     # eu-central-1
    (11, 205, 122, 175),                         # eu-west-1
    (212, 112, 165),                             # eu-west-2
    (128, 70),                                   # ap-northeast-1
    (60,),                                       # ap-south-1
)


def _symmetric(upper: Sequence[Sequence[float]]) -> List[List[float]]:
    n = len(upper) + 1
    table = [[0.0] * n for _ in range(n)]
    for a, row in enumerate(upper):
        for offset, rtt in enumerate(row):
            b = a + 1 + offset
            table[a][b] = table[b][a] = float(rtt)
    return table


RTT_MS: List[List[float]] = _symmetric(_RTT_UPPER)


def one_way_table_ms() -> List[List[float]]:
    """The table of one-way delays (``Parameters.link_delay_ms``): half of
    every round trip."""
    return [[rtt / 2.0 for rtt in row] for row in RTT_MS]


def one_way_ms(a: int, b: int) -> float:
    """One-way delay from region ``a`` to region ``b``: RTT / 2."""
    return RTT_MS[a][b] / 2.0


# -- (b) a plain delay line ---------------------------------------------------


def delay_line(events: Sequence[Tuple[float, Hashable]],
               delay_s: Dict[Hashable, float]) -> List[float]:
    """Release times of frames handed over as ``events`` = [(hand-over
    time, link)], in hand-over order: a frame leaves its link no earlier
    than hand-over + the link's delay, and not before the frame handed to
    that link before it."""
    last: Dict[Hashable, float] = {}
    out = []
    for handed, link in events:
        release = max(handed + delay_s[link], last.get(link, float("-inf")))
        last[link] = release
        out.append(release)
    return out


# -- (c) the floor ---------------------------------------------------------------


def closure(one_way: Sequence[Sequence[float]]) -> List[List[float]]:
    """The table closed under relaying (min-plus), own blocks at delay 0."""
    n = len(one_way)
    d = [[0.0 if a == b else float(one_way[a][b]) for b in range(n)]
         for a in range(n)]
    for c in range(n):
        for a in range(n):
            for b in range(n):
                if d[a][c] + d[c][b] < d[a][b]:
                    d[a][b] = d[a][c] + d[c][b]
    return d


def quorum_of(n: int) -> int:
    """2f + 1 of n = 3f + 1 validators of equal stake (7 of 10, 3 of 4)."""
    return 2 * n // 3 + 1


def finality_floor_s(v: int,
                     one_way_table: Optional[Sequence[Sequence[float]]] = None,
                     ) -> float:
    """The least seconds from a transaction's hand-over at validator ``v``
    to its commit notification there, from the table of one-way delays
    (milliseconds; default: this file's) alone.  Wave length 3."""
    d = closure(one_way_table or one_way_table_ms())
    n = len(d)
    q = quorum_of(n)
    best = float("inf")
    for u in range(n):
        leader = d[v][u]
        vote = [leader + d[u][w] for w in range(n)]
        certificate = [sorted(vote[w] + d[w][x] for w in range(n))[q - 1]
                       for x in range(n)]
        decided = sorted(certificate[x] + d[x][v] for x in range(n))[q - 1]
        best = min(best, decided)
    return best / 1e3


def finality_floors_s(one_way_table: Optional[Sequence[Sequence[float]]]
                      = None) -> List[float]:
    table = one_way_table or one_way_table_ms()
    return [finality_floor_s(v, table) for v in range(len(table))]


def lower_median(values: Sequence[float]) -> float:
    ordered = sorted(values)
    return ordered[(len(ordered) - 1) // 2]


if __name__ == "__main__":
    for region, floor in zip(REGIONS, finality_floors_s()):
        print(f"{region:16s} {floor * 1e3:7.1f} ms")
