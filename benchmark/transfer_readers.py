"""Arithmetic of the readers of the signed-transfer cell
(``transfers10-signed``): the validators' two signature counters over the
window, the launches of the unknown-signer kernel (``blob`` in the
service's dispatch counts, ``verify_blob`` in ``trace_names.json``), and
the bytes such a launch must move.  A program without signed transactions
has no ``verified_tx_signatures_total`` and launches no such kernel: every
function then returns None and the metric is left out of the line."""
from __future__ import annotations

from typing import Optional

from benchmark import readers

BLOB_KERNEL = "blob"  # ops/ed25519._dispatch_blob's name in dispatch_counts
BLOB_LABEL = "verify_blob"  # its launches' label in trace_names.json


def window_signatures(run) -> Optional[tuple]:
    """(transaction signatures, block signatures) the validators verified
    over the window, summed over the nodes: gateway and receipt checks on
    ``verified_tx_signatures_total``, block signatures on
    ``verified_signatures_total``."""
    tx = readers.node_deltas(run, "verified_tx_signatures_total")
    blocks = readers.node_deltas(run, "verified_signatures_total")
    if not tx or not blocks or sum(tx) <= 0:
        return None
    return sum(tx), sum(blocks)


def signatures_per_launch(run) -> Optional[float]:
    """Signatures of both kinds over the kernel launches the service
    counted in the window."""
    counted = window_signatures(run)
    launches = readers.dispatches(run)
    if not counted or not launches:
        return None
    return sum(counted) / launches


TRACED = ("trace_start", "trace_end")


def blob_lanes(run, edges=TRACED) -> Optional[float]:
    """Mean lanes of the launches of the unknown-signer kernel that the
    service counted between two snapshots: by default those taken as the
    profiler was armed and as the traced window closed, so that the
    trace's device seconds are divided by lanes of the same interval."""
    snaps = run.snapshots
    if edges[0] not in snaps or edges[1] not in snaps:
        return None

    def of(snapshot):
        rows = [d for d in snapshot["dispatches"]
                if d["kernel"] == BLOB_KERNEL]
        return (sum(d["count"] for d in rows),
                sum(d["count"] * d["bucket"] for d in rows))

    (n0, lanes0), (n1, lanes1) = of(snaps[edges[0]]), of(snaps[edges[1]])
    return (lanes1 - lanes0) / (n1 - n0) if n1 > n0 else None


def blob_launch_seconds(run) -> Optional[float]:
    """Device seconds of one launch of the unknown-signer kernel in the
    traced window."""
    row = ((run.trace_reduced or {}).get("kernels") or {}).get(BLOB_LABEL)
    if not row or not row["launches"]:
        return None
    return row["seconds"] / row["launches"]


def blob_launch_bytes(lanes: float) -> float:
    """Bytes one launch of the unknown-signer kernel must move at the
    least: the packed batch in ((lanes, 33) uint32: R, the signer's key,
    the digest, s, host flag - ops/ed25519.pack_blob) and one uint32
    verdict a lane out.  No key table: the keys ride in the batch."""
    return lanes * 33 * 4 + lanes * 4
