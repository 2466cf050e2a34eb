"""The deployment's roofline share: the reference's finality floor
(``reference/wan.py``: from the table of link delays alone), lower median
over the gateways, over the client's median finality.  The median of groups
each bounded below by its floor is bounded below by the lower median of the
floors, so a reading over 100% is a fault, never a gain."""
import statistics

from benchmark.reference import wan


def read(run):
    latencies = (run.observed.get("client") or {}).get("latencies")
    floors = (run.observed.get("wan") or {}).get("floors_s")
    if not latencies or not floors:
        return None
    return 100.0 * wan.lower_median(floors) / statistics.median(latencies)
