"""Blocks a second the returned validator received and verified while it
caught up: the growth of its ``verified_signatures_total{outcome=
"accepted"}`` (a block is one signature here) from its first answer after
the restart to the scrape at which it was in step (``recover_s``), or to
the curve's end where it never was, over the seconds between."""


def read(run):
    rejoin = run.observed.get("rejoin")
    if not rejoin:
        return None
    back, until = rejoin["back"], rejoin["recover_s"]
    points = [(at, row[back]["blocks"]) for at, row in rejoin["curve"]
              if row[back] is not None and (until is None or at <= until)]
    if len(points) < 2 or points[-1][0] <= points[0][0]:
        return None
    return (points[-1][1] - points[0][1]) / (points[-1][0] - points[0][0])
