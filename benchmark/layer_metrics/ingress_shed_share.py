"""Share of the window's submitted transactions that a gateway answered
SHED (ingress; the client's own count of the replies)."""


def read(run):
    client = run.observed.get("client") or {}
    if not client.get("submitted"):
        return None
    return 100.0 * client["shed"] / client["submitted"]
