"""Commits the returned validator is behind at the window's end: the
median ``committed_height`` of the validators that never died less its
own, from the scrape at the window's end."""


def read(run):
    return (run.observed.get("rejoin") or {}).get("lag_commits")
