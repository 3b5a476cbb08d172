"""Median seconds of a walk holding the host symbolic lock, from its
journey (host-walk locked to host-walk done): the walk without its wait
for the lock. A program that records no `locked` event gives nothing
to read."""

from harness import quantile


def read(run):
    walls = []
    for events in run.get("journeys") or []:
        t = {}
        for row in events:
            if row.get("tier") == "host-walk":
                t.setdefault(row.get("event"), row["t"])
        if "locked" in t and "done" in t:
            walls.append(t["done"] - t["locked"])
    return quantile(walls, 0.5)
