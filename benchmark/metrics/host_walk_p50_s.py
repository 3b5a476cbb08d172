"""Median seconds of a settled job's host walk, from its journey
(host-walk start to host-walk done)."""

from harness import quantile


def read(run):
    walls = []
    for events in run.get("journeys") or []:
        t = {}
        for row in events:
            if row.get("tier") == "host-walk":
                t.setdefault(row.get("event"), row["t"])
        if "start" in t and "done" in t:
            walls.append(t["done"] - t["start"])
    return quantile(walls, 0.5)
