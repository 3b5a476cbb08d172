"""Median seconds of a walk doing its own work, from its journey
(host-walk locked, where the walk has begun its own work, to host-walk
done): the walk without its wait to begin, whether walks take turns on
the host symbolic lock or run side by side. A program that records no
`locked` event gives nothing to read."""

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
