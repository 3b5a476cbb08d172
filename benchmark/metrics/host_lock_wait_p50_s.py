"""Median seconds a walked job waited for the host symbolic lock, from
its journey (host-walk start to host-walk locked). A program that
records no `locked` event gives nothing to read."""

from harness import quantile


def read(run):
    waits = []
    for events in run.get("journeys") or []:
        t = {}
        for row in events:
            if row.get("tier") == "host-walk":
                t.setdefault(row.get("event"), row["t"])
        if "start" in t and "locked" in t:
            waits.append(t["locked"] - t["start"])
    return quantile(waits, 0.5)
