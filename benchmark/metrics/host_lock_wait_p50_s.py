"""Median seconds a walked job waited before its walk began its own
work, from its journey (host-walk start to host-walk locked): the wait
for the host symbolic lock where walks take turns on it, for a free
walker where they run side by side. A program that records no
`locked` event gives nothing to read."""

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
