"""Median seconds from a job's admission to its lane grant, from its
journey; jobs that never waited for lanes (answered at admission) are
left out."""

from harness import quantile


def read(run):
    waits = []
    for events in run.get("journeys") or []:
        admitted = next((r["t"] for r in events if r.get("tier") == "admission"), None)
        granted = next((r["t"] for r in events if r.get("tier") == "lane-grant"), None)
        if admitted is not None and granted is not None:
            waits.append(granted - admitted)
    return quantile(waits, 0.5)
