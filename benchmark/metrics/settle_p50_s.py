"""Median seconds from submit to settled report over the window's jobs
(a job still running at the close counts with its wait). The closed
loop keeps every client waiting, so the engine runs at capacity and
its latency is a layer reading beside the completed rate."""

from harness import quantile


def read(run):
    return quantile(run.get("latencies") or [], 0.50)
