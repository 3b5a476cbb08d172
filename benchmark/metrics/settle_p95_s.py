"""95th percentile of the seconds from submit to settled report over
the window's jobs (some tens of samples: the tail is nearly the
slowest job)."""

from harness import quantile


def read(run):
    return quantile(run.get("latencies") or [], 0.95)
