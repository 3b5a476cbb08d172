"""Milliseconds of host CDCL per query in the window, in the process
that holds the chip (the solver attribution's host-cdcl row)."""


def read(run):
    row = (run.get("solver") or {}).get("host-cdcl") or {}
    if not row.get("queries"):
        return None
    return 1000.0 * row["wall_s"] / row["queries"]
