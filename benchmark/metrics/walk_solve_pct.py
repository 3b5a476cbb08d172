"""Percent of host-walk time spent in the solver: the walks' `solve_s`
(every get_model call of the walk, from its journey `done` event) over
their time doing their own work (locked, where a walk has begun it, to
done), whether walks take turns on the host symbolic lock or run side
by side. A program that records no `locked` event or no `solve_s`
gives nothing to read."""


def read(run):
    solve = held = 0.0
    walks = 0
    for events in run.get("journeys") or []:
        t, attrs = {}, {}
        for row in events:
            if row.get("tier") == "host-walk" and row.get("event") not in t:
                t[row.get("event")] = row["t"]
                if row.get("event") == "done":
                    attrs = row.get("attrs") or {}
        if "locked" in t and "done" in t and "solve_s" in attrs:
            solve += attrs["solve_s"]
            held += t["done"] - t["locked"]
            walks += 1
    if not walks or held <= 0:
        return None
    return 100.0 * solve / held
